"""Autotune trajectory benchmark — tuned plan vs untuned default per op.

For each op the tuner knows (matmul / attention / MoE / serve) this sweeps
the applicable (mode, topology, block, use_kernel) plans via
``repro.autotune.tune``, persists the winner to the plan cache, and
reports tuned-vs-default wall time. Two properties are *asserted*, not
just reported:

* the tuned plan is never slower than the op's untuned default beyond the
  tuner's noise band (the tie-break may trade <=NOISE time for fewer link
  bytes);
* a second ``best_plan`` lookup after the sweep is answered from the cache
  with **zero** re-measurement (``measure.trial_count()`` stays 0).

The ``speedup`` leaves in BENCH_autotune.json are gated by
``check_regression`` just like the serving ``tok_s`` leaves: a tuned plan
falling >25% behind its own default means the tuner (or a stale committed
cache) regressed. The cache itself lands in AUTOTUNE_CACHE.json at the
repo root (override with $REPRO_AUTOTUNE_CACHE).

Cache keys use the shapes the *model* paths look up — attention/decode key
on the [B,S,D] activations entering ``gqa_forward``/``gqa_decode``, MoE on
the tokens entering ``apply_moe`` — so a sweep here pre-populates the
plans that ``Config.autotune`` picks up at trace time.

Default is the --quick sweep (no kernel plans, 2 timing iters, a 3-plan
serve shortlist) so CI and ``benchmarks.run`` stay cheap; pass --full for
the whole space.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.bench_autotune
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from benchmarks.common import emit, emit_json
from repro.autotune import (
    Plan,
    best_plan,
    candidates,
    global_cache,
    tune,
)
from repro.autotune import measure
from repro.autotune.space import DEFAULT_PLAN
from repro.configs import ServeConfig, get_smoke_config
from repro.configs.base import ModelConfig
from repro.core import collective_matmul as cm
from repro.core import ring_attention as ra
from repro.core import topology as topo_lib
from repro.core.ring_moe import systolic_ring_moe
from repro.launch.mesh import make_mesh
from repro.models import build_model, moe as moe_lib, split_tree
from repro.serve.sharded_cache import RingShardedBackend

# the winner may trade <=NOISE wall time for fewer link bytes, plus a
# little slack for back-to-back trial jitter on shared CI runners
SLACK = 0.05

# untuned baselines: what each call site runs with no plan applied
DEFAULTS = {
    "matmul": DEFAULT_PLAN,
    "attention": DEFAULT_PLAN,
    "moe": DEFAULT_PLAN,
    "serve": Plan(mode="qlr", topology="ring"),   # backend ctor default
}


# ---------------------------------------------------------------------------
# builders: plan -> (un-jitted fn, args); measure jits for timing and
# probes the eager call for link bytes
# ---------------------------------------------------------------------------


def matmul_builder(mesh, b=2, s=128, d=64, f=64):
    n = mesh.shape["model"]
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, f), jnp.float32)

    def build(plan: Plan):
        topo = topo_lib.resolve_safe(plan.topology, "model", n)

        def body(x_l, w_l):
            (y,) = cm.ring_ag_matmul(x_l, [w_l], topo, plan.mode,
                                     use_kernel=plan.use_kernel,
                                     block=plan.block)
            return y

        fn = shard_map(body, mesh=mesh,
                       in_specs=(P(None, "model", None), P(None, "model")),
                       out_specs=P(None, None, "model"))
        return fn, (x, w)

    return build, (b, s, d)


def attention_builder(mesh, b=2, s=128, h=4, kv=2, hd=16):
    n = mesh.shape["model"]
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, hd), jnp.float32)

    def build(plan: Plan):
        topo = topo_lib.resolve_safe(plan.topology, "model", n)

        def fn(q, k, v):
            return ra.systolic_ring_attention(q, k, v, mesh, plan.mode,
                                              topo=topo,
                                              use_kernel=plan.use_kernel)

        return fn, (q, k, v)

    # key on the [B,S,D] activations gqa_forward sees
    return build, (b, s, h * hd)


def moe_builder(mesh, b=2, s=64, d=32, f=64, e=8, k=2):
    n = mesh.shape["model"]
    cfg = ModelConfig(
        name="autotune-moe", family="moe", d_model=d, d_ff=f,
        d_ff_expert=f, num_experts=e, experts_per_token=k,
        capacity_factor=2.0, dtype="float32", param_dtype="float32")
    params, _ = split_tree(moe_lib.init_moe(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, d), jnp.float32)
    cap = moe_lib.expert_capacity(cfg, s)

    def build(plan: Plan):
        topo = topo_lib.resolve_safe(plan.topology, "model", n)

        def fn(p, x):
            logits = jnp.einsum("bsd,de->bse", x, p["router"])
            weights, idx, _ = moe_lib._topk_routing(logits, cfg)
            pos = moe_lib._positions_in_expert(idx, e)
            return systolic_ring_moe(x, idx, pos, weights, p["w_gate"],
                                     p["w_up"], p["w_down"], cap, mesh,
                                     plan.mode, topo=topo,
                                     use_kernel=plan.use_kernel,
                                     block=plan.block)

        return fn, (params, x)

    return build, (b, s, d)


def serve_builder(mesh):
    cfg = get_smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    scfg = ServeConfig(max_batch=8, max_seq_len=64, temperature=0.0)
    tokens = jnp.ones((scfg.max_batch, 1), jnp.int32)
    active = jnp.ones((scfg.max_batch,), bool)

    def build(plan: Plan):
        be = RingShardedBackend(cfg, scfg, params, mesh, plan=plan)
        return be._make_step(), (be.params, be.cache, tokens, active)

    return build, (scfg.max_batch, scfg.max_seq_len, cfg.d_model)


def plan_set(op: str, n: int, quick: bool) -> list[Plan]:
    if op == "serve":
        if quick:
            # shortlist: each plan is a full backend build + step compile
            return [Plan(mode="baseline"), Plan(mode="qlr"),
                    Plan(mode="qlr", topology="snake_fold")]
        return candidates(op, n, kernels=(False,))
    if quick:
        return candidates(op, n, kernels=(False,))
    return candidates(op, n, blocks=(0, 64))


def run(n_dev: int = 8, quick: bool = True, iters: int = 3):
    if quick:
        iters = min(iters, 2)
    mesh = make_mesh((n_dev,), ("model",))
    serve_mesh = make_mesh((n_dev // 4, 4), ("data", "model"))
    cache = global_cache()

    builders = {
        "matmul": (matmul_builder, mesh),
        "attention": (attention_builder, mesh),
        "moe": (moe_builder, mesh),
        "serve": (serve_builder, serve_mesh),
    }

    ops: dict = {}
    for op, (make, op_mesh) in builders.items():
        build, shape = make(op_mesh)
        plans = plan_set(op, op_mesh.shape["model"], quick)
        default = DEFAULTS[op]
        assert default in plans, (op, default)

        measure.reset_trials()
        winner, results = tune(op, shape, "float32", op_mesh, build,
                               cache=cache, plans=plans, iters=iters)
        trials = measure.trial_count()
        tuned = results[winner.label()]
        default_r = results[default.label()]
        assert default_r["us"] != float("inf"), \
            (op, "default plan failed", default_r)
        assert tuned["us"] <= default_r["us"] * (1.0 + SLACK), \
            (op, "tuned slower than default", tuned, default_r)

        # exact cache hit answers without a single new trial
        measure.reset_trials()
        again = best_plan(op, shape, "float32", op_mesh, cache=cache)
        assert again == winner, (op, again, winner)
        assert measure.trial_count() == 0, \
            (op, "cache hit re-measured", measure.trial_count())

        speedup = default_r["us"] / tuned["us"]
        emit(f"autotune_{op}", tuned["us"],
             f"speedup={speedup:.2f};plan={winner.label()};"
             f"n_plans={len(plans)}")
        ops[op] = {
            "default_us": round(default_r["us"], 1),
            "tuned_us": round(tuned["us"], 1),
            "speedup": round(speedup, 3),
            "plan": winner.to_dict(),
            "n_plans": len(plans),
            "trials": trials,
        }

    emit_json("autotune", {"ops": ops},
              config={"n_devices": n_dev, "quick": quick, "iters": iters,
                      "cache": cache.path})
    return ops


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="(default) kernel-free sweep, 2 iters")
    ap.add_argument("--full", action="store_true",
                    help="whole plan space incl. kernel/block plans")
    args = ap.parse_args()
    assert jax.device_count() >= 8, \
        "run under XLA_FLAGS=--xla_force_host_platform_device_count=8"
    run(8, quick=not args.full)
