"""Multi-device check: expert-ring MoE dispatch matches the dense
gather/scatter shared-L1 baseline in every link mode (values and grads,
fp32, 8 fake CPU devices: data=2 x model=4), including top-2 routing with
capacity overflow. Prints one JSON line with results."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json
from dataclasses import replace

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.ring_moe import MODES, ring_moe_applicable, systolic_ring_moe
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_lib
from repro.models.common import split_tree, use_sharding

results = {}


def record(name, ok, detail=""):
    results[name] = {"ok": bool(ok), "detail": str(detail)}


mesh = make_mesh((2, 4), ("data", "model"))

CFG = ModelConfig(
    name="ring-moe-check", family="moe", d_model=16, d_ff=32, d_ff_expert=32,
    num_experts=8, experts_per_token=2, capacity_factor=2.0,
    dtype="float32", param_dtype="float32")

params, _ = split_tree(moe_lib.init_moe(jax.random.PRNGKey(0), CFG))
x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16), jnp.float32)

# dense shared-L1 reference (the current path, systolic_mode="baseline")
y_ref, aux_ref = jax.jit(lambda p, x: moe_lib.apply_moe(p, x, CFG))(params, x)


# --- direct schedule: systolic_ring_moe vs the dense dispatch --------------
def routing(params, x, cfg):
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["router"])
    weights, idx, _ = moe_lib._topk_routing(logits, cfg)
    pos = moe_lib._positions_in_expert(idx, cfg.num_experts)
    return weights, idx, pos


cap = moe_lib.expert_capacity(CFG, x.shape[1])
for mode in MODES:          # baseline here = all-gather inside the harness
    def direct(p, x, m=mode):
        weights, idx, pos = routing(p, x, CFG)
        return systolic_ring_moe(x, idx, pos, weights, p["w_gate"],
                                 p["w_up"], p["w_down"], cap, mesh, m)
    y = jax.jit(direct)(params, x)
    err = float(jnp.abs(y - y_ref).max())
    record(f"ring_moe_{mode}", err < 1e-4, err)


# --- wired path: apply_moe behind cfg.systolic_mode ------------------------
with use_sharding(mesh):
    for mode in ("sw", "xqueue", "qlr"):
        cfg = replace(CFG, systolic_mode=mode)
        fn = jax.jit(lambda p, x, c=cfg: moe_lib.apply_moe(p, x, c))
        y, aux = fn(params, x)
        err = max(float(jnp.abs(y - y_ref).max()), abs(float(aux - aux_ref)))
        # the ring must actually engage: queue hops leave collective-permutes
        hlo = fn.lower(params, x).compile().as_text()
        ok = err < 1e-4 and hlo.count("collective-permute") > 0
        record(f"ring_moe_model_{mode}", ok,
               f"err={err};cperm={hlo.count('collective-permute')}")

    # grads flow through both ring passes (scatter + gather + queue hops)
    def loss(p, x, cfg):
        y, aux = moe_lib.apply_moe(p, x, cfg)
        return jnp.sum(y ** 2) + aux

    g_ref = jax.jit(lambda p, x: jax.grad(loss, argnums=(0, 1))(p, x, CFG))(
        params, x)
    for mode in ("sw", "xqueue", "qlr"):
        cfg = replace(CFG, systolic_mode=mode)
        g = jax.jit(lambda p, x, c=cfg: jax.grad(loss, argnums=(0, 1))(
            p, x, c))(params, x)
        errs = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), g, g_ref)
        err = max(jax.tree_util.tree_leaves(errs))
        record(f"ring_moe_grad_{mode}", err < 1e-3, err)

    # top-2 routing with guaranteed capacity overflow: 4 experts, cap 16,
    # ~32 assignments per expert-row -> about half the slots drop
    OCFG = ModelConfig(
        name="ring-moe-overflow", family="moe", d_model=16, d_ff=32,
        d_ff_expert=32, num_experts=4, experts_per_token=2,
        capacity_factor=0.5, dtype="float32", param_dtype="float32")
    oparams, _ = split_tree(moe_lib.init_moe(jax.random.PRNGKey(2), OCFG))
    ox = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 16), jnp.float32)
    ocap = moe_lib.expert_capacity(OCFG, ox.shape[1])
    assert ocap < ox.shape[1] * OCFG.experts_per_token // OCFG.num_experts, \
        "overflow case must actually overflow"
    oy_ref, _ = jax.jit(lambda p, x: moe_lib.apply_moe(p, x, OCFG))(oparams, ox)
    og_ref = jax.jit(lambda p, x: jax.grad(loss, argnums=(0, 1))(
        p, x, OCFG))(oparams, ox)
    for mode in ("sw", "xqueue", "qlr"):
        cfg = replace(OCFG, systolic_mode=mode)
        oy, _ = jax.jit(lambda p, x, c=cfg: moe_lib.apply_moe(p, x, c))(
            oparams, ox)
        err = float(jnp.abs(oy - oy_ref).max())
        og = jax.jit(lambda p, x, c=cfg: jax.grad(loss, argnums=(0, 1))(
            p, x, c))(oparams, ox)
        gerrs = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), og, og_ref)
        err = max([err] + jax.tree_util.tree_leaves(gerrs))
        record(f"ring_moe_overflow_{mode}", err < 1e-3, err)


# --- fallback gate: sub-experts / shared experts / indivisible stay dense --
gate_ok = (
    ring_moe_applicable(CFG, x, mesh)
    and not ring_moe_applicable(replace(CFG, moe_subexperts=2), x, mesh)
    and not ring_moe_applicable(replace(CFG, num_shared_experts=1), x, mesh)
    and not ring_moe_applicable(replace(CFG, num_experts=6), x, mesh)
    and not ring_moe_applicable(CFG, x[:, :30], mesh)   # seq % model != 0
)
record("ring_moe_gate", gate_ok)

print(json.dumps(results))
failed = {k: v for k, v in results.items() if not v["ok"]}
raise SystemExit(1 if failed else 0)
