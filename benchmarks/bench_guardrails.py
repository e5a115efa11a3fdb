"""Guardrail overhead benchmark — what the robustness layer costs.

Three questions, answered on 8 fake devices (mesh 1x4 for the serve rows,
an 8-ring for the stream rows) and persisted to BENCH_guardrails.json:

1. checked links on the raw stream driver — us/hop for an unchecked vs
   checked ``queues.stream`` circuit per link mode (the tag/checksum
   sidecar is one extra narrow message plus two integer compares per hop);
2. the checked serve step — decode step us/tick for the ring backend with
   and without ``checked=True`` (fault vector threaded as a jit argument);
3. the canary link probe — us per probe call, the per-tick price the
   health monitor pays for continuous link monitoring.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.bench_guardrails [--quick]
"""
from __future__ import annotations

import argparse

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from benchmarks.common import emit, emit_json, time_fn
from repro.configs import ServeConfig, get_smoke_config
from repro.core import faults, queues
from repro.core.topology import ring
from repro.launch.mesh import make_mesh
from repro.models import build_model, split_tree
from repro.serve.sharded_cache import RingShardedBackend


def bench_streams(results: dict, n: int, k: int, iters: int):
    mesh = make_mesh((n,), ("pe",))
    topo = ring("pe", n)
    xs = jax.random.normal(jax.random.PRNGKey(0), (n, k), jnp.float32)

    def make(mode, checked):
        def local(x, vec):
            with faults.scope(vec):
                out = queues.stream(topo, x, n,
                                    lambda s, b, t: s + jnp.sum(b),
                                    jnp.zeros(()), mode, checked=checked)
            return (out[0][None], out[2][None]) if checked \
                else (out[0][None],)
        specs = (P("pe"), P("pe", None, None)) if checked else (P("pe"),)
        return jax.jit(shard_map(local, mesh=mesh,
                                 in_specs=(P("pe", None), P()),
                                 out_specs=specs, check_vma=False))

    vec = faults.no_fault_vec()
    for mode in queues.MODES:
        t_plain = time_fn(make(mode, False), xs, vec, iters=iters)
        t_check = time_fn(make(mode, True), xs, vec, iters=iters)
        emit(f"stream_{mode}_unchecked", t_plain / n, f"us_per_circuit={t_plain:.1f}")
        emit(f"stream_{mode}_checked", t_check / n,
             f"overhead={t_check / t_plain:.2f}x")
        results[f"stream_{mode}"] = {
            "unchecked_us": round(t_plain, 1),
            "checked_us": round(t_check, 1),
            "overhead_x": round(t_check / t_plain, 3),
        }


def bench_serve_step(results: dict, iters: int):
    cfg = get_smoke_config("qwen3-0.6b")
    scfg = ServeConfig(max_batch=4, max_seq_len=64, temperature=0.0)
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    mesh = make_mesh((1, 4), ("data", "model"),
                     devices=jax.devices()[:4])
    tokens = np.ones((scfg.max_batch, 1), np.int32)
    active = np.ones(scfg.max_batch, bool)

    for checked in (False, True):
        be = RingShardedBackend(cfg, scfg, params, mesh, mode="qlr",
                                checked=checked)
        t = time_fn(lambda: be.step(tokens, active), iters=iters)
        tag = "checked" if checked else "unchecked"
        emit(f"serve_step_qlr_{tag}", t, f"batch={scfg.max_batch}")
        results[f"serve_step_{tag}_us"] = round(t, 1)
        if checked:
            tp = time_fn(lambda: be._probe(faults.no_fault_vec()),
                         iters=iters)
            emit("serve_link_probe", tp, "per-tick canary circuit")
            results["link_probe_us"] = round(tp, 1)
    results["serve_step_overhead_x"] = round(
        results["serve_step_checked_us"] / results["serve_step_unchecked_us"],
        3)


def run(quick: bool = False):
    results: dict = {}
    iters = 3 if quick else 10
    bench_streams(results, n=8, k=256 if quick else 4096, iters=iters)
    bench_serve_step(results, iters=iters)
    emit_json("guardrails", {"measurements": results},
              config={"n_devices": jax.device_count(), "quick": quick})


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller payloads / fewer iters for CI smoke")
    args = ap.parse_args()
    assert jax.device_count() >= 8, \
        "run under XLA_FLAGS=--xla_force_host_platform_device_count=8"
    run(quick=args.quick)
