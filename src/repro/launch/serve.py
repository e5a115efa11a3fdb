"""Serving driver: batched continuous-batching engine on a smoke config.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --requests 8

The same host-side scheduler drives two backends:
  --backend dense         one jitted decode step, cache wherever jit puts it
  --backend ring          KV cache ring-sharded along the 'model' mesh axis,
                          queries streamed systolically (--mode sw/xqueue/
                          qlr, or baseline for the all-gather reference).
For the ring backend pass --mesh DxM (e.g. 2x4 on 8 devices); run under
XLA_FLAGS=--xla_force_host_platform_device_count=8 to try it on CPU.

Robustness flags (serve/health.py): --checked arms tag/checksum-checked
links plus a per-tick canary probe on the ring backend; --monitor guards
every tick (snapshot/rollback, poisoned-request eviction, mode-ladder
degradation); --deadline SECONDS adds a wall-clock budget per step;
--eos-token retires a slot when it samples that token.

Observability flags (DESIGN.md §8): --metrics-out FILE.json writes the
metrics snapshot (a FILE.prom Prometheus text twin lands next to it);
--trace-out DIR records a profiler trace of the run there: the engine's
serve.* spans (repro.obs.trace) and the device's operations on one
timeline (open it in TensorBoard's profile plugin or Perfetto);
--telemetry arms link-traffic counters on the ring backend (queue
push/pop, payload bytes, checked-link errors) folded into the metrics as
repro_link_*.
"""
from __future__ import annotations

import argparse
import contextlib
import time

import numpy as np

import jax

from repro.configs import ServeConfig, apply_overrides, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model, split_tree
from repro.serve.engine import ServeEngine
from repro.serve.sharded_cache import RingShardedBackend


def _make_mesh(spec: str):
    d, m = (int(x) for x in spec.lower().split("x"))
    return make_mesh((d, m), ("data", "model"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--full", action="store_true",
                    help="full config (default: smoke)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--backend", choices=("dense", "ring"), default="dense")
    ap.add_argument("--mode", default="qlr",
                    choices=("baseline", "sw", "xqueue", "qlr"),
                    help="ring link mode (ignored for --backend dense)")
    ap.add_argument("--mesh", default="1x4",
                    help="DATAxMODEL mesh for --backend ring, e.g. 2x4")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="block-prefill up to this many prompt tokens")
    ap.add_argument("--eos-token", type=int, default=-1,
                    help="retire a slot when it samples this id (< 0 = off)")
    ap.add_argument("--checked", action="store_true",
                    help="checked queue links + per-tick probe (ring only)")
    ap.add_argument("--monitor", action="store_true",
                    help="guard every tick with the health monitor")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-step wall-clock budget in seconds (0 = off)")
    ap.add_argument("--metrics-out", default="",
                    help="write metrics snapshot JSON here (+ .prom twin)")
    ap.add_argument("--trace-out", default="",
                    help="record a profiler trace of the run in this "
                         "directory")
    ap.add_argument("--telemetry", action="store_true",
                    help="arm link-traffic telemetry (ring only)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    scfg = ServeConfig(max_batch=args.max_batch, max_seq_len=args.max_seq,
                       temperature=args.temperature,
                       prefill_chunk=args.prefill_chunk,
                       eos_token=args.eos_token)
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    backend = None
    if args.backend == "ring":
        backend = RingShardedBackend(cfg, scfg, params, _make_mesh(args.mesh),
                                     mode=args.mode, checked=args.checked,
                                     telemetry=args.telemetry)
    health = None
    if args.monitor or args.deadline > 0:
        from repro.serve.health import HealthConfig
        health = HealthConfig(deadline_s=args.deadline)
    engine = ServeEngine(cfg, scfg, params, backend=backend, health=health)

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=rng.integers(2, 12)).astype(np.int32)
        engine.submit(prompt, max_new_tokens=args.max_new)
    reqs = list(engine.pending)

    with (jax.profiler.trace(args.trace_out) if args.trace_out
          else contextlib.nullcontext()):
        t0 = time.perf_counter()
        ticks = engine.run()
        dt = time.perf_counter() - t0
    total_new = sum(len(r.out_tokens) for r in reqs)
    print(f"served {len(reqs)} requests ({engine.backend.name}), "
          f"{total_new} tokens, {ticks} engine ticks, "
          f"{total_new / dt:.1f} tok/s")
    for r in reqs[:4]:
        print(f"  rid={r.rid} prompt_len={len(r.prompt)} "
              f"status={r.status} finish={r.finish_reason or '-'} "
              f"out={r.out_tokens}")
    if engine.monitor is not None and engine.monitor.events:
        print("health events:")
        for ev in engine.monitor.events:
            print(f"  tick={ev.tick} [{ev.kind}] mode={ev.mode}: {ev.detail}")

    if args.metrics_out:
        prom = args.metrics_out.rsplit(".", 1)[0] + ".prom"
        engine.export_observability(metrics_json=args.metrics_out,
                                    metrics_prom=prom)
        print(f"wrote {args.metrics_out}\nwrote {prom}")
    if args.trace_out:
        print(f"wrote a profiler trace under {args.trace_out}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
