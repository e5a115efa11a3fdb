"""Batched serving example: continuous batching over one jitted decode step.

Requests with different prompt lengths and generation budgets stream through
a fixed slot batch; per-row cache positions + the active-row mask keep each
request's KV state independent. The host-side scheduler
(src/repro/serve/scheduler.py) is backend-agnostic: pass --ring to serve
from a KV cache ring-sharded along the 'model' mesh axis, with each row's
query streamed systolically around the resident shards
(src/repro/serve/sharded_cache.py). On CPU, fake the devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/serve_batched.py --ring --mode qlr
"""
import argparse
import time

import numpy as np

import jax

from repro.configs import ServeConfig, get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import build_model, split_tree
from repro.serve.engine import ServeEngine
from repro.serve.sharded_cache import RingShardedBackend


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ring", action="store_true",
                    help="ring-sharded KV backend over all visible devices")
    ap.add_argument("--mode", default="qlr",
                    choices=("baseline", "sw", "xqueue", "qlr"))
    ap.add_argument("--prefill-chunk", type=int, default=0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    scfg = ServeConfig(max_batch=args.max_batch, max_seq_len=128,
                       temperature=args.temperature,
                       prefill_chunk=args.prefill_chunk)
    backend = None
    if args.ring:
        mesh = make_mesh((1, jax.device_count()), ("data", "model"))
        backend = RingShardedBackend(cfg, scfg, params, mesh, mode=args.mode)
    engine = ServeEngine(cfg, scfg, params, backend=backend)

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=int(rng.integers(2, 20)))
        engine.submit(prompt.astype(np.int32),
                      max_new_tokens=int(rng.integers(4, 12)))
    reqs = list(engine.pending)

    t0 = time.perf_counter()
    ticks = engine.run()
    dt = time.perf_counter() - t0
    done = sum(r.done for r in reqs)
    toks = sum(len(r.out_tokens) for r in reqs)
    print(f"{done}/{len(reqs)} requests ({engine.backend.name}), "
          f"{toks} tokens in {ticks} ticks "
          f"({toks / dt:.1f} tok/s, slot batch {args.max_batch})")
    for r in reqs[:5]:
        print(f"  rid={r.rid:2d} prompt={len(r.prompt):2d} -> {r.out_tokens}")


if __name__ == "__main__":
    main()
