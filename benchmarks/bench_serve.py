"""Serving benchmark — prefill + decode tok/s per backend/link mode.

Drives the continuous-batching engine with ``max_batch`` equal-length
prompts (every slot admitted up front, so the prompt-streaming phase and
the decode phase are cleanly separable in time) and reports tokens/s for
each phase, per backend:

  dense            single-device jitted decode step
  ring-baseline    KV ring-sharded, queries all-gathered (multicast ref)
  ring-sw/xqueue/qlr   queries streamed over the systolic links

Block prefill (``prefill_chunk > 0``) is additionally measured for every
backend: the prompt head goes through one full-sequence forward instead
of P-1 streamed ticks. Running it uniformly keeps the BENCH_serve.json
leaf set identical across backends, so the regression gate compares the
same leaves every run.

Per-mode numbers are also persisted to BENCH_serve.json at the repo root.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.bench_serve
"""
from __future__ import annotations

import time
from dataclasses import replace

import numpy as np

import jax

from benchmarks.common import emit, emit_json
from repro.configs import ServeConfig, get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import build_model, split_tree
from repro.serve.engine import ServeEngine
from repro.serve.sharded_cache import DecodeBackend, RingShardedBackend

P_LEN = 8      # prompt tokens per request (equal lengths)
N_NEW = 16     # generation budget per request


def drive_phases(cfg, scfg, params, backend, prompts):
    """One full serve of ``prompts``; returns (t_prefill_s, t_decode_s)."""
    for s in range(scfg.max_batch):
        backend.free_slot(s)
    eng = ServeEngine(cfg, scfg, params, backend=backend)
    for p in prompts:
        eng.submit(p, max_new_tokens=N_NEW)
    t0 = time.perf_counter()
    eng._admit()                      # block prefill happens here, if on
    stream_ticks = P_LEN - backend.prefill_len(P_LEN)
    for _ in range(stream_ticks):     # prompt phase (last tick samples #1)
        eng.step()
    jax.block_until_ready(backend.cache)
    t1 = time.perf_counter()
    for _ in range(N_NEW - 1):        # pure decode phase
        eng.step()
    jax.block_until_ready(backend.cache)
    t2 = time.perf_counter()
    assert not eng.sched.busy, "phase arithmetic is off"
    return t1 - t0, t2 - t1


def bench_backend(name, cfg, scfg, params, backend, results):
    B = scfg.max_batch
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=P_LEN).astype(np.int32)
               for _ in range(B)]
    drive_phases(cfg, scfg, params, backend, prompts)          # compile
    tp, td = drive_phases(cfg, scfg, params, backend, prompts)
    pre_tps = B * P_LEN / tp
    dec_tps = B * (N_NEW - 1) / td
    tag = "block" if scfg.prefill_chunk else "stream"
    emit(f"serve_prefill_{tag}_{name}", tp / P_LEN * 1e6,
         f"tok_s={pre_tps:.0f}")
    if not scfg.prefill_chunk:
        emit(f"serve_decode_{name}", td / (N_NEW - 1) * 1e6,
             f"tok_s={dec_tps:.0f}")
    rec = results.setdefault(name, {})
    rec[f"prefill_{tag}_tok_s"] = round(pre_tps, 1)
    rec.setdefault("decode_tok_s", round(dec_tps, 1))


def run(n_dev: int = 8):
    cfg = get_smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    scfg = ServeConfig(max_batch=8, max_seq_len=64, temperature=0.0)
    mesh = make_mesh((n_dev // 4, 4), ("data", "model"))

    results: dict = {}
    backends = [("dense", None, scfg)]
    for mode in ("baseline", "sw", "xqueue", "qlr"):
        backends.append((f"ring-{mode}", mode, scfg))
    # block prefill variants — every backend, so the regression gate sees
    # a uniform leaf set (prefill_block_tok_s for all, not just two)
    scfg_block = replace(scfg, prefill_chunk=P_LEN - 1)
    backends.append(("dense", None, scfg_block))
    for mode in ("baseline", "sw", "xqueue", "qlr"):
        backends.append((f"ring-{mode}", mode, scfg_block))

    for name, mode, sc in backends:
        be = DecodeBackend(cfg, sc, params) if mode is None else \
            RingShardedBackend(cfg, sc, params, mesh, mode=mode)
        bench_backend(name, cfg, sc, params, be, results)

    emit_json("serve", {"backends": results},
              config={"arch": "qwen3-0.6b-smoke", "max_batch": scfg.max_batch,
                      "max_seq_len": scfg.max_seq_len, "prompt_len": P_LEN,
                      "max_new_tokens": N_NEW, "n_devices": n_dev,
                      "mesh": f"{n_dev // 4}x4"})


if __name__ == "__main__":
    assert jax.device_count() >= 8, \
        "run under XLA_FLAGS=--xla_force_host_platform_device_count=8"
    run(8)
