#!/usr/bin/env python3
"""Read the serving engine's own spans in a traced window, on the chip.

  python3 bench/tools/trace_spans.py --workload qwen3-0.6b.chat \
      --seed 1 --seconds 51 [--keep DIR]

sets a serving cell up and traces its window as ``bench/run.py --trace 1``
does, then prints one JSON line: the ticks in the window, the device
numbers of ``tracing.reduce`` (busy and window seconds, the decode step's
and block prefill's milliseconds a call), the harness's
``queue_wait_p95_s``, the per-layer numbers ``bench/spans.py`` reads from
the program's ``serve.*`` spans, and the device's idle seconds by the
innermost program span. It checks nothing against the reference.

  python3 bench/tools/trace_spans.py --workload qwen3-0.6b.chat \
      --seed 1 --record bench/tests/data/spans.xplane.pb.gz

records a short window instead: two requests decoding, then one admitted
with a prompt longer than the block prefill, and a few ticks, inside a
``bench_window`` span; writes the gzipped ``.xplane.pb`` to the path.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def window(cell, seed: int, seconds: float, keep: str) -> dict:
    from bench import serving as sd
    from bench import spans, tracing
    from bench.layer import per_call_s, Context
    engine, win = sd.setup(cell, seed)
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    tracing.start(tdir)
    length = win.run(seconds)
    tracing.stop()
    waits = sd.queue_waits(win)
    out = {"workload": cell.name, "seed": seed, "window_s": length,
           "ticks": len(win.ticks), "prefills": len(win.prefills),
           "queue_wait_p95_s": sd.p95(waits) if waits else None}
    sd.free(engine)
    del engine
    try:
        path = tracing.find_xplane(tdir)
        red = tracing.reduce(path, sd.HOST_SPANS)
        ctx = Context(cell.config, cell.traffic, "", 1, red)
        for name, prog in (("decode_step_ms", "decode_step"),
                           ("prefill_ms", "prefill_into_cache")):
            s = per_call_s(ctx, prog)
            out[name] = None if s is None else 1e3 * s
        out.update(busy_s=red["busy_s"], trace_window_s=red["window_s"],
                   programs=red["programs"], idle_gaps=red["idle_gaps"])
        prog = red["program_spans"]
        out.update({k: f(prog) for k, f in spans.METRICS.items()})
        out["idle_by_span"] = prog["idle"]
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(
                keep, f"{cell.name}.{seed}.xplane.pb"))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return out


def record(cell, seed: int, dest: str) -> dict:
    """A few ticks and one block-prefill admission inside a window."""
    import numpy as np
    from jax.profiler import TraceAnnotation
    from bench import serving as sd
    from bench import tracing, traffic
    cell.traffic = dict(cell.traffic, warm_s=0.0)   # no schedule before it
    engine, _ = sd.setup(cell, seed)
    rng = traffic.rng_for(seed, 6)
    vocab = engine.cfg.vocab_size
    chunk = engine.scfg.prefill_chunk
    for _ in range(2):
        engine.submit(rng.integers(0, vocab, 24).astype(np.int32), 40)
    engine._admit()
    for _ in range(2):
        engine.step()
    long_prompt = rng.integers(0, vocab, chunk + 40).astype(np.int32)
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        tracing.start(tdir)
        with TraceAnnotation(tracing.WINDOW):
            engine.submit(long_prompt, 8)
            for _ in range(3):
                engine._admit()
                engine.step()
        tracing.stop()
        with open(tracing.find_xplane(tdir), "rb") as f, \
                gzip.open(dest, "wb") as g:
            g.write(f.read())
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return {"recorded": dest, "bytes": os.path.getsize(dest),
            "prompt": len(long_prompt), "chunk": chunk}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--keep", default="",
                    help="copy the window's .xplane.pb into this directory")
    ap.add_argument("--record", default="",
                    help="record a short window to this .xplane.pb.gz")
    args = ap.parse_args()
    import jax
    from bench import run, spec
    if jax.devices()[0].platform != "tpu":
        sys.exit("trace_spans: needs a TPU")
    run.enable_cache()
    cell = spec.load_cell(args.workload)
    out = (record(cell, args.seed, args.record) if args.record
           else window(cell, args.seed, args.seconds, args.keep))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
