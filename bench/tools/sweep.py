#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: one set-up, then one window
per offered rate, each from an empty server, in one process on the chip.

  python3 bench/tools/sweep.py --workload qwen3-0.6b.chat --seed 1 \
      --seconds 60 --warm 60 --rates 1.3 1.45 1.6 1.75 1.9

Each window follows a warm-up at the same rate (``--warm`` seconds, the
mix's ``warm_s`` by default). Prints one JSON line per rate: offered and
finished requests per second, output tokens per second, the TTFT and
inter-token p95s, the p95 wait for a slot, and the requests running and
queued at the window's start and end. The knee is the highest rate at
which no queue builds and the finished rate keeps up with the offered
one.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--warm", type=float, default=None)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    import jax
    from bench import run, serving as sd, spec, traffic
    from repro.serve.scheduler import Scheduler
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: needs a TPU")
    run.enable_cache()
    cell = spec.load_cell(args.workload)
    engine, _ = sd.setup(cell, args.seed)
    s = engine.sched
    for rate in args.rates:
        mix = dict(copy.deepcopy(cell.traffic), rate_rps=rate)
        if args.warm is not None:
            mix["warm_s"] = args.warm
        engine.sched = Scheduler(s.max_batch, s.max_seq, s.bos_token,
                                 s.eos_token)
        win = sd.Window(engine, mix, traffic.requests(
            mix, args.seed, engine.cfg.vocab_size))
        win.warm()
        start = {"running_start": sum(h is not None
                                      for h in engine.sched.slot_req),
                 "queued_start": len(engine.sched.pending)}
        length = win.run(args.seconds)
        e2e = sd.end_to_end(win)
        done = sum(r.handle.status == "done" and win.in_window(r.finished)
                   for r in win.sent)
        print(json.dumps({
            "rate_rps": rate, "window_s": length,
            "due": len(sd.due_in_window(win)),
            "finished_rps": done / length, **e2e,
            "queue_wait_p95_s": sd.p95(sd.queue_waits(win)), **start,
            "running_end": sum(h is not None for h in engine.sched.slot_req),
            "queued_end": len(engine.sched.pending)}), flush=True)


if __name__ == "__main__":
    main()
