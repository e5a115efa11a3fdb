#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up (``setup_s``), measures for ``--seconds``, then checks
what the window produced against the plain float32 reference in
``bench/reference.py`` and prints, as the last line of standard output, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks`` (each number compared
with its limit, also the last lines of standard error). ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer ones,
read from a profiler trace of the window.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, where the program under ``src/`` is missing, or
where the configuration's family or backend has no file under ``bench/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache() -> str:
    """The program's compile cache (``$JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``), keeping every program, however small."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def device_info(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def serve(cell, args, devices):
    """A serving run: (end-to-end metrics, records for the per-layer
    readers, numbers compared, counts and device)."""
    from bench import serving as sd
    engine, win = sd.setup(cell, args.seed, devices)
    setup_s = time.perf_counter() - T_START
    tdir = _trace_start(args)
    length = win.run(args.seconds)
    _trace_stop(tdir)
    e2e = {"setup_s": setup_s, **sd.end_to_end(win)}
    late = sorted(win.lateness)
    log(f"set-up {setup_s!r} s; window {length!r} s: {len(win.sent)} "
        f"requests sent, {len(sd.due_in_window(win))} due in the window, "
        f"{sum(r.handle.status == 'done' for r in win.sent)} finished, "
        f"{len(win.ticks)} ticks, {len(win.prefills)} block prefills")
    if late:
        log(f"generator lateness: median {late[len(late) // 2]!r} s, max "
            f"{late[-1]!r} s over {len(late)} submissions")
    counts = {"attempted": len(win.sent),
              "failed": sum(r.handle.status in ("failed", "error")
                            for r in win.sent),
              "device": device_info(devices)}
    picked = sd.sample_finished(win, args.seed,
                                cell.traffic["check_tokens"])
    host = {"ticks": win.ticks, "prefills": win.prefills,
            "queue_waits": sd.queue_waits(win)}
    sd.free(engine)
    del engine
    trace = _trace_reduce(tdir, sd.HOST_SPANS)
    t0 = time.perf_counter()
    chk = sd.check(cell, args.seed, picked)
    log(f"reference took {time.perf_counter() - t0!r} s")
    log(f"reference over {chk.get('requests', 0)} finished requests, "
        f"{chk['tokens']} served tokens: {chk}")
    checks = {"max_logit_gap": chk["max_gap"]}
    return e2e, {"host": host, "trace": trace}, checks, counts


def train(cell, args, devices):
    """A training run, returning what ``serve`` does."""
    from bench import training as td
    tr = td.Trainer(cell, args.seed)
    prog = tr.checked_steps()
    setup_s = time.perf_counter() - T_START
    tdir = _trace_start(args)
    steps, length = tr.run(args.seconds)
    _trace_stop(tdir)
    mix = cell.traffic
    e2e = {"setup_s": setup_s,
           "train_tok_s": steps * mix["batch"] * mix["seq_len"] / length}
    log(f"set-up {setup_s!r} s; window {length!r} s: {steps} steps; "
        f"checked-step losses {prog['losses']}")
    counts = {"attempted": steps, "failed": 0, "device": device_info(devices)}
    tr.free()
    del tr
    trace = _trace_reduce(tdir, td.HOST_SPANS)
    t0 = time.perf_counter()
    ref = td.reference_readings(cell, args.seed)
    log(f"reference took {time.perf_counter() - t0!r} s")
    cmp = td.compare(prog, ref)
    log(f"reference losses {ref['losses']}; {cmp}")
    checks = {k: v for k, v in cmp.items() if k.endswith("_gap")}
    if not all(math.isfinite(x) for x in prog["losses"]):
        checks["loss_gap"] = math.inf
    return e2e, {"host": {}, "trace": trace}, checks, counts


def _trace_start(args):
    """Start the profiler in a fresh directory under ``$TMPDIR``."""
    if not args.trace:
        return None
    from bench import tracing
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    tracing.start(tdir)
    return tdir


def _trace_stop(tdir) -> None:
    if not tdir:
        return
    from bench import tracing
    t0 = time.perf_counter()
    tracing.stop()
    log(f"profiler stopped in {time.perf_counter() - t0!r} s")


def _trace_reduce(tdir, spans):
    if not tdir:
        return None
    from bench import tracing
    try:
        t0 = time.perf_counter()
        tr = tracing.read(tracing.find_xplane(tdir))
        t1 = time.perf_counter()
        out = tracing.reduce(tr, spans)
        log(f"trace read in {t1 - t0!r} s, reduced in "
            f"{time.perf_counter() - t1!r} s")
        return out
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def run(cell, args, devices) -> dict:
    """One run of ``cell`` on ``devices``: the result object."""
    from bench.layer import Context, read
    kind = cell.traffic["kind"]
    e2e, rec, checks, counts = (train if kind == "train" else serve)(
        cell, args, devices)
    gc.collect()
    result = {"correct": True, "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": {},
              "device": counts["device"]}
    if args.trace:
        t = rec["trace"]
        ctx = Context(cell.config, cell.traffic, devices[0].device_kind,
                      cell.workload["chips"], t, rec["host"])
        for m in cell.per_layer:
            v = read(m["name"], ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"].update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    compared = {}
    for name, value in checks.items():
        limit = cell.limits[name]
        compared[name] = {"value": value, "limit": limit}
        if not (value <= limit):
            result["correct"] = False
    if counts["failed"]:
        result["correct"] = False
    result["checks"] = compared
    return result


def main(argv=None, *, cell=None, require_chip: bool = True) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no program under {ROOT / 'src'}")
        return 2
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import plugins, spec
    cell = cell or spec.load_cell(args.workload)
    try:
        plugins.check(cell.config)
    except FileNotFoundError as e:
        log(str(e))
        return 2
    import jax
    devices = jax.devices()
    chips = cell.workload["chips"]
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        log(f"{args.workload} needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform}")
        return 3
    devices = devices[:chips]
    if require_chip:
        log(f"compile cache: {enable_cache()}")
    log(f"{args.workload}: {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}), seed {args.seed}, {args.seconds} s, "
        f"trace {args.trace}")
    result = run(cell, args, devices)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
