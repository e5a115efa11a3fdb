"""The profiler trace of a run, and its reduction to device numbers.

A traced run records one ``jax.profiler`` trace around its window. The
harness marks the window with a host span named ``WINDOW`` and its own
phases with further host spans. :func:`reduce` reads the ``.xplane.pb``
and gives, for the window: the device busy time (the union of the
intervals in which an operation ran, averaged over the chips), each jitted
program's device time and count (by its stable name, e.g.
``decode_step``), the collective time during which no other operation ran
on that chip, the device operations that took most time, and the idle
time grouped by the innermost harness span open at the time.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench_window"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv",
                        re.IGNORECASE)


def start(directory: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def program_name(module: str) -> str:
    """'jit_decode_step(123)' -> 'decode_step'."""
    name = module.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def union(intervals):
    """Sorted, merged [start, end) list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> int:
    return sum(e - s for s, e in merged)


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _intersect_len(a, b) -> int:
    """Length of the intersection of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def self_times(events) -> dict:
    """Self time per op name: an op's duration minus the
    time of the ops nested in it (a while loop holds its body's ops)."""
    out: dict = {}
    stack: list = []                      # [name, end, child time]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            n, end, child, dur = stack.pop()
            out[n] = out.get(n, 0) + dur - child
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0, e - s])
    for n, end, child, dur in stack:
        out[n] = out.get(n, 0) + dur - child
    return out


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def reduce(path: str, host_spans: tuple = ()) -> dict:
    """Device numbers of the traced window of ``path`` (see module doc).
    ``host_spans`` names the harness spans that idle time is put down to;
    idle time under none of them is put down to 'other'."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "/" not in \
                plane.name[len("/device:TPU:"):]:
            lines = {ln.name: _events(ln) for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += _events(ln)
    win = [ev for ev in host if ev[0] == WINDOW]
    if not win or not devices:
        raise ValueError(f"trace has {len(win)} window spans and "
                         f"{len(devices)} TPU planes with XLA Ops")
    lo, hi = win[0][1], win[0][2]
    spans = [ev for ev in host if ev[0] in host_spans and ev[2] > lo
             and ev[1] < hi]
    n = len(devices)
    busy = 0
    programs: dict = {}
    ops: dict = {}
    idle: dict = {}
    exposed = 0
    for lines in devices:
        op_ev = _clip([(s, e) for _, s, e in lines["XLA Ops"]], lo, hi)
        merged = union(op_ev)
        busy += _length(merged)
        mods = sorted((s, e, program_name(nm)) for nm, s, e in
                      lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        inside = []
        for nm, s, e in lines["XLA Ops"]:
            if e > lo and s < hi:
                i = bisect.bisect_right(starts, s) - 1
                prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                op = nm.split(" = ")[0].lstrip("%")
                inside.append((f"{prog}/{op}", max(s, lo), min(e, hi)))
        for name, t in self_times(inside).items():
            ops[name] = ops.get(name, 0) + t
        for name, s, e in lines.get("XLA Modules", []):
            if s >= lo and s < hi:
                p = programs.setdefault(program_name(name), [0, 0])
                p[0] += 1
                p[1] += e - s
        coll = union(_clip([(s, e) for nm, s, e in lines["XLA Ops"]
                            if COLLECTIVE.search(nm)], lo, hi))
        comp = union(_clip([(s, e) for nm, s, e in lines["XLA Ops"]
                            if not COLLECTIVE.search(nm)], lo, hi))
        exposed += _length(coll) - _intersect_len(coll, comp)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) / 2
            open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
            who = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ \
                else "other"
            idle[who] = idle.get(who, 0) + ge - gs
    ns = 1e-9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / n,
        "programs": {k: {"count": c / n, "device_s": t * ns / n}
                     for k, (c, t) in programs.items()},
        "collective_exposed_s": exposed * ns / n,
        "device_ops": [[k, v * ns / n] for k, v in top],
        "idle_gaps": [[k, v * ns / n] for k, v in gaps],
    }
