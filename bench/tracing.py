"""The profiler trace of a run, and its reduction to device numbers.

A traced run records one ``jax.profiler`` trace around its window. The
harness marks the window with a host span named ``WINDOW`` and its own
phases with further host spans. :func:`read` walks the ``.xplane.pb``
once, and :func:`reduce` gives from that walk, for the window: the device busy time (the union of the
intervals in which an operation ran, averaged over the chips), each jitted
program's device time and count (by its stable name, e.g.
``decode_step``), the collective time during which no other operation ran
on that chip, the device operations that took most time, and the idle
time grouped by the innermost harness span open at the time; and under
``program_spans`` the program's own spans of the window
(``spans.reduce``), which the span readers under ``metrics/`` take.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
import sys

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


class Trace:
    """One walk over a trace's planes: the host events, and each chip's
    XLA Ops and XLA Modules as (name, start, end), in nanoseconds, with
    the window ``lo``-``hi`` of the host span ``bench_window``."""

    def __init__(self, pd):
        self.host = []                    # (name, start, end, event)
        self.devices = []                 # (ops, modules) per chip
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:") and "/" not in \
                    plane.name[len("/device:TPU:"):]:
                lines = {ln.name: ln for ln in plane.lines
                         if ln.name in ("XLA Ops", "XLA Modules")}
                if "XLA Ops" in lines:
                    self.devices.append(tuple(
                        [(sys.intern(e.name), e.start_ns,
                          e.start_ns + e.duration_ns)
                         for e in lines[k].events] if k in lines else []
                        for k in ("XLA Ops", "XLA Modules")))
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    self.host += [(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns, e)
                                  for e in ln.events]
        win = [ev for ev in self.host if ev[0] == WINDOW]
        if not win or not self.devices:
            raise ValueError(f"trace has {len(win)} window spans and "
                             f"{len(self.devices)} TPU planes with XLA Ops")
        self.lo, self.hi = win[0][1], win[0][2]


def read(path: str) -> Trace:
    """The trace of the ``.xplane.pb`` (or ``.xplane.pb.gz``) at ``path``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return Trace(ProfileData.from_serialized_xspace(f.read()))
    return Trace(ProfileData.from_file(path))


_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9-]*)\(")


def op_info(text: str) -> tuple:
    """(instruction name, is a collective) of an XLA Ops event's HLO text,
    '%name = type opcode(operands), attributes': a collective by its name
    or its opcode, never by an operand or a called computation."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    return (name.lstrip("%"), bool(COLLECTIVE.search(name) or
                                   (m and COLLECTIVE.search(m.group(1)))))


def shortest_open(spans, points) -> list:
    """For each of the sorted ``points``, the name of the shortest of
    ``spans`` ((name, start, end)) open at it, the first listed among
    equals, or None."""
    order = sorted(range(len(spans)), key=lambda k: spans[k][1])
    heap: list = []
    out, i = [], 0
    for t in points:
        while i < len(order) and spans[order[i]][1] <= t:
            k = order[i]
            heapq.heappush(heap, (spans[k][2] - spans[k][1], k))
            i += 1
        while heap and spans[heap[0][1]][2] <= t:
            heapq.heappop(heap)
        out.append(spans[heap[0][1]][0] if heap else None)
    return out


def reduce(path, host_spans: tuple = ()) -> dict:
    """Device numbers of the traced window of ``path`` (a path or a
    :class:`Trace`; see the module doc). ``host_spans`` names the harness
    spans that idle time is put down to; idle time under none of them is
    put down to 'other'."""
    tr = path if isinstance(path, Trace) else read(path)
    lo, hi = tr.lo, tr.hi
    spans = [ev[:3] for ev in tr.host if ev[0] in host_spans and ev[2] > lo
             and ev[1] < hi]
    n = len(tr.devices)
    busy = 0
    programs: dict = {}
    ops: dict = {}
    idle: dict = {}
    exposed = 0
    info: dict = {}
    merged_by_chip = []
    for op_events, mod_events in tr.devices:
        mods = sorted((s, e, program_name(nm)) for nm, s, e in mod_events)
        starts = [m[0] for m in mods]
        inside, coll, comp = [], [], []
        for nm, s, e in op_events:
            if e > lo and s < hi:
                op, is_coll = info.get(nm) or info.setdefault(nm, op_info(nm))
                i = bisect.bisect_right(starts, s) - 1
                prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                iv = (max(s, lo), min(e, hi))
                inside.append((f"{prog}/{op}",) + iv)
                (coll if is_coll else comp).append(iv)
        merged = union(coll + comp)
        merged_by_chip.append(merged)
        busy += _length(merged)
        for name, t in self_times(inside).items():
            ops[name] = ops.get(name, 0) + t
        for name, s, e in mod_events:
            if s >= lo and s < hi:
                p = programs.setdefault(program_name(name), [0, 0])
                p[0] += 1
                p[1] += e - s
        coll = union(coll)
        exposed += _length(coll) - _intersect_len(coll, union(comp))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                if ge > gs]
        who = shortest_open(spans, [(gs + ge) / 2 for gs, ge in gaps])
        for (gs, ge), name in zip(gaps, who):
            name = name or "other"
            idle[name] = idle.get(name, 0) + ge - gs
    ns = 1e-9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    from bench import spans as program
    return {
        "chips": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / n,
        "programs": {k: {"count": c / n, "device_s": t * ns / n}
                     for k, (c, t) in programs.items()},
        "collective_exposed_s": exposed * ns / n,
        "device_ops": [[k, v * ns / n] for k, v in top],
        "idle_gaps": [[k, v * ns / n] for k, v in gaps],
        "program_spans": program.reduce(tr, merged_by_chip),
    }
