"""The program's own spans in a traced window, and what they say.

The serving engine marks each layer boundary with a ``serve.*`` host span
on the profiler's clock (``src/repro/obs/trace.py`` lists them and their
metadata). :func:`reduce` reads them from the ``.xplane.pb`` of a traced
window (the host span ``bench_window``, as in ``tracing.reduce``): each
span that starts in the window with its start, end and metadata, and the
device's idle time in the window put down to the innermost ``serve.*``
span open at the time. The functions after it turn that into per-layer
numbers; each gives None where the window holds nothing to read, as a
program without these spans gives.
"""
from __future__ import annotations

import numpy as np

from bench import tracing

PREFIX = "serve."
TICK = "serve.tick"
WAIT = "serve.device_wait"
ADMIT_REQUEST = "serve.admit_request"
# a tick's own span and its phases: idle time under any of them is idle
# time inside the tick
TICK_FAMILY = (TICK, "serve.plan", "serve.step.dispatch", "serve.probe",
               "serve.sample.dispatch", WAIT, "serve.commit")
NONE = "none"


def innermost(spans, points) -> list:
    """For each of the sorted ``points``, the name of the innermost of
    ``spans`` ((start, end, name), nested as one thread's spans are) open
    at it, or None."""
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, stack, i = [], [], 0
    for t in points:
        while i < len(order) and order[i][0] <= t:
            while stack and stack[-1][1] <= order[i][0]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def reduce(path, merged=None) -> dict:
    """The window's program spans and the device idle time by span (see
    the module doc). Times in seconds from the window's start. ``path`` is
    a path or a ``tracing.Trace``; ``merged`` is each chip's union of
    operation intervals in the window, where it has been taken already."""
    tr = path if isinstance(path, tracing.Trace) else tracing.read(path)
    lo, hi = tr.lo, tr.hi
    if merged is None:
        merged = [tracing.union(tracing._clip([ev[1:] for ev in ops], lo, hi))
                  for ops, _ in tr.devices]
    ns = 1e-9
    prog = [ev for ev in tr.host if ev[0].startswith(PREFIX)]
    spans = sorted(([name, (s - lo) * ns, (e - lo) * ns, dict(ev.stats)]
                    for name, s, e, ev in prog if lo <= s < hi),
                   key=lambda sp: (sp[1], -sp[2]))
    open_ = [(s, e, name) for name, s, e, _ in prog if e > lo and s < hi]
    idle: dict = {}
    for chip in merged:
        edges = [lo] + [x for iv in chip for x in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                if ge > gs]
        who = innermost(open_, [(gs + ge) / 2 for gs, ge in gaps])
        for (gs, ge), name in zip(gaps, who):
            idle[name or NONE] = idle.get(name or NONE, 0) + ge - gs
    n = len(merged)
    return {"chips": n, "window_s": (hi - lo) * ns, "spans": spans,
            "idle": {k: v * ns / n for k, v in
                     sorted(idle.items(), key=lambda kv: -kv[1])}}


def _ticks(red) -> list:
    return [s for s in (red or {}).get("spans", []) if s[0] == TICK]


def tick_host_ms(red) -> float | None:
    """Mean host milliseconds of a tick: its span less the time its host
    blocked in ``serve.device_wait``."""
    ticks = _ticks(red)
    if not ticks:
        return None
    waits = [s for s in red["spans"] if s[0] == WAIT]
    host = []
    for _, s, e, _ in ticks:
        host.append(e - s - sum(we - ws for _, ws, we, _ in waits
                                if s <= ws and we <= e))
    return 1e3 * float(np.mean(host))


def tick_idle_ms(red) -> float | None:
    """Device idle milliseconds inside the window's ticks, per tick."""
    ticks = _ticks(red)
    if not ticks:
        return None
    idle = sum(red["idle"].get(name, 0.0) for name in TICK_FAMILY)
    return 1e3 * idle / len(ticks)


def admit_wait_p95_s(red) -> float | None:
    """p95 of the wait from submit to admission over the requests the
    window admitted."""
    waits = [s[3]["queued_s"] for s in (red or {}).get("spans", [])
             if s[0] == ADMIT_REQUEST and "queued_s" in s[3]]
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits, np.float64), 95))


def decode_prompt_row_share(red) -> float | None:
    """Percent of the decode step's active rows that fed a prompt token."""
    meta = [s[3] for s in _ticks(red) if "active" in s[3]]
    active = sum(m["active"] for m in meta)
    if not active:
        return None
    return 100.0 * sum(m["prompt_rows"] for m in meta) / active


def window_compiles(red) -> int | None:
    """Backend compiles in the window's ticks and admissions."""
    counts = [s[3]["compiles"] for s in (red or {}).get("spans", [])
              if "compiles" in s[3]]
    return int(sum(counts)) if counts else None


METRICS = {f.__name__: f for f in (tick_host_ms, tick_idle_ms,
                                   admit_wait_p95_s, decode_prompt_row_share,
                                   window_compiles)}
