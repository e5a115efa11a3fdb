"""What the per-layer readers under ``metrics/`` share: the context they
read from, and the device time per call of one jitted program."""
from __future__ import annotations

from dataclasses import dataclass, field

from bench import plugins


@dataclass
class Context:
    conf: dict                  # the configuration file
    mix: dict                   # the traffic file
    device_kind: str
    chips: int
    trace: dict | None = None   # tracing.reduce of the traced window
    host: dict = field(default_factory=dict)   # the harness's own records


def per_call_s(ctx: Context, program: str) -> float | None:
    """Mean device seconds of one call of ``program`` in the window."""
    p = (ctx.trace or {}).get("programs", {}).get(program)
    if not p or p["count"] <= 0 or p["device_s"] <= 0:
        return None
    return p["device_s"] / p["count"]


def idle_percent(ctx: Context) -> float | None:
    t = ctx.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def read(name: str, ctx: Context):
    """Run ``metrics/<name>.py``'s ``read``; None where it finds nothing."""
    return plugins.load("metrics", name).read(ctx)
