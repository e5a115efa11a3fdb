"""The decode step's share of the chip's peak: the least time the work
the window's ticks needed could take (``work.decode_step``: weights once,
the K/V of each active row's valid positions once, its new K/V once), over
the device time the decode step program took, per call."""
import numpy as np

from bench import peaks, work
from bench.layer import per_call_s


def read(ctx):
    s = per_call_s(ctx, "decode_step")
    ticks = ctx.host.get("ticks")
    if s is None or not ticks:
        return None
    least = np.mean([peaks.least_seconds(*work.decode_step(ctx.conf, r, p),
                                         ctx.device_kind, ctx.chips)
                     for r, p in ticks])
    return 100.0 * float(least) / s
