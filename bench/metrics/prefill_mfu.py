"""Block prefill's share of the chip's peak: the least time the window's
prompts needed (``work.prefill``: each prompt's own tokens once), over the
device time the prefill program took, per call."""
import numpy as np

from bench import peaks, work
from bench.layer import per_call_s


def read(ctx):
    s = per_call_s(ctx, "prefill_into_cache")
    n = ctx.host.get("prefills")
    if s is None or not n:
        return None
    least = np.mean([peaks.least_seconds(*work.prefill(ctx.conf, k),
                                         ctx.device_kind, ctx.chips)
                     for k in n])
    return 100.0 * float(least) / s
