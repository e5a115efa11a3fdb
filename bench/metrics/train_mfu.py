"""The train step's share of the chip's peak: the least time one step's
work needs (``work.train_step``: 6 x matmul weights x tokens plus causal
attention, no recomputation), over the device time per train step."""
from bench import peaks, work
from bench.layer import per_call_s


def read(ctx):
    s = per_call_s(ctx, "train_step")
    if s is None:
        return None
    f, b = work.train_step(ctx.conf, ctx.mix["batch"], ctx.mix["seq_len"],
                           ctx.conf["train"]["master_weights"])
    return 100.0 * peaks.least_seconds(f, b, ctx.device_kind, ctx.chips) / s
