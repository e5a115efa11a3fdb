"""Device milliseconds per call of the block prefill program."""
from bench.layer import per_call_s


def read(ctx):
    s = per_call_s(ctx, "prefill_into_cache")
    return None if s is None else 1e3 * s
