"""Device milliseconds per call of the decode step program."""
from bench.layer import per_call_s


def read(ctx):
    s = per_call_s(ctx, "decode_step")
    return None if s is None else 1e3 * s
