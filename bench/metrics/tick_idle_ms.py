"""Device idle milliseconds inside the window's ticks, per tick. Read from
the program's ``serve.*`` spans of the traced window (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.tick_idle_ms((ctx.trace or {}).get("program_spans"))
