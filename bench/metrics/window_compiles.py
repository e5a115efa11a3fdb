"""Backend compiles in the window's ticks and admissions. Read from the
program's ``serve.*`` spans of the traced window (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.window_compiles((ctx.trace or {}).get("program_spans"))
