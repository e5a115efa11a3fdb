"""p95 of the wait from submit to admission over the requests the window
admitted. Read from the program's ``serve.*`` spans of the traced window
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.admit_wait_p95_s((ctx.trace or {}).get("program_spans"))
