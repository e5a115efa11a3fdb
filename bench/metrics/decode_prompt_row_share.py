"""Percent of the decode step's active rows that fed a prompt token. Read
from the program's ``serve.*`` spans of the traced window
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.decode_prompt_row_share(
        (ctx.trace or {}).get("program_spans"))
