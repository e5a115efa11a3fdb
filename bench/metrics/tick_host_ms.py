"""Mean host milliseconds of a tick: its span less the time its host blocked
in ``serve.device_wait``. Read from the program's ``serve.*`` spans of the
traced window (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.tick_host_ms((ctx.trace or {}).get("program_spans"))
