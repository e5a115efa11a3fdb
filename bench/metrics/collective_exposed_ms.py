"""Milliseconds a tick in which a collective ran on a chip and nothing
else did (``tracing.reduce``'s ``collective_exposed_s``, averaged over the
chips), over the ticks of the window: the part of the ring's exchange
that compute does not hide."""


def read(ctx):
    ticks = ctx.host.get("ticks")
    if not ctx.trace or not ticks:
        return None
    return 1e3 * ctx.trace["collective_exposed_s"] / len(ticks)
