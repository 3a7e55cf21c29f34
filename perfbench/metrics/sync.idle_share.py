"""Share of the traced window, in %, in which no op ran on the chip:
1 minus the union of the device-op intervals over the window."""
from perfbench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    share = trace.idle_share(ctx.trace)
    return None if share is None else 100 * share
