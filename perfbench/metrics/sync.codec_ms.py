"""Mean time per round the host waits for the device codec: the
program's ``sync:codec`` spans, from the ``encode_delta`` or
``encode_message`` dispatch through the host's read of its overflow
flag, summed over the window and divided by its rounds."""
from perfbench import progspans


def read(ctx):
    if ctx.trace is None:
        return None
    return progspans.per_round_ms(ctx.trace, "sync:codec")
