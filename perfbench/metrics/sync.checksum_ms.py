"""Mean time per round spent on the wire's CRC-32 at both ends: the
program's ``sync:checksum`` spans (the trainer) and ``serve:verify``
spans (the replica), summed over the window and divided by its rounds."""
from perfbench import progspans


def read(ctx):
    if ctx.trace is None:
        return None
    return progspans.per_round_ms(ctx.trace, "sync:checksum", "serve:verify")
