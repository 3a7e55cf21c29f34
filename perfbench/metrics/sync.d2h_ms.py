"""Mean time per round the encoded wire takes to reach the host: the
program's ``sync:d2h`` spans (``jax.device_get`` of each bucket's message,
raw buckets' and raw leaves' host copies), summed over the window and
divided by its rounds."""
from perfbench import progspans


def read(ctx):
    if ctx.trace is None:
        return None
    return progspans.per_round_ms(ctx.trace, "sync:d2h")
