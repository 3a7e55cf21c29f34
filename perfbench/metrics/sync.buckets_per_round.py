"""Plan buckets a round encodes: the program's ``sync:bucket`` spans in
the window, one per bucket of an encode, over the window's rounds."""
from perfbench import progspans


def read(ctx):
    if ctx.trace is None:
        return None
    spans = progspans.program_span_s(ctx.trace, "sync:bucket")
    return len(spans) / progspans.rounds(ctx.trace) if spans else None
