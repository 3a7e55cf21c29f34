"""Mean time per round from ``publish`` to the encoded update in hand
(``publish`` + ``update_for``: the store's copy, the delta codec, the
transfer to the host and the checksum), from the benchmark's host span."""
from perfbench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    spans = trace.spans_named(ctx.trace, "bench.sync.encode")
    return 1e3 * sum(spans) / len(spans) if spans else None
