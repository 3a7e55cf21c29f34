"""Mean time per round the replica takes to apply an update
(``ServeEngine.ingest_weights``: checksum, fence, decode against its base,
ending at ``block_until_ready``), from the benchmark's host span."""
from perfbench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    spans = trace.spans_named(ctx.trace, "bench.sync.apply")
    return 1e3 * sum(spans) / len(spans) if spans else None
