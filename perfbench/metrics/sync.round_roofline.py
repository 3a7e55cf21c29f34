"""The whole round's share of the chip's HBM peak, in %: the least time a
lossless round needs (``counts.sync_round_bytes`` over the HBM peak) over
the mean round time on the host clock.  Work is counted in raw bytes only,
so the share reads the same whatever implements the codec."""
from perfbench import counts


def read(ctx):
    rounds = ctx.counters.get("rounds") or []
    if not rounds:
        return None
    raw = sum(r["raw_bytes"] for r in rounds) / len(rounds)
    mean_s = sum(r["s"] for r in rounds) / len(rounds)
    return 100 * counts.roofline_share(
        hbm_bytes=counts.sync_round_bytes(raw), seconds=mean_s,
        peak=ctx.peaks())
