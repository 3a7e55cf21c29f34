"""The bit-plane packer's and unpacker's share of the HBM roofline, in %:
the least time their work needs over the device time of the programs
``bitplane_pack`` and ``bitplane_unpack`` in the window (their name
patterns in ``perfbench/kernels/``, matched against each device op's
program).

The work is counted from the rounds' counters as the HBM bytes any
implementation has to move: the packer reads the element planes it packs,
and the unpacker writes them back, ``raw_bytes`` each way (a float's
planes hold exactly its bits).  The packed words, which the packer writes
and the unpacker reads, are left out: the counters do not split them from
the exception lists in ``wire_bytes``, and the count may never exceed the
work.  A program without these two programs gives nothing."""
import json
import os

from perfbench import counts, trace

KERNELS = ("bitplane_pack", "bitplane_unpack")
KERNEL_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "kernels")


def patterns() -> list:
    out = []
    for k in KERNELS:
        with open(os.path.join(KERNEL_DIR, f"{k}.json")) as f:
            out += json.load(f)["programs"]
    return out


def program_time_s(tr, pick) -> float:
    """Device seconds of the window's ops whose program ``pick`` accepts,
    averaged over chips."""
    lo, hi = tr.window
    per = [sum(e - s for s, e in trace.merge(
        [o for o in ops if pick(o[3])], lo, hi)) * 1e-9
        for ops in tr.device_ops.values()]
    return sum(per) / len(per)


def read(ctx):
    rounds = ctx.counters.get("rounds") or []
    if ctx.trace is None or not ctx.trace.device_ops or not rounds:
        return None
    seconds = program_time_s(ctx.trace, trace.name_matcher(patterns()))
    if seconds <= 0:
        return None
    work = sum(2 * r["raw_bytes"] for r in rounds)
    return 100 * counts.roofline_share(hbm_bytes=work, seconds=seconds,
                                       peak=ctx.peaks())
