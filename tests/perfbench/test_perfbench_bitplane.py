"""``sync.bitplane_roofline`` on hand-made planes whose answer is counted
by hand, and silent where the programs it reads are absent."""
import os
from types import SimpleNamespace as NS

import pytest

from perfbench import counts, harness, trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = "TPU v5 lite"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _trace(programs):
    """One chip over a window 0..10,000 ns: the programs ``programs`` run
    back to back from 1,000 ns, 1,000 ns each, one op each."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 10_000)])])
    mods = [_ev(f"{p}(123)", 1000 * (i + 1), 1000)
            for i, p in enumerate(programs)]
    ops = [_ev(f"%fusion.{i} = u32[8] fusion(u32[8] %a)", 1000 * (i + 1),
               1000) for i in range(len(programs))]
    chip = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=mods), NS(name="XLA Ops", events=ops)])
    return trace.from_planes([host, chip])


def _read(tr, rounds):
    reader = harness.reader_of(REPO, "sync.bitplane_roofline")
    return reader.read(harness.ReadContext(
        trace=tr, counters={"rounds": rounds}, device_kind=V5E))


def test_share_counts_the_two_programs_only():
    tr = _trace(["jit_bitplane_pack", "jit_gather", "jit_bitplane_unpack",
                 "jit_bitplane_pack_extra"])
    rounds = [{"raw_bytes": 100, "wire_bytes": 60, "s": 1.0}] * 3
    hbm = counts.peaks(V5E)["hbm_bytes_per_s"]
    want = 100 * (3 * 2 * 100 / hbm) / 2e-6  # 2 programs of 1,000 ns
    assert _read(tr, rounds) == pytest.approx(want)


def test_silent_without_the_programs_or_rounds():
    rounds = [{"raw_bytes": 100, "wire_bytes": 60, "s": 1.0}]
    assert _read(_trace(["jit_gather"]), rounds) is None
    assert _read(_trace(["jit_bitplane_pack"]), []) is None
    assert _read(None, rounds) is None
