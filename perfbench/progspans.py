"""The program's own spans in a traced window, per sync round.

The program (``repro.obs``) records each span twice: as a profiler
annotation, on the trace's clock, and in its in-process ring buffer
(``obs.spans()``), on ``perf_counter``.  A :class:`trace.Trace` keeps only
the benchmark's ``bench.*`` host spans, so the readers here take the ring
buffer's copy.  Its clock is not the trace's, so the window is found by
counting rounds: the trace counts the window's ``bench.sync.round`` spans,
each round opens with the program's one ``sync:publish`` span, and no
round runs after the window.  The window's spans are those that start at
or after the ``sync:publish`` that many rounds back from the newest.

On a program that records no span of a name (an older one, or one run
with ``REPRO_OBS=0``) the readers find nothing and return None.
"""
from __future__ import annotations

from perfbench import trace

ROUND_SPAN = "bench.sync.round"
ROUND_OPENS = "sync:publish"


def rounds(tr: trace.Trace) -> int:
    """The sync rounds of the window."""
    return len(trace.spans_named(tr, ROUND_SPAN))


def program_span_s(tr: trace.Trace, name: str) -> list:
    """Durations in seconds of the program's spans ``name`` that lie in
    the window's rounds."""
    from repro import obs

    recs = [r for r in obs.spans() if r.ph == "X"]
    opens = sorted(r.ts for r in recs if r.name == ROUND_OPENS)
    n = rounds(tr)
    if n == 0 or len(opens) < n:
        return []
    t0 = opens[-n]
    return [r.dur for r in recs if r.name == name and r.ts >= t0]


def per_round_ms(tr: trace.Trace, *names: str):
    """Milliseconds per round of the program spans ``names``, summed; None
    where the window holds none of them."""
    spans = [s for name in names for s in program_span_s(tr, name)]
    return 1e3 * sum(spans) / rounds(tr) if spans else None
