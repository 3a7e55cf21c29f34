"""Device trace capture and its reduction to numbers.

A traced run wraps its measured window in :func:`capture`, which runs the
JAX profiler and marks the window with a host span.  :func:`load` reads the
profiler's ``.xplane.pb`` into a :class:`Trace`: the window, each chip's
device ops, and the benchmark's own host spans (``bench.*``), all on the
profiler's one clock.  The functions below reduce it: the union of busy
intervals, the idle share, the time of ops picked by name (collectives,
codec kernels), and the breakdown printed with a traced result.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"  # the ops that count as busy
PROGRAMS_LINE = "XLA Modules"  # the compiled program each op ran in
# HLO collectives as they are named in the trace (async pairs included)
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|reduce-scatter|collective-permute|all-reduce")


@dataclasses.dataclass
class Trace:
    window: tuple  # (start_ns, end_ns) of the measured window
    # device plane name -> [(start_ns, end_ns, HLO text, program name)]
    device_ops: dict
    spans: list  # [(name, start_ns, end_ns)] host spans of the benchmark

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


@contextlib.contextmanager
def capture(log_dir: str, enabled: bool):
    """Trace the block (when ``enabled``) and mark it as the window."""
    import jax

    if not enabled:
        yield
        return
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans only: no per-call events
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> Trace:
    """The newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_planes(ProfileData.from_file(paths[-1]).planes)


def from_planes(planes) -> Trace:
    """Build a :class:`Trace` from profiler planes (anything with ``name``,
    ``lines``; lines with ``name``, ``events``; events with ``name``,
    ``start_ns``, ``duration_ns``)."""
    device_ops, spans = {}, []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line.events for line in plane.lines}
            programs = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns),
                 e.name.split("(")[0])
                for e in lines.get(PROGRAMS_LINE, ()))
            starts = [p[0] for p in programs]
            ops = []
            for e in lines.get(OPS_LINE, ()):
                s = int(e.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                program = programs[i][2] if i >= 0 and s < programs[i][1] \
                    else ""
                ops.append((s, int(e.start_ns + e.duration_ns), e.name,
                            program))
            device_ops[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    window = max(windows, key=lambda w: w[1] - w[0])
    return Trace(window=window, device_ops=device_ops,
                 spans=sorted(((n, s, e) for n, s, e in spans
                               if n != WINDOW_SPAN), key=lambda x: x[1]))


def merge(intervals, lo: int, hi: int) -> list:
    """Sorted disjoint union of ``(start, end, ...)`` intervals clipped to
    ``[lo, hi]``, as ``[(start, end)]``."""
    out = []
    for iv in sorted(intervals):
        s, e = max(iv[0], lo), min(iv[1], hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(intervals, lo: int, hi: int) -> list:
    """The idle intervals of ``[lo, hi]`` between the merged intervals."""
    out, t = [], lo
    for s, e in merge(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def busy_s(tr: Trace) -> float:
    """Seconds of the window in which some op ran, averaged over chips."""
    if not tr.device_ops:
        return 0.0
    lo, hi = tr.window
    per = [sum(e - s for s, e in merge(ops, lo, hi)) * 1e-9
           for ops in tr.device_ops.values()]
    return sum(per) / len(per)


def idle_share(tr: Trace):
    """1 minus busy over the window, or None without device planes."""
    if not tr.device_ops or tr.window_s <= 0:
        return None
    return 1.0 - busy_s(tr) / tr.window_s


def op_time_s(tr: Trace, pick) -> float:
    """Device seconds of the window's ops whose name ``pick`` accepts,
    averaged over chips (the union, so nested events count once)."""
    if not tr.device_ops:
        return 0.0
    lo, hi = tr.window
    per = [sum(e - s for s, e in merge(
        [o for o in ops if pick(o[2])], lo, hi)) * 1e-9
        for ops in tr.device_ops.values()]
    return sum(per) / len(per)


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name))


def name_matcher(patterns):
    """A ``pick`` for :func:`op_time_s` from regular expressions."""
    rx = [re.compile(p) for p in patterns]
    return lambda name: any(r.search(name) for r in rx)


def spans_named(tr: Trace, name: str) -> list:
    """Durations in seconds of the host spans ``name`` inside the window."""
    lo, hi = tr.window
    return [(e - s) * 1e-9 for n, s, e in tr.spans
            if n == name and s >= lo and e <= hi]


def label_at(tr: Trace, t: int) -> str:
    """The innermost benchmark span open at ``t``."""
    best = None
    for n, s, e in tr.spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "outside any span"


def op_label(name: str, program: str) -> str:
    """``"<program> <op>"``: ``jit_scatter-add %fusion`` for the op
    ``%fusion = s32[...] fusion(...)`` of the program
    ``jit_scatter-add(<hash>)``."""
    return f"{program} {name.split(' = ')[0]}".strip()


def breakdown(tr: Trace, n: int = 10) -> dict:
    """The device ops that took most time (summed by program and op name,
    averaged over chips) and the longest idle gaps, each named by the host
    span open at its middle (on the first chip)."""
    lo, hi = tr.window
    by_name: dict = {}
    for ops in tr.device_ops.values():
        for s, e, name, program in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = op_label(name, program)
                by_name[key] = by_name.get(key, 0.0) + (e - s) * 1e-9
    k = max(len(tr.device_ops), 1)
    top = sorted(((name, t / k) for name, t in by_name.items()),
                 key=lambda x: -x[1])[:n]
    first = sorted(tr.device_ops)[0] if tr.device_ops else None
    idle = gaps(tr.device_ops[first], lo, hi) if first else [(lo, hi)]
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:n]
    return {"device_ops": [[name, t] for name, t in top],
            "idle_gaps": [[label_at(tr, (s + e) // 2), (e - s) * 1e-9]
                          for s, e in idle]}
