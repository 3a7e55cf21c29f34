"""Run one cell once: find its files by name, hold its chips, drive it,
reduce what it measured, and assemble the result line.

The cell's generator (``generators/<mix generator>.py``) sets up, warms
up, runs the measured window inside :meth:`RunContext.window` and compares
what the window produced with its own reference.  This module adds what
every cell shares: the look for the chips, the checks on the program's
kernel dispatch, the per-layer readers of a traced run, and the result
line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time

from perfbench import chain, counts, trace as trace_lib


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list  # metric entries of BENCHMARK.json that apply here
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    w = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "mixes",
                           f"{w['traffic']}.json")) as f:
        mix = json.load(f)
    return Cell(name=workload, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)])


def load_module(path: str):
    """Import the file ``path`` as a module of its own."""
    name = "perfbench_file_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator_of(root: str, cell: Cell):
    return load_module(os.path.join(root, "perfbench", "generators",
                                    f"{cell.mix['generator']}.py"))


def reader_of(root: str, metric: str):
    return load_module(os.path.join(root, "perfbench", "metrics",
                                    f"{metric}.py"))


class CompileCounter:
    """Counts XLA compilations process-wide."""

    def __init__(self):
        import jax

        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


@dataclasses.dataclass
class RunContext:
    """What a generator gets: the cell, the run's arguments and the chips."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float  # perf_counter at process start
    trace_dir: str
    compiles: CompileCounter
    hooks: dict  # seams for the control and the tests; empty in a run
    setup_s: float = None
    window_compiles: int = 0

    @property
    def key(self):
        return chain.seed_key(self.seed)

    def end_setup(self) -> None:
        """Call right before the first timed operation."""
        self.setup_s = time.perf_counter() - self.t_start

    @contextlib.contextmanager
    def window(self):
        """Wrap the measured window: traced in a ``--trace 1`` run, and
        its compilations counted."""
        c0 = self.compiles.compiles
        try:
            with trace_lib.capture(self.trace_dir, self.trace):
                yield
        finally:
            self.window_compiles = self.compiles.compiles - c0


@dataclasses.dataclass
class Outcome:
    """What a generator returns."""

    end_to_end: dict  # metric name -> value (setup_s is added here)
    counters: dict  # for the per-layer readers
    attempted: int
    failed: int
    checks: list  # [(name, value, limit)]: correct needs value <= limit
    memory_peak_bytes: int
    plan_kinds: tuple  # compressed plan kinds the run must have used


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader gets."""

    trace: object  # trace.Trace, or None
    counters: dict
    device_kind: str

    def peaks(self) -> dict:
        return counts.peaks(self.device_kind)


def find_chips(chips: int, require_tpu: bool = True) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def program_checks(platform: str, plan_kinds) -> list:
    """The program's kernel dispatch: on the TPU every compressed plan has
    to drive the compiled Pallas kernels, and no kernel may have fallen
    back to its reference."""
    from repro import kernels, sched

    want_pallas = platform == "tpu"
    plans = [p for p in sched.default_cache().plans() if p.raw_bytes > 0]
    off = sum(1 for p in plans
              if p.backend != platform or bool(p.use_pallas) != want_pallas)
    missing = len(set(plan_kinds) - {p.kind for p in plans})
    return [("plans_off_kernels", off + missing, 0),
            ("kernel_fallbacks", sum(kernels.fallback_counts().values()), 0)]


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float = None, require_tpu: bool = True,
             hooks: dict = None, err=None) -> dict:
    """Run the cell once and return its result line (a dict).  Prints each
    number compared, beside its limit, as the last lines on ``err``."""
    err = sys.stderr if err is None else err
    t_start = time.perf_counter() if t_start is None else t_start
    cell = resolve(root, workload)
    devices = find_chips(cell.chips, require_tpu)
    platform, kind = devices[0].platform, devices[0].device_kind
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace,
                     devices=devices, t_start=t_start,
                     trace_dir=os.path.join(root, ".bench_trace", workload),
                     compiles=CompileCounter(), hooks=hooks or {})
    out = generator_of(root, cell).run(ctx)
    checks = list(out.checks) + program_checks(platform, out.plan_kinds)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": None, "attempted": out.attempted,
              "failed": out.failed, "metrics": {}, "device": device}
    if trace:
        tr = trace_lib.load(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device["busy_s"] = trace_lib.busy_s(tr)
        device["window_s"] = tr.window_s
        rctx = ReadContext(trace=tr, counters=out.counters,
                           device_kind=kind)
        for m in cell.per_layer:
            value = reader_of(root, m["name"]).read(rctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = trace_lib.breakdown(tr)
    else:
        values = dict(out.end_to_end, setup_s=ctx.setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["correct"] = out.failed == 0 and all(v <= lim
                                                for _, v, lim in checks)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    print(f"info: setup_s {ctx.setup_s} window_compiles "
          f"{ctx.window_compiles} attempted {out.attempted} failed "
          f"{out.failed}", file=err)
    for n, v, lim in checks:
        print(f"check {n} {v} limit {lim} "
              f"{'ok' if v <= lim else 'FAILED'}", file=err)
    err.flush()
    return result
