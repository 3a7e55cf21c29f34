"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig7,fig9] [--json out.json]
                                            [--smoke]

``--json`` additionally writes a machine-readable summary (per-module wall
time / pass-fail / fallback counts / gate measurements, plus the obs
metrics snapshot) without changing anything on stdout — CI diffs the
file, humans read the console — and appends one record (date, per-module
wall + gates, failures, obs snapshot digest) to the repo-root
``BENCH_TRAJECTORY.json`` perf trajectory (schema: benchmarks/README.md).

``--smoke`` runs each module in its CI-gate configuration (``run(smoke=
True)`` where the module supports it) and ENFORCES the module's stated
wall-clock budget: a gate module declares ``SMOKE_BUDGET_S`` and a smoke
run that exceeds it is a failure — "finishes fast" is part of the smoke
contract (benchmarks/README.md), not a hope.
"""
from __future__ import annotations

import argparse
import datetime
import hashlib
import importlib
import inspect
import json
import os
import sys
import time
import traceback

MODULES = [
    ("table1", "benchmarks.table1_ratios"),
    ("fig3", "benchmarks.fig3_sublinear"),
    ("fig5c", "benchmarks.fig5c_local_tables"),
    ("fig7", "benchmarks.fig7_p2p"),
    ("fig8", "benchmarks.fig8_collectives"),
    ("fig9", "benchmarks.fig9_twoshot"),
    ("fig11", "benchmarks.fig11_kv_transfer"),
    ("fig12", "benchmarks.fig12_stability"),
    ("fig13", "benchmarks.fig13_dtypes"),
    ("fig15", "benchmarks.fig15_strategies"),
    ("fig16", "benchmarks.fig16_resources"),
    ("sched", "benchmarks.fig_sched"),
    ("encode", "benchmarks.fig_encode"),
    ("sync", "benchmarks.fig_sync"),
    ("faults", "benchmarks.fig_faults"),
    ("tree", "benchmarks.fig_tree"),
    ("drift", "benchmarks.fig_drift"),
    ("obs", "repro.obs.dump"),
]


def _scalarize(obj, depth: int = 3):
    """Keep the JSON-scalar skeleton of a module's ``run()`` return value
    (gate measurements); drop tables/arrays/objects."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if depth > 0 and isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            s = _scalarize(v, depth - 1)
            if s is not None or v is None:
                out[str(k)] = s
        return out or None
    try:  # 0-d numpy / jax scalars
        return _scalarize(obj.item(), 0)
    except (AttributeError, ValueError, TypeError):
        return None


def _supports_smoke(fn) -> bool:
    try:
        return "smoke" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated keys, e.g. fig7,fig9")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a machine-readable run summary to PATH")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-gate mode: run(smoke=True) where supported and "
                         "enforce each module's SMOKE_BUDGET_S wall budget")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro import kernels, obs
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    failures = []
    total: dict = {}
    modules_out = []
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.time()
        # Reset the counters per module: fallback attribution must name the
        # benchmark that actually degraded, not accumulate across figs (the
        # once-per-op warning also re-arms, so each module logs its own).
        # Same for the observatory: regret samples / drift windows / the
        # flight recorder must describe the module being measured, not its
        # predecessors (the metrics registry itself keeps accumulating —
        # the final snapshot is the whole run's).
        kernels.clear_fallbacks()
        obs.clear_observatory()
        ok = True
        budget_s = None
        gates = None
        try:
            mod = importlib.import_module(modname)
            if args.smoke and _supports_smoke(mod.run):
                budget_s = getattr(mod, "SMOKE_BUDGET_S", None)
                gates = _scalarize(mod.run(smoke=True))
            else:
                gates = _scalarize(mod.run())
            print(f"  [{key} done in {time.time()-t0:.1f}s]")
        except Exception:
            ok = False
            failures.append(key)
            print(f"  [{key} FAILED]")
            traceback.print_exc()
        wall_s = round(time.time() - t0, 3)
        # "finishes fast" is part of the smoke contract: a gate module
        # that blows its declared budget fails the run even if its
        # assertions passed
        over_budget = bool(args.smoke and ok and budget_s is not None
                           and wall_s > budget_s)
        if over_budget:
            ok = False
            failures.append(key)
            print(f"  [{key} OVER BUDGET: {wall_s:.1f}s > "
                  f"SMOKE_BUDGET_S={budget_s}s]")
        # Surface silent fast-path degrades (kernels.record_fallback): a
        # benchmark that quietly ran reference fallbacks would otherwise
        # report numbers for a dispatch it never exercised.
        per_module = kernels.fallback_counts()
        if per_module:
            print(f"  [{key} kernel fast-path fallbacks: {per_module}]")
        for op, c in per_module.items():
            total[op] = total.get(op, 0) + c
        modules_out.append({"key": key, "module": modname, "ok": ok,
                            "wall_s": wall_s, "budget_s": budget_s,
                            "over_budget": over_budget,
                            "fallbacks": per_module, "gates": gates})
    print(f"\nkernel fast-path fallbacks (all benchmarks): "
          f"{total if total else 'none'}")
    print(f"{'ALL BENCHMARKS PASSED' if not failures else 'FAILED: ' + ', '.join(failures)}")
    if args.json:
        obs_snap = obs.snapshot()
        summary = {
            "modules": modules_out,
            "failures": failures,
            "fallbacks_total": total,
            "obs": obs_snap,
        }
        path = os.path.abspath(args.json)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
        # one perf-trajectory record per recorded run: per-module wall +
        # gate measurements, tied to the obs snapshot by digest (schema in
        # benchmarks/README.md)
        digest = hashlib.sha256(
            json.dumps(obs_snap, sort_keys=True, default=str)
            .encode()).hexdigest()[:16]
        from benchmarks.common import append_trajectory
        append_trajectory({
            "date": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "source": "benchmarks.run",
            "smoke": bool(args.smoke),
            "modules": {m["key"]: {"ok": m["ok"], "wall_s": m["wall_s"],
                                   "gates": m["gates"]}
                        for m in modules_out},
            "failures": failures,
            "obs_digest": digest,
        })
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
