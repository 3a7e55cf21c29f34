"""A whole run on the CPU at a tiny size, past the look for a chip: the
result line's schema in both modes; and the real entry point, which exits
non-zero without a TPU and prints no result."""
import io
import json
import os
import subprocess
import sys

import pytest

from perfbench import counts, harness

from conftest import CELL, REPO, TINY_CELL, TINY_METRIC

TOP = ["correct", "attempted", "failed", "metrics", "device"]
BIG_SEED = 2**31 + 123


def _run(root, trace, **kw):
    err = io.StringIO()
    r = harness.run_cell(root, TINY_CELL, BIG_SEED, 0.3, trace,
                         require_tpu=False, err=err, **kw)
    json.dumps(r, allow_nan=False)
    return r, err.getvalue()


def test_untraced_result_line(bench_root, fresh_program):
    r, err = _run(bench_root, False)
    assert list(r)[:5] == TOP and list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"sync_ms", "setup_s"}
    assert r["metrics"]["sync_ms"]["unit"] == "ms"
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert r["checks"]["mismatched_elements"] == {"value": 0, "limit": 0}
    assert set(r["checks"]) == {"mismatched_elements", "plans_off_kernels",
                                "kernel_fallbacks"}
    last = err.strip().splitlines()[-len(r["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in last)


def test_traced_result_line(bench_root, fresh_program, monkeypatch):
    # the CPU is in no peaks table: read this run as if it were a v5e's
    monkeypatch.setattr(harness.ReadContext, "peaks",
                        lambda self: counts.peaks("TPU v5 lite"))
    r, _ = _run(bench_root, True)
    assert r["correct"] is True
    assert {"sync.encode_ms", "sync.apply_ms", "sync.wire_ratio",
            "sync.round_roofline"} <= set(r["metrics"])
    # no device plane on the CPU: the idle share is left out, never 0
    assert "sync.idle_share" not in r["metrics"]
    assert 0 < r["metrics"]["sync.wire_ratio"]["value"] < 1
    # the metric added as a file of its own
    assert r["metrics"][TINY_METRIC] == {"value": r["attempted"],
                                         "unit": "rounds"}
    assert 0 < r["metrics"]["sync.round_roofline"]["value"] < 100
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(r)[-1] == "checks"
    assert not os.path.exists(os.path.join(bench_root, ".bench_trace",
                                           TINY_CELL))


@pytest.mark.parametrize("trace", ["0", "1"])
def test_no_tpu_exits_nonzero_without_a_result(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed",
         str(BIG_SEED), "--seconds", "1", "--trace", trace], cwd=REPO,
        env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
