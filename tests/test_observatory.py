"""Wire-efficiency observatory: flight recorder, per-bucket wire ledger
+ width regret, drift detection, reporting, and the perf trajectory."""
import json
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import policy as policy_mod
from repro.obs import drift as drift_lib
from repro.obs import regret as regret_lib
from repro.obs.drift import DriftDetector
from repro.obs.recorder import FlightRecorder, sparkline


@pytest.fixture(autouse=True)
def _isolate():
    """Every test starts from an empty observatory, obs enabled."""
    obs.set_enabled(True)
    obs.reset()
    yield
    obs.set_enabled(None)  # restore the env-derived setting
    obs.reset()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_recorder_window_and_eviction():
    rec = FlightRecorder(capacity=4)
    for v in range(10):
        rec.record("m", float(v + 1))
    got = rec.samples("m")
    assert [s.value for s in got] == [7.0, 8.0, 9.0, 10.0]  # ring evicted
    assert [s.step for s in got] == [7, 8, 9, 10]  # steps keep counting
    st = rec.window("m")
    assert (st.count, st.total, st.mean) == (4, 34.0, 8.5)
    assert (st.minimum, st.maximum, st.last) == (7.0, 10.0, 10.0)
    assert (st.first_step, st.last_step) == (7, 10)
    # n= trims within the retained ring
    assert [s.value for s in rec.samples("m", n=2)] == [9.0, 10.0]
    assert rec.window("missing") is None
    rec.clear()
    assert rec.series() == () and rec.record("m", 1.0) == 1  # step reset


def test_recorder_quantiles():
    rec = FlightRecorder(capacity=32)
    for v in range(1, 11):
        rec.record("m", float(v))
    st = rec.window("m")
    assert st.p50 == pytest.approx(5.5)
    assert st.p90 == pytest.approx(9.1)
    assert st.p99 == pytest.approx(9.91)


def test_recorder_label_kwargs_resolve_against_specs():
    rec = FlightRecorder(capacity=8)
    rec.record("plan_exec_total", 1.0, "kind=psum")
    got = rec.samples("plan_exec_total", kind="psum")  # kwargs -> spec order
    assert len(got) == 1 and got[0].value == 1.0
    assert rec.window("plan_exec_total", kind="psum").series == \
        "plan_exec_total|kind=psum"
    with pytest.raises(ValueError):
        rec.samples("plan_exec_total", wrong="x")
    with pytest.raises(ValueError):
        rec.samples("plan_exec_total", labels_key="kind=psum", kind="psum")


def test_registry_tee_feeds_recorder():
    """obs.metric() observations land in the flight recorder with the
    registry's exact series key — counters record the increment, gauges
    the level, histograms the observation, dec a negative value."""
    obs.metric("plan_exec_total").inc(kind="psum")
    obs.metric("plan_exec_total").inc(2, kind="psum")
    obs.metric("serve_queue_depth").inc()
    obs.metric("serve_queue_depth").dec()
    obs.metric("plan_wire_ratio").set(0.25, kind="psum")
    obs.metric("p2p_encode_seconds").observe(0.125, codec="width")
    rec = obs.recorder()
    assert [s.value for s in rec.samples("plan_exec_total", kind="psum")] \
        == [1.0, 2.0]
    assert [s.value for s in rec.samples("serve_queue_depth")] == [1.0, -1.0]
    assert [s.value for s in rec.samples("plan_wire_ratio", kind="psum")] \
        == [0.25]
    assert [s.value for s in rec.samples("p2p_encode_seconds",
                                         codec="width")] == [0.125]
    # the tee still validates: bad labels raise, nothing recorded
    with pytest.raises(ValueError):
        obs.metric("plan_exec_total").inc(wrong="x")
    # registry values unaffected by the tee
    assert obs.snapshot()["counters"]["plan_exec_total"] == {"kind=psum": 3}


def test_recorder_thread_safety():
    rec = FlightRecorder(capacity=1000)

    def worker(i):
        for _ in range(250):
            rec.record("m", 1.0, f"t={i}")

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    per = [rec.samples("m", labels_key=f"t={i}") for i in range(4)]
    assert [len(p) for p in per] == [250] * 4
    steps = sorted(s.step for p in per for s in p)
    assert steps == list(range(1, 1001))  # globally unique, gap-free


def test_sparkline():
    assert sparkline([]) == ""
    assert sparkline([1.0, 1.0]) == "▁▁"  # flat series
    s = sparkline([0, 1, 2, 3])
    assert len(s) == 4 and s[0] == "▁" and s[-1] == "█"


# ---------------------------------------------------------------------------
# per-bucket wire ledger: exact agreement with the roofline summary
# ---------------------------------------------------------------------------

def _run_plan_psum():
    from jax.sharding import PartitionSpec as P

    from repro import sched
    from repro.core.policy import CompressionPolicy

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    pol = CompressionPolicy(min_bytes=0)
    cache = sched.PlanCache()
    tree = {"w": jnp.arange(4096, dtype=jnp.float32)}

    def fn(t):
        return sched.psum_with_plan(t, "data", policy=pol, cache=cache)

    f = jax.shard_map(fn, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
                      axis_names={"data"}, check_vma=False)
    return f(tree)


def test_bucket_ledger_agrees_exactly_with_wire_reports():
    """The acceptance contract: the per-bucket ledger sums to EXACTLY the
    consolidated plan:* WireReport totals (the executor re-forwards each
    bucket capture), so regret analytics and the roofline agree."""
    from repro.roofline.analysis import summarize_wire_reports

    policy_mod.clear_wire_reports()
    _run_plan_psum()
    reports = policy_mod.wire_reports()
    res = regret_lib.check_ledger_exactness(reports)
    assert res["ok"], res["diffs"]
    summ = summarize_wire_reports(
        [r for r in reports if r.name.startswith("plan:")])
    led = regret_lib.ledger_totals()
    assert led["by_kind"]["psum"]["raw_bytes"] == summ["raw_bytes"]
    assert led["by_kind"]["psum"]["wire_bytes"] == summ["wire_bytes"]
    # ledger rows carry real (kind, dtype, width) coordinates
    assert all(k == "psum" and d == "float32"
               for (k, d, _) in led["by_bucket"])


def test_ledger_exactness_flags_diffs():
    """A ledger entry with no plan counterpart is a reported diff."""
    obs.metric("bucket_wire_raw_bytes_total").inc(
        100, kind="psum", dtype="float32", width=5)
    obs.metric("bucket_wire_bytes_total").inc(
        40, kind="psum", dtype="float32", width=5)
    res = regret_lib.check_ledger_exactness([])
    assert not res["ok"] and "psum" in res["diffs"]


def test_plan_wire_ratio_hist_and_drift_observation():
    """One plan execution populates the labeled ratio histogram (satellite
    2) and feeds the drift detector with a zero-excess observation —
    static executor wires match their prediction exactly, so stationary
    traffic can never fire it."""
    _run_plan_psum()
    snap = obs.snapshot()
    h = snap["histograms"]["plan_wire_ratio_hist"]["kind=psum"]
    assert h["count"] == 1
    assert snap["gauges"]["plan_wire_ratio"]["kind=psum"] == \
        pytest.approx(h["sum"])  # gauge kept alongside the histogram
    # the tee recorded the ratio series for sparkline reports
    assert len(obs.recorder().samples("plan_wire_ratio_hist",
                                      kind="psum")) == 1
    st = drift_lib.detector()._state
    assert len(st) == 1
    (key, ks), = st.items()
    assert ks.kind == "psum" and list(ks.ring) == [pytest.approx(1.0)]
    assert drift_lib.detector().report().events == ()


# ---------------------------------------------------------------------------
# host-path ledger + samples + width regret
# ---------------------------------------------------------------------------

def _sync_workload(n=4096, warm=3, shifted=0, shift_scale=0.5):
    from benchmarks.fig_sync import _calibrated_policy, _make_params, \
        _optimizer_step

    from repro.sync import WeightSyncEngine, apply_update

    params = _make_params(n, seed=7)
    v1 = _optimizer_step(params, 2e-4, seed=8)
    policy, _ = _calibrated_policy(params, v1)
    eng = WeightSyncEngine(policy=policy)
    held = None
    modes = []
    for it in range(warm + shifted):
        if 0 < it < warm:
            params = _optimizer_step(params, 2e-4, seed=10 + it)
        elif it >= warm:
            params = _optimizer_step(params, shift_scale, seed=50 + it)
        eng.publish(params)
        upd = eng.update_for("r0")
        held = apply_update(upd, base_params=held
                            if upd.base_version is not None else None)
        eng.ack("r0", upd.version, upd.epoch)
        modes.append(upd.mode)
    return modes


def test_wsync_host_ledger_samples_and_regret():
    modes = _sync_workload(warm=3)
    assert "delta" in modes  # the warm loop actually took the delta path
    led = regret_lib.ledger_totals()
    assert "wsync_host" in led["by_kind"]
    assert led["by_kind"]["wsync_host"]["raw_bytes"] > 0
    assert 0 < led["by_kind"]["wsync_host"]["ratio"] < 1
    # host kinds stay OUT of the plan-kind exactness check
    assert regret_lib.check_ledger_exactness([])["ok"]
    samp = regret_lib.samples()
    assert ("wsync_host", "bfloat16") in samp
    assert any(e.base is not None for e in samp[("wsync_host", "bfloat16")])
    rows = regret_lib.width_regret()
    assert rows and rows[0].kind == "wsync_host"
    r = rows[0]
    assert r.dtype_name == "bfloat16" and r.n_samples >= 1
    assert r.achieved_raw_bytes > 0 and r.optimal_width >= 1
    assert r.regret_bytes == r.achieved_wire_bytes - r.optimal_wire_bytes
    assert r.optimal_delta_widths is not None  # delta-base pair retained
    d = r.to_dict()
    json.dumps(d)  # report row must be JSON-clean


def test_sample_store_downsamples_and_bounds():
    big = np.arange(regret_lib.SAMPLE_MAX_ELEMS * 4, dtype=np.float32)
    regret_lib.record_sample("k", "float32", big, base=big + 1)
    (s,) = regret_lib.samples()[("k", "float32")]
    assert s.elems == big.size and s.x.size <= regret_lib.SAMPLE_MAX_ELEMS
    assert np.all(s.base == s.x + 1)  # element pairing survives the stride
    for i in range(regret_lib.SAMPLE_CAPACITY + 3):
        regret_lib.record_sample("k", "float32", np.ones(4) * i)
    ring = regret_lib.samples()[("k", "float32")]
    assert len(ring) == regret_lib.SAMPLE_CAPACITY  # bounded


def _float_bits(shape, dtype, seed):
    """Random bit patterns of ``dtype``'s width with NaN payloads (quiet
    and signalling, both signs), subnormals, infinities and zeros
    planted, as a device array of ``dtype``."""
    fi = jnp.finfo(dtype)
    bits, nmant = fi.bits, fi.nmant
    uint = np.dtype(f"uint{bits}")
    exp_all = ((1 << (bits - 1 - nmant)) - 1) << nmant
    sign = 1 << (bits - 1)
    special = [exp_all | 1, sign | exp_all | (1 << (nmant - 1)) | 5,
               exp_all | ((1 << nmant) - 1), 1, sign | ((1 << nmant) - 1),
               exp_all, sign | exp_all, 0, sign]
    rng = np.random.default_rng(seed)
    u = rng.integers(0, 1 << bits, size=int(np.prod(shape)),
                     dtype=np.uint64).astype(uint)
    u[rng.choice(u.size, 64 * len(special), replace=False)] = np.repeat(
        np.asarray(special, uint), 64)
    u[::regret_lib.SAMPLE_MAX_ELEMS // 7] = special[0]  # some are sampled
    return jax.lax.bitcast_convert_type(jnp.asarray(u.reshape(shape)), dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16, jnp.float32])
def test_device_sample_is_the_host_stride_bit_for_bit(dtype, monkeypatch):
    """A device bucket is strided on the device: the sample and its base
    are the bits ``np.asarray(x).reshape(-1)[::stride]`` keeps, and only
    the sample crosses to the host."""
    shape = (7, 28091)  # 196,637 elements: stride 4
    x, base = _float_bits(shape, dtype, 0), _float_bits(shape, dtype, 1)
    stride = -(-x.size // regret_lib.SAMPLE_MAX_ELEMS)
    want_x = np.asarray(x).reshape(-1)[::stride]
    want_base = np.asarray(base).reshape(-1)[::stride]
    crossed = []
    strided = regret_lib._strided_bits

    def spy(arrays, s):
        out = strided(arrays, s)
        crossed.append([o.size for o in out])
        return out

    monkeypatch.setattr(regret_lib, "_strided_bits", spy)
    regret_lib.record_sample("wsync_host", jnp.dtype(dtype).name, x,
                             base=base)
    (s,) = regret_lib.samples()[("wsync_host", jnp.dtype(dtype).name)]
    uint = f"uint{jnp.finfo(dtype).bits}"
    assert s.elems == x.size and s.x.dtype == want_x.dtype
    assert np.array_equal(s.x.view(uint), want_x.view(uint))
    assert np.array_equal(s.base.view(uint), want_base.view(uint))
    assert np.isnan(s.x.astype(np.float32)).any()  # NaNs were sampled
    assert crossed == [[want_x.size, want_base.size]]  # one call


# ---------------------------------------------------------------------------
# drift detection
# ---------------------------------------------------------------------------

def test_drift_fires_once_rearms_and_refires():
    det = DriftDetector(window=4, min_count=2, enter=0.2, exit=0.05)
    assert not any(det.observe("k", "psum", 0.5, 0.5) for _ in range(5))
    fired = [det.observe("k", "psum", 0.5, 1.0) for _ in range(4)]
    assert sum(fired) == 1  # once per excursion, however long it lasts
    rep = det.report()
    assert len(rep.events) == 1 and len(rep.stale) == 1
    ev = rep.events[0]
    assert ev.kind == "psum" and ev.live_ratio > ev.predicted_ratio
    assert rep.stale[0].key_hex == ev.key_hex
    # recovery re-arms (window refills with matching traffic) ...
    for _ in range(6):
        det.observe("k", "psum", 0.5, 0.5)
    assert det.report().stale == ()
    # ... and a second excursion fires a second event
    assert sum(det.observe("k", "psum", 0.5, 1.0) for _ in range(4)) == 1
    assert len(det.report().events) == 2
    # the default detector's firings also hit the metric + instant span
    assert drift_lib.observe("m", "wsync", 0.1, 1.0) is False  # min_count
    drift_lib.observe("m", "wsync", 0.1, 1.0)
    assert drift_lib.observe("m", "wsync", 0.1, 1.0) is True
    snap = obs.snapshot()
    # every firing (the scripted detector's two psum excursions included)
    # hits the shared counter, labeled by plan kind
    assert snap["counters"]["wire_drift_events_total"] == \
        {"kind=psum": 2, "kind=wsync": 1}
    assert any(s.name == "drift:fire" for s in obs.spans())


def test_drift_min_count_and_bad_prediction():
    det = DriftDetector(window=8, min_count=3)
    assert det.observe("k", "psum", 0.5, 5.0) is False
    assert det.observe("k", "psum", 0.5, 5.0) is False  # still < min_count
    assert det.observe("k", "psum", 0.5, 5.0) is True
    assert det.observe("k2", "psum", 0.0, 5.0) is False  # no prediction
    assert det.observe("k2", "psum", 0.0, 5.0) is False
    assert det.observe("k2", "psum", 0.0, 5.0) is False
    with pytest.raises(ValueError):
        DriftDetector(enter=0.1, exit=0.2)  # hysteresis must open downward


def test_drift_stationary_jitter_never_fires():
    det = DriftDetector()
    for i in range(50):
        live = 0.5 * (1.01 if i % 2 else 0.99)  # +/-1% measurement noise
        assert det.observe("k", "psum", 0.5, live) is False
    assert det.report().events == ()


def test_drift_mode_transition_is_not_drift():
    """Regression: the window holds live/predicted residuals, so a
    legitimate prediction change (full send -> cheap delta once a base is
    acked) must not read old full-ratio observations as drift against the
    new delta prediction."""
    det = DriftDetector()
    det.observe("k", "wsync", 0.8, 0.8)  # full-send regime
    for _ in range(10):
        assert det.observe("k", "wsync", 0.2, 0.2) is False  # delta regime
    assert det.report().events == ()


def test_sync_engine_drift_fires_on_entropy_shift():
    """End-to-end: warm deltas match the plan's prediction; a shifted
    update distribution overflows into full sends and the detector names
    the plan stale."""
    modes = _sync_workload(warm=4, shifted=2)
    assert modes[-1] == "full"  # the shift really forced the fallback
    rep = drift_lib.detector().report()
    assert len(rep.events) >= 1
    assert rep.events[0].kind == "wsync"
    assert rep.stale and rep.stale[0].live_ratio > rep.stale[0].predicted_ratio
    snap = obs.snapshot()
    assert snap["counters"]["wire_drift_events_total"]["kind=wsync"] >= 1


# ---------------------------------------------------------------------------
# disabled mode: the whole observatory no-ops
# ---------------------------------------------------------------------------

def test_disabled_mode_noops():
    obs.set_enabled(False)
    obs.metric("plan_exec_total").inc(kind="psum")
    assert obs.recorder().series() == ()  # no tee
    regret_lib.record_sample("k", "float32", np.zeros(8))
    assert regret_lib.samples() == {}
    assert drift_lib.observe("k", "psum", 0.5, 5.0) is False
    assert drift_lib.observe("k", "psum", 0.5, 5.0) is False
    assert drift_lib.observe("k", "psum", 0.5, 5.0) is False
    assert drift_lib.detector().report() == drift_lib.DriftReport((), ())
    with pytest.raises(KeyError):
        obs.metric("not_a_metric")  # typo check stays on while disabled


def test_clear_observatory_keeps_registry():
    obs.metric("plan_exec_total").inc(kind="psum")
    regret_lib.record_sample("k", "float32", np.zeros(8))
    drift_lib.observe("k", "psum", 0.5, 5.0)
    obs.clear_observatory()
    assert obs.recorder().series() == ()
    assert regret_lib.samples() == {}
    assert drift_lib.detector()._state == {}
    # the registry itself is NOT part of the observatory clear
    assert obs.snapshot()["counters"]["plan_exec_total"] == {"kind=psum": 1}


# ---------------------------------------------------------------------------
# static guard: every obs name literal in the runtime resolves
# ---------------------------------------------------------------------------

def test_every_obs_name_literal_resolves():
    """Grep every string-literal obs.metric/span/instant call under
    src/repro/ and resolve it against obs.names — an instrumented call
    site cannot reference a name the registry does not declare.
    (f-string call sites like plan:<kind> are covered by the span-name
    table test instead.)"""
    from repro.obs import names
    from repro.sched.compile import PLAN_KINDS

    span_names = {n for n, _, _ in names.SPANS}
    # "plan:<kind>" is a templated family: accept its instantiations
    span_names |= {f"plan:{k}" for k in PLAN_KINDS}
    pat = re.compile(
        r"""obs\s*\.\s*(metric|span|instant)\(\s*["']([^"']+)["']""")
    src = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    unknown, hits = [], 0
    for root, _, files in os.walk(src):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                text = f.read()
            for what, name in pat.findall(text):
                hits += 1
                table = names.SPECS if what == "metric" else span_names
                if name not in table:
                    unknown.append((fn, what, name))
    assert hits > 30, "the grep found implausibly few call sites"
    assert not unknown, f"unresolvable obs names: {unknown}"


# ---------------------------------------------------------------------------
# reporting surface + perf trajectory
# ---------------------------------------------------------------------------

def test_dump_report_artifacts(tmp_path):
    from repro.obs import dump as dump_mod

    paths = dump_mod.dump("sync", str(tmp_path), steps=2, report=True)
    assert set(paths) >= {"report_json", "report_md"}
    rep = json.load(open(paths["report_json"]))
    assert set(rep) >= {"regret", "drift", "ledger_by_kind",
                        "ledger_by_bucket", "ratio_series"}
    assert any(k.startswith("wsync_host/") for k in rep["ledger_by_bucket"])
    md = open(paths["report_md"]).read()
    assert md.startswith("# Wire-efficiency observatory")
    assert "regret" in md and "Drift" in md


def test_append_trajectory(tmp_path):
    from benchmarks.common import append_trajectory

    path = str(tmp_path / "traj.json")
    append_trajectory({"date": "d1", "source": "s"}, path)
    append_trajectory({"date": "d2", "source": "s"}, path)
    recs = json.load(open(path))
    assert [r["date"] for r in recs] == ["d1", "d2"]
    with open(path, "w") as f:
        f.write("not json{")
    append_trajectory({"date": "d3", "source": "s"}, path)  # recovers
    assert [r["date"] for r in json.load(open(path))] == ["d3"]
