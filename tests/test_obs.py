"""Observability layer: registry semantics, span tracing, Chrome-trace
export, runtime instrumentation, and the REPRO_OBS=0 no-op contract."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import policy as policy_mod
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import SpanTracer


@pytest.fixture(autouse=True)
def _isolate():
    """Every test starts from an empty registry/buffer, obs enabled."""
    obs.set_enabled(True)
    obs.reset()
    yield
    obs.set_enabled(None)  # restore the env-derived setting
    obs.reset()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", labels=("kind",))
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(kind="b")
    assert c.series() == {"kind=a": 3, "kind=b": 1}
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")  # counters are monotonic

    g = reg.gauge("g")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.series() == {"": 6}

    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    s = h.series()[""]
    assert s["count"] == 4 and s["sum"] == pytest.approx(6.05)
    assert s["buckets"] == {"le=0.1": 1, "le=1": 2, "le=+Inf": 1}


def test_label_validation_and_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("c", labels=("kind",))
    with pytest.raises(ValueError):
        c.inc()  # missing label
    with pytest.raises(ValueError):
        c.inc(kind="a", extra="b")  # unknown label
    # get-or-create: same spec returns the same object ...
    assert reg.counter("c", labels=("kind",)) is c
    # ... different type or labels raises
    with pytest.raises(ValueError):
        reg.gauge("c", labels=("kind",))
    with pytest.raises(ValueError):
        reg.counter("c", labels=("other",))


def test_snapshot_and_markdown():
    reg = MetricsRegistry()
    reg.counter("a_total", labels=("k",)).inc(3, k="x")
    reg.gauge("b").set(1.5)
    reg.histogram("c_seconds", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["a_total"] == {"k=x": 3}
    assert snap["gauges"]["b"] == {"": 1.5}
    assert snap["histograms"]["c_seconds"][""]["count"] == 1
    json.loads(reg.to_json())  # snapshot must be JSON-clean
    md = reg.to_markdown()
    assert md.splitlines()[0] == "| metric | type | labels | value |"
    assert "| a_total | counter | k=x | 3 |" in md


def test_canonical_names_resolve_and_typos_raise():
    for spec in obs.METRICS:
        m = obs.metric(spec.name)
        assert m.name == spec.name and m.kind == spec.kind
    with pytest.raises(KeyError):
        obs.metric("no_such_metric_total")


# ---------------------------------------------------------------------------
# disabled mode (REPRO_OBS=0)
# ---------------------------------------------------------------------------

def test_disabled_mode_is_noop():
    obs.set_enabled(False)
    m = obs.metric("plan_exec_total")
    assert m is obs.NOOP_METRIC
    m.inc(kind="psum")  # absorbed
    sp = obs.span("plan:psum")
    assert sp is obs.NOOP_SPAN
    with sp as s:
        s.args["kind"] = "psum"  # assignments vanish by design
    obs.instant("plan_cache:hit")
    assert obs.spans() == ()
    assert obs.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}
    with pytest.raises(KeyError):
        obs.metric("typo_total")  # names still validated when disabled


# ---------------------------------------------------------------------------
# span tracer + Chrome trace
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_order():
    with obs.span("train:step", step=1):
        with obs.span("plan:psum"):
            pass
        obs.instant("plan_cache:hit")
    recs = obs.spans()
    # completion order: inner span first, then the instant, then the outer
    assert [(r.name, r.depth, r.ph) for r in recs] == [
        ("plan:psum", 1, "X"), ("plan_cache:hit", 1, "i"),
        ("train:step", 0, "X")]
    outer = recs[-1]
    inner = recs[0]
    assert outer.args == {"step": 1}
    assert outer.ts <= inner.ts and outer.dur >= inner.dur


def test_span_ring_buffer_cap():
    tr = SpanTracer(capacity=4)
    for i in range(10):
        with tr.span("sync:publish", i=i):
            pass
    recs = tr.spans()
    assert len(recs) == 4 and [r.args["i"] for r in recs] == [6, 7, 8, 9]


def test_chrome_trace_schema(tmp_path):
    with obs.span("sync:publish", version=3):
        obs.instant("sync:memo_hit")
    path = obs.export_chrome_trace(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert len(events) == 2
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    for e in events:
        assert set(e) >= {"name", "ph", "ts", "pid", "tid", "cat", "args"}
        assert e["cat"] == e["name"].split(":")[0]
    complete = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert len(complete) == 1 and len(instants) == 1
    assert complete[0]["name"] == "sync:publish"
    assert complete[0]["dur"] >= 0 and complete[0]["args"] == {"version": 3}
    assert instants[0]["s"] == "t" and "dur" not in instants[0]


# ---------------------------------------------------------------------------
# runtime instrumentation
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


def test_executor_metrics_agree_with_wire_reports():
    """The acceptance contract: per-kind wire totals in the snapshot ==
    summarize_wire_reports over the plan:* reports of the same run."""
    from repro.roofline.analysis import summarize_wire_reports

    policy_mod.clear_wire_reports()
    _run_plan_psum()
    reports = [r for r in policy_mod.wire_reports()
               if r.name.startswith("plan:")]
    assert reports, "plan execution must emit a consolidated report"
    summ = summarize_wire_reports(reports)
    snap = obs.snapshot()
    assert sum(snap["counters"]["plan_wire_raw_bytes_total"].values()) == \
        summ["raw_bytes"]
    assert sum(snap["counters"]["plan_wire_bytes_total"].values()) == \
        summ["wire_bytes"]
    # per-kind agreement, exact
    for name, d in summ["by_name"].items():
        kind = name.split(":", 1)[1]
        assert snap["counters"]["plan_wire_raw_bytes_total"][
            f"kind={kind}"] == d["raw_bytes"]
        assert snap["counters"]["plan_wire_bytes_total"][
            f"kind={kind}"] == d["wire_bytes"]
    assert snap["counters"]["plan_exec_total"] == {"kind=psum": 1}
    ratio = snap["gauges"]["plan_wire_ratio"]["kind=psum"]
    assert ratio == pytest.approx(reports[-1].ratio)
    # the execution also left a plan:psum span and cache events
    names = [s.name for s in obs.spans()]
    assert "plan:psum" in names and "plan_cache:compile" in names


def test_cache_instrumentation_and_gauges():
    from repro import sched

    cache = sched.PlanCache(capacity=2)
    cache.get_or_compile(("k", 1), lambda: "p1")
    cache.get_or_compile(("k", 1), lambda: "p1")
    names = [(s.name, s.ph) for s in obs.spans()]
    assert ("plan_cache:compile", "X") in names
    assert ("plan_cache:hit", "i") in names
    snap = obs.snapshot()
    assert snap["gauges"]["plan_cache_hits"]["cache=local"] == 1
    assert snap["gauges"]["plan_cache_misses"]["cache=local"] == 1
    assert snap["gauges"]["plan_cache_size"]["cache=local"] == 1


def test_kernel_fallback_mirror():
    from repro import kernels

    kernels.clear_fallbacks()
    kernels.record_fallback("bitplane_pack", "ragged shape")
    kernels.record_fallback("bitplane_pack", "ragged shape")
    snap = obs.snapshot()
    assert snap["counters"]["kernel_fallback_total"] == {
        "op=bitplane_pack": 2}
    kernels.clear_fallbacks()


def test_sync_engine_instrumentation():
    from repro.core.policy import CompressionPolicy
    from repro.sync.engine import WeightSyncEngine, apply_update

    params = {"w": jnp.asarray(np.linspace(0, 1, 4096), jnp.bfloat16)}
    eng = WeightSyncEngine(policy=CompressionPolicy(min_bytes=0))
    v1 = eng.publish(params)
    upd = eng.update_for("r0")
    apply_update(upd)
    eng.ack("r0", v1)
    upd2 = eng.update_for("r0")  # base moved to v1: fresh (delta) encode
    upd3 = eng.update_for("r0")  # same (version, base): memo hit
    assert upd3 is upd2
    snap = obs.snapshot()
    assert snap["counters"]["sync_publish_total"] == {"": 1}
    assert sum(snap["counters"]["sync_updates_total"].values()) == 2
    assert sum(snap["counters"]["sync_buckets_total"].values()) >= 2
    assert snap["counters"]["sync_memo_hits_total"] == {"": 1}
    wire = sum(snap["counters"]["sync_update_wire_bytes_total"].values())
    assert wire == upd.wire_bytes + upd2.wire_bytes  # exact, by mode
    assert snap["gauges"]["sync_replica_version_lag"] == {"replica=r0": 0}
    names = [s.name for s in obs.spans()]
    assert "sync:publish" in names and "sync:update" in names
    assert "sync:encode" in names
    assert any(s.name == "sync:memo_hit" and s.ph == "i"
               for s in obs.spans())


def test_sync_delta_exception_counters():
    """After one delta update the exception counters read, per plane, the
    exceptions the delta really has and the lists' static capacity."""
    from repro.core import codec, packing
    from repro.core.policy import CompressionPolicy
    from repro.sync.engine import WeightSyncEngine

    n = 4096
    rng = np.random.default_rng(0)
    base = jnp.asarray(rng.normal(0, 0.02, n), jnp.bfloat16)
    flip = rng.integers(0, 8, n).astype(np.uint16)
    flip[rng.random(n) > 0.3] = 0
    flip[rng.choice(n, 20, replace=False)] |= 1 << 6  # lo exceptions
    flip[[3, 9]] |= np.array([1 << 7, 1 << 10], np.uint16)  # block 0's
    # exponent deltas 1 and 8: a range the 2-bit exponent width cannot hold
    new = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(base, jnp.uint16) ^ jnp.asarray(flip),
        jnp.bfloat16)
    eng = WeightSyncEngine(policy=CompressionPolicy(min_bytes=0))
    eng.ack("r0", eng.publish({"w": base}))
    eng.publish({"w": new})
    upd = eng.update_for("r0")
    assert upd.mode == "delta"

    (b,) = eng.plan_for({"w": new}).buckets
    exp, lo = codec.split_bits(codec.xor_bits(new, base),
                               codec.LAYOUTS["bfloat16"])
    lo_used = int(np.sum(np.asarray(lo) > (1 << b.delta_lo_width) - 1))
    e = np.asarray(exp).astype(np.int32).reshape(-1, b.block)
    nz = e != 0
    span = (np.where(nz, e, 0).max(1) - np.where(nz, e, 255).min(1) + 1)
    exp_used = int(np.sum(nz.any(1) & (span >= 1 << b.delta_width)))
    assert lo_used >= 20 and exp_used == 1
    lo_cap = min(n, max(4, int(np.ceil(n * b.exc_frac))))
    exp_cap = packing.exception_capacity(n // b.block, b.exc_frac)
    c = obs.snapshot()["counters"]
    assert c["sync_delta_exceptions_total"] == {
        "plane=lo": lo_used, "plane=exp": exp_used}
    assert c["sync_delta_exception_slots_total"] == {
        "plane=lo": lo_cap, "plane=exp": exp_cap}


def test_p2p_compressor_spans_and_histograms():
    from repro.p2p.engine import Compressor

    comp = Compressor(codec_name="packed")
    x = jnp.asarray(np.random.default_rng(0).normal(size=4096), jnp.float32)
    msg = comp.encode(x)
    out = comp.decode(msg)
    assert np.array_equal(np.asarray(out), np.asarray(x))
    names = [s.name for s in obs.spans()]
    assert "p2p:encode" in names and "p2p:pack" in names
    assert "p2p:decode" in names
    snap = obs.snapshot()
    enc = snap["histograms"]["p2p_encode_seconds"]["codec=packed"]
    dec = snap["histograms"]["p2p_decode_seconds"]["codec=packed"]
    assert enc["count"] == 1 and dec["count"] == 1
    # the encode span carries the wire accounting args
    sp = [s for s in obs.spans() if s.name == "p2p:encode"][0]
    assert sp.args["raw_bytes"] == msg.raw_bytes
    assert sp.args["wire_bytes"] == msg.wire_bytes()


# ---------------------------------------------------------------------------
# thread safety
# ---------------------------------------------------------------------------

def test_concurrent_counter_increments():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    barrier = threading.Barrier(4)

    def worker():
        barrier.wait()
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.series() == {"": 4000}


def test_wire_report_sinks_are_thread_local():
    """A capture opened in one thread must not swallow another thread's
    reports (satellite: core/policy sink stack is per-thread)."""
    policy_mod.clear_wire_reports()
    inside = threading.Event()
    release = threading.Event()
    captured = {}

    def worker():
        with policy_mod.capture_wire_reports() as caught:
            inside.set()
            release.wait(timeout=5)
            captured["worker"] = list(caught)

    t = threading.Thread(target=worker)
    t.start()
    inside.wait(timeout=5)
    rep = policy_mod.WireReport(name="x", axis="data", raw_bytes=8,
                                wire_bytes=4)
    policy_mod.record_wire_report(rep)  # main thread, capture open elsewhere
    release.set()
    t.join()
    assert captured["worker"] == []  # the worker's capture saw nothing
    assert policy_mod.wire_reports() == (rep,)  # base list got it


def test_spans_from_multiple_threads_share_one_buffer():
    barrier = threading.Barrier(4)  # all alive at once: distinct idents

    def worker(i):
        barrier.wait()
        with obs.span("train:step", worker=i):
            pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = [r for r in obs.spans() if r.name == "train:step"]
    assert len(recs) == 4
    assert sorted(r.args["worker"] for r in recs) == [0, 1, 2, 3]
    assert len({r.tid for r in recs}) == 4  # distinct Chrome-trace lanes
    assert all(r.depth == 0 for r in recs)  # nesting is per-thread


# ---------------------------------------------------------------------------
# dump CLI
# ---------------------------------------------------------------------------

def test_dump_cli_sync_target(tmp_path):
    from repro.obs import dump as dump_mod

    paths = dump_mod.dump("sync", str(tmp_path), steps=3)
    doc = json.load(open(paths["trace"]))
    assert doc["traceEvents"]
    names = {e["name"] for e in doc["traceEvents"]}
    assert "sync:publish" in names and "sync:encode" in names
    metrics = json.load(open(paths["metrics_json"]))
    assert metrics["counters"]["sync_publish_total"] == {"": 3}
    md = open(paths["metrics_md"]).read()
    assert md.startswith("| metric | type | labels | value |")
    with pytest.raises(KeyError):
        dump_mod.dump("no_such_target", str(tmp_path))
