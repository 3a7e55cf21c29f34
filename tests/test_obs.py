"""Observability layer: registry semantics, span tracing, Chrome-trace
export, runtime instrumentation, the REPRO_OBS=0 no-op contract, and the
perf trajectory file."""
import collections
import gc
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


def test_disabled_mode_noops():
    """Disabled, every canonical metric is the shared no-op: writes of
    each kind leave the registry empty, and typos still raise."""
    obs.set_enabled(False)
    for spec in obs.METRICS:
        m = obs.metric(spec.name)
        assert m is obs.NOOP_METRIC
        labels = {k: "x" for k in spec.labels}
        if spec.kind == "counter":
            m.inc(3, **labels)
        elif spec.kind == "gauge":
            m.set(3, **labels)
        else:
            m.observe(3, **labels)
    obs.set_enabled(True)
    assert obs.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}
    obs.set_enabled(False)
    with pytest.raises(KeyError):
        obs.metric("not_a_metric")  # typo check stays on while disabled


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

def _run_plan(kind):
    """One plan execution of ``kind`` on a one-device ``data`` mesh."""
    from jax.sharding import PartitionSpec as P

    from repro import sched
    from repro.core.policy import CompressionPolicy

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    pol = CompressionPolicy(min_bytes=0)
    cache = sched.PlanCache()
    x = jnp.arange(4096, dtype=jnp.float32)
    run = {
        "psum": lambda v: sched.psum_with_plan(
            {"w": v}, "data", policy=pol, cache=cache),
        "reduce_scatter": lambda v: sched.reduce_scatter_with_plan(
            v, "data", policy=pol, cache=cache),
        "all_gather": lambda v: sched.all_gather_with_plan(
            v, "data", policy=pol, cache=cache),
    }[kind]
    f = jax.shard_map(run, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
                      axis_names={"data"}, check_vma=False)
    return f(x)


@pytest.mark.parametrize("kind", ["psum", "reduce_scatter", "all_gather"])
def test_executor_metrics_agree_with_wire_reports(kind):
    """The acceptance contract: per-kind wire totals in the snapshot ==
    summarize_wire_reports over the plan:* reports of the same run."""
    from repro.roofline.analysis import summarize_wire_reports

    policy_mod.clear_wire_reports()
    _run_plan(kind)
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
    assert [r.name for r in reports] == [f"plan:{kind}"]
    assert reports[0].raw_bytes > 0
    assert snap["counters"]["plan_exec_total"] == {f"kind={kind}": 1}
    ratio = snap["gauges"]["plan_wire_ratio"][f"kind={kind}"]
    assert ratio == pytest.approx(reports[-1].ratio)
    # the execution also left a plan:<kind> span and cache events
    names = [s.name for s in obs.spans()]
    assert f"plan:{kind}" in names and "plan_cache:compile" in names


def test_plan_wire_ratio_hist_and_gauge():
    """One plan execution populates the labeled ratio histogram, and the
    last-ratio gauge is kept alongside it."""
    _run_plan("psum")
    snap = obs.snapshot()
    h = snap["histograms"]["plan_wire_ratio_hist"]["kind=psum"]
    assert h["count"] == 1
    assert snap["gauges"]["plan_wire_ratio"]["kind=psum"] == \
        pytest.approx(h["sum"])


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


SYNC_DTYPES = [jnp.bfloat16, jnp.float16, jnp.float32]


def _bucket_modes(*updates):
    return collections.Counter(
        f"mode={m}" for u in updates for _, _, m, _ in u.buckets)


@pytest.mark.parametrize("force", [None, "full", "raw"])
@pytest.mark.parametrize("dtype", SYNC_DTYPES)
def test_sync_engine_instrumentation(dtype, force):
    from repro.core.policy import CompressionPolicy
    from repro.sync.engine import WeightSyncEngine, apply_update

    params = {"w": jnp.asarray(np.linspace(0, 1, 4096), dtype)}
    eng = WeightSyncEngine(policy=CompressionPolicy(min_bytes=0))
    v1 = eng.publish(params)
    upd = eng.update_for("r0", force=force)
    apply_update(upd)
    eng.ack("r0", v1)
    # unforced, the base moved to v1: a fresh (delta) encode; a forced
    # update ignores the base, so it is the memo of the first
    upd2 = eng.update_for("r0", force=force)
    upd3 = eng.update_for("r0", force=force)  # same key: memo hit
    assert upd3 is upd2
    encoded = [upd, upd2] if force is None else [upd]
    assert (upd2 is upd) == (force is not None)
    modes = _bucket_modes(*encoded)
    if force == "raw":
        assert set(modes) == {"mode=raw"}
    elif force == "full":
        assert "mode=delta" not in modes
    else:
        assert upd2.mode == "delta"
    snap = obs.snapshot()
    assert snap["counters"]["sync_publish_total"] == {"": 1}
    assert sum(snap["counters"]["sync_updates_total"].values()) == \
        len(encoded)
    assert snap["counters"]["sync_buckets_total"] == dict(modes)
    assert snap["counters"]["sync_memo_hits_total"] == {
        "": 3 - len(encoded)}
    wire = sum(snap["counters"]["sync_update_wire_bytes_total"].values())
    assert wire == sum(u.wire_bytes for u in encoded)  # exact, by mode
    assert snap["gauges"]["sync_replica_version_lag"] == {"replica=r0": 0}
    names = [s.name for s in obs.spans()]
    assert "sync:publish" in names and "sync:update" in names
    assert "sync:encode" in names
    # obs:sample wraps each encoded bucket's counters, once per bucket
    assert names.count("obs:sample") == sum(modes.values())
    assert any(s.name == "sync:memo_hit" and s.ph == "i"
               for s in obs.spans())


def _flip_low_bits(x, rng):
    """``x`` with a random low mantissa bit pattern XORed into 30% of its
    elements: a small one-step delta of ``x``'s dtype."""
    u = np.dtype(f"uint{8 * x.dtype.itemsize}")
    flip = rng.integers(0, 8, x.size).astype(u)
    flip[rng.random(x.size) > 0.3] = 0
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x, u) ^ jnp.asarray(flip), x.dtype)


def _wire_of(upd):
    """Every byte an update ships, with its dtypes, in order, copied out
    of the update (on the CPU a raw wire may view its device buffer)."""
    arrays = [np.asarray(a) for _, _, _, msg in upd.buckets
              for a in jax.tree_util.tree_leaves(msg)]
    arrays += [np.asarray(a) for _, a in upd.raw_leaves]
    return ([(a.dtype.str, a.shape, a.tobytes()) for a in arrays],
            [(d, m, mode) for d, m, mode, _ in upd.buckets],
            upd.wire_bytes, upd.raw_bytes, upd.checksum, upd.base_version)


def _sync_rounds(dtype, force, n_rounds, *, on_round=None):
    """``n_rounds`` publish/update/ack rounds of one 2**17-element leaf
    through an engine that retains 2 versions; returns each round's
    ``(mode, _wire_of(update))``.  ``on_round(i)`` runs after round ``i``
    with no array or update of the test's own still referenced."""
    from repro.core.policy import CompressionPolicy
    from repro.sync.engine import WeightSyncEngine

    rng = np.random.default_rng(3)
    eng = WeightSyncEngine(policy=CompressionPolicy(min_bytes=0),
                           history=2)
    w = jnp.asarray(rng.normal(0, 0.02, 1 << 17), dtype)
    rounds = []
    for i in range(n_rounds):
        if i:
            w = _flip_low_bits(w, rng)
        version = eng.publish({"w": w})
        upd = eng.update_for("r0", force=force)
        eng.ack("r0", version, upd.epoch)
        rounds.append((upd.mode, _wire_of(upd)))
        del upd
        if on_round is not None:
            on_round(i)
    return rounds


@pytest.mark.parametrize("force", [None, "full", "raw"])
@pytest.mark.parametrize("dtype", SYNC_DTYPES)
def test_observation_never_changes_or_keeps_the_wire(dtype, force):
    """With obs on and off, every round ships the same bytes, sizes and
    CRC; and once the store's history is full, more rounds leave the
    count of live device arrays flat: the observation keeps nothing."""
    n_rounds, settled = 6, 3  # history 2 is full, and warm, by round 3
    live = {}

    def count_live(i):
        if i >= settled:
            gc.collect()
            live[i] = len(jax.live_arrays())

    on = _sync_rounds(dtype, force, n_rounds, on_round=count_live)
    obs.set_enabled(False)
    off = _sync_rounds(dtype, force, n_rounds)
    if force is None:
        assert [m for m, _ in on[1:]] == ["delta"] * (n_rounds - 1)
    assert on == off
    assert len(set(live.values())) == 1, live
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
# perf trajectory
# ---------------------------------------------------------------------------

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
