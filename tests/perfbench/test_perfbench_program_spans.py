"""The program's own spans: a recorded profiler trace of one weight-sync
round holds them, nested in the benchmark's spans on the window's clock;
none with ``REPRO_OBS=0``; and the per-round readers of their ring-buffer
copy, on hand-made records whose answers are counted by hand."""
import glob
import os
import re
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness, progspans, trace
from repro import obs
from repro.obs.trace import SpanRecord

from conftest import REPO

# the obs naming convention; the runtime's own TraceMe names (``Foo::Bar``)
# do not match it
PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*:[a-z][a-z0-9_]*$")
ENCODE_SPANS = {"sync:codec", "sync:d2h", "sync:checksum", "obs:sample"}
APPLY_SPANS = {"serve:verify", "sync:apply"}


@pytest.fixture(autouse=True)
def _obs_on():
    obs.set_enabled(True)
    obs.reset()
    yield
    obs.set_enabled(None)
    obs.reset()


def _program_events(log_dir):
    """Host events of the newest trace under ``log_dir`` named by the obs
    convention, as ``[(name, start_ns, end_ns)]``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if PROGRAM_SPAN.match(e.name)]


def _traced_round(log_dir):
    """One full round outside the trace, then one traced delta round,
    through ``WeightSyncEngine`` and ``ServeEngine.ingest_weights`` at
    smollm_135m's smoke size, annotated as the benchmark annotates it."""
    from repro.configs import smollm_135m
    from repro.core.policy import CompressionPolicy
    from repro.models import transformer
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.sync import WeightSyncEngine

    cfg = smollm_135m.SMOKE
    v0 = transformer.init(jax.random.PRNGKey(0), cfg)
    v1 = jax.tree.map(lambda x: x * jnp.asarray(1.001, x.dtype), v0)
    engine = WeightSyncEngine(policy=CompressionPolicy(min_bytes=0))
    replica = ServeEngine(cfg, jax.tree.map(jnp.zeros_like, v0),
                          ServeConfig(batch_slots=1, max_len=16))

    def one_round(params):
        with jax.profiler.TraceAnnotation("bench.sync.round"):
            with jax.profiler.TraceAnnotation("bench.sync.encode"):
                engine.publish(params)
                update = engine.update_for("r0")
            with jax.profiler.TraceAnnotation("bench.sync.apply"):
                replica.ingest_weights(update)
                jax.block_until_ready(replica.params)
        engine.ack("r0", update.version, update.epoch)
        return update

    one_round(v0)
    with trace.capture(log_dir, True):
        update = one_round(v1)
    return update


def test_a_round_traced_holds_the_program_spans(tmp_path):
    update = _traced_round(str(tmp_path))
    assert update.mode == "delta"
    tr = trace.load(str(tmp_path))
    # the benchmark's spans come back as they did before program spans
    assert [n for n, _, _ in tr.spans] == [
        "bench.sync.round", "bench.sync.encode", "bench.sync.apply"]
    bench = {n: (s, e) for n, s, e in tr.spans}
    events = _program_events(str(tmp_path))
    names = {n for n, _, _ in events}
    assert ENCODE_SPANS | APPLY_SPANS | {"serve:ingest", "sync:update",
                                        "sync:publish"} <= names
    lo, hi = tr.window
    for name, s, e in events:
        assert lo <= s <= e <= hi, name
        if name in ENCODE_SPANS or name in APPLY_SPANS:
            outer = "bench.sync.encode" if name in ENCODE_SPANS else \
                "bench.sync.apply"
            assert bench[outer][0] <= s <= e <= bench[outer][1], name
    # the profiler's copy and the ring buffer's agree on what ran
    ring = {r.name for r in obs.spans() if r.ph == "X"}
    assert ENCODE_SPANS | APPLY_SPANS <= ring


def test_no_program_span_with_obs_off(tmp_path):
    obs.set_enabled(False)
    _traced_round(str(tmp_path))
    assert _program_events(str(tmp_path)) == []
    assert obs.spans() == ()
    # the benchmark's own spans are still there
    assert {n for n, _, _ in trace.load(str(tmp_path)).spans} == {
        "bench.sync.round", "bench.sync.encode", "bench.sync.apply"}


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _window_of_two_rounds():
    """A window 0..1000 ns holding two rounds."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 1000), _ev("bench.sync.round", 10, 400),
        _ev("bench.sync.round", 500, 400), _ev("Foo::Bar", 20, 5)])])
    return trace.from_planes([host])


def _rec(name, ts, dur):
    return SpanRecord(name=name, ts=ts, dur=dur, tid=1, depth=0, args={})


# the ring buffer: a warm-up round before the window, then the window's two
# rounds; each opens with sync:publish
RECORDS = (
    _rec("sync:publish", 1.0, 0.1), _rec("sync:codec", 1.2, 5.0),
    _rec("sync:checksum", 6.3, 0.7),
    _rec("sync:publish", 10.0, 0.1), _rec("sync:codec", 10.2, 1.0),
    _rec("sync:d2h", 11.3, 0.25), _rec("sync:checksum", 11.6, 0.125),
    _rec("sync:encode", 10.2, 1.4), _rec("serve:verify", 12.0, 0.125),
    _rec("sync:publish", 20.0, 0.1), _rec("sync:codec", 20.2, 2.0),
    _rec("sync:d2h", 22.3, 0.75), _rec("sync:checksum", 22.6, 0.375),
    _rec("serve:verify", 23.0, 0.375),
    SpanRecord(name="sync:codec", ts=24.0, dur=0.0, tid=1, depth=0,
               args={}, ph="i"),
)


@pytest.mark.parametrize("metric, want_ms", [
    ("sync.codec_ms", 1e3 * (1.0 + 2.0) / 2),
    ("sync.d2h_ms", 1e3 * (0.25 + 0.75) / 2),
    ("sync.checksum_ms", 1e3 * (0.125 + 0.375 + 0.125 + 0.375) / 2),
])
def test_readers_sum_the_window_spans_per_round(metric, want_ms,
                                                monkeypatch):
    monkeypatch.setattr(obs, "spans", lambda: RECORDS)
    ctx = harness.ReadContext(trace=_window_of_two_rounds(), counters={},
                              device_kind="TPU v5 lite")
    read = harness.reader_of(REPO, metric).read
    assert read(ctx) == pytest.approx(want_ms)
    assert read(harness.ReadContext(trace=None, counters={},
                                    device_kind="TPU v5 lite")) is None


def test_readers_find_nothing_without_the_spans(monkeypatch):
    tr = _window_of_two_rounds()
    # a program that records only the older spans, or none at all
    monkeypatch.setattr(obs, "spans", lambda: RECORDS[:1] + (
        _rec("sync:publish", 10.0, 0.1), _rec("sync:update", 10.1, 1.0),
        _rec("sync:publish", 20.0, 0.1)))
    assert progspans.per_round_ms(tr, "sync:codec") is None
    monkeypatch.setattr(obs, "spans", lambda: ())
    assert progspans.per_round_ms(tr, "sync:codec") is None
    # fewer round openings than rounds: the window cannot be found
    monkeypatch.setattr(obs, "spans", lambda: RECORDS[-6:])
    assert progspans.program_span_s(tr, "sync:codec") == []
    assert progspans.rounds(tr) == 2
