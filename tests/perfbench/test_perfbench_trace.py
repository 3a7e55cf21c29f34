"""The reduction from a trace to metrics, on a small recorded trace and on
hand-made planes whose answers are counted by hand."""
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from perfbench import trace


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    """Two chips over a window 0..1000 ns; the host ran an encode span
    100..600 and an apply span 600..950 inside it."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 1000), _ev("bench.sync.encode", 100, 500),
        _ev("bench.sync.apply", 600, 350), _ev("other", 0, 10)])])
    chip0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_step(8312)", 0, 900)]),
        NS(name="XLA Ops", events=[
            _ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 100, 200),  # 100..300
            _ev("all-gather.2", 250, 100),      # 250..350 overlaps
            _ev("encode_fused_kernel", 700, 100),   # 700..800
            _ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 1100, 50)])])
        # the last op is outside the window and outside any program
    chip1 = NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[
        _ev("reduce-scatter-start.3", 0, 400),  # 0..400
        _ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 500, 100)])])  # 500..600
    return [host, chip0, chip1, NS(name="/host:metadata", lines=[])]


@pytest.fixture
def tr():
    return trace.from_planes(_planes())


def test_window_and_spans(tr):
    assert tr.window == (0, 1000)
    assert tr.window_s == pytest.approx(1e-6)
    assert [n for n, _, _ in tr.spans] == ["bench.sync.encode",
                                           "bench.sync.apply"]
    assert trace.spans_named(tr, "bench.sync.apply") == [pytest.approx(
        350e-9)]


def test_union_of_busy_intervals_and_idle_share(tr):
    assert trace.merge([(5, 9), (0, 3), (2, 4), (20, 30)], 0, 25) == [
        (0, 4), (5, 9), (20, 25)]
    assert trace.gaps([(5, 9), (0, 3)], 0, 12) == [(3, 5), (9, 12)]
    # chip 0 busy 100..350 and 700..800 = 350 ns; chip 1 0..400, 500..600
    assert trace.busy_s(tr) == pytest.approx((350 + 500) / 2 * 1e-9)
    assert trace.idle_share(tr) == pytest.approx(1 - 425 / 1000)


def test_collective_and_codec_time(tr):
    # all-gather 100 ns on chip 0, reduce-scatter 400 ns on chip 1
    assert trace.op_time_s(tr, trace.is_collective) == pytest.approx(
        250e-9)
    codec = trace.name_matcher([r"encode_fused", r"decode_reduce"])
    assert trace.op_time_s(tr, codec) == pytest.approx(50e-9)
    assert not trace.is_collective("%fusion.1 = f32[8] fusion(f32[8] %a)")


def test_breakdown_names_gaps_by_host_span(tr):
    b = trace.breakdown(tr)
    assert b["device_ops"][0] == ["reduce-scatter-start.3",
                                  pytest.approx(200e-9)]
    # ops are named by their program (the hash dropped) and their HLO name
    ops = dict((n, t) for n, t in b["device_ops"])
    assert ops["jit_step %fusion.1"] == pytest.approx(100e-9)
    assert ops["%fusion.1"] == pytest.approx(50e-9)  # chip 1 has no program
    assert ops["jit_step all-gather.2"] == pytest.approx(50e-9)
    # chip 0 idles 0..100 (no span), 350..700 (encode then apply: the
    # middle, 525, is in encode) and 800..1000 (apply until 950)
    assert b["idle_gaps"] == [["bench.sync.encode", pytest.approx(350e-9)],
                              ["bench.sync.apply", pytest.approx(200e-9)],
                              ["outside any span", pytest.approx(100e-9)]]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        trace.from_planes(_planes()[1:])


def test_a_recorded_trace(tmp_path):
    """A real profiler trace of this process: the window and the host
    spans come back on one clock (the CPU has no device plane)."""
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with trace.capture(str(tmp_path), True):
        with jax.profiler.TraceAnnotation("bench.sync.encode"):
            f(x).block_until_ready()
    tr = trace.load(str(tmp_path))
    (n, s, e), = tr.spans
    assert n == "bench.sync.encode"
    assert tr.window[0] <= s <= e <= tr.window[1]
    assert trace.idle_share(tr) is None and trace.busy_s(tr) == 0.0
    with trace.capture(str(tmp_path / "off"), False):
        pass
    assert not (tmp_path / "off").exists()
