"""Fused transmit-side encode (one-pass split+pack): bit-parity with the
legacy three-pass composition, ragged-tile Pallas dispatch, round-trip
through the fused receive, policy/plan threading, and fallback accounting.

The parity oracle everywhere is the EXISTING composition —
``codec.split_planes`` + ``packing.bitplane_pack`` +
``packing.pack_exponents`` — which the fused dispatch must reproduce
field-by-field at the bit level, including both legacy padding modes
(exponent edge-pad to the block, lo zero-pad to the group) on ragged
shapes.  8-device plan parity lives in tests/drivers/multidev.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import given, settings, strategies as st  # hypothesis or fallback

from repro import kernels
from repro.core import codec, packing
from repro.core import compressed_collectives as cc
from repro.core import policy as policy_lib
from repro.core.policy import CompressionPolicy
from repro.kernels import ops, ref
from repro.kernels.encode_fused import TILE_B

TILE = 512 * TILE_B  # elements per kernel grid step


def legacy_wire(x, width, block=512, exc_frac=0.02):
    """The unfused composition the fused encode must match bitwise."""
    lay = codec.layout_of(x.dtype)
    exp, lo = codec.split_planes(x)
    lo_planes = packing.bitplane_pack(
        packing._pad_to(lo.astype(jnp.uint32), packing.GROUP, "zero"),
        lay.lo_bits)
    pk = packing.pack_exponents(exp, width=width, block=block,
                                exc_frac=exc_frac)
    return {"lo": lo_planes, "payload": pk.payload, "bases": pk.bases,
            "exc_idx": pk.exc_idx, "exc_raw": pk.exc_raw,
            "overflow": pk.overflow}


def assert_wire_equal(got, want, ctx=""):
    for k in want:
        assert got[k].dtype == want[k].dtype, (ctx, k)
        assert got[k].shape == want[k].shape, (ctx, k)
        assert bool(jnp.all(got[k] == want[k])), (ctx, k)


def make_input(dt_name, n, seed=0, zeros=0.08, poison=True):
    lay = codec.LAYOUTS[dt_name]
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 0.02, n)
    x[rng.random(n) < zeros] = 0.0  # exercise the zero escape
    if poison and n > 128:  # force exception blocks
        x[n // 3] = 1e30 if dt_name == "float32" else 3e4
        x[2 * n // 3] = 1e-30
    return jnp.asarray(x, lay.dtype)


# ---------------------------------------------------------------------------
# fused == legacy composition, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dt", ["bfloat16", "float32", "float16"])
@pytest.mark.parametrize("width", [1, 2, 3, 5, 8])
def test_fused_jnp_matches_composition(dt, width):
    x = make_input(dt, 3 * 4096, seed=width)
    got = ops.encode_fused(x, width, use_pallas=False)
    assert_wire_equal(got, legacy_wire(x, width), (dt, width))


# ragged shapes: below a block, block-but-not-tile, tile+tail, group-ragged
RAGGED = [37, 600, 1536, 5000, TILE + 513, 2 * TILE]


@pytest.mark.parametrize("dt", ["bfloat16", "float32"])
@pytest.mark.parametrize("n", RAGGED)
def test_fused_jnp_ragged_matches_composition(dt, n):
    x = make_input(dt, n, seed=n, poison=n > 1000)
    got = ops.encode_fused(x, 5, use_pallas=False)
    assert_wire_equal(got, legacy_wire(x, 5), (dt, n))


@pytest.mark.parametrize("dt", ["bfloat16", "float32"])
@pytest.mark.parametrize("n", [TILE, 600, TILE + 513])
def test_fused_pallas_matches_composition(dt, n):
    """Interpret-mode Pallas kernel, including the ragged pad-to-tile path
    (no silent fallback: these shapes run the kernel grid)."""
    x = make_input(dt, n, seed=n)
    got = ops.encode_fused(x, 5, use_pallas=True)
    assert_wire_equal(got, legacy_wire(x, 5), (dt, n))


def test_fused_pallas_kernel_planes_match_ref():
    """Kernel vs jnp oracle at the plane level (payload/lo/bases/rng)."""
    from repro.kernels import encode_fused as ek
    x = make_input("bfloat16", TILE, seed=3)
    got = ek.encode_fused(x, 5, interpret=True)
    want = ref.encode_fused(x, 5)
    for g, w in zip(got, want):  # the kernel's planes are tiles, the ref's flat
        assert g.dtype == w.dtype and (g.reshape(w.shape) == w).all()


@given(st.integers(1, 8), st.integers(1, 40))
@settings(max_examples=20, deadline=None)
def test_fused_property_random_width_and_shape(width, blocks_third):
    """Property sweep: arbitrary widths x ragged lengths stay bit-identical
    (lengths stride over group/block/tile boundaries)."""
    n = 171 * blocks_third  # strides across block boundaries
    x = make_input("bfloat16", n, seed=width * 100 + n, poison=False)
    got = ops.encode_fused(x, width, use_pallas=False)
    assert_wire_equal(got, legacy_wire(x, width), (width, n))


@pytest.mark.parametrize("width", [12, 16, 24, 30])
def test_fused_wide_width_matches_composition(width):
    """Widths past the 8-bit exponent range are wasteful but legal (extra
    all-zero planes); parity must hold up to the composition's own int32
    comparison limit (width 30)."""
    x = make_input("bfloat16", 2048, seed=width, poison=False)
    got = ops.encode_fused(x, width, use_pallas=False)
    assert_wire_equal(got, legacy_wire(x, width), width)


@pytest.mark.parametrize("width", list(range(1, 33, 3)) + [32])
def test_bitplane_pack_width_sweep_roundtrip(width):
    """pack/unpack parity+inversion for every plane count up to 32 (the
    full uint32 lane) — the fused encode emits this exact layout."""
    rng = np.random.default_rng(width)
    hi = 1 << min(width, 31)
    vals = jnp.asarray(rng.integers(0, hi, 32 * 256), jnp.uint32)
    pk = ops.pack(vals, width, use_pallas=True)
    assert (pk == ref.pack(vals, width)).all()
    assert (ops.unpack(pk, width, use_pallas=True) == vals).all()


def test_fused_all_zero_and_uniform_blocks():
    """Degenerate statistics: all-zero blocks (base escape -> 1) and
    constant blocks (rng == 1) must match the composition exactly."""
    x = jnp.zeros((2048,), jnp.bfloat16)
    assert_wire_equal(ops.encode_fused(x, 4, use_pallas=False),
                      legacy_wire(x, 4), "zeros")
    x = jnp.full((2048,), 0.5, jnp.bfloat16)
    assert_wire_equal(ops.encode_fused(x, 1, use_pallas=False),
                      legacy_wire(x, 1), "const")


def test_fused_overflow_flag_parity():
    """Exception-capacity overflow must fire identically on both paths."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(2.0 ** rng.uniform(-30, 30, 4096), jnp.bfloat16)
    got = ops.encode_fused(x, 2, exc_frac=0.01, use_pallas=False)
    want = legacy_wire(x, 2, exc_frac=0.01)
    assert int(got["overflow"]) == int(want["overflow"]) == 1
    assert_wire_equal(got, want, "overflow")


# ---------------------------------------------------------------------------
# chunked encode + round-trip through the fused receive
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dt", ["bfloat16", "float32"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_encode_chunks_fused_matches_legacy(dt, use_pallas):
    lay = codec.LAYOUTS[dt]
    rng = np.random.default_rng(9)
    x = rng.normal(0, 0.02, (4, 2048))
    x[rng.random((4, 2048)) < 0.05] = 0.0
    x = jnp.asarray(x, lay.dtype)
    got = cc._encode_chunks(x, width=5, block=512, exc_frac=0.02,
                            fused=True, use_pallas=use_pallas)
    want = cc._encode_chunks(x, width=5, block=512, exc_frac=0.02,
                             fused=False)
    assert_wire_equal(got, want, (dt, use_pallas))


@pytest.mark.parametrize("dt", ["bfloat16", "float32"])
def test_fused_encode_roundtrip_through_decode_reduce(dt):
    """encode_fused wire -> fused decode+reduce == sequential f32 sum of
    the original chunks: the full fused transmit+receive loop is lossless
    (exceptions included)."""
    lay = codec.LAYOUTS[dt]
    rng = np.random.default_rng(11)
    x = rng.normal(0, 0.02, (3, 4096))
    x[rng.random((3, 4096)) < 0.05] = 0.0
    x[0, 100] = 1e30 if dt == "float32" else 3e4  # exception block
    x = jnp.asarray(x, lay.dtype)
    wire = cc._encode_chunks(x, width=4, block=512, exc_frac=0.02, fused=True)
    acc, flag = cc._decode_reduce_chunks(wire, dtype=x.dtype, n=4096,
                                         width=4, block=512)
    want = cc._seq_sum(x, jnp.float32)
    assert int(flag) == 0
    assert (jax.lax.bitcast_convert_type(acc, jnp.uint32)
            == jax.lax.bitcast_convert_type(want, jnp.uint32)).all()


def test_encode_message_fused_default_and_roundtrip():
    """packing.encode_message routes through the fused dispatch by default,
    bit-identical to the legacy composition, and decode_message inverts."""
    x = make_input("bfloat16", 3000, seed=13)
    m_fused = packing.encode_message(x, width=4)
    m_legacy = packing.encode_message(x, width=4, fused=False)
    assert (m_fused.lo == m_legacy.lo).all()
    for f in ("payload", "bases", "exc_idx", "exc_raw", "overflow"):
        assert (getattr(m_fused.exp, f) == getattr(m_legacy.exp, f)).all(), f
    y = packing.decode_message(m_fused)
    u = codec.LAYOUTS["bfloat16"].uint_dtype
    assert (jax.lax.bitcast_convert_type(y, u)
            == jax.lax.bitcast_convert_type(x, u)).all()


# ---------------------------------------------------------------------------
# probe-driven dispatch (REPRO_USE_PALLAS) and fallback accounting
# ---------------------------------------------------------------------------

def test_probe_drives_fused_encode(monkeypatch):
    """REPRO_USE_PALLAS=1: use_pallas=None routes the encode through the
    interpret-mode Pallas kernel, bit-identical to the reference."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    kernels.probe_cache_clear()
    try:
        x = make_input("bfloat16", TILE + 600, seed=17)
        got = ops.encode_fused(x, 5, use_pallas=None)  # None -> probe -> True
        assert_wire_equal(got, legacy_wire(x, 5), "probe")
    finally:
        monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
        kernels.probe_cache_clear()


def test_kernel_fallbacks_counted_and_exposed():
    """The ops fast paths count (instead of silently absorbing) every
    requested-Pallas-but-shape-gated degrade; the fused encode does NOT
    degrade on ragged shapes (pad-to-tile keeps it on the kernel)."""
    kernels.clear_fallbacks()
    try:
        vals = jnp.zeros((32 * 3,), jnp.uint32)  # not a 32*TILE_G multiple
        ops.pack(vals, 4, use_pallas=True)
        ops.unpack(jnp.zeros((3 * 4,), jnp.uint32), 4, use_pallas=True)
        ops.split_with_stats(jnp.zeros((1024,), jnp.bfloat16),
                             use_pallas=True)
        counts = kernels.fallback_counts()
        assert counts == {"pack": 1, "unpack": 1, "split_with_stats": 1}
        # ragged fused encode: Pallas path, NO fallback recorded
        ops.encode_fused(make_input("bfloat16", 600, poison=False), 5,
                         use_pallas=True)
        assert kernels.fallback_counts() == counts
        # misaligned chunked encode degrades VISIBLY to the composition
        cc._encode_chunks(jnp.zeros((2, 600), jnp.bfloat16), width=4,
                          block=512, exc_frac=0.02, fused=True)
        assert kernels.fallback_counts()["encode_fused_chunks"] == 1
    finally:
        kernels.clear_fallbacks()


# ---------------------------------------------------------------------------
# policy knob, wire accounting, and plan threading
# ---------------------------------------------------------------------------

def _trace_psum_reports(fused_encode):
    from benchmarks.fig_encode import trace_encode_reports
    return trace_encode_reports(8, 1 << 18, jnp.bfloat16,
                                fused_encode=fused_encode)


def test_wire_reports_carry_encode_side_accounting():
    """Every compressed send phase reports the split-plane round-trip;
    the fused_encode knob moves it between paid and eliminated."""
    from repro.roofline.analysis import summarize_wire_reports
    s_f = summarize_wire_reports(_trace_psum_reports(True))
    s_u = summarize_wire_reports(_trace_psum_reports(False))
    assert s_f["encode_hbm_eliminated"] > 0 and s_f["encode_hbm_paid"] == 0
    assert s_u["encode_hbm_paid"] == s_f["encode_hbm_eliminated"]
    assert s_u["encode_hbm_eliminated"] == 0


def test_policy_fused_encode_bit_identical_one_device():
    """fused_encode on/off produce bit-identical collectives (1-dev mesh)."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    tree = {"w": make_input("bfloat16", 1 << 14, seed=19, poison=False),
            "b": make_input("float32", 4096, seed=20, poison=False)}
    outs = []
    for fe in (True, False):
        pol = CompressionPolicy(min_bytes=0, fused_encode=fe)
        out, flag = jax.jit(jax.shard_map(
            lambda t, _p=pol: cc.tree_psum_compressed(t, "data", policy=_p),
            mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
            axis_names={"data"}, check_vma=False))(tree)
        assert int(flag) == 0
        outs.append(out)
    for k in tree:
        u = codec.layout_of(tree[k].dtype).uint_dtype
        assert (jax.lax.bitcast_convert_type(outs[0][k], u)
                == jax.lax.bitcast_convert_type(outs[1][k], u)).all(), k


def test_plan_records_encode_fused_and_fingerprint_misses():
    """BucketPlan.encode_fused follows the policy knob; flipping the knob
    is a fingerprint change -> plan-cache miss (stale schedules never
    replay)."""
    from repro import sched
    from repro.sched import compile as sched_compile
    tree = {"w": jnp.zeros((1 << 15,), jnp.bfloat16)}
    pol = CompressionPolicy(min_bytes=0)
    plan = sched_compile.compile_psum_plan(tree, "data", policy=pol, n_dev=8)
    assert all(b.encode_fused for b in plan.buckets)
    assert plan.summary()["n_encode_fused"] == 1
    pol_off = dataclasses.replace(pol, fused_encode=False)
    plan_off = sched_compile.compile_psum_plan(tree, "data", policy=pol_off,
                                               n_dev=8)
    assert not any(b.encode_fused for b in plan_off.buckets)
    cache = sched.PlanCache()
    for p in (pol, pol_off):
        key = sched_compile.psum_plan_key(tree, "data", p, "gradient", 8)
        cache.get_or_compile(key, lambda _p=p, _k=key: (
            sched_compile.compile_psum_plan(tree, "data", policy=_p, n_dev=8,
                                            key=_k)))
    assert cache.stats.misses == 2  # knob flip cannot hit the old plan


def test_plan_executor_encode_parity_one_device():
    """psum_with_plan replays the recorded encode_fused flag bit-identically
    to the planless path, for both knob settings."""
    from jax.sharding import PartitionSpec as P
    from repro import sched
    mesh = jax.make_mesh((1,), ("data",))
    tree = {"w": make_input("bfloat16", 1 << 14, seed=23, poison=False)}
    for fe in (True, False):
        pol = CompressionPolicy(min_bytes=0, fused_encode=fe)
        a, fa = jax.jit(jax.shard_map(
            lambda t, _p=pol: sched.psum_with_plan(
                t, "data", policy=_p, cache=sched.PlanCache()),
            mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
            axis_names={"data"}, check_vma=False))(tree)
        b, fb = jax.jit(jax.shard_map(
            lambda t, _p=pol: cc.tree_psum_compressed(t, "data", policy=_p),
            mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
            axis_names={"data"}, check_vma=False))(tree)
        assert int(fa) == int(fb) == 0
        assert (jax.lax.bitcast_convert_type(a["w"], jnp.uint16)
                == jax.lax.bitcast_convert_type(b["w"], jnp.uint16)).all()


def test_encode_send_fused_parity_one_device():
    """encode_send's fused encode is bit-identical to its legacy path and
    lossless through the wire (identity perm)."""
    from jax.sharding import PartitionSpec as P
    from repro.core.split_send import encode_send
    mesh = jax.make_mesh((1,), ("data",))
    x = make_input("bfloat16", 2048 + 100, seed=29, poison=False)

    def body(v):
        a, f1 = encode_send(v, "data", [(0, 0)], width=5, fused_encode=True)
        b, f2 = encode_send(v, "data", [(0, 0)], width=5, fused_encode=False)
        return a, b, jnp.maximum(f1, f2)

    a, b, flag = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(),), out_specs=(P(), P(), P()),
        axis_names={"data"}, check_vma=False))(x)
    assert int(flag) == 0
    assert (jax.lax.bitcast_convert_type(a, jnp.uint16)
            == jax.lax.bitcast_convert_type(b, jnp.uint16)).all()
    assert (jax.lax.bitcast_convert_type(a, jnp.uint16)
            == jax.lax.bitcast_convert_type(x, jnp.uint16)).all()


# ---------------------------------------------------------------------------
# benchmark smoke (CI gate: must stay fast)
# ---------------------------------------------------------------------------

def test_fig_encode_smoke_runs():
    from benchmarks.fig_encode import run
    out = run(smoke=True)
    assert out["parity"] is True
    assert out["min_reduction"] >= 2.0
