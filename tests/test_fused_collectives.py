"""Fused decode+reduce receive path: bit-identical to the unfused path.

Chunk-level tests drive the collective internals directly (no mesh): the
wire dicts produced by ``_encode_chunks`` are exactly what arrives after
the all_to_all, so ``_decode_reduce_chunks`` (fused) vs ``_decode_chunks``
+ ``_seq_sum`` (unfused) is the receive-side comparison the paper's §3.4
makes.  Mesh-level parity across 8 real devices lives in test_multidev.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec, packing
from repro.core import compressed_collectives as cc
from repro.core import policy as policy_lib
from repro.kernels import ops, ref
from repro.kernels.decode_reduce import TILE_G

DTYPES = ["bfloat16", "float32", "float16"]


def bits32(a):
    return jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)


def make_chunks(dt_name, n_chunks, chunk, seed=0, zeros=0.05, poison=()):
    """Realistic gradient-like chunks with exact zeros and optional poison
    values that force exception blocks."""
    lay = codec.LAYOUTS[dt_name]
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 0.02, (n_chunks, chunk))
    x[rng.random((n_chunks, chunk)) < zeros] = 0.0
    for (c, i, v) in poison:
        x[c, i] = v
    return jnp.asarray(x, lay.dtype)


def fused_vs_unfused(x, width, *, block=512, exc_frac=0.02, use_pallas=False):
    chunk = x.shape[1]
    wire = cc._encode_chunks(x, width=width, block=block, exc_frac=exc_frac)
    vals, f1 = cc._decode_chunks(wire, dtype=x.dtype, n=chunk, width=width,
                                 block=block)
    unfused = cc._seq_sum(vals, jnp.float32)
    fused, f2 = cc._decode_reduce_chunks(wire, dtype=x.dtype, n=chunk,
                                         width=width, block=block,
                                         use_pallas=use_pallas)
    return unfused, fused, int(f1), int(f2)


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("width", [3, 4, 5])
def test_fused_bit_identical(dt, width):
    x = make_chunks(dt, 4, 4096, seed=width)
    unfused, fused, f1, f2 = fused_vs_unfused(x, width)
    assert f1 == f2
    assert (bits32(unfused) == bits32(fused)).all()


@pytest.mark.parametrize("dt", ["bfloat16", "float32"])
def test_fused_exception_blocks_exact(dt):
    """Poisoned wide-dynamic-range blocks ride the exception region; the
    fused patch-up must reproduce the unfused result bit-for-bit AND the
    true f32 sum (flag stays 0: capacity covers the poisons)."""
    hi, lo = (1e30, 1e-30)
    x = make_chunks(dt, 3, 4096, seed=1,
                    poison=[(0, 100, hi), (1, 700, lo), (2, 700, -hi)])
    unfused, fused, f1, f2 = fused_vs_unfused(x, width=4)
    assert f1 == 0 and f2 == 0
    assert (bits32(unfused) == bits32(fused)).all()
    truth = cc._seq_sum(x, jnp.float32)
    assert (bits32(truth) == bits32(fused)).all()


def test_fused_overflow_flag_and_parity():
    """Wild-but-finite data at a tiny width overflows exception capacity:
    the flag must fire on BOTH paths and the outputs still agree bitwise
    (the caller discards them and retries uncompressed either way)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(2.0 ** rng.uniform(-30, 30, (2, 4096)), jnp.bfloat16)
    wire = cc._encode_chunks(x, width=2, block=512, exc_frac=0.01)
    vals, f1 = cc._decode_chunks(wire, dtype=x.dtype, n=4096, width=2,
                                 block=512)
    unfused = cc._seq_sum(vals, jnp.float32)
    fused, f2 = cc._decode_reduce_chunks(wire, dtype=x.dtype, n=4096,
                                         width=2, block=512)
    assert int(f1) == 1 and int(f2) == 1
    assert (bits32(unfused) == bits32(fused)).all()


def test_fused_pallas_kernel_path():
    """TILE_G-aligned chunks take the Pallas kernel (interpret mode on CPU)
    and must match the unfused path bitwise."""
    chunk = 32 * TILE_G  # n_groups == TILE_G: kernel-aligned
    x = make_chunks("bfloat16", 2, chunk, seed=4)
    unfused, fused, f1, f2 = fused_vs_unfused(x, width=5, use_pallas=True)
    assert (bits32(unfused) == bits32(fused)).all()


def test_tile_misaligned_falls_back():
    """n_groups % TILE_G != 0: ops.decode_reduce stays on the Pallas kernel
    (its last tile is partial) instead of falling back to the reference,
    and matches the unfused path bitwise."""
    from repro import kernels

    chunk = 512 * 3  # 48 groups: not a TILE_G multiple
    x = make_chunks("bfloat16", 2, chunk, seed=5)
    kernels.clear_fallbacks()
    unfused, fused, f1, f2 = fused_vs_unfused(x, width=5, use_pallas=True)
    assert (bits32(unfused) == bits32(fused)).all()
    assert "decode_reduce" not in kernels.fallback_counts()


def test_acc_dtype_fallback_unfused():
    """Non-f32 accumulation has no fused kernel: reduce_scatter_compressed
    must fall back without error (1-device axis via shard_map)."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.asarray(np.random.default_rng(6).normal(0, 1, 2048), jnp.bfloat16)
    out, flag = jax.jit(jax.shard_map(
        lambda v: cc.reduce_scatter_compressed(
            v, "data", width=5, acc_dtype=jnp.bfloat16, use_fused=True),
        mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))(x)
    assert int(flag) == 0


def test_reduce_scatter_roundtrip_one_device():
    """k=1 reduce-scatter == exact decode of own chunk (fused path)."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.asarray(np.random.default_rng(7).normal(0, 0.02, 4096),
                    jnp.bfloat16)
    out, flag = jax.jit(jax.shard_map(
        lambda v: cc.reduce_scatter_compressed(v, "data", width=5),
        mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))(x)
    assert int(flag) == 0
    assert (bits32(x.astype(jnp.float32)) == bits32(out)).all()


def test_tree_psum_mixed_dtype_lossless_one_device():
    """{f32, bf16} pytree: per-dtype bucketing keeps every leaf bit-exact
    at its own precision (k=1: the sum is the identity, so any cast of the
    f32 leaf through bf16 — the old bug — would show up as bit drift)."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    rng = np.random.default_rng(8)
    tree = {
        "w_bf16": jnp.asarray(rng.normal(0, 0.02, (128, 32)), jnp.bfloat16),
        "b_f32": jnp.asarray(rng.normal(0, 1, (4096,)), jnp.float32),
        "h_f16": jnp.asarray(rng.normal(0, 1, (2048,)), jnp.float16),
        "step": jnp.asarray(7, jnp.int32),
    }
    pol = policy_lib.CompressionPolicy(min_bytes=0)
    out, flag = jax.jit(jax.shard_map(
        lambda t: cc.tree_psum_compressed(t, "data", policy=pol),
        mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))(tree)
    assert int(flag) == 0
    for k in ("w_bf16", "b_f32", "h_f16"):
        assert out[k].dtype == tree[k].dtype, k
        a = jax.lax.bitcast_convert_type(
            out[k], codec.layout_of(out[k].dtype).uint_dtype)
        b = jax.lax.bitcast_convert_type(
            tree[k], codec.layout_of(tree[k].dtype).uint_dtype)
        assert (a == b).all(), k
    assert int(out["step"]) == 7


def test_wire_reports_emitted_and_fused_flagged():
    """Tracing the two-shot over an abstract 8-device mesh emits WireReports
    whose fused flag follows the policy knob and whose decoded-HBM
    accounting moves from 'paid' to 'eliminated'."""
    from benchmarks.fig9_twoshot import trace_wire_reports
    from repro.roofline.analysis import summarize_wire_reports

    rs_fused = [r for r in trace_wire_reports(8, 1 << 18, fused=True)
                if r.name == "reduce_scatter"]
    rs_unfused = [r for r in trace_wire_reports(8, 1 << 18, fused=False)
                  if r.name == "reduce_scatter"]
    assert rs_fused and rs_unfused
    assert all(r.fused for r in rs_fused)
    assert not any(r.fused for r in rs_unfused)
    assert all(0 < r.wire_bytes < r.raw_bytes for r in rs_fused)
    s_f = summarize_wire_reports(rs_fused)
    s_u = summarize_wire_reports(rs_unfused)
    assert s_f["decode_hbm_eliminated"] > 0 and s_f["decode_hbm_paid"] == 0
    assert s_u["decode_hbm_paid"] == s_f["decode_hbm_eliminated"]


def test_decode_reduce_kernel_zero_escape_matches_wire_format():
    """The kernel decodes the REAL wire (pack_exponents zero-escape) —
    non-exception data must match unpack_exponents + merge + add exactly."""
    lay = codec.LAYOUTS["bfloat16"]
    rng = np.random.default_rng(9)
    n = 32 * TILE_G
    x = jnp.asarray(rng.normal(0, 0.02, n), jnp.bfloat16)
    x = x.at[:n // 4].set(0.0)  # exercise the zero escape heavily
    exp, lo = codec.split_planes(x)
    pk = packing.pack_exponents(exp, width=8, block=512)  # w=8: no escapes
    assert int(pk.overflow) == 0
    gb = jnp.repeat(pk.bases.astype(jnp.uint32), 512 // packing.GROUP)
    lo_planes = packing.bitplane_pack(lo.astype(jnp.uint32), lay.lo_bits)
    acc = jnp.asarray(rng.normal(0, 1, n), jnp.float32)
    got = ops.decode_reduce(pk.payload, lo_planes, gb, acc, "bfloat16", 8,
                            use_pallas=True)
    want_vals = codec.merge_planes(packing.unpack_exponents(pk),
                                   lo.astype(lay.uint_dtype),
                                   lay.dtype, (n,))
    want = acc + want_vals.astype(jnp.float32)
    assert (bits32(got) == bits32(want)).all()
    # and the jnp reference agrees with the kernel
    got_ref = ref.decode_reduce(pk.payload, lo_planes, gb, acc, "bfloat16", 8)
    assert (bits32(got_ref) == bits32(got)).all()
