"""Pallas kernels vs pure-jnp oracles: shape/dtype/width sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ans as core_ans
from repro.core import codec, packing
from repro.kernels import ops, ref
from repro.kernels.bitpack import TILE_G
from repro.kernels.plane_split import TILE_B


@pytest.mark.parametrize("width", [1, 2, 4, 5, 8, 11, 24])
@pytest.mark.parametrize("tiles", [1, 3])
def test_bitpack_kernel_matches_ref(width, tiles):
    rng = np.random.default_rng(width)
    n = 32 * TILE_G * tiles
    vals = jnp.asarray(rng.integers(0, 1 << width, n), jnp.uint32)
    assert (ops.pack(vals, width, use_pallas=True) == ref.pack(vals, width)).all()
    pk = ref.pack(vals, width)
    assert (ops.unpack(pk, width, use_pallas=True) == vals).all()


@pytest.mark.parametrize("dt", list(codec.LAYOUTS))
@pytest.mark.parametrize("tiles", [1, 2])
def test_plane_split_kernel_matches_ref(dt, tiles):
    lay = codec.LAYOUTS[dt]
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(0, 2, 512 * TILE_B * tiles), lay.dtype)
    got = ops.split_with_stats(x, use_pallas=True)
    want = ref.split_with_stats(x)
    for g, w in zip(got, want):
        assert (g == w).all()


@pytest.mark.parametrize("dt", ["bfloat16", "float32"])
@pytest.mark.parametrize("width", [3, 5, 8])
def test_decode_reduce_kernel_matches_ref(dt, width):
    """Kernel vs jnp oracle on the REAL wire format (pack_exponents
    zero-escape; exception blocks carry clamped payload — the kernel and
    oracle must agree on those too, the collective patches them after)."""
    lay = codec.LAYOUTS[dt]
    rng = np.random.default_rng(8)
    n = 32 * TILE_G
    x = np.asarray(rng.normal(0, 1, n))
    x[rng.random(n) < 0.05] = 0.0  # exact zeros: exercise the escape
    x = jnp.asarray(x, lay.dtype)
    exp, lo = codec.split_planes(x)
    pk = packing.pack_exponents(exp, width=width, block=512)
    gb = jnp.repeat(pk.bases.astype(jnp.uint32), 512 // 32)
    lo_planes = packing.bitplane_pack(lo.astype(jnp.uint32), lay.lo_bits)
    acc = jnp.asarray(rng.normal(0, 1, n), jnp.float32)
    got = ops.decode_reduce(pk.payload, lo_planes, gb, acc, dt, width,
                            use_pallas=True)
    want = ref.decode_reduce(pk.payload, lo_planes, gb, acc, dt, width)
    assert (got == want).all()
    if width == 8:  # no exception blocks possible: exact vs unfused decode
        full = codec.merge_planes(packing.unpack_exponents(pk),
                                  lo.astype(lay.uint_dtype), lay.dtype, (n,))
        assert (got == acc + full.astype(jnp.float32)).all()


@pytest.mark.parametrize("per", [1, 8, 64])
@pytest.mark.parametrize("lanes", [128, 256])
def test_rans_kernel_matches_ref_and_inverts(per, lanes):
    rng = np.random.default_rng(per * 1000 + lanes)
    syms = jnp.asarray(
        np.clip(rng.normal(120, 4, (per, lanes)), 0, 255).astype(np.uint32)
    )
    table = core_ans.build_freq_table(syms.astype(jnp.uint8).reshape(-1))
    wk, mk, sk = ops.rans_encode(syms, table, use_pallas=True)
    wr, mr, sr = ref.rans_encode(syms, table.freq, table.cum[:256])
    assert (wk == wr).all() and (mk == mr).all() and (sk == sr).all()
    out = ops.rans_decode(wk, sk, table, use_pallas=True)
    assert (out == syms).all()


def test_rans_kernel_adversarial_uniform():
    """Incompressible symbols: kernel must stay exact (just emits ~every row)."""
    rng = np.random.default_rng(99)
    syms = jnp.asarray(rng.integers(0, 256, (32, 128)).astype(np.uint32))
    table = core_ans.build_freq_table(syms.astype(jnp.uint8).reshape(-1))
    w, m, s = ops.rans_encode(syms, table, use_pallas=True)
    assert (ops.rans_decode(w, s, table, use_pallas=True) == syms).all()
    # uniform-256 data costs ~8 bits/sym ~= 0.5 words/sym (state absorbs a bit)
    assert float(m.mean()) > 0.4


# ---------------------------------------------------------------------------
# backend probe: nothing may hide the device
# ---------------------------------------------------------------------------

def test_backend_probe_failure_raises(monkeypatch):
    """A backend that fails to start is an error, never reported as the
    CPU (which would move every wire onto the jnp reference)."""
    from repro import kernels

    def broken():
        raise RuntimeError("backend failed to start")

    monkeypatch.setattr(jax, "default_backend", broken)
    kernels.probe_cache_clear()
    try:
        with pytest.raises(RuntimeError, match="failed to start"):
            kernels.backend()
    finally:
        monkeypatch.undo()
        kernels.probe_cache_clear()


def test_false_use_pallas_env_is_an_error_on_tpu(monkeypatch):
    """On the TPU the compiled kernels are the only path: a false
    REPRO_USE_PALLAS raises instead of running the reference."""
    from repro import kernels

    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    try:
        for env, want in (("0", RuntimeError), ("1", True), (None, True)):
            if env is None:
                monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
            else:
                monkeypatch.setenv("REPRO_USE_PALLAS", env)
            kernels.default_use_pallas.cache_clear()
            if want is RuntimeError:
                with pytest.raises(RuntimeError, match="REPRO_USE_PALLAS"):
                    kernels.default_use_pallas()
            else:
                assert kernels.default_use_pallas() is want
                assert kernels.default_interpret() is False
    finally:
        monkeypatch.undo()
        kernels.probe_cache_clear()


class _Traced(Exception):
    """Raised by the pallas_call spy once the kernel's mode is known."""


def _u32(*shape):
    return jnp.zeros(shape, jnp.uint32)


def _kernel_calls():
    from repro.kernels import bitpack, decode_reduce, encode_fused, plane_split
    from repro.kernels import rans

    return {  # each takes a size multiplier (fresh shapes -> fresh traces)
        "encode_fused": lambda k: encode_fused.encode_fused(
            jnp.zeros((4096 * k,), jnp.float32), 5),
        "decode_reduce": lambda k: decode_reduce.decode_reduce(
            _u32(256 * k, 5), _u32(256 * k, 24), _u32(256 * k),
            jnp.zeros((8192 * k,), jnp.float32), "float32", 5),
        "bitpack.pack": lambda k: bitpack.pack(_u32(8192 * k), 4),
        "bitpack.unpack": lambda k: bitpack.unpack(_u32(256 * k, 4), 4),
        "plane_split": lambda k: plane_split.split_with_stats(
            jnp.zeros((4096 * k,), jnp.bfloat16)),
        "rans.encode": lambda k: rans.encode(_u32(k, 128), _u32(256),
                                             _u32(256)),
        "rans.decode": lambda k: rans.decode(
            _u32(k, 128), _u32(128), _u32(256), _u32(256), _u32(rans.M)),
    }


@pytest.mark.parametrize("kernel", sorted(_kernel_calls()))
def test_kernel_interpret_none_resolves_through_probe(monkeypatch, kernel):
    """``interpret=None`` (every kernel's default) follows the backend
    probe: interpreted on the CPU, compiled where the probe finds a TPU."""
    from jax.experimental import pallas as pl
    from repro import kernels

    seen = []

    def spy(*args, interpret=None, **kw):
        seen.append(interpret)
        raise _Traced

    monkeypatch.setattr(pl, "pallas_call", spy)
    call = _kernel_calls()[kernel]
    with pytest.raises(_Traced):
        call(1)  # the CPU: interpret mode
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    with pytest.raises(_Traced):
        call(2)
    assert seen == [True, False]
