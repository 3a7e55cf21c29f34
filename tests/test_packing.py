"""Static wire codec: bitplane packing, exceptions, overflow semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import given, settings, strategies as st  # hypothesis or fallback

from repro.core import codec, packing


@pytest.mark.parametrize("width", [1, 2, 3, 4, 5, 7, 8, 11, 16, 24])
def test_bitplane_roundtrip(width):
    rng = np.random.default_rng(width)
    vals = jnp.asarray(rng.integers(0, 1 << width, 32 * 17), jnp.uint32)
    pk = packing.bitplane_pack(vals, width)
    assert pk.shape == (17 * width,)
    up = packing.bitplane_unpack(pk, width)
    assert (up == vals).all()


@given(st.integers(1, 8), st.integers(1, 20))
@settings(max_examples=25, deadline=None)
def test_bitplane_roundtrip_property(width, groups):
    rng = np.random.default_rng(width * 100 + groups)
    vals = jnp.asarray(rng.integers(0, 1 << width, 32 * groups), jnp.uint32)
    assert (packing.bitplane_unpack(packing.bitplane_pack(vals, width), width) == vals).all()


@pytest.mark.parametrize("dt", list(codec.LAYOUTS))
@pytest.mark.parametrize("width", [4, 8])
def test_message_roundtrip(dt, width):
    lay = codec.LAYOUTS[dt]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.uniform(-1, 1, 3000), lay.dtype)
    m = packing.encode_message(x, width=width)
    y = packing.decode_message(m)
    xb = jax.lax.bitcast_convert_type(x, lay.uint_dtype)
    yb = jax.lax.bitcast_convert_type(y, lay.uint_dtype)
    assert (xb == yb).all()
    assert int(m.exp.overflow) == 0


def test_exceptions_restore_wild_blocks():
    """Blocks with exponent range > 2^W must round-trip via the exception
    region (paper's 'tails raw', made exact)."""
    rng = np.random.default_rng(4)
    x = np.random.default_rng(4).uniform(0.5, 1.0, 4096).astype(np.float32)
    # poison two blocks with huge dynamic range
    x[100] = 1e-30
    x[1500] = 1e30
    x = jnp.asarray(x)
    m = packing.encode_message(x, width=2)
    assert int(m.exp.overflow) == 0  # capacity covers 2 blocks
    y = packing.decode_message(m)
    assert (jax.lax.bitcast_convert_type(x, jnp.uint32)
            == jax.lax.bitcast_convert_type(y, jnp.uint32)).all()


def test_overflow_flag_fires_and_never_lies():
    """If overflow==0 the decode MUST be exact; if the data is too wild for
    (W, capacity), the flag must be 1."""
    rng = np.random.default_rng(5)
    # exponents uniform over the full range -> every block escapes
    bits = rng.integers(0, 1 << 16, 8192).astype(np.uint16)
    x = jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.bfloat16)
    m = packing.encode_message(x, width=2, exc_frac=0.01)
    assert int(m.exp.overflow) == 1
    # generous capacity: exact again
    m2 = packing.encode_message(x, width=2, exc_frac=1.0)
    assert int(m2.exp.overflow) == 0
    y2 = packing.decode_message(m2)
    assert (jax.lax.bitcast_convert_type(y2, jnp.uint16) == jnp.asarray(bits)).all()


@given(
    st.integers(1, 8),
    st.lists(st.integers(0, 255), min_size=1, max_size=600),
)
@settings(max_examples=30, deadline=None)
def test_pack_exponents_property(width, exps):
    """For arbitrary exponent bytes: overflow==0 implies exact decode."""
    exp = jnp.asarray(np.asarray(exps, np.uint8))
    p = packing.pack_exponents(exp, width=width, block=64, exc_frac=0.5)
    out = packing.unpack_exponents(p)
    if int(p.overflow) == 0:
        assert (out == exp).all()


def test_wire_ratio_accounting():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.uniform(-1, 1, 1 << 18), jnp.bfloat16)
    m = packing.encode_message(x, width=4)
    # bf16 W=4: (8 + 4 + eps) / 16 ~ 0.75 + exception overhead
    assert 0.70 < m.ratio() < 0.80, m.ratio()
    m8 = packing.encode_message(x, width=8)
    assert m8.ratio() > 1.0  # W=8 == raw + overhead (no compression claimed)


def test_jit_static_shapes():
    """Wire shapes are static: the same jitted encoder serves every step."""
    enc = jax.jit(lambda v: packing.encode_message(v, width=4))
    x1 = jnp.ones((2048,), jnp.bfloat16)
    x2 = jnp.zeros((2048,), jnp.bfloat16)
    m1, m2 = enc(x1), enc(x2)
    assert m1.lo.shape == m2.lo.shape
    assert m1.exp.payload.shape == m2.exp.payload.shape


@pytest.mark.parametrize("size_of", [lambda n: 1, lambda n: max(1, n // 50),
                                     lambda n: n],
                         ids=["size1", "size_n_50", "size_n"])
@pytest.mark.parametrize("n,density", [(32 * 333, 0.0), (32 * 333, 0.005),
                                       (32 * 333, 0.1), (32 * 333, 1.0),
                                       (32, 0.5)],
                         ids=["empty", "sparse", "dense", "full", "one_word"])
def test_exception_indices_equal_nonzero(n, density, size_of):
    """The two-level exception index is ``jnp.nonzero`` exactly: the same
    first ``size`` indices (truncation included), the same fill, int32."""
    mask = jnp.asarray(np.random.default_rng(n + int(density * 1000))
                       .random(n) < density)
    size = size_of(n)
    got = packing.exception_indices(mask, size=size, fill=n)
    (want,) = jnp.nonzero(mask, size=size, fill_value=n)
    assert got.dtype == jnp.int32 and got.shape == (size,)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _padded_pack(vals, width):
    """The packer as it was before the flat wire: a ``(n // 32, width)``
    array, lane-padded on a TPU."""
    g = vals.reshape(-1, 32).astype(jnp.uint32)
    pos = jnp.arange(32, dtype=jnp.uint32)
    return jnp.stack([jnp.sum(((g >> jnp.uint32(b)) & 1) << pos, axis=-1,
                              dtype=jnp.uint32) for b in range(width)], -1)


def _padded_unpack(packed, width):
    pos = jnp.arange(32, dtype=jnp.uint32)
    vals = jnp.zeros((packed.shape[0], 32), jnp.uint32)
    for b in range(width):
        vals = vals | (((packed[:, b:b + 1] >> pos) & 1) << jnp.uint32(b))
    return vals.reshape(-1)


@pytest.mark.parametrize("width", range(1, 9))
@pytest.mark.parametrize("n", [
    32,                                # one group
    packing._SLICE - 32,               # just under one slice: no loop
    2 * packing._SLICE + 32 * 5,       # a loop of two slices, a ragged tail
    3 * packing._SLICE,                # a loop of whole slices only
])
def test_bitplane_flat_matches_padded_form(width, n):
    """The flat packer and unpacker are bit-identical to the lane-padded
    form, word for word in ``[g, b]`` order, looped or not; bits above
    ``width`` are ignored by both; narrow dtypes in and out."""
    rng = np.random.default_rng(width * 1000 + n)
    vals = jnp.asarray(rng.integers(0, 1 << (width + 2), n), jnp.uint32)
    want = _padded_pack(vals, width)
    got = packing.bitplane_pack(vals, width)
    assert got.shape == (n // 32 * width,) and got.dtype == jnp.uint32
    assert (got == want.reshape(-1)).all()
    assert (packing.bitplane_pack(vals.astype(jnp.uint8), width)
            == packing.bitplane_pack(vals.astype(jnp.uint8).astype(
                jnp.uint32), width)).all()
    back = packing.bitplane_unpack(got, width)
    assert back.dtype == jnp.uint32
    assert (back == _padded_unpack(want, width)).all()
    narrow = packing.bitplane_unpack(got, width, jnp.uint8)
    assert narrow.dtype == jnp.uint8 and (narrow == back.astype(
        jnp.uint8)).all()
