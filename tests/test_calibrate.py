"""Width calibration + selective-compression policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import given, settings, strategies as st  # hypothesis or fallback

from repro.core import codec, packing
from repro.core.calibrate import (CompressionProfile, block_range_stats,
                                  calibrate_tree, choose_width)
from repro.core.policy import CompressionPolicy


def test_choose_width_concentrated_vs_wild():
    rng = np.random.default_rng(0)
    narrow = jnp.asarray(rng.normal(0, 0.02, 1 << 16), jnp.bfloat16)
    c = choose_width(narrow)
    assert c.width <= 6
    assert c.est_exc_rate <= 1e-3
    wild = jax.lax.bitcast_convert_type(
        jnp.asarray(rng.integers(0, 1 << 16, 1 << 14), jnp.uint16),
        jnp.bfloat16)
    cw = choose_width(wild)
    assert cw.width >= 7  # near-uniform exponents need full width


def test_choose_width_prediction_matches_encoder():
    """The calibrated (W, exc) must actually produce overflow == 0."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1.0, 1 << 16), jnp.bfloat16)
    c = choose_width(x)
    m = packing.encode_message(x, width=c.width, exc_frac=c.exc_frac)
    assert int(m.exp.overflow) == 0
    y = packing.decode_message(m)
    assert bool(jnp.all(jax.lax.bitcast_convert_type(x, jnp.uint16)
                        == jax.lax.bitcast_convert_type(y, jnp.uint16)))


@given(st.integers(1, 6))
@settings(max_examples=8, deadline=None)
def test_block_range_stats_bound_is_tight(seed):
    """stat < 2^W  <=>  the block packs without escaping."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 10.0 ** rng.integers(-3, 3), 4096),
                    jnp.bfloat16)
    stats = np.asarray(block_range_stats(x, block=512))
    for w in range(1, 9):
        pk = packing.pack_exponents(codec.split_planes(x)[0], width=w,
                                    block=512, exc_frac=1.0)
        n_escaped = int((np.asarray(pk.exc_idx) < len(stats)).sum())
        assert n_escaped == int((stats >= (1 << w)).sum())


def test_calibrate_tree():
    rng = np.random.default_rng(2)
    tree = {"a": jnp.asarray(rng.normal(0, 0.02, (128, 64)), jnp.bfloat16),
            "b": jnp.asarray(rng.normal(0, 1e-4, (4096,)), jnp.float32)}
    prof = calibrate_tree(tree, tensor_class="gradient")
    assert 1 <= prof.width_for("gradient") <= 8


def test_policy_gates():
    pol = CompressionPolicy()  # default: >1MB, data/pod axes only
    big = jnp.zeros((1 << 20,), jnp.bfloat16)  # 2 MB
    small = jnp.zeros((1 << 8,), jnp.bfloat16)
    ints = jnp.zeros((1 << 20,), jnp.int32)
    assert pol.should_compress(big, "data")
    assert pol.should_compress(big, ("data", "pod"))
    assert not pol.should_compress(small, "data"), "1MB threshold (paper)"
    assert not pol.should_compress(ints, "data"), "dtype gate"
    assert not pol.should_compress(big, "model"), "TP wires stay raw"
    assert not CompressionPolicy.disabled().should_compress(big, "data")


def test_profile_defaults_cover_all_dtypes():
    for name in ["bfloat16", "float32", "float16", "float8_e4m3fn",
                 "float8_e5m2"]:
        prof = CompressionProfile.default(name)
        assert 1 <= prof.width_for("gradient") <= 8


def _delta_widths_whole_tree(x, base, block=512, target=1e-3, exc=0.02):
    """``choose_delta_widths`` as one eager pass over the whole tree: the
    block-escape curve of the delta's exponent plane and the lo plane's
    per-element escape rate at every width."""
    from repro.core import calibrate

    lay = codec.layout_of(x.dtype)
    exp, lo = codec.split_bits(codec.xor_bits(x.reshape(-1),
                                              base.reshape(-1)), lay)
    rngs = np.asarray(calibrate._exp_range_stats(exp, block))
    w_exp = next((w for w in range(1, lay.exp_bits + 1)
                  if float(np.mean(rngs >= (1 << w))) <= target),
                 lay.exp_bits)
    lo = np.asarray(lo.astype(jnp.uint32))
    w_lo = next((w for w in range(1, lay.lo_bits + 1)
                 if float(np.mean(lo >= (1 << w))) <= exc / 2), lay.lo_bits)
    return w_exp, w_lo


@pytest.mark.parametrize("dtype", ["bfloat16", "float32", "float16",
                                   "float8_e4m3fn"])
@pytest.mark.parametrize("n", [100, 512, 70_001])
def test_sliced_delta_widths_match_the_whole_tree(monkeypatch, dtype, n):
    """``choose_delta_widths`` sums its escape counts over slices: the
    widths are the whole-tree pass's, for warm, mid and cold deltas, a
    ragged tail and slices of one block up to more than the tree."""
    from repro.core import calibrate

    rng = np.random.default_rng(n)
    b = rng.standard_normal(n).astype(np.float32) * 0.02
    for step in (1e-6, 1e-3, 1.0):
        x = b - step * rng.standard_normal(n).astype(np.float32) * (
            rng.random(n) < 0.5)
        x, base = jnp.asarray(x, dtype), jnp.asarray(b, dtype)
        want = _delta_widths_whole_tree(x, base)
        for sl in (512, 4096, 1 << 24):
            monkeypatch.setattr(calibrate, "_CAL_SLICE", sl)
            assert calibrate.choose_delta_widths(x, base) == want, (step, sl)
