"""Pallas TPU kernel: fused transmit-side encode — split + stats + pack.

The paper's §3.2 Step 1 does the float split and the entropy-coder feed in
ONE kernel so the input tensor is read from HBM once and only wire-format
bytes are written back.  The unfused TPU composition
(``codec.split_planes`` -> ``packing.pack_exponents`` /
``packing.bitplane_pack``) instead materializes the exponent plane and the
lo plane in HBM between the split and the pack — a write + re-read of
~``(1 + itemsize)`` bytes per element that this kernel eliminates.

One grid step reads a ``(TILE_B, block)`` float tile and emits, per tile:
  * the packed exponent payload — ``width`` uint32 bit-planes per group of
    32 residuals, the exact layout of ``packing.bitplane_pack``;
  * the packed lo planes (sign relocated next to the mantissa,
    ``codec.split_planes`` layout, ``lo_bits`` planes);
  * per-block ``base`` (min NONZERO exponent; 1 for all-zero blocks) and
    ``rng`` (max residual code value) — the localized statistic of
    ``packing.pack_exponents``'s zero-escape wire format.

Exception blocks (``rng >= 2**width``) carry clamped payload exactly like
``pack_exponents`` and are patched by the caller (``kernels/ops``) from a
re-read of ONLY the exception rows (<= ``exc_frac`` of the input) — the
bulk stays one-pass.

The residual/pack algebra is pure VPU bit arithmetic on 32-bit lanes
(16- and 8-bit inputs are zero-extended on load: Mosaic has no 16-bit
shifts), held as ``int32`` because Mosaic has no unsigned min/max or
reduction: exponents fit in 8 bits, and a plane word is a sum of distinct
powers of two, so the int32 sum has the uint32 word's bits.  The input is
viewed as ``(blocks, block/32, 32)`` so every 32-element group is one row
of lanes: per-block stats are a lane then a sublane min/max, and each
bit-plane word is a lane sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import codec
from repro.core.packing import GROUP
from repro.kernels import resolve_interpret

TILE_B = 8  # blocks per grid step (matches plane_split.py)


def _plane_words(v, n_planes: int, out_ref):
    """Bit-plane pack int32 (..., 32) groups: plane ``b``'s word is the lane
    sum of ``bit_b << lane``, bitcast to uint32."""
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, GROUP), 2)
    for b in range(n_planes):  # static unroll: one lane reduction per plane
        out_ref[:, :, b] = jax.lax.bitcast_convert_type(
            jnp.sum(((v >> b) & 1) << pos, axis=-1), jnp.uint32)


def _encode_kernel(lay: codec.FloatLayout, width: int, x_ref, pay_ref, lo_ref,
                   base_ref, rng_ref):
    x = jax.lax.bitcast_convert_type(x_ref[...], lay.uint_dtype)
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)
    exp = (bits >> lay.mant_bits) & ((1 << lay.exp_bits) - 1)
    sign = (bits >> (lay.total_bits - 1)) & 1
    lo = (sign << lay.mant_bits) | (bits & ((1 << lay.mant_bits) - 1))

    # zero-escape stats (wire format of packing.pack_exponents): base is the
    # min NONZERO exponent (1 when the block is all-zero), rng the max code
    # value ``max_nz - base + 1`` (0 when all-zero: 0 - 1 + 1 wraps to 0).
    nz = exp != 0
    lo_e = jnp.min(jnp.min(jnp.where(nz, exp, 255), axis=2, keepdims=True),
                   axis=1, keepdims=True)  # (TILE_B, 1, 1)
    hi_e = jnp.max(jnp.max(jnp.where(nz, exp, 0), axis=2, keepdims=True),
                   axis=1, keepdims=True)
    base = jnp.where(hi_e > 0, lo_e, 1)
    base_ref[...] = base.astype(jnp.uint32)
    rng_ref[...] = jax.lax.bitcast_convert_type(hi_e - base + 1, jnp.uint32)

    # residuals: code 0 = exponent 0, code r>0 = exp - base + 1, clamped to
    # width bits (exception blocks: payload is garbage, restored from the
    # raw exception region by the caller — identical to pack_exponents)
    resid = jnp.where(nz, exp - base + 1, 0)
    resid = jnp.minimum(resid, (1 << width) - 1)
    _plane_words(resid, width, pay_ref)
    _plane_words(lo, lay.lo_bits, lo_ref)


@functools.partial(jax.jit, static_argnames=("width", "block", "interpret"))
def encode_fused(x: jax.Array, width: int, block: int = 512,
                 interpret: bool | None = None):
    """x float (n,), n % (block*TILE_B) == 0, 1 <= width <= 32.

    Returns (payload uint32 (n//32, width), lo_planes uint32 (n//32,
    lo_bits), bases uint32 (n_blocks,), rng uint32 (n_blocks,)) — one HBM
    pass over ``x``; bit-identical to ``kernels/ref.encode_fused`` (and
    through it to the split_planes + pack_exponents composition).
    ``interpret=None`` resolves through ``kernels.resolve_interpret``.
    """
    lay = codec.layout_of(x.dtype)
    n = x.shape[0]
    assert n % (block * TILE_B) == 0, (n, block, TILE_B)
    assert 1 <= width <= 32, width
    nb = n // block
    gpb = block // GROUP  # packed groups per block
    xb = x.reshape(nb, gpb, GROUP)
    pay, lo, base, rng = pl.pallas_call(
        functools.partial(_encode_kernel, lay, width),
        out_shape=(
            jax.ShapeDtypeStruct((nb, gpb, width), jnp.uint32),
            jax.ShapeDtypeStruct((nb, gpb, lay.lo_bits), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1, 1), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1, 1), jnp.uint32),
        ),
        grid=(nb // TILE_B,),
        in_specs=[pl.BlockSpec((TILE_B, gpb, GROUP), lambda i: (i, 0, 0))],
        out_specs=(
            pl.BlockSpec((TILE_B, gpb, width), lambda i: (i, 0, 0)),
            pl.BlockSpec((TILE_B, gpb, lay.lo_bits), lambda i: (i, 0, 0)),
            pl.BlockSpec((TILE_B, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((TILE_B, 1, 1), lambda i: (i, 0, 0)),
        ),
        interpret=resolve_interpret(interpret),
        name="encode_fused",  # the kernel's name in a profiler trace
    )(xb)
    return (pay.reshape(-1, width), lo.reshape(-1, lay.lo_bits),
            base.reshape(-1), rng.reshape(-1))
