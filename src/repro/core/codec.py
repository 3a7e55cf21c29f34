"""Bit-plane split/merge for floating-point tensors (paper §2.1.2, Step 1).

Every float is decomposed into
  - the *exponent plane*  (narrow, skewed distribution -> compressible), and
  - the *lo plane*        (sign + mantissa, near-uniform -> transmitted raw).

Formats (paper §4.1): float32, float16, bfloat16, float8_e4m3fn, float8_e5m2.
For fp8 formats the paper packs two exponent fields per byte for
byte-granular split-stage writes; :func:`pack_fp8_exp_pairs` mirrors that on
the raw-wire path.  The block packer (packing.py) consumes the *unpacked*
uint8 exponent stream.

All functions are pure jnp, shape-static, and exactly invertible (bit-exact,
including NaN payloads and infinities): ``merge(split(x)) == x`` bitwise.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class FloatLayout:
    """Bit layout of a supported floating-point format."""

    name: str
    dtype: jnp.dtype
    total_bits: int
    exp_bits: int
    mant_bits: int  # mantissa (fraction) bits; sign is always 1

    @property
    def lo_bits(self) -> int:  # sign + mantissa
        return 1 + self.mant_bits

    @property
    def uint_dtype(self):
        return {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32}[self.total_bits]


LAYOUTS: dict[str, FloatLayout] = {
    "float32": FloatLayout("float32", jnp.float32, 32, 8, 23),
    "float16": FloatLayout("float16", jnp.float16, 16, 5, 10),
    "bfloat16": FloatLayout("bfloat16", jnp.bfloat16, 16, 8, 7),
    "float8_e4m3fn": FloatLayout("float8_e4m3fn", jnp.float8_e4m3fn, 8, 4, 3),
    "float8_e5m2": FloatLayout("float8_e5m2", jnp.float8_e5m2, 8, 5, 2),
}


def layout_of(dtype) -> FloatLayout:
    name = jnp.dtype(dtype).name
    if name not in LAYOUTS:
        raise ValueError(f"unsupported dtype for codec: {name}")
    return LAYOUTS[name]


def split_planes(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Split ``x`` (any shape) into ``(exp_plane, lo_plane)``.

    exp_plane: uint8 (N,), one exponent field per element.
    lo_plane:  uint of the element width (N,), holding ``sign << mant_bits |
               mantissa`` — i.e. the sign bit relocated adjacent to the
               mantissa so every lo value fits in ``lo_bits`` bits and the
               wire layer can bit-pack it densely (one memory pass — Step 1).
    """
    lay = layout_of(x.dtype)
    return split_bits(
        jax.lax.bitcast_convert_type(x.reshape(-1), lay.uint_dtype), lay)


def split_bits(bits: jax.Array, lay: FloatLayout):
    """:func:`split_planes` of a flat ``lay.uint_dtype`` bit pattern."""
    u = lay.uint_dtype
    mant_mask = u((1 << lay.mant_bits) - 1)
    exp = (
        (bits >> u(lay.mant_bits)) & u((1 << lay.exp_bits) - 1)
    ).astype(jnp.uint8)
    sign = bits >> u(lay.total_bits - 1)
    lo = (sign << u(lay.mant_bits)) | (bits & mant_mask)
    return exp, lo


def merge_planes(
    exp: jax.Array, lo: jax.Array, dtype, shape: tuple[int, ...]
) -> jax.Array:
    """Exact inverse of :func:`split_planes`."""
    lay = layout_of(dtype)
    n = int(np.prod(shape)) if shape else 1
    bits = merge_bits(exp, lo, lay, n)
    return jax.lax.bitcast_convert_type(bits, lay.dtype).reshape(shape)


def merge_bits(exp: jax.Array, lo: jax.Array, lay: FloatLayout,
               n: int) -> jax.Array:
    """Exact inverse of :func:`split_bits`: the flat (n,) bit pattern."""
    u = lay.uint_dtype
    lo = lo.reshape(-1)[:n].astype(u)
    exp = exp.reshape(-1)[:n].astype(u)
    sign = lo >> u(lay.mant_bits)
    mant = lo & u((1 << lay.mant_bits) - 1)
    return (sign << u(lay.total_bits - 1)) | (exp << u(lay.mant_bits)) | mant


# ---------------------------------------------------------------------------
# XOR delta transform (weight-sync subsystem, src/repro/sync/).
#
# Consecutive policy-weight versions differ by small optimizer steps, so the
# bitwise XOR of a version against the receiver's base version concentrates
# its nonzero bits in the low mantissa positions (and is EXACTLY zero for
# weights the step didn't move — ubiquitous for bf16, where sub-ULP updates
# round away).  The delta is a bit pattern of the same width, so the
# existing split (``split_bits``) and pack apply to it unchanged; the
# transform is a pure involution on the raw bits — NaN payloads, infinities
# and subnormals round-trip exactly.
# ---------------------------------------------------------------------------


def xor_bits(x: jax.Array, base: jax.Array) -> jax.Array:
    """Bitwise XOR of two same-shape, same-dtype float tensors, as their
    uint bit pattern.

    Self-inverse: ``xor_bits(x, base) ^ bits(base)`` is bit-identical to
    ``bits(x)`` — the receiver reconstructs by XORing the decoded delta
    against its own copy of ``base``.  Pure bit movement (bitcast + xor):
    no float arithmetic touches the values, so every NaN payload / Inf /
    subnormal bit survives.  The delta stays in the uint domain: as a
    float it is mostly subnormal, and a TPU flushes subnormals to zero
    wherever a program unpacks them."""
    lay = layout_of(x.dtype)
    if jnp.dtype(base.dtype) != jnp.dtype(x.dtype) or base.shape != x.shape:
        raise ValueError(
            f"xor_bits needs matching operands, got {x.shape}/{x.dtype} "
            f"vs {base.shape}/{base.dtype}")
    u = lay.uint_dtype
    return (jax.lax.bitcast_convert_type(x, u)
            ^ jax.lax.bitcast_convert_type(base, u))


def concat_bits(parts: list) -> jax.Array:
    """Concatenate same-dtype float arrays WITHOUT touching their bits.

    XLA's float concatenate may quiet signaling-NaN payloads (observed on
    CPU); routing through the uint domain keeps bucket fusion exactly
    bit-preserving — required wherever the wire contract is bitwise (the
    weight-sync buckets)."""
    if len(parts) == 1:
        return parts[0]
    return _concat_bits(tuple(parts))


@jax.jit  # one program: op by op, each part's uint copy would be a buffer
def _concat_bits(parts: tuple) -> jax.Array:
    lay = layout_of(parts[0].dtype)
    u = lay.uint_dtype
    bits = jnp.concatenate(
        [jax.lax.bitcast_convert_type(p, u) for p in parts])
    return jax.lax.bitcast_convert_type(bits, lay.dtype)


@partial(jax.jit, static_argnames=("lo", "hi"))  # no whole-array uint copy
def slice_bits(x: jax.Array, lo: int, hi: int) -> jax.Array:
    """``x[lo:hi]`` for a flat float array, in the uint domain (XLA's float
    slice may quiet signaling-NaN payloads, like its concatenate; the
    weight-sync bucket scatter must be exactly bit-preserving)."""
    lay = layout_of(x.dtype)
    bits = jax.lax.bitcast_convert_type(x, lay.uint_dtype)
    return jax.lax.bitcast_convert_type(bits[lo:hi], lay.dtype)


def concat_members(src, members) -> jax.Array:
    """Fuse pytree leaves into one flat bucket, bit-exactly.

    ``members`` is the plan-IR membership tuple ``((leaf_index, shape,
    size), ...)``; every weight-sync path (planless wire, plan executor,
    host engine) fuses through HERE so the bucket layout — and the sNaN-
    safe uint-domain concat — can never diverge between them."""
    return concat_bits([src[i].reshape(-1) for i, _, _ in members])


def split_members(got, members):
    """Inverse of :func:`concat_members`: yield ``(leaf_index, leaf)``
    pairs sliced bit-exactly out of the fused bucket (trailing codec
    padding, if any, is ignored)."""
    offs = np.cumsum([0] + [m[2] for m in members])
    for k, (i, shape, _) in enumerate(members):
        yield i, slice_bits(got, int(offs[k]), int(offs[k + 1])).reshape(shape)


def pad_flat_bits(x: jax.Array, multiple: int) -> jax.Array:
    """Zero-pad a flat float array to a multiple, in the uint domain (the
    bit-preserving twin of the collectives' ``_pad_flat``)."""
    r = (-x.shape[0]) % multiple
    if r == 0:
        return x
    lay = layout_of(x.dtype)
    bits = jax.lax.bitcast_convert_type(x, lay.uint_dtype)
    bits = jnp.concatenate([bits, jnp.zeros((r,), lay.uint_dtype)])
    return jax.lax.bitcast_convert_type(bits, lay.dtype)


# ---------------------------------------------------------------------------
# fp8 exponent pair packing (paper §4.1: "pack two FP8 values into a single
# 16-bit unit and jointly extract their exponent fields").
# ---------------------------------------------------------------------------

def pack_fp8_exp_pairs(exp: jax.Array, exp_bits: int) -> jax.Array:
    """Pack two fp8 exponent fields per lane (uint8 for e4m3, uint16 for e5m2)."""
    n = exp.shape[0]
    if n % 2:
        exp = jnp.concatenate([exp, jnp.zeros((1,), jnp.uint8)])
    e2 = exp.reshape(-1, 2)
    if exp_bits <= 4:
        return (e2[:, 0] | (e2[:, 1] << jnp.uint8(exp_bits))).astype(jnp.uint8)
    pk = e2[:, 0].astype(jnp.uint16) | (
        e2[:, 1].astype(jnp.uint16) << jnp.uint16(exp_bits)
    )
    return jax.lax.bitcast_convert_type(pk, jnp.uint8).reshape(-1)


def unpack_fp8_exp_pairs(packed: jax.Array, exp_bits: int, n: int) -> jax.Array:
    """Inverse of :func:`pack_fp8_exp_pairs`; returns uint8 (n,)."""
    mask = (1 << exp_bits) - 1
    if exp_bits <= 4:
        lo_e = packed & jnp.uint8(mask)
        hi_e = (packed >> jnp.uint8(exp_bits)) & jnp.uint8(mask)
    else:
        p16 = jax.lax.bitcast_convert_type(packed.reshape(-1, 2), jnp.uint16)
        p16 = p16.reshape(-1)
        lo_e = (p16 & jnp.uint16(mask)).astype(jnp.uint8)
        hi_e = ((p16 >> jnp.uint16(exp_bits)) & jnp.uint16(mask)).astype(jnp.uint8)
    return jnp.stack([lo_e, hi_e], axis=-1).reshape(-1)[:n]


# ---------------------------------------------------------------------------
# Plane-size accounting (used by the policy + roofline + benchmarks).
# ---------------------------------------------------------------------------

def plane_fractions(dtype) -> tuple[float, float]:
    """(uncompressed_fraction, compressible_fraction) of the raw size.

    Paper Property 2: bf16 -> (0.5, 0.5); f32 -> (0.75, 0.25).
    """
    lay = layout_of(dtype)
    return lay.lo_bits / lay.total_bits, lay.exp_bits / lay.total_bits


def exponent_entropy_bits(exp_plane: jax.Array, exp_bits: int) -> jax.Array:
    """Empirical entropy (bits/symbol) of an exponent plane — the floor any
    entropy coder (the paper's ANS) can reach.  Used by calibrate + benchmarks.
    """
    nsym = 1 << exp_bits
    counts = jnp.bincount(exp_plane.astype(jnp.int32).reshape(-1), length=nsym)
    p = counts / jnp.maximum(counts.sum(), 1)
    return -jnp.sum(jnp.where(p > 0, p * jnp.log2(jnp.where(p > 0, p, 1.0)), 0.0))
