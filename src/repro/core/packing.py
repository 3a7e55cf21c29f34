"""Static-shape block-local wire codec (TPU adaptation of paper §3.3).

The paper's *localized frequency tables* replace a global ANS table with
per-block statistics so compression can fuse into the collective datapath.
On TPU the collective datapath (XLA) additionally requires *static* buffer
shapes, so the per-block statistic degenerates further: each block of ``B``
exponents stores its minimum (``base``, uint8) and the residuals
``exp - base`` are bit-packed at a *calibrated* fixed width ``W``.

Losslessness is unconditional:
  * blocks whose residual range exceeds ``W`` bits are *exception blocks*:
    their raw exponent bytes ride in a static-capacity exception region and
    are scatter-restored at decode (paper's "tails transmitted raw", made
    exact);
  * if exceptions overflow the provisioned capacity, ``overflow`` is set and
    the caller (training loop) retries the transfer uncompressed — data is
    never silently corrupted.

Packing itself is *bit-plane* packing: groups of 32 residuals map to ``W``
uint32 words (one word per bit-plane).  This is a pure-VPU transform — the
Pallas kernel in ``kernels/bitpack.py`` implements the identical layout.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec

GROUP = 32  # residuals per packed group (one uint32 word per bit-plane)


# ---------------------------------------------------------------------------
# Bit-plane pack / unpack (pure jnp reference; kernels/bitpack.py mirrors it)
# ---------------------------------------------------------------------------

def bitplane_pack(vals: jax.Array, width: int) -> jax.Array:
    """Pack ``vals`` (uint32 (n,), n % 32 == 0, each < 2**width) into
    bit-planes: returns uint32 (n // 32, width); word ``[g, b]`` holds bit
    ``b`` of the 32 values of group ``g`` (value ``i`` at bit position ``i``).
    """
    assert vals.shape[0] % GROUP == 0, vals.shape
    g = vals.reshape(-1, GROUP).astype(jnp.uint32)
    pos = jnp.arange(GROUP, dtype=jnp.uint32)
    planes = [
        jnp.sum(((g >> jnp.uint32(b)) & jnp.uint32(1)) << pos, axis=-1, dtype=jnp.uint32)
        for b in range(width)
    ]
    return jnp.stack(planes, axis=-1)


def bitplane_unpack(packed: jax.Array, width: int) -> jax.Array:
    """Inverse of :func:`bitplane_pack`; returns uint32 (n,)."""
    pos = jnp.arange(GROUP, dtype=jnp.uint32)
    vals = jnp.zeros((packed.shape[0], GROUP), jnp.uint32)
    for b in range(width):
        vals = vals | (
            ((packed[:, b : b + 1] >> pos) & jnp.uint32(1)) << jnp.uint32(b)
        )
    return vals.reshape(-1)


# ---------------------------------------------------------------------------
# Packed exponent plane
# ---------------------------------------------------------------------------

@partial(
    jax.tree_util.register_dataclass,
    data_fields=("payload", "bases", "exc_idx", "exc_raw", "overflow"),
    meta_fields=("width", "block", "n", "exp_bits"),
)
@dataclasses.dataclass(frozen=True)
class PackedPlane:
    payload: jax.Array  # uint32 (n_pad // 32, width) bit-planes of residuals
    bases: jax.Array  # uint8  (n_blocks,) per-block minimum exponent
    exc_idx: jax.Array  # int32  (E,) exception block ids (n_blocks = unused)
    exc_raw: jax.Array  # uint8  (E, block) raw exponents of exception blocks
    overflow: jax.Array  # int32 scalar: 1 if exceptions overflowed capacity
    width: int
    block: int
    n: int  # original element count (pre-padding)
    exp_bits: int

    @property
    def n_blocks(self) -> int:
        return self.bases.shape[0]

    def wire_bits_per_element(self) -> float:
        """Exponent-plane wire cost in bits/element (for ratio accounting)."""
        total = (
            self.payload.size * 32
            + self.bases.size * 8
            + self.exc_idx.size * 32
            + self.exc_raw.size * 8
            + 32
        )
        return total / self.n


def _pad_to(x: jax.Array, m: int, pad_mode: str = "edge") -> jax.Array:
    n = x.shape[0]
    r = (-n) % m
    if r == 0:
        return x
    if pad_mode == "edge":
        return jnp.concatenate([x, jnp.broadcast_to(x[-1:], (r,) + x.shape[1:])])
    return jnp.concatenate([x, jnp.zeros((r,) + x.shape[1:], x.dtype)])


def exception_capacity(n_blocks: int, exc_frac: float) -> int:
    """Static exception-region capacity: ``exc_frac`` of blocks with a floor
    of 4 (for small messages the floor's overhead is negligible and avoids
    spurious overflow→uncompressed-retry on isolated outliers)."""
    return min(n_blocks, max(4, int(np.ceil(n_blocks * exc_frac))))


def pack_exponents(
    exp: jax.Array,
    *,
    width: int,
    block: int = 512,
    exc_frac: float = 0.02,
) -> PackedPlane:
    """Encode a uint8 exponent plane into the static wire format.

    Zero-escape: exponent 0 (zeros/subnormals — ubiquitous in gradients,
    e.g. untouched embedding rows) maps to code 0; nonzero exponents map to
    ``exp - base + 1`` with ``base`` the *nonzero* block minimum.  A block
    fits width W iff its nonzero exponent range + 1 < 2^W, so sparse-but-
    normal blocks stay packable (the ANS coder the paper uses absorbs zeros
    as just another symbol; the static codec needs the explicit escape)."""
    assert block % GROUP == 0
    n = exp.shape[0]
    expp = _pad_to(exp, block)
    blocks = expp.reshape(-1, block)
    nb = blocks.shape[0]
    nz = blocks != 0
    big = jnp.where(nz, blocks, jnp.uint8(255))
    base = jnp.min(big, axis=-1)  # 255 if block is all-zero
    base = jnp.where(jnp.any(nz, axis=-1), base, jnp.uint8(1))
    mx = jnp.max(jnp.where(nz, blocks, jnp.uint8(0)), axis=-1)
    rng = mx.astype(jnp.int32) - base.astype(jnp.int32) + 1  # max code value
    ok = rng < (1 << width)

    resid = jnp.where(
        nz,
        blocks.astype(jnp.int32) - base[:, None].astype(jnp.int32) + 1,
        0,
    ).astype(jnp.uint32)
    resid = jnp.minimum(resid, jnp.uint32((1 << width) - 1))  # exc blocks: payload is garbage, restored from exc_raw
    payload = bitplane_pack(resid.reshape(-1), width)

    cap = exception_capacity(nb, exc_frac)
    bad = ~ok
    n_bad = jnp.sum(bad.astype(jnp.int32))
    (exc_idx,) = jnp.nonzero(bad, size=cap, fill_value=nb)
    exc_idx = exc_idx.astype(jnp.int32)
    exc_raw = blocks[jnp.minimum(exc_idx, nb - 1)]
    exc_raw = jnp.where((exc_idx < nb)[:, None], exc_raw, 0)
    overflow = (n_bad > cap).astype(jnp.int32)
    return PackedPlane(
        payload=payload,
        bases=base,
        exc_idx=exc_idx,
        exc_raw=exc_raw,
        overflow=overflow,
        width=width,
        block=block,
        n=n,
        exp_bits=8,
    )


def unpack_exponents(p: PackedPlane) -> jax.Array:
    """Exact inverse of :func:`pack_exponents` (when ``overflow == 0``)."""
    resid = bitplane_unpack(p.payload, p.width).reshape(p.n_blocks, p.block)
    blocks = jnp.where(
        resid == 0,
        jnp.uint32(0),
        resid + p.bases[:, None].astype(jnp.uint32) - 1,
    ).astype(jnp.uint8)
    blocks = blocks.at[p.exc_idx].set(p.exc_raw, mode="drop")
    return blocks.reshape(-1)[: p.n]


# ---------------------------------------------------------------------------
# Whole-message codec: lo plane (bit-packed, "uncompressed part") + packed
# exponent plane.  This is the in-collective wire format.
# ---------------------------------------------------------------------------

@partial(
    jax.tree_util.register_dataclass,
    data_fields=("lo", "exp"),
    meta_fields=("dtype_name", "shape"),
)
@dataclasses.dataclass(frozen=True)
class CompressedMessage:
    lo: jax.Array  # uint32 (n_pad // 32, lo_bits) bit-planes of sign|mantissa
    exp: PackedPlane
    dtype_name: str
    shape: tuple

    def wire_bytes(self) -> int:
        e = self.exp
        return int(
            self.lo.size * 4
            + e.payload.size * 4
            + e.bases.size
            + e.exc_idx.size * 4
            + e.exc_raw.size
            + 4
        )

    def raw_bytes(self) -> int:
        lay = codec.LAYOUTS[self.dtype_name]
        return int(np.prod(self.shape)) * lay.total_bits // 8

    def ratio(self) -> float:
        return self.wire_bytes() / self.raw_bytes()


def encode_message(
    x: jax.Array, *, width: int, block: int = 512, exc_frac: float = 0.02,
    fused: bool = True, use_pallas: bool | None = None,
) -> CompressedMessage:
    """Encode a float tensor into the in-collective wire format.

    ``fused=True`` (default) routes through the one-pass split+pack dispatch
    (``kernels/ops.encode_fused``: Pallas on TPU / fused jnp elsewhere,
    ragged shapes pad to the kernel tile); ``fused=False`` keeps the legacy
    three-pass composition.  Both are bit-identical."""
    lay = codec.layout_of(x.dtype)
    xf = x.reshape(-1)
    if fused:
        from repro.kernels import ops as kernel_ops  # lazy: kernels import us

        w = kernel_ops.encode_fused(xf, width, block=block, exc_frac=exc_frac,
                                    use_pallas=use_pallas)
        packed = PackedPlane(
            payload=w["payload"], bases=w["bases"], exc_idx=w["exc_idx"],
            exc_raw=w["exc_raw"], overflow=w["overflow"], width=width,
            block=block, n=xf.shape[0], exp_bits=8,
        )
        return CompressedMessage(
            lo=w["lo"], exp=packed, dtype_name=lay.name, shape=tuple(x.shape)
        )
    exp, lo = codec.split_planes(x)
    lo32 = _pad_to(lo.astype(jnp.uint32), GROUP, pad_mode="zero")
    lo_planes = bitplane_pack(lo32, lay.lo_bits)
    packed = pack_exponents(exp, width=width, block=block, exc_frac=exc_frac)
    return CompressedMessage(
        lo=lo_planes, exp=packed, dtype_name=lay.name, shape=tuple(x.shape)
    )


def decode_message(m: CompressedMessage) -> jax.Array:
    lay = codec.LAYOUTS[m.dtype_name]
    n = int(np.prod(m.shape)) if m.shape else 1
    lo = bitplane_unpack(m.lo, lay.lo_bits)[:n].astype(lay.uint_dtype)
    exp = unpack_exponents(m.exp)
    return codec.merge_planes(exp, lo, lay.dtype, m.shape)


# ---------------------------------------------------------------------------
# XOR-delta wire format (weight sync, src/repro/sync/).
#
# A warm delta (consecutive weight versions) is mostly-zero in BOTH planes:
# the exponent-delta plane packs with the existing block codec at width ~1
# (zero-escape absorbs the untouched elements), and the lo-delta plane —
# which the standard wire ships raw, because sign|mantissa of live floats is
# near-uniform — concentrates in the low few bits, so it gets its own width
# packer.  Lo deltas have a geometric carry tail (an update that crosses a
# mantissa power boundary flips a long run of bits), so the lo packer
# escapes at ELEMENT granularity: outliers ride a static-capacity
# (idx, raw) exception list, exactly restored at decode.  Losslessness is
# unconditional: if exceptions overflow the capacity, ``overflow`` is set
# and the caller falls back to a full-tensor send (sync/engine.py does this
# automatically on the host path).
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("payload", "exc_idx", "exc_raw", "overflow"),
    meta_fields=("width", "n"),
)
@dataclasses.dataclass(frozen=True)
class DeltaPlane:
    """Width-packed lo-delta plane with element-granular exact exceptions."""

    payload: jax.Array  # uint32 (n_pad // 32, width) bit-planes
    exc_idx: jax.Array  # int32 (E,) element indices (n_pad = unused slot)
    exc_raw: jax.Array  # uint32 (E,) raw lo values of exception elements
    overflow: jax.Array  # int32 scalar: 1 if exceptions overflowed capacity
    width: int
    n: int  # original element count (pre-padding)


def pack_delta_plane(vals: jax.Array, width: int, *,
                     exc_frac: float = 0.02) -> DeltaPlane:
    """Pack a uint32 lo-delta stream at ``width`` bits/element.

    Elements that do not fit (the carry tail) escape exactly through a
    static-capacity exception list of ``max(4, exc_frac * n)`` entries;
    ``overflow`` reports capacity exhaustion (decode would be lossy — the
    caller must fall back to a full send)."""
    assert width >= 1, width
    n = vals.shape[0]
    v = _pad_to(vals.astype(jnp.uint32), GROUP, pad_mode="zero")
    mask = jnp.uint32((1 << width) - 1)
    fits = v <= mask
    payload = bitplane_pack(jnp.where(fits, v, jnp.uint32(0)), width)
    cap = min(n, max(4, int(np.ceil(n * exc_frac))))
    bad = ~fits
    n_bad = jnp.sum(bad.astype(jnp.int32))
    (exc_idx,) = jnp.nonzero(bad, size=cap, fill_value=v.shape[0])
    exc_idx = exc_idx.astype(jnp.int32)
    exc_raw = v[jnp.minimum(exc_idx, v.shape[0] - 1)]
    exc_raw = jnp.where(exc_idx < v.shape[0], exc_raw, 0)
    overflow = (n_bad > cap).astype(jnp.int32)
    return DeltaPlane(payload=payload, exc_idx=exc_idx, exc_raw=exc_raw,
                      overflow=overflow, width=width, n=n)


def unpack_delta_plane(p: DeltaPlane) -> jax.Array:
    """Exact inverse of :func:`pack_delta_plane` (when ``overflow == 0``).
    Returns uint32 (n,)."""
    vals = bitplane_unpack(p.payload, p.width)
    vals = vals.at[p.exc_idx].set(p.exc_raw, mode="drop")
    return vals[: p.n]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("lo", "exp"),
    meta_fields=("dtype_name", "shape"),
)
@dataclasses.dataclass(frozen=True)
class DeltaMessage:
    """Encoded XOR delta of one tensor against a shared base version.

    The existing split applies to the delta's raw bit pattern
    (``codec.xor_bits``, never a float array): the exponent-delta
    plane rides the standard block packer, the lo-delta plane the width
    packer above.  Static shapes throughout — the wire size depends only on
    (n, widths), so plans can record it via ``eval_shape``."""

    lo: DeltaPlane
    exp: PackedPlane
    dtype_name: str
    shape: tuple

    def wire_bytes(self) -> int:
        e, l = self.exp, self.lo
        return int(
            l.payload.size * 4 + l.exc_idx.size * 4 + l.exc_raw.size * 4 + 4
            + e.payload.size * 4 + e.bases.size + e.exc_idx.size * 4
            + e.exc_raw.size + 4
        )

    def raw_bytes(self) -> int:
        lay = codec.LAYOUTS[self.dtype_name]
        return int(np.prod(self.shape)) * lay.total_bits // 8

    def ratio(self) -> float:
        return self.wire_bytes() / self.raw_bytes()

    @property
    def overflow(self) -> jax.Array:
        """1 if EITHER plane's exceptions overflowed (decode would be lossy)."""
        return jnp.maximum(self.exp.overflow, self.lo.overflow)


def encode_delta(
    x: jax.Array, base: jax.Array, *, width: int, lo_width: int,
    block: int = 512, exc_frac: float = 0.02,
) -> DeltaMessage:
    """XOR ``x`` against ``base`` and encode the delta bit pattern.

    ``width`` packs the exponent-delta plane (existing block codec, zero
    escape), ``lo_width`` the lo-delta plane (element-exception packer).
    Bit-exact through :func:`decode_delta` whenever ``overflow == 0`` —
    including NaN payloads, infinities and subnormals in either operand."""
    lay = codec.layout_of(x.dtype)
    exp, lo = codec.split_bits(codec.xor_bits(x.reshape(-1),
                                              base.reshape(-1)), lay)
    packed = pack_exponents(exp, width=width, block=block, exc_frac=exc_frac)
    lo_plane = pack_delta_plane(lo.astype(jnp.uint32), lo_width,
                                exc_frac=exc_frac)
    return DeltaMessage(lo=lo_plane, exp=packed, dtype_name=lay.name,
                        shape=tuple(x.shape))


def decode_delta(m: DeltaMessage, base: jax.Array) -> jax.Array:
    """Exact inverse of :func:`encode_delta` given the SAME base version
    (the sync protocol's invariant — version fencing guarantees it)."""
    lay = codec.LAYOUTS[m.dtype_name]
    n = int(np.prod(m.shape)) if m.shape else 1
    lo = unpack_delta_plane(m.lo)[:n].astype(lay.uint_dtype)
    exp = unpack_exponents(m.exp)
    bits = codec.merge_bits(exp, lo, lay, n) ^ jax.lax.bitcast_convert_type(
        base.reshape(-1), lay.uint_dtype)
    return jax.lax.bitcast_convert_type(bits, lay.dtype).reshape(m.shape)
