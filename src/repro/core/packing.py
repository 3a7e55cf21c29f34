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
uint32 words (one word per bit-plane), carried flat in group-major order
(word ``g * W + b``).  The packer sums each plane's bits into words by a
bf16 matmul against powers of two; the Pallas kernel in
``kernels/bitpack.py`` implements the identical layout on the VPU, tiled as
``(n // 32, W)``.
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

_SLICE = 1 << 20  # elements per step of the packer's and unpacker's loops
_WORD_ROW = 512  # values per row of the word-packing matmuls


def _plane_weights(width: int, b: int) -> np.ndarray:
    """(512, 32 * width) bf16-exact powers of two for one matmul over a row
    of 512 values: bit ``b`` of value ``i`` lands in word ``(i // 32) *
    width + b`` of the row's 16 groups, at bit ``i % 32``.  Column ``w``
    (``16 * width + w``) sums the low (high) 16 bits of word ``w``: under
    2**16, exact in f32."""
    lane = np.arange(_WORD_ROW)
    words = _WORD_ROW // GROUP * width
    wts = np.zeros((_WORD_ROW, 2 * words), np.float32)
    wts[lane, (lane % GROUP) // 16 * words + lane // GROUP * width + b] = (
        2.0 ** (lane % 16))
    return wts


def _pack_groups(v: jax.Array, width: int) -> jax.Array:
    """Pack lane-dense rows of 512 values by one bf16 matmul per bit-plane
    (each plane's weights fill only its own words, so the sum is exact)."""
    n = v.shape[0]
    rows = jnp.pad(v, (0, (-n) % _WORD_ROW)).reshape(-1, _WORD_ROW)
    halves = sum(
        jnp.dot(((rows >> b) & 1).astype(jnp.bfloat16),
                jnp.asarray(_plane_weights(width, b), jnp.bfloat16),
                preferred_element_type=jnp.float32)
        for b in range(width)).astype(jnp.uint32)
    words = _WORD_ROW // GROUP * width
    flat = (halves[:, :words] | (halves[:, words:] << 16)).reshape(-1)
    return flat[: n // GROUP * width]


def _unpack_groups(p: jax.Array, width: int, dtype) -> jax.Array:
    p = p.reshape(-1, width)
    pos = jnp.arange(GROUP, dtype=jnp.uint32)
    vals = jnp.zeros((p.shape[0], GROUP), jnp.uint32)
    for b in range(width):
        vals = vals | (
            ((p[:, b : b + 1] >> pos) & jnp.uint32(1)) << jnp.uint32(b)
        )
    return vals.astype(dtype).reshape(-1)


def _sliced(fn, x: jax.Array, step_in: int, step_out: int, n_out: int,
            dtype) -> jax.Array:
    """``fn`` over ``x`` in slices of ``step_in`` elements, each giving
    ``step_out`` elements of the flat output: a loop over the whole slices,
    then the ragged tail, so every temporary scales with the slice."""
    full, tail = divmod(x.shape[0], step_in)
    if full == 0:
        return fn(x)
    out = jnp.zeros((n_out,), dtype)

    def part(x):
        # the barrier keeps XLA from moving ``fn``'s reshapes across the
        # slice, onto the whole of ``x``
        return fn(jax.lax.optimization_barrier(x))

    def body(i, out):
        return jax.lax.dynamic_update_slice_in_dim(
            out, part(jax.lax.dynamic_slice_in_dim(x, i * step_in, step_in)),
            i * step_out, 0)

    out = jax.lax.fori_loop(0, full, body, out)
    if tail:
        out = jax.lax.dynamic_update_slice_in_dim(
            out, part(x[full * step_in:]), full * step_out, 0)
    return out


@partial(jax.jit, static_argnames=("width",))
def bitplane_pack(vals: jax.Array, width: int) -> jax.Array:
    """Pack ``vals`` (uint (n,), n % 32 == 0, each < 2**width) into
    bit-planes: returns flat uint32 (n // 32 * width,); word ``g * width +
    b`` holds bit ``b`` of the 32 values of group ``g`` (value ``i`` at bit
    position ``i``).

    The wire is flat so that no bucket-sized array has a minor dimension
    narrower than the chip's 128 lanes (a ``(n // 32, 32)`` uint32 array
    takes 16 bytes per element in TPU memory): the values are packed
    ``_SLICE`` at a time, each slice as lane-dense rows of 512 by
    :func:`_pack_groups`' matmuls, into the flat output."""
    n = vals.shape[0]
    assert n % GROUP == 0, vals.shape
    return _sliced(lambda v: _pack_groups(v, width), vals, _SLICE,
                   _SLICE // GROUP * width, n // GROUP * width, jnp.uint32)


@partial(jax.jit, static_argnames=("width", "dtype"))
def bitplane_unpack(packed: jax.Array, width: int,
                    dtype=jnp.uint32) -> jax.Array:
    """Inverse of :func:`bitplane_pack` (flat words in); returns ``dtype``
    (n,) (values above ``dtype``'s range keep their low bits)."""
    assert packed.ndim == 1 and packed.shape[0] % width == 0, (
        packed.shape, width)
    return _sliced(lambda q: _unpack_groups(q, width, dtype), packed,
                   _SLICE // GROUP * width, _SLICE,
                   packed.shape[0] // width * GROUP, dtype)


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
    payload: jax.Array  # uint32 (n_pad // 32 * width,) bit-planes of residuals
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
    cap = exception_capacity(-(-n // block), exc_frac)
    resid, base, exc_idx, exc_raw, overflow = _block_codes(
        exp, width=width, block=block, cap=cap)
    return PackedPlane(
        payload=bitplane_pack(resid, width),
        bases=base,
        exc_idx=exc_idx,
        exc_raw=exc_raw,
        overflow=overflow,
        width=width,
        block=block,
        n=n,
        exp_bits=8,
    )


# The codec's steps around the packer and unpacker are programs of their
# own: run op by op, each intermediate of a bucket-sized plane would be a
# bucket-sized buffer, and the host runs ahead of the chip.

@partial(jax.jit, static_argnames=("width", "block", "cap"))
def _block_codes(exp: jax.Array, *, width: int, block: int, cap: int):
    """:func:`pack_exponents`' codes (flat uint8), block bases and
    exception list."""
    blocks = _pad_to(exp, block).reshape(-1, block)
    nb = blocks.shape[0]
    nz = blocks != 0
    big = jnp.where(nz, blocks, jnp.uint8(255))
    base = jnp.min(big, axis=-1)  # 255 if block is all-zero
    base = jnp.where(jnp.any(nz, axis=-1), base, jnp.uint8(1))
    mx = jnp.max(jnp.where(nz, blocks, jnp.uint8(0)), axis=-1)
    rng = mx.astype(jnp.int32) - base.astype(jnp.int32) + 1  # max code value
    ok = rng < (1 << width)

    # a nonzero exponent is at least its block's base, so every code fits
    # in uint8: the residuals stay one byte per element
    resid = jnp.where(nz, blocks - base[:, None] + jnp.uint8(1), jnp.uint8(0))
    if width < 8:  # exc blocks: payload is garbage, restored from exc_raw
        resid = jnp.minimum(resid, jnp.uint8((1 << width) - 1))

    bad = ~ok
    n_bad = jnp.sum(bad.astype(jnp.int32))
    (exc_idx,) = jnp.nonzero(bad, size=cap, fill_value=nb)
    exc_idx = exc_idx.astype(jnp.int32)
    exc_raw = blocks[jnp.minimum(exc_idx, nb - 1)]
    exc_raw = jnp.where((exc_idx < nb)[:, None], exc_raw, 0)
    overflow = (n_bad > cap).astype(jnp.int32)
    return resid.reshape(-1), base, exc_idx, exc_raw, overflow


def unpack_exponents(p: PackedPlane) -> jax.Array:
    """Exact inverse of :func:`pack_exponents` (when ``overflow == 0``)."""
    return _block_exponents(bitplane_unpack(p.payload, p.width, jnp.uint8),
                            p.bases, p.exc_idx, p.exc_raw, n=p.n,
                            block=p.block)


@partial(jax.jit, static_argnames=("n", "block"))
def _block_exponents(resid, bases, exc_idx, exc_raw, *, n: int, block: int):
    # uint8 arithmetic wraps as the uint8 result of the uint32 sum would
    resid = resid.reshape(-1, block)
    blocks = jnp.where(resid == 0, jnp.uint8(0),
                       resid + bases[:, None].astype(jnp.uint8)
                       - jnp.uint8(1))
    blocks = blocks.at[exc_idx].set(exc_raw, mode="drop")
    return blocks.reshape(-1)[:n]


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
    lo: jax.Array  # uint32 (n_pad // 32 * lo_bits,) bit-planes of sign|mantissa
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


# programs of their own, as the steps around the packer (above)
_split_planes = jax.jit(codec.split_planes)
_merge_planes = jax.jit(codec.merge_planes, static_argnums=(2, 3))


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
    exp, lo = _split_planes(x)
    lo_planes = bitplane_pack(_pad_to(lo, GROUP, pad_mode="zero"), lay.lo_bits)
    packed = pack_exponents(exp, width=width, block=block, exc_frac=exc_frac)
    return CompressedMessage(
        lo=lo_planes, exp=packed, dtype_name=lay.name, shape=tuple(x.shape)
    )


def decode_message(m: CompressedMessage) -> jax.Array:
    lay = codec.LAYOUTS[m.dtype_name]
    n = int(np.prod(m.shape)) if m.shape else 1
    lo = bitplane_unpack(m.lo, lay.lo_bits, lay.uint_dtype)[:n]
    exp = unpack_exponents(m.exp)
    return _merge_planes(exp, lo, lay.dtype, m.shape)


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

    payload: jax.Array  # uint32 (n_pad // 32 * width,) bit-planes
    exc_idx: jax.Array  # int32 (E,) element indices (n_pad = unused slot)
    exc_raw: jax.Array  # uint32 (E,) raw lo values of exception elements
    overflow: jax.Array  # int32 scalar: 1 if exceptions overflowed capacity
    width: int
    n: int  # original element count (pre-padding)


@partial(jax.jit, static_argnames=("size", "fill"))
def exception_indices(mask: jax.Array, *, size: int, fill: int) -> jax.Array:
    """``jnp.nonzero(mask, size=size, fill_value=fill)[0]`` as int32, for a
    bool ``mask`` whose length is a multiple of 32.

    ``jnp.nonzero`` scatters one update per element of ``mask``.  Here the
    index is found in two levels.  The mask is packed into 32-element words
    (bit ``i`` of word ``r`` is element ``32 r + i``, as in
    :func:`bitplane_pack`) by one matmul against :func:`bitplane_pack`'s
    powers of two over lane-dense rows of 512 (the bool mask straight into
    the matmul: through ``_pack_groups``' uint8 shifts, a described v5e
    compile needs over 2 bytes of temp per element).  The word that holds the ``k``-th true element comes
    from ``jnp.nonzero``'s own cumsum-of-bincount trick over the words'
    counts (one scatter update per word); its rank inside the word from the
    slot where that word's run of slots starts, and the bit from a popcount
    binary search.  Every op after the word counts is over ``size``, with
    one gather."""
    n = mask.shape[0]
    assert n % GROUP == 0, n
    m = jnp.pad(mask, (0, (-n) % _WORD_ROW)).reshape(-1, _WORD_ROW)
    halves = jnp.dot(m.astype(jnp.bfloat16),
                     jnp.asarray(_plane_weights(1, 0), jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.uint32)
    nw = _WORD_ROW // GROUP
    words = (halves[:, :nw] | (halves[:, nw:] << 16)).reshape(-1)
    incl = jnp.cumsum(jax.lax.population_count(words).astype(jnp.int32))
    # r[k] = #{words whose inclusive count is <= k}: the k-th true element's word
    r = jnp.cumsum(jnp.zeros(size, jnp.int32).at[incl].add(1, mode="drop"))
    k = jnp.arange(size, dtype=jnp.int32)
    new_word = jnp.concatenate([jnp.ones(1, bool), r[1:] != r[:-1]])
    j = k - jax.lax.cummax(jnp.where(new_word, k, 0))  # rank in its word
    w = words[jnp.minimum(r, words.shape[0] - 1)]
    bit = jnp.zeros(size, jnp.int32)
    for s in (16, 8, 4, 2, 1):  # the j-th set bit of w, from bit 0 up
        low = jax.lax.population_count(
            w & jnp.uint32((1 << s) - 1)).astype(jnp.int32)
        up = j >= low
        j = jnp.where(up, j - low, j)
        w = jnp.where(up, w >> jnp.uint32(s), w)
        bit = jnp.where(up, bit + s, bit)
    return jnp.where(k < incl[-1], GROUP * r + bit, jnp.int32(fill))


def pack_delta_plane(vals: jax.Array, width: int, *,
                     exc_frac: float = 0.02) -> DeltaPlane:
    """Pack an unsigned lo-delta stream at ``width`` bits/element.

    Elements that do not fit (the carry tail) escape exactly through a
    static-capacity exception list of ``max(4, exc_frac * n)`` entries;
    ``overflow`` reports capacity exhaustion (decode would be lossy — the
    caller must fall back to a full send)."""
    assert width >= 1, width
    n = vals.shape[0]
    v = _pad_to(vals, GROUP, pad_mode="zero")
    kept, bad = _split_fits(v, width=width)
    payload = bitplane_pack(kept, width)
    cap = min(n, max(4, int(np.ceil(n * exc_frac))))
    exc_idx = exception_indices(bad, size=cap, fill=v.shape[0])
    exc_raw, overflow = _gather_exceptions(v, bad, exc_idx)
    return DeltaPlane(payload=payload, exc_idx=exc_idx, exc_raw=exc_raw,
                      overflow=overflow, width=width, n=n)


@partial(jax.jit, static_argnames=("width",))
def _split_fits(v: jax.Array, *, width: int):
    """The values that fit ``width`` bits (the others zeroed), and the
    mask of those that do not."""
    dt = v.dtype.type
    fits = v <= dt(min((1 << width) - 1, jnp.iinfo(v.dtype).max))
    return jnp.where(fits, v, dt(0)), ~fits


@jax.jit
def _gather_exceptions(v: jax.Array, bad: jax.Array, exc_idx: jax.Array):
    """The exception list's raw values, and whether it overflowed."""
    exc_raw = v[jnp.minimum(exc_idx, v.shape[0] - 1)].astype(jnp.uint32)
    exc_raw = jnp.where(exc_idx < v.shape[0], exc_raw, jnp.uint32(0))
    overflow = (jnp.sum(bad.astype(jnp.int32)) > exc_idx.shape[0])
    return exc_raw, overflow.astype(jnp.int32)


def unpack_delta_plane(p: DeltaPlane, dtype=jnp.uint32) -> jax.Array:
    """Exact inverse of :func:`pack_delta_plane` (when ``overflow == 0``).
    Returns ``dtype`` (n,): the packed stream's own dtype, or wider."""
    return _scatter_exceptions(bitplane_unpack(p.payload, p.width, dtype),
                               p.exc_idx, p.exc_raw, n=p.n)


@partial(jax.jit, static_argnames=("n",))
def _scatter_exceptions(vals, exc_idx, exc_raw, *, n: int):
    vals = vals.at[exc_idx].set(exc_raw.astype(vals.dtype), mode="drop")
    return vals[:n]


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
    exp, lo = _xor_split(x, base)
    packed = pack_exponents(exp, width=width, block=block, exc_frac=exc_frac)
    lo_plane = pack_delta_plane(lo, lo_width, exc_frac=exc_frac)
    return DeltaMessage(lo=lo_plane, exp=packed, dtype_name=lay.name,
                        shape=tuple(x.shape))


def decode_delta(m: DeltaMessage, base: jax.Array) -> jax.Array:
    """Exact inverse of :func:`encode_delta` given the SAME base version
    (the sync protocol's invariant — version fencing guarantees it)."""
    lay = codec.LAYOUTS[m.dtype_name]
    n = int(np.prod(m.shape)) if m.shape else 1
    lo = unpack_delta_plane(m.lo, lay.uint_dtype)[:n]
    exp = unpack_exponents(m.exp)
    return _merge_xor(exp, lo, base, m.shape)


@jax.jit
def _xor_split(x: jax.Array, base: jax.Array):
    """The delta's planes in one program (no bucket-sized intermediates)."""
    return codec.split_bits(codec.xor_bits(x.reshape(-1), base.reshape(-1)),
                            codec.layout_of(x.dtype))


@partial(jax.jit, static_argnames=("shape",))
def _merge_xor(exp: jax.Array, lo: jax.Array, base: jax.Array,
               shape: tuple) -> jax.Array:
    """The delta's planes merged and XORed back onto ``base``, in one
    program."""
    lay = codec.layout_of(base.dtype)
    n = int(np.prod(shape)) if shape else 1
    bits = codec.merge_bits(exp, lo, lay, n) ^ jax.lax.bitcast_convert_type(
        base.reshape(-1), lay.uint_dtype)
    return jax.lax.bitcast_convert_type(bits, lay.dtype).reshape(shape)
