"""Public jit'd entry points for the Pallas kernels.

``use_pallas`` selects the Pallas implementation (interpret-mode on CPU,
compiled on TPU); the default pure-jnp path lowers to the same algebra and
is what the production train/serve steps trace (XLA fuses it aggressively),
keeping the dry-run HLO clean.  The kernels are the TPU hot-spot
implementation, validated against ref.py across shapes and dtypes.

``use_pallas=None`` / ``interpret=None`` defer to the backend probe
(``kernels.default_use_pallas`` / ``default_interpret``): a real TPU takes
the compiled Pallas path automatically, CPU/GPU keep the jnp reference —
the ROADMAP "Compiled Pallas on real TPU" wiring.  Explicit booleans always
win (tests force interpret-mode Pallas on CPU).

Dispatch accounting: whenever Pallas was requested (explicitly or via the
probe) but a shape gate routes to the reference anyway, the degrade is
counted and logged once per op (``kernels.record_fallback``) so effective
backend coverage is observable instead of silent.

Fused transmit-side encode (paper §3.2 Step 1): :func:`encode_fused` /
:func:`encode_fused_chunks` produce the complete wire-format parts
(lo planes + packed exponent payload + bases + exceptions) in ONE pass over
the input — the transmit twin of :func:`decode_reduce`.  They are the
DEFAULT encode dispatch for ``core/packing.encode_message`` and every
compressed send phase in ``core/compressed_collectives`` /
``core/split_send.encode_send``.  Ragged shapes do NOT fall back: the
Pallas path pads the input to the kernel tile with an exponent-preserving
pad element (see :func:`_edge_exp_pad`) and slices the outputs, so real
model shapes hit the fast path.  The sched plan IR records the routing in
``BucketPlan.encode_fused``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ans as core_ans
from repro.core import codec, packing
from repro.kernels import bitpack as _bitpack
from repro.kernels import decode_reduce as _decode_reduce
from repro.kernels import encode_fused as _encode_fused
from repro.kernels import plane_split as _plane_split
from repro.kernels import rans as _rans
from repro.kernels import ref as _ref
from repro.kernels import record_fallback, resolve_interpret, resolve_use_pallas

GROUP = packing.GROUP


def pack(vals, width: int, *, use_pallas: bool | None = None,
         interpret: bool | None = None):
    """``packing.bitplane_pack``'s flat words."""
    use_pallas, interpret = resolve_use_pallas(use_pallas), resolve_interpret(interpret)
    if use_pallas:
        if vals.shape[0] % (32 * _bitpack.TILE_G) == 0:
            return _bitpack.pack(vals, width, interpret=interpret).reshape(-1)
        record_fallback("pack", f"n={vals.shape[0]} not a "
                                f"{32 * _bitpack.TILE_G} multiple")
    return _ref.pack(vals, width)


def unpack(packed, width: int, *, use_pallas: bool | None = None,
           interpret: bool | None = None):
    """``packing.bitplane_unpack`` of flat words."""
    use_pallas, interpret = resolve_use_pallas(use_pallas), resolve_interpret(interpret)
    if use_pallas:
        groups = packed.reshape(-1, width)
        if groups.shape[0] % _bitpack.TILE_G == 0:
            return _bitpack.unpack(groups, width, interpret=interpret)
        record_fallback("unpack", f"n_groups={groups.shape[0]} not a "
                                  f"{_bitpack.TILE_G} multiple")
    return _ref.unpack(packed, width)


def split_with_stats(x, block: int = 512, *, use_pallas: bool | None = None,
                     interpret: bool | None = None):
    use_pallas, interpret = resolve_use_pallas(use_pallas), resolve_interpret(interpret)
    if use_pallas:
        if x.shape[0] % (block * _plane_split.TILE_B) == 0:
            return _plane_split.split_with_stats(x, block, interpret=interpret)
        record_fallback("split_with_stats",
                        f"n={x.shape[0]} not a {block * _plane_split.TILE_B} "
                        "multiple")
    return _ref.split_with_stats(x, block)


def decode_reduce(payload, lo_planes, group_bases, acc, dtype_name: str,
                  width: int, *, use_pallas: bool | None = None,
                  interpret: bool | None = None):
    """Decode the flat wire words ``payload`` and ``lo_planes`` and add
    them into ``acc``."""
    use_pallas, interpret = resolve_use_pallas(use_pallas), resolve_interpret(interpret)
    if use_pallas:  # any n_groups: the kernel's last tile may be partial
        lo_bits = codec.LAYOUTS[dtype_name].lo_bits
        return _decode_reduce.decode_reduce(
            payload.reshape(-1, width), lo_planes.reshape(-1, lo_bits),
            group_bases, acc, dtype_name, width, interpret=interpret,
        )
    return _ref.decode_reduce(payload, lo_planes, group_bases, acc, dtype_name, width)


# ---------------------------------------------------------------------------
# Fused transmit-side encode (split + stats + pack in one pass)
# ---------------------------------------------------------------------------

def _pad_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _edge_exp_pad(x: jax.Array, lay: codec.FloatLayout) -> jax.Array:
    """The (1,)-shaped pad element for ragged encodes: ``x[-1]``'s exponent
    field with zero sign/mantissa.

    Padding ``x`` with this value reproduces BOTH legacy pad modes at once:
    the exponent plane is edge-padded (``pack_exponents``'s ``_pad_to(exp,
    block)``) while the lo plane is zero-padded (``encode_message``'s
    ``_pad_to(lo, GROUP, "zero")``) — so the fused one-pass encode of the
    padded input is bit-identical to the unfused composition on ragged n."""
    u = lay.uint_dtype
    bits = jax.lax.bitcast_convert_type(x[-1:], u)
    expbits = bits & u(((1 << lay.exp_bits) - 1) << lay.mant_bits)
    return jax.lax.bitcast_convert_type(expbits, lay.dtype)


def _encode_planes(xf: jax.Array, width: int, block: int, use_pallas: bool,
                   interpret: bool):
    """Core plane encode of a flat block-multiple array.

    Returns (payload (n//32*width,), lo_planes (n//32*lo_bits,), bases
    uint32 (nb,), rng uint32 (nb,)), the planes as flat words.  The Pallas
    path pads to the kernel tile (exponent-preserving pad) and
    slices — ragged-vs-tile never falls back; ``use_pallas=False`` is the
    fused jnp reference."""
    lay = codec.layout_of(xf.dtype)
    n = xf.shape[0]
    assert n % block == 0, (n, block)
    if not use_pallas:
        return _ref.encode_fused(xf, width, block)
    tile = block * _encode_fused.TILE_B
    n_tile = _pad_up(n, tile)
    if n_tile != n:
        xf = jnp.concatenate([
            xf, jnp.broadcast_to(_edge_exp_pad(xf, lay), (n_tile - n,))])
    pay, lo, bases, rng = _encode_fused.encode_fused(
        xf, width, block, interpret=interpret)
    pay = pay[: n // GROUP].reshape(-1)  # the kernel's tiles, as flat words
    lo = lo[: n // GROUP].reshape(-1)
    return pay, lo, bases[: n // block], rng[: n // block]


def _exceptions_from(x_blocks: jax.Array, rng: jax.Array, lay, width: int,
                     cap: int):
    """Exception extraction on the per-block stats (pure jnp, negligible:
    ``nb`` elements of decision + a gather of <= ``cap`` rows re-read from
    the INPUT — the only second touch the fused encode ever makes, bounded
    by the exception capacity).  Mirrors ``packing.pack_exponents``."""
    nb = x_blocks.shape[0]
    u = lay.uint_dtype
    bad = ~(rng <= jnp.uint32((1 << width) - 1))
    n_bad = jnp.sum(bad.astype(jnp.int32))
    (exc_idx,) = jnp.nonzero(bad, size=cap, fill_value=nb)
    exc_idx = exc_idx.astype(jnp.int32)
    rows = x_blocks[jnp.minimum(exc_idx, nb - 1)]
    rbits = jax.lax.bitcast_convert_type(rows, u)
    exc_exp = ((rbits >> u(lay.mant_bits)) & u((1 << lay.exp_bits) - 1)
               ).astype(jnp.uint8)
    exc_raw = jnp.where((exc_idx < nb)[:, None], exc_exp, 0)
    overflow = (n_bad > cap).astype(jnp.int32)
    return exc_idx, exc_raw, overflow


def encode_fused(x: jax.Array, width: int, *, block: int = 512,
                 exc_frac: float = 0.02, use_pallas: bool | None = None,
                 interpret: bool | None = None) -> dict:
    """One-pass transmit-side encode of a flat float array (any n >= 1).

    Returns the wire dict ``{lo, payload, bases, exc_idx, exc_raw,
    overflow}`` — bit-identical, field by field, to the unfused composition
    ``codec.split_planes`` + ``packing.bitplane_pack(lo)`` +
    ``packing.pack_exponents(exp)`` (including both of its padding modes;
    see :func:`_edge_exp_pad`).  ``payload`` covers ``n`` padded to a block
    multiple, ``lo`` covers ``n`` padded to a GROUP multiple, both as flat
    words like the legacy composition's.
    """
    use_pallas, interpret = resolve_use_pallas(use_pallas), resolve_interpret(interpret)
    lay = codec.layout_of(x.dtype)
    n = x.shape[0]
    n_blk = _pad_up(n, block)
    n_grp = _pad_up(n, GROUP)
    nb = n_blk // block
    # pad ONCE: straight to the kernel tile on the Pallas path (blocks past
    # n_blk are sliced off below), to the block multiple on the jnp path
    target = (_pad_up(n, block * _encode_fused.TILE_B) if use_pallas
              else n_blk)
    xe = x
    if target != n:
        xe = jnp.concatenate([
            x, jnp.broadcast_to(_edge_exp_pad(x, lay), (target - n,))])
    if use_pallas:
        pay, lo, bases, rng = _encode_fused.encode_fused(
            xe, width, block, interpret=interpret)
        # the kernel's tiles, as flat words
        pay, lo = pay[: n_blk // GROUP].reshape(-1), lo.reshape(-1)
        bases, rng = bases[:nb], rng[:nb]
    else:
        pay, lo, bases, rng = _ref.encode_fused(xe, width, block)
    lo = lo[: n_grp // GROUP * lay.lo_bits]
    cap = packing.exception_capacity(nb, exc_frac)
    exc_idx, exc_raw, overflow = _exceptions_from(
        xe[: n_blk].reshape(nb, block), rng, lay, width, cap)
    return {
        "lo": lo,
        "payload": pay,
        "bases": bases.astype(jnp.uint8),
        "exc_idx": exc_idx,
        "exc_raw": exc_raw,
        "overflow": overflow,
    }


def encode_fused_chunks(x2d: jax.Array, width: int, *, block: int = 512,
                        exc_frac: float = 0.02,
                        use_pallas: bool | None = None,
                        interpret: bool | None = None) -> dict:
    """Fused encode of ``(n_chunks, chunk)`` rows, ``chunk % block == 0``.

    ONE kernel sweep over the flattened rows produces every chunk's planes
    (block boundaries never straddle chunks, so the flat payload/bases
    reshape into per-chunk wire fields exactly); exceptions are then
    extracted per chunk.  Bit-identical to vmapping :func:`encode_fused`
    over the rows — the wire dict layout of
    ``compressed_collectives._encode_chunks``.
    """
    use_pallas, interpret = resolve_use_pallas(use_pallas), resolve_interpret(interpret)
    lay = codec.layout_of(x2d.dtype)
    n_chunks, chunk = x2d.shape
    assert chunk % block == 0, (chunk, block)
    nb_c = chunk // block
    gpc = chunk // GROUP
    pay, lo, bases, rng = _encode_planes(
        x2d.reshape(-1), width, block, use_pallas, interpret)
    pay = pay.reshape(n_chunks, gpc * width)
    lo = lo.reshape(n_chunks, gpc * lay.lo_bits)
    bases = bases.reshape(n_chunks, nb_c)
    rng = rng.reshape(n_chunks, nb_c)
    cap = packing.exception_capacity(nb_c, exc_frac)
    exc_idx, exc_raw, overflow = jax.vmap(
        lambda xb, r: _exceptions_from(xb, r, lay, width, cap)
    )(x2d.reshape(n_chunks, nb_c, block), rng)
    return {
        "lo": lo,
        "payload": pay,
        "bases": bases.astype(jnp.uint8),
        "exc_idx": exc_idx,
        "exc_raw": exc_raw,
        "overflow": overflow,
    }


# ---------------------------------------------------------------------------
# rANS
# ---------------------------------------------------------------------------

def rans_encode(syms, table: core_ans.FreqTable, *, use_pallas: bool | None = None,
                interpret: bool | None = None):
    """Dense-emission rANS over (per, lanes) uint32 symbols."""
    use_pallas, interpret = resolve_use_pallas(use_pallas), resolve_interpret(interpret)
    freq, cum = table.freq, table.cum[:256]
    if use_pallas:
        if syms.shape[1] % _rans.LANE_TILE == 0:
            return _rans.encode(syms, freq, cum, interpret=interpret)
        record_fallback("rans_encode", f"lanes={syms.shape[1]} not a "
                                       f"{_rans.LANE_TILE} multiple")
    return _ref.rans_encode(syms, freq, cum)


def rans_decode(words, state, table: core_ans.FreqTable, *,
                use_pallas: bool | None = None, interpret: bool | None = None):
    use_pallas, interpret = resolve_use_pallas(use_pallas), resolve_interpret(interpret)
    s2s = core_ans._slot_to_symbol(table).astype(jnp.uint32)
    freq, cum = table.freq, table.cum[:256]
    if use_pallas:
        if words.shape[1] % _rans.LANE_TILE == 0:
            return _rans.decode(words, state, freq, cum, s2s, interpret=interpret)
        record_fallback("rans_decode", f"lanes={words.shape[1]} not a "
                                       f"{_rans.LANE_TILE} multiple")
    return _ref.rans_decode(words, state, freq, cum, s2s)
