"""Pure-jnp oracles for every Pallas kernel (allclose/bit-exact targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import codec, packing

# --- bitpack -----------------------------------------------------------------

def pack(vals: jax.Array, width: int) -> jax.Array:
    return packing.bitplane_pack(vals, width)


def unpack(packed: jax.Array, width: int) -> jax.Array:
    return packing.bitplane_unpack(packed, width)


# --- plane_split -------------------------------------------------------------

def split_with_stats(x: jax.Array, block: int = 512):
    exp, lo = codec.split_planes(x)
    b = exp.reshape(-1, block).astype(jnp.uint32)
    base = jnp.min(b, axis=-1)
    rng = jnp.max(b, axis=-1) - base
    return exp.astype(jnp.uint32), lo.astype(jnp.uint32), base, rng


# --- encode_fused ------------------------------------------------------------

def encode_fused(x: jax.Array, width: int, block: int = 512):
    """One-pass jnp oracle of the fused split+pack kernel.

    x float (n,), n % block == 0.  Returns (payload uint32 (n//32*width,),
    lo_planes uint32 (n//32*lo_bits,), bases uint32 (n_blocks,), rng uint32
    (n_blocks,)), the planes as flat words (the kernel writes them as
    ``(n//32, width)`` tiles).  ``payload``/``bases`` are bit-identical to
    ``packing.pack_exponents``'s wire fields (zero-escape, clamped exception
    payload), ``lo_planes`` to ``packing.bitplane_pack(lo, lo_bits)``, and
    ``rng`` is the max residual code value (``rng < 2**width`` iff the block
    is not an exception).  XLA fuses this single dataflow; the Pallas kernel
    (kernels/encode_fused.py) is the explicit one-HBM-pass form.
    """
    lay = codec.layout_of(x.dtype)
    assert x.shape[0] % block == 0, (x.shape, block)
    exp, lo = codec.split_planes(x)
    b = exp.reshape(-1, block).astype(jnp.uint32)
    nz = b != 0
    base = jnp.min(jnp.where(nz, b, jnp.uint32(255)), axis=-1)
    base = jnp.where(jnp.any(nz, axis=-1), base, jnp.uint32(1))
    mx = jnp.max(jnp.where(nz, b, jnp.uint32(0)), axis=-1)
    rng = mx - base + jnp.uint32(1)  # wraps to 0 for all-zero blocks
    resid = jnp.where(nz, b - base[:, None] + jnp.uint32(1), jnp.uint32(0))
    resid = jnp.minimum(resid, jnp.uint32((1 << width) - 1))
    payload = packing.bitplane_pack(resid.reshape(-1), width)
    lo_planes = packing.bitplane_pack(lo, lay.lo_bits)
    return payload, lo_planes, base, rng


# --- decode_reduce -----------------------------------------------------------

def decode_reduce(payload, lo_planes, group_bases, acc, dtype_name: str, width: int):
    """Zero-escape wire decode + f32 accumulate (packing.pack_exponents
    format: code 0 -> exponent 0, code r>0 -> r + base - 1)."""
    lay = codec.LAYOUTS[dtype_name]
    resid = packing.bitplane_unpack(payload, width)
    r2 = resid.reshape(group_bases.shape[0], packing.GROUP)
    exp = jnp.where(
        r2 == 0, jnp.uint32(0), r2 + group_bases[:, None].astype(jnp.uint32) - 1
    ).reshape(-1).astype(jnp.uint8)
    lo = packing.bitplane_unpack(lo_planes, lay.lo_bits, lay.uint_dtype)
    vals = codec.merge_planes(exp, lo, lay.dtype, (resid.shape[0],))
    return acc.reshape(-1) + vals.astype(jnp.float32)


# --- rans (dense-emission formulation; mirrors kernels/rans.py exactly) ------

PROB_BITS = 12
M = 1 << PROB_BITS
RANS_L = 1 << 16


def rans_encode(syms: jax.Array, freq: jax.Array, cum: jax.Array):
    per, lanes = syms.shape

    def body(carry, r):
        state = carry
        s = syms[r]
        f = freq[s]
        c = cum[s]
        x_max = ((jnp.uint32(RANS_L) >> jnp.uint32(PROB_BITS)) << jnp.uint32(16)) * f
        need = state >= x_max
        word = jnp.where(need, state & jnp.uint32(0xFFFF), jnp.uint32(0))
        state = jnp.where(need, state >> jnp.uint32(16), state)
        q = state // f
        state = (q << jnp.uint32(PROB_BITS)) + (state - q * f) + c
        return state, (word, need.astype(jnp.uint32))

    state0 = jnp.full((lanes,), jnp.uint32(RANS_L))
    state, (words, mask) = jax.lax.scan(
        body, state0, jnp.arange(per - 1, -1, -1)
    )
    # scan visited rows in reverse; restore row order
    return words[::-1], mask[::-1], state


def rans_decode(words, state, freq, cum, s2s):
    per, lanes = words.shape

    def body(carry, r):
        st = carry
        slot = st & jnp.uint32(M - 1)
        sym = s2s[slot]
        f = freq[sym]
        c = cum[sym]
        st = f * (st >> jnp.uint32(PROB_BITS)) + slot - c
        need = st < jnp.uint32(RANS_L)
        st = jnp.where(need, (st << jnp.uint32(16)) | words[r], st)
        return st, sym

    _, syms = jax.lax.scan(body, state, jnp.arange(per))
    return syms
