"""Pallas TPU kernel: interleaved-lane rANS encode/decode (dense emission).

The paper's warp-level ANS (§3.4 "Warp-level execution") maps each warp to
one compression block.  The TPU has no warps — the VPU is a 8x128 SIMD
array — so the adaptation runs **one independent rANS stream per vector
lane** and keeps every lane's control flow identical:

  * *dense emission*: instead of per-lane append-to-stream (a divergent
    scatter GPUs do with ballot/prefix tricks), encode writes its maybe-
    emitted word for row ``r`` to ``words[r, lane]`` unconditionally, plus
    an emission mask.  rANS's encode/decode symmetry guarantees the decoder
    pulls at exactly the rows the encoder emitted, so the dense buffer IS
    the stream — no compaction needed for decode.  Compaction (dropping
    non-emitted slots) happens outside the kernel only when the wire is a
    real variable-length transport (host P2P path), as a cheap XLA
    cumsum+gather on ~2 bits/element of metadata.
  * integer div/mod by the symbol frequency: real TPU deployment would use
    reciprocal multiplication with per-symbol magic constants (as ryg_rans
    does); interpret-mode validation uses the plain ops.

Sequential dependency is over rows (symbols-per-lane); lanes are the
parallel axis, so the grid tiles lanes: BlockSpec keeps a (per, LANE_TILE)
strip of symbols/words resident in VMEM (~512·per bytes per buffer at
LANE_TILE=128).

State: 32-bit, 16-bit renorm, PROB_BITS=12, L = 1<<16 (same parameters as
core/ans.py; one conditional emission per symbol).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

PROB_BITS = 12
M = 1 << PROB_BITS
RANS_L = 1 << 16
LANE_TILE = 128


def _encode_kernel(per: int, syms_ref, freq_ref, cum_ref, words_ref, mask_ref, state_ref):
    freq = freq_ref[0, :]  # (256,)
    cum = cum_ref[0, :]
    lanes = syms_ref.shape[1]
    state0 = jnp.full((lanes,), jnp.uint32(RANS_L))

    def body(i, state):
        r = per - 1 - i
        s = syms_ref[pl.ds(r, 1), :][0]  # (lanes,) uint32
        f = freq[s]
        c = cum[s]
        x_max = ((jnp.uint32(RANS_L) >> jnp.uint32(PROB_BITS)) << jnp.uint32(16)) * f
        need = state >= x_max
        word = jnp.where(need, state & jnp.uint32(0xFFFF), jnp.uint32(0))
        words_ref[pl.ds(r, 1), :] = word[None]
        mask_ref[pl.ds(r, 1), :] = need.astype(jnp.uint32)[None]
        state = jnp.where(need, state >> jnp.uint32(16), state)
        q = state // f
        rem = state - q * f
        return (q << jnp.uint32(PROB_BITS)) + rem + c

    state = jax.lax.fori_loop(0, per, body, state0)
    state_ref[0, :] = state


def _decode_kernel(per: int, words_ref, state_ref, freq_ref, cum_ref, s2s_ref, syms_ref):
    freq = freq_ref[0, :]
    cum = cum_ref[0, :]
    s2s = s2s_ref[0, :]  # (M,) slot -> symbol
    state0 = state_ref[0, :]

    def body(r, state):
        slot = state & jnp.uint32(M - 1)
        sym = s2s[slot]
        f = freq[sym]
        c = cum[sym]
        state = f * (state >> jnp.uint32(PROB_BITS)) + slot - c
        need = state < jnp.uint32(RANS_L)
        w = words_ref[pl.ds(r, 1), :][0]
        state = jnp.where(need, (state << jnp.uint32(16)) | w, state)
        syms_ref[pl.ds(r, 1), :] = sym[None]
        return state

    jax.lax.fori_loop(0, per, body, state0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode(syms: jax.Array, freq: jax.Array, cum: jax.Array,
           interpret: bool | None = None):
    """syms uint32 (per, lanes); lanes % LANE_TILE == 0.

    Returns (words u32 (per, lanes), mask u32 (per, lanes), state u32 (lanes,)).
    Wire size = (mask.sum() + 2*lanes) 16-bit words + the table.
    """
    per, lanes = syms.shape
    assert lanes % LANE_TILE == 0, lanes
    words, mask, state = pl.pallas_call(
        functools.partial(_encode_kernel, per),
        out_shape=(
            jax.ShapeDtypeStruct((per, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((per, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((1, lanes), jnp.uint32),
        ),
        grid=(lanes // LANE_TILE,),
        in_specs=[
            pl.BlockSpec((per, LANE_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, 256), lambda i: (0, 0)),
            pl.BlockSpec((1, 256), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((per, LANE_TILE), lambda i: (0, i)),
            pl.BlockSpec((per, LANE_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, LANE_TILE), lambda i: (0, i)),
        ),
        interpret=resolve_interpret(interpret),
    )(syms, freq.reshape(1, 256), cum.reshape(1, 256))
    return words, mask, state[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode(
    words: jax.Array, state: jax.Array, freq: jax.Array, cum: jax.Array,
    s2s: jax.Array, interpret: bool | None = None,
):
    """Inverse of :func:`encode`; returns syms u32 (per, lanes)."""
    per, lanes = words.shape
    assert lanes % LANE_TILE == 0, lanes
    return pl.pallas_call(
        functools.partial(_decode_kernel, per),
        out_shape=jax.ShapeDtypeStruct((per, lanes), jnp.uint32),
        grid=(lanes // LANE_TILE,),
        in_specs=[
            pl.BlockSpec((per, LANE_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, LANE_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, 256), lambda i: (0, 0)),
            pl.BlockSpec((1, 256), lambda i: (0, 0)),
            pl.BlockSpec((1, M), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((per, LANE_TILE), lambda i: (0, i)),
        interpret=resolve_interpret(interpret),
    )(words, state.reshape(1, lanes), freq.reshape(1, 256), cum.reshape(1, 256),
      s2s.reshape(1, M))
