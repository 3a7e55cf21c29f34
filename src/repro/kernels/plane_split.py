"""Pallas TPU kernel: fused float split + per-block statistics.

This is the paper's Step 1 *fused with* localized-table construction
(§3.3.1): one HBM pass reads the float tensor and emits
  - the exponent plane (uint8 per element — wait, TPU: kept in uint16/uint32
    lanes until the pack stage),
  - the lo plane (sign relocated next to mantissa, codec.py layout),
  - per-block (base, range) — the degenerate "frequency table" that the
    static wire format needs (DESIGN.md §2).

On GPU the paper builds a histogram here; on TPU the localized statistic is
(min, max) because the downstream coder is fixed-width packing — a
cross-lane min/max reduction, natively supported by the VPU, instead of a
scatter-increment histogram which the VPU has no efficient primitive for.
This is a deliberate hardware adaptation, recorded in DESIGN.md §7.

Tiling: one grid step processes TILE_B blocks x B elements.  With B = 512
and TILE_B = 8 a bf16 step moves 8*512*2 B = 8 KiB in and a bit more out —
small enough that several steps pipeline inside VMEM while HBM streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import codec
from repro.kernels import resolve_interpret

TILE_B = 8  # blocks per grid step


def _split_kernel(lay: codec.FloatLayout, x_ref, exp_ref, lo_ref, base_ref, rng_ref):
    bits = jax.lax.bitcast_convert_type(x_ref[...], lay.uint_dtype)
    u = lay.uint_dtype
    mant_mask = u((1 << lay.mant_bits) - 1)
    exp = ((bits >> u(lay.mant_bits)) & u((1 << lay.exp_bits) - 1)).astype(
        jnp.uint32
    )
    sign = bits >> u(lay.total_bits - 1)
    lo = (sign << u(lay.mant_bits)) | (bits & mant_mask)
    exp_ref[...] = exp
    lo_ref[...] = lo.astype(jnp.uint32)
    base_ref[...] = jnp.min(exp, axis=-1, keepdims=True)
    rng_ref[...] = jnp.max(exp, axis=-1, keepdims=True) - jnp.min(
        exp, axis=-1, keepdims=True
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def split_with_stats(x: jax.Array, block: int = 512,
                     interpret: bool | None = None):
    """x float (n,), n % (block*TILE_B) == 0.

    Returns (exp uint32 (n,), lo uint32 (n,), bases uint32 (n_blocks,),
    ranges uint32 (n_blocks,)).  uint32 lanes: the native VPU width; the
    pack stage consumes these directly, so no uint8 repack roundtrip.
    """
    lay = codec.layout_of(x.dtype)
    n = x.shape[0]
    assert n % (block * TILE_B) == 0, (n, block, TILE_B)
    nb = n // block
    xb = x.reshape(nb, block)
    exp, lo, base, rng = pl.pallas_call(
        functools.partial(_split_kernel, lay),
        out_shape=(
            jax.ShapeDtypeStruct((nb, block), jnp.uint32),
            jax.ShapeDtypeStruct((nb, block), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1), jnp.uint32),
        ),
        grid=(nb // TILE_B,),
        in_specs=[pl.BlockSpec((TILE_B, block), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((TILE_B, block), lambda i: (i, 0)),
            pl.BlockSpec((TILE_B, block), lambda i: (i, 0)),
            pl.BlockSpec((TILE_B, 1), lambda i: (i, 0)),
            pl.BlockSpec((TILE_B, 1), lambda i: (i, 0)),
        ),
        interpret=resolve_interpret(interpret),
    )(xb)
    return exp.reshape(-1), lo.reshape(-1), base.reshape(-1), rng.reshape(-1)
