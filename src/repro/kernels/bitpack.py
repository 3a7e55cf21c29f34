"""Pallas TPU kernel: bit-plane pack/unpack (wire codec hot loop).

Layout matches ``core/packing.py``: 32 residuals -> ``W`` uint32 words (one
per bit-plane).  The transform is pure VPU bit arithmetic — no MXU, no
gathers — so the kernel's job is purely tiling: stream (TILE_G, 32) value
tiles HBM->VMEM, emit (TILE_G, W) word tiles, one pass each way.

Tiling: TILE_G = 256 groups/step = 8192 values.  A step touches
256*32*4 B = 32 KiB in + 256*W*4 B out — comfortably inside VMEM with
double-buffering headroom; the (·, 32) trailing dim is below the 128-lane
width, so index maps keep the last dimension contiguous (values) and we let
Mosaic fold the 32-lane minor into registers.  Values and words are uint32
lanes, the native VPU word width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import GROUP
from repro.kernels import resolve_interpret

TILE_G = 256


def _pack_kernel(width: int, x_ref, o_ref):
    g = x_ref[...]  # (TILE_G, 32) uint32
    pos = jax.lax.broadcasted_iota(jnp.uint32, (1, GROUP), 1)
    for b in range(width):  # static unroll: W plane reductions
        plane = jnp.sum(
            ((g >> jnp.uint32(b)) & jnp.uint32(1)) << pos,
            axis=-1,
            dtype=jnp.uint32,
        )
        o_ref[:, b] = plane


def _unpack_kernel(width: int, p_ref, o_ref):
    pos = jax.lax.broadcasted_iota(jnp.uint32, (1, GROUP), 1)
    acc = jnp.zeros((p_ref.shape[0], GROUP), jnp.uint32)
    for b in range(width):
        word = p_ref[:, b][:, None]  # (TILE_G, 1)
        acc = acc | (((word >> pos) & jnp.uint32(1)) << jnp.uint32(b))
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def pack(vals: jax.Array, width: int, interpret: bool | None = None) -> jax.Array:
    """vals uint32 (n,), n % (32*TILE_G) == 0 -> uint32 (n//32, width)."""
    g = vals.reshape(-1, GROUP)
    n_g = g.shape[0]
    assert n_g % TILE_G == 0, (n_g, TILE_G)
    return pl.pallas_call(
        functools.partial(_pack_kernel, width),
        out_shape=jax.ShapeDtypeStruct((n_g, width), jnp.uint32),
        grid=(n_g // TILE_G,),
        in_specs=[pl.BlockSpec((TILE_G, GROUP), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE_G, width), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
    )(g)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def unpack(packed: jax.Array, width: int, interpret: bool | None = None) -> jax.Array:
    """packed uint32 (n_g, width) -> uint32 (n_g*32,)."""
    n_g = packed.shape[0]
    assert n_g % TILE_G == 0, (n_g, TILE_G)
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, width),
        out_shape=jax.ShapeDtypeStruct((n_g, GROUP), jnp.uint32),
        grid=(n_g // TILE_G,),
        in_specs=[pl.BlockSpec((TILE_G, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE_G, GROUP), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
    )(packed)
    return out.reshape(-1)
