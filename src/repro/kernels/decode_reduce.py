"""Pallas TPU kernel: fused unpack + merge + reduce.

The TPU analogue of the paper's modified ``CopyReducePacks`` (§3.4): in the
two-shot all-reduce the receiver must decompress each remote chunk *and*
accumulate it.  Doing those as separate XLA ops costs an extra HBM
round-trip for the decoded floats; this kernel streams the packed wire
(payload bit-planes + per-block bases + lo planes) and an f32 accumulator
through VMEM once, emitting the updated accumulator.

The exponent decode implements the wire format of ``packing.pack_exponents``
exactly, including the zero-escape (residual 0 -> exponent 0; residual r>0
-> ``r + base - 1``), so for non-exception blocks the fused output is
bit-identical to ``unpack_exponents`` + ``merge_planes`` + add.  Exception
blocks (whose payload is clamped garbage by construction) are patched up by
the caller AFTER the fused pass from the raw ``exc_idx``/``exc_raw`` wire —
see ``compressed_collectives._decode_reduce_chunks``.

One grid step handles TILE_G groups of 32 elements; the last step may be
partial, so any number of groups takes the kernel.  The per-block base is
pre-broadcast to a per-GROUP base outside (bases are n/512 elements —
negligible traffic) so the kernel's index maps stay rectangular.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import codec
from repro.core.packing import GROUP
from repro.kernels import resolve_interpret

TILE_G = 256


def _decode_reduce_kernel(
    lay: codec.FloatLayout, width: int, pay_ref, lo_ref, base_ref, acc_ref, o_ref
):
    pos = jax.lax.broadcasted_iota(jnp.uint32, (1, GROUP), 1)
    resid = jnp.zeros((pay_ref.shape[0], GROUP), jnp.uint32)
    for b in range(width):
        word = pay_ref[:, b][:, None]
        resid = resid | (((word >> pos) & jnp.uint32(1)) << jnp.uint32(b))
    # zero-escape decode (wire format of packing.pack_exponents): code 0 is
    # exponent 0 (zeros/subnormals); code r>0 is exponent r + base - 1.  The
    # exponent plane is uint8 by format — mask to 8 bits so clamped garbage
    # in exception blocks (patched by the caller) wraps identically to the
    # unfused unpack_exponents path.
    base = base_ref[...]  # (TILE_G, 1), broadcasts against (TILE_G, 32)
    exp = jnp.where(
        resid == 0,
        jnp.uint32(0),
        (resid + base - jnp.uint32(1)) & jnp.uint32(0xFF),
    )

    lo = jnp.zeros((lo_ref.shape[0], GROUP), jnp.uint32)
    for b in range(lay.lo_bits):
        word = lo_ref[:, b][:, None]
        lo = lo | (((word >> pos) & jnp.uint32(1)) << jnp.uint32(b))

    # rebuild the element's bit pattern in uint32 lanes (Mosaic has no
    # 16-bit shifts): a bf16 value is the high half of the f32 with the same
    # value, other narrow formats are truncated to their width and converted
    sign = lo >> jnp.uint32(lay.mant_bits)
    mant = lo & jnp.uint32((1 << lay.mant_bits) - 1)
    bits = ((sign << jnp.uint32(lay.total_bits - 1))
            | (exp << jnp.uint32(lay.mant_bits)) | mant)
    if lay.total_bits == 32:
        vals = jax.lax.bitcast_convert_type(bits, jnp.float32)
    elif lay.name == "bfloat16":
        vals = jax.lax.bitcast_convert_type(bits << jnp.uint32(16), jnp.float32)
    else:
        vals = jax.lax.bitcast_convert_type(
            bits.astype(lay.uint_dtype), lay.dtype).astype(jnp.float32)
    o_ref[...] = acc_ref[...] + vals


@functools.partial(jax.jit, static_argnames=("dtype_name", "width", "interpret"))
def decode_reduce(
    payload: jax.Array,  # uint32 (n_g, width) exponent bit-planes
    lo_planes: jax.Array,  # uint32 (n_g, lo_bits)
    group_bases: jax.Array,  # uint32 (n_g,) per-GROUP base (pre-broadcast)
    acc: jax.Array,  # float32 (n_g*32,)
    dtype_name: str,
    width: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns acc + decode(wire) in one fused pass (f32 (n,)).

    ``interpret=None`` resolves through ``kernels.resolve_interpret``."""
    lay = codec.LAYOUTS[dtype_name]
    n_g = payload.shape[0]
    out = pl.pallas_call(
        functools.partial(_decode_reduce_kernel, lay, width),
        out_shape=jax.ShapeDtypeStruct((n_g, GROUP), jnp.float32),
        grid=(pl.cdiv(n_g, TILE_G),),
        in_specs=[
            pl.BlockSpec((TILE_G, width), lambda i: (i, 0)),
            pl.BlockSpec((TILE_G, lay.lo_bits), lambda i: (i, 0)),
            pl.BlockSpec((TILE_G, 1), lambda i: (i, 0)),
            pl.BlockSpec((TILE_G, GROUP), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_G, GROUP), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
        name="decode_reduce",  # the kernel's name in a profiler trace
    )(payload, lo_planes, group_bases.reshape(-1, 1), acc.reshape(-1, GROUP))
    return out.reshape(-1)
