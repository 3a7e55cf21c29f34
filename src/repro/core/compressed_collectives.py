"""Compression-integrated collectives (paper §3.4 + §5.2.2 + Fig. 9).

These run inside ``shard_map`` manual axes and replace the raw XLA
collectives on data-parallel / cross-pod wires.  The wire payload is the
static packed format of ``packing.py`` — the lowered HLO genuinely moves
fewer bytes, which is what the roofline's collective term measures.

Implemented primitives:
  * ``psum_compressed``        — all-reduce; ``two_shot`` (paper-recommended,
    Fig. 9: reduce-scatter + all-gather, ONE encode/decode per phase) or
    ``ring`` (paper's negative baseline: per-hop re-compression).
  * ``reduce_scatter_compressed`` / ``all_gather_compressed`` — the two-shot
    phases, usable directly (ZeRO-1 uses them natively).
  * ``all_to_all_compressed``  — MoE expert dispatch (paper Fig. 8a).
  * ``ppermute_compressed``    — compressed P2P (paper Fig. 7).
  * ``tree_psum_compressed``   — gradient-bucket sync for pytrees: all
    compressible leaves are fused into one large flat bucket (the paper's
    large-block-granularity principle) and synced with one two-shot.

Reduction is performed in float32 regardless of wire dtype (decode is
bit-exact; only the summation order differs from a raw ``lax.psum``).
All reduce paths — fused, unfused, and the raw baselines — accumulate in
*device-index order* (:func:`_seq_sum` / the fused streaming scan), so the
fused and unfused collectives are bit-identical and deterministic across
backends.

Fused execution (paper §3.4, the modified ``CopyReducePacks``): the
receive side of every reduce-scatter streams each received chunk through
``kernels/ops.decode_reduce`` — one pass that unpacks the wire, merges the
planes, and adds into the f32 accumulator — instead of materializing all
decoded floats in HBM and summing them afterwards.  Exception blocks are
patched up exactly after each chunk's fused pass (the accumulator rows are
saved before the kernel and rewritten as ``saved + exact``, preserving the
accumulation order bit-for-bit).  ``use_fused=False`` keeps the unfused
decode-then-reduce path for A/B comparison; ``n_groups % TILE_G != 0``
falls back from the Pallas kernel to the fused pure-jnp reference
automatically (``kernels/ops.decode_reduce``).

Fused TRANSMIT side (paper §3.2 Step 1): every compressed send phase
encodes through ``kernels/ops.encode_fused_chunks`` by default — one pass
that reads each input block from HBM once and emits the packed exponent
payload and lo planes directly, instead of materializing the split planes
between ``codec.split_planes`` and the bit-plane pack.  ``fused_encode=
False`` (policy knob ``CompressionPolicy.fused_encode``) keeps the
three-pass composition for A/B accounting; both are bit-identical, and the
Pallas-vs-jnp choice inside the fused dispatch follows the backend probe
(``use_pallas``) with ragged shapes padded to the kernel tile rather than
silently degrading.

Every compressed wire records a trace-time ``WireReport``
(``policy.record_wire_report``) with raw vs wire bytes and the decoded-
float HBM round-trip the unfused path would incur — the roofline and
``benchmarks/fig9_twoshot.py`` read these.

Every primitive returns ``(value, overflow_flag)`` where the flag is the
max of all wire ``overflow`` headers — the caller (fault-tolerant training
loop) retries the step uncompressed when it fires, so losslessness is
unconditional (DESIGN.md §2).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.core import codec, packing
from repro.core.policy import (CompressionPolicy, WireReport,
                               record_wire_report)
from repro.kernels import ops as kernel_ops


def _axis_size(axis_name) -> int:
    if isinstance(axis_name, (tuple, list)):
        return int(np.prod([jax.lax.axis_size(a) for a in axis_name]))
    return jax.lax.axis_size(axis_name)


def _pad_flat(x: jax.Array, multiple: int) -> jax.Array:
    r = (-x.shape[0]) % multiple
    if r:
        x = jnp.concatenate([x, jnp.zeros((r,), x.dtype)])
    return x


_PROMOTE = ("bfloat16", "float16", "float8_e4m3fn", "float8_e5m2")
_WIRE_UINT = {"bfloat16": jnp.uint16, "float16": jnp.uint16,
              "float8_e4m3fn": jnp.uint8, "float8_e5m2": jnp.uint8}


def _to_wire(x):
    """Bitcast sub-f32 floats to a same-width uint for pure data-movement
    collectives: XLA's promotion passes rewrite bf16 all-to-all/all-gather
    to f32 (2x wire bytes) on some backends; integers are never promoted,
    so the HLO the roofline measures moves exactly the logical bytes."""
    name = jnp.dtype(x.dtype).name
    if name in _WIRE_UINT:
        return jax.lax.bitcast_convert_type(x, _WIRE_UINT[name]), x.dtype
    return x, None


def _from_wire(x, orig_dtype):
    if orig_dtype is None:
        return x
    return jax.lax.bitcast_convert_type(x, orig_dtype)


def raw_all_to_all(x, axes, split_axis=0, concat_axis=0):
    w, dt = _to_wire(x)
    out = jax.lax.all_to_all(w, axes, split_axis, concat_axis, tiled=False)
    return _from_wire(out, dt)


def raw_all_gather(x, axes, axis=0, tiled=True):
    w, dt = _to_wire(x)
    axes_t = tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
    out = w
    for a in reversed(axes_t):
        out = jax.lax.all_gather(out, a, axis=axis, tiled=tiled)
    return _from_wire(out, dt)


def raw_ppermute(x, axes, perm):
    w, dt = _to_wire(x)
    return _from_wire(jax.lax.ppermute(w, axes, perm), dt)


def psum_safe(x: jax.Array, axes):
    """psum that promotes sub-f32 floats to f32 on the wire.

    Used for small tensors only (norms, flags): XLA-CPU crashes on bf16
    all-reduce, and on TPU the f32 promotion of tiny tensors is noise."""
    if jnp.dtype(x.dtype).name in _PROMOTE:
        return jax.lax.psum(x.astype(jnp.float32), axes).astype(x.dtype)
    return jax.lax.psum(x, axes)


def _seq_sum(vals: jax.Array, acc_dtype=jnp.float32) -> jax.Array:
    """Deterministic device-index-order accumulation over axis 0.

    The SAME order as the fused streaming pass (zeros, then += chunk 0, 1,
    ...), so fused and unfused reduce paths are bit-identical.  A plain
    ``jnp.sum`` is NOT order-stable across backends (XLA reassociates)."""
    acc0 = jnp.zeros(vals.shape[1:], acc_dtype)
    acc, _ = jax.lax.scan(lambda a, v: (a + v.astype(acc_dtype), None),
                          acc0, vals)
    return acc


def psum_raw_twoshot(x: jax.Array, axes, *, acc_dtype=jnp.float32):
    """Uncompressed all-reduce as all_to_all-RS + all-gather.

    Byte-exact twin of the compressed two-shot (moves 2(k-1)/k·n bytes at
    the wire dtype), so raw-vs-compressed roofline deltas measure ONLY the
    compression, not a dtype promotion."""
    axes_t = tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
    n_dev = int(np.prod([jax.lax.axis_size(a) for a in axes_t]))
    n = int(np.prod(x.shape))
    xf = _pad_flat(x.reshape(-1), n_dev)
    rows = xf.reshape(n_dev, -1)
    recv = raw_all_to_all(rows, axes_t, 0, 0)
    red = _seq_sum(recv, acc_dtype).astype(x.dtype)
    gathered = raw_all_gather(red[None], axes_t, axis=0, tiled=True)
    return gathered.reshape(-1)[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# Chunk codec: vectorized encode/decode of (n_chunks, chunk_len) payloads.
# One vectorized encode == paper's "compress once as a large chunk or batch".
# ---------------------------------------------------------------------------

def _encode_chunks(x2d: jax.Array, *, width: int, block: int, exc_frac: float,
                   fused: bool = True, use_pallas: bool | None = None):
    """Vectorized transmit-side encode of (n_chunks, chunk) rows.

    ``fused=True`` (default) is the one-pass split+pack dispatch (paper
    §3.2 Step 1): ``kernels/ops.encode_fused_chunks`` reads each input
    element from HBM once and emits the packed wire directly (Pallas kernel
    under the backend probe, fused jnp reference elsewhere).  ``fused=False``
    keeps the legacy three-pass composition — split, materialize planes,
    pack — for A/B accounting.  Both are bit-identical."""
    lay = codec.layout_of(x2d.dtype)
    if fused:
        if x2d.shape[1] % block == 0:
            return kernel_ops.encode_fused_chunks(
                x2d, width, block=block, exc_frac=exc_frac,
                use_pallas=use_pallas)
        # every in-repo collective pads chunks to a block multiple; a
        # future misaligned caller degrades VISIBLY, not silently
        kernels.record_fallback(
            "encode_fused_chunks",
            f"chunk={x2d.shape[1]} not a {block} multiple")

    def enc(row):
        exp, lo = codec.split_planes(row)
        lo_planes = packing.bitplane_pack(
            packing._pad_to(lo, packing.GROUP, "zero"), lay.lo_bits)
        pk = packing.pack_exponents(exp, width=width, block=block, exc_frac=exc_frac)
        return {
            "lo": lo_planes,
            "payload": pk.payload,
            "bases": pk.bases,
            "exc_idx": pk.exc_idx,
            "exc_raw": pk.exc_raw,
            "overflow": pk.overflow,
        }

    return jax.vmap(enc)(x2d)


def _decode_chunks(wire: dict, *, dtype, n: int, width: int, block: int):
    lay = codec.layout_of(dtype)
    nb = wire["bases"].shape[-1]

    def dec(w):
        pk = packing.PackedPlane(
            payload=w["payload"],
            bases=w["bases"],
            exc_idx=w["exc_idx"],
            exc_raw=w["exc_raw"],
            overflow=w["overflow"],
            width=width,
            block=block,
            n=n,
            exp_bits=lay.exp_bits,
        )
        exp = packing.unpack_exponents(pk)
        lo = packing.bitplane_unpack(w["lo"], lay.lo_bits, lay.uint_dtype)[:n]
        return codec.merge_planes(exp, lo, lay.dtype, (n,))

    # one chunk at a time: a vmapped decode keeps every chunk's per-plane
    # temporaries live at once, which overflows a chip's HBM at real sizes
    vals = jax.lax.map(dec, wire)
    flag = jnp.max(wire["overflow"])
    return vals, flag


def wire_nbytes(wire: dict) -> int:
    """Static wire size of an encoded chunk dict (for accounting)."""
    return sum(int(np.prod(v.shape)) * v.dtype.itemsize for v in wire.values())


def encode_hbm_bytes_for(n_elems: int, itemsize: int) -> int:
    """Redundant split-plane HBM round-trip of an UNFUSED encode: the
    exponent plane (1 B/elem) and lo plane (itemsize B/elem) are written
    after the split and re-read by the pack — 2*(1+itemsize) B/element.
    The fused one-pass encode (kernels/ops.encode_fused) eliminates it."""
    return int(2 * (1 + itemsize) * n_elems)


def _record_collective(name: str, axis_name, *, raw_bytes: int, wire: dict,
                       fused: bool, decoded_elems: int = 0,
                       encoded_elems: int = 0, itemsize: int = 0,
                       encode_fused: bool = True) -> None:
    """Emit the trace-time WireReport for one compressed wire.

    ``decoded_elems`` is the decoded-f32 element count an UNFUSED receive
    side materializes between decode and reduce (write + re-read = 8 bytes
    per element); pass 0 where no reduction follows the decode.  ``fused``
    records whether this wire actually paid that round-trip (False) or
    eliminated it (True).

    ``encoded_elems``/``itemsize`` give the transmit-side mirror: the
    split-plane round-trip an unfused encode materializes between split and
    pack (:func:`encode_hbm_bytes_for`); ``encode_fused`` records whether
    this wire's encode eliminated it (one-pass split+pack) or paid it."""
    record_wire_report(WireReport(
        name=name,
        axis=str(axis_name),
        raw_bytes=int(raw_bytes),
        wire_bytes=wire_nbytes(wire),
        fused=bool(fused),
        decode_hbm_bytes=int(8 * decoded_elems),
        encode_fused=bool(encode_fused),
        encode_hbm_bytes=encode_hbm_bytes_for(encoded_elems, itemsize),
    ))


def _decode_reduce_chunks(
    wire: dict, *, dtype, n: int, width: int, block: int,
    acc: jax.Array | None = None, use_pallas: bool | None = None,
):
    """Fused streaming decode+reduce over received chunks (paper §3.4).

    Scans the leading (chunk) axis of ``wire``; each step runs the fused
    unpack+merge+accumulate kernel (``kernels/ops.decode_reduce``) and then
    patches the chunk's exception blocks EXACTLY: the accumulator rows of
    those blocks are saved before the kernel and rewritten afterwards as
    ``saved + merge(exc_raw, lo)``, which preserves both losslessness and
    the device-index accumulation order bit-for-bit (the kernel's garbage
    contribution at those rows is discarded, not subtracted).

    ``n`` must be a multiple of ``block`` (the collectives pad to it).
    Returns ``(acc f32 (n,), overflow_flag)``.
    """
    lay = codec.layout_of(dtype)
    assert n % block == 0, (n, block)
    nb = n // block
    gpb = block // packing.GROUP  # payload/lo groups per block
    cap = wire["exc_idx"].shape[-1]

    def body(acc, w):
        group_bases = jnp.repeat(w["bases"].astype(jnp.uint32), gpb)
        exc_idx = w["exc_idx"]  # (cap,) block ids; fill value nb = unused
        pos = (exc_idx[:, None] * block
               + jnp.arange(block, dtype=jnp.int32)[None, :]).reshape(-1)
        saved = acc[jnp.minimum(pos, n - 1)]
        wpb = gpb * lay.lo_bits  # lo words per block
        words = (jnp.minimum(exc_idx, nb - 1)[:, None] * wpb
                 + jnp.arange(wpb, dtype=jnp.int32)[None, :]).reshape(-1)
        lo_vals = packing.bitplane_unpack(w["lo"][words], lay.lo_bits,
                                          lay.uint_dtype)
        exact = codec.merge_planes(
            w["exc_raw"].reshape(-1), lo_vals, lay.dtype, (cap * block,)
        ).astype(jnp.float32)
        acc = kernel_ops.decode_reduce(
            w["payload"], w["lo"], group_bases, acc, lay.name, width,
            use_pallas=use_pallas,
        )
        # fill entries have pos >= n and are dropped; real entries rewrite
        # the kernel's garbage contribution with the exact value
        acc = acc.at[pos].set(saved + exact, mode="drop")
        return acc, None

    if acc is None:
        acc = jnp.zeros((n,), jnp.float32)
    acc, _ = jax.lax.scan(body, acc, wire)
    return acc, jnp.max(wire["overflow"])


# ---------------------------------------------------------------------------
# Two-shot all-reduce (paper Fig. 9) and its phases
# ---------------------------------------------------------------------------

def reduce_scatter_compressed(
    x: jax.Array, axis_name, *, width: int, block: int = 512,
    exc_frac: float = 0.02, acc_dtype=jnp.float32, use_fused: bool = True,
    use_pallas: bool | None = None, fused_encode: bool = True,
):
    """Compressed reduce-scatter over a flat array.

    Device i ends with ``sum_j chunk_i(device j)`` for its chunk.  The wire
    is one ``all_to_all`` on packed planes; each device encodes its chunks
    in ONE vectorized pass (large-granularity, paper §5.2.2).

    The receive side is FUSED by default (paper §3.4): each received chunk
    streams through ``kernels/ops.decode_reduce`` straight into the f32
    accumulator, eliminating the decoded-float HBM round-trip of the
    decode-then-sum baseline.  ``use_fused=False`` keeps that baseline
    (bit-identical output — both accumulate in device-index order); a
    non-f32 ``acc_dtype`` also falls back (the fused kernel is f32-only).
    Returns (local_chunk_sum acc_dtype (chunk,), overflow_flag).
    """
    n_dev = _axis_size(axis_name)
    xf = _pad_flat(x.reshape(-1), n_dev * block)
    chunks = xf.reshape(n_dev, -1)
    wire = _encode_chunks(chunks, width=width, block=block, exc_frac=exc_frac,
                          fused=fused_encode, use_pallas=use_pallas)
    # all_to_all: leaf axis 0 is the destination-device axis
    recv = jax.tree.map(
        lambda a: jax.lax.all_to_all(a, axis_name, 0, 0, tiled=False), wire
    )
    fused = use_fused and acc_dtype == jnp.float32
    _record_collective(
        "reduce_scatter", axis_name, raw_bytes=chunks.size * x.dtype.itemsize,
        wire=wire, fused=fused, decoded_elems=chunks.size,
        encoded_elems=chunks.size, itemsize=x.dtype.itemsize,
        encode_fused=fused_encode,
    )
    if fused:
        return _decode_reduce_chunks(
            recv, dtype=x.dtype, n=chunks.shape[1], width=width, block=block,
            use_pallas=use_pallas,
        )
    vals, flag = _decode_chunks(
        recv, dtype=x.dtype, n=chunks.shape[1], width=width, block=block
    )
    return _seq_sum(vals, acc_dtype), flag


def all_gather_compressed(
    y: jax.Array, axis_name, *, width: int, block: int = 512,
    exc_frac: float = 0.02, fused_encode: bool = True,
    use_pallas: bool | None = None,
):
    """Compressed all-gather of a flat local chunk: ONE encode at the source
    (fused split+pack by default), one decode of the gathered wire.  The
    decode output IS the result (no reduction follows), so there is nothing
    to fuse on the receive side of this phase.
    Returns (stacked (n_dev, chunk), flag)."""
    n_dev = _axis_size(axis_name)
    yf = _pad_flat(y.reshape(-1), block)
    wire = _encode_chunks(yf[None], width=width, block=block,
                          exc_frac=exc_frac, fused=fused_encode,
                          use_pallas=use_pallas)
    gathered = jax.tree.map(
        lambda a: jax.lax.all_gather(a, axis_name, axis=0, tiled=False), wire
    )
    gathered = jax.tree.map(lambda a: a.reshape((n_dev,) + a.shape[2:]), gathered)
    _record_collective(
        "all_gather", axis_name,
        raw_bytes=n_dev * yf.size * y.dtype.itemsize,
        wire=gathered, fused=False, decoded_elems=0,
        encoded_elems=yf.size, itemsize=y.dtype.itemsize,
        encode_fused=fused_encode,
    )
    vals, flag = _decode_chunks(
        gathered, dtype=y.dtype, n=yf.shape[0], width=width, block=block
    )
    return vals, flag


def psum_compressed(
    x: jax.Array, axis_name, *, policy: CompressionPolicy,
    tensor_class: str = "gradient", out_dtype=None,
):
    """Compressed all-reduce.  Falls back per policy: big tensors use the
    byte-exact raw two-shot; small ones a plain (f32-promoted) psum."""
    out_dtype = out_dtype or x.dtype
    if not policy.should_compress(x, axis_name, tensor_class=tensor_class):
        nbytes = int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        if nbytes >= policy.min_bytes:
            return psum_raw_twoshot(x, axis_name).astype(out_dtype), jnp.int32(0)
        return psum_safe(x, axis_name).astype(out_dtype), jnp.int32(0)
    if policy.allreduce_algorithm == "ring":
        return psum_compressed_ring(
            x, axis_name, width=policy.width_for(tensor_class),
            block=policy.profile.block, exc_frac=policy.profile.exc_frac,
            out_dtype=out_dtype, use_fused=policy.fused_decode_reduce,
            fused_encode=policy.fused_encode,
        )
    width = policy.width_for(tensor_class)
    block = policy.profile.block
    exc = policy.profile.exc_frac
    n = int(np.prod(x.shape))
    red, f1 = reduce_scatter_compressed(
        x, axis_name, width=width, block=block, exc_frac=exc,
        use_fused=policy.fused_decode_reduce,
        fused_encode=policy.fused_encode,
    )
    # The reduced chunk is a different distribution (sums of D values shift
    # exponents by ~log2(D) uniformly, which the per-block base absorbs);
    # block *ranges* stay comparable, so the calibrated W is reused and the
    # exception region + overflow flag cover the tail exactly.
    ag_width = min(width + policy.profile.ag_extra_bits, 8)
    gath, f2 = all_gather_compressed(
        red.astype(out_dtype), axis_name, width=ag_width, block=block,
        exc_frac=exc, fused_encode=policy.fused_encode,
    )
    out = gath.reshape(-1)[:n].reshape(x.shape).astype(out_dtype)
    return out, jnp.maximum(f1, f2)


def psum_compressed_ring(
    x: jax.Array, axis_name, *, width: int, block: int = 512,
    exc_frac: float = 0.02, out_dtype=None, use_fused: bool = True,
    fused_encode: bool = True, use_pallas: bool | None = None,
):
    """Ring all-reduce with per-hop encode/decode — the paper's NEGATIVE
    baseline (Fig. 9b): every chunk is re-compressed at every hop.  Kept for
    benchmarks/tests; the production policy uses two_shot.

    The reduce-scatter-phase hops fuse decode+accumulate into the received
    chunk (same ``decode_reduce`` streaming pass as the two-shot); the
    all-gather-phase hops are pure decodes — nothing to fuse."""
    out_dtype = out_dtype or x.dtype
    n_dev = _axis_size(axis_name)
    if isinstance(axis_name, (tuple, list)):
        raise ValueError("ring variant supports a single axis")
    idx = jax.lax.axis_index(axis_name)
    n = int(np.prod(x.shape))
    xf = _pad_flat(x.reshape(-1), n_dev * block).reshape(n_dev, -1)
    chunk = xf.shape[1]
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    acc = xf.astype(jnp.float32)
    flag = jnp.int32(0)

    def hop(v, phase):
        wire = _encode_chunks(v[None], width=width, block=block,
                              exc_frac=exc_frac, fused=fused_encode,
                              use_pallas=use_pallas)
        recv = jax.tree.map(lambda a: jax.lax.ppermute(a, axis_name, perm), wire)
        _record_collective(
            f"ring_hop_{phase}", axis_name,
            raw_bytes=chunk * v.dtype.itemsize, wire=wire,
            fused=use_fused and phase == "rs",
            decoded_elems=chunk if phase == "rs" else 0,
            encoded_elems=chunk, itemsize=v.dtype.itemsize,
            encode_fused=fused_encode,
        )
        return recv

    def send_recv(v):
        recv = hop(v, "ag")
        vals, f = _decode_chunks(recv, dtype=v.dtype, n=chunk, width=width,
                                 block=block)
        return vals[0], f

    def send_recv_reduce(v, acc_row):
        """Fused hop: acc_row + decode(received wire) in one pass."""
        recv = hop(v, "rs")
        if use_fused:
            return _decode_reduce_chunks(
                recv, dtype=v.dtype, n=chunk, width=width, block=block,
                acc=acc_row,
            )
        vals, f = _decode_chunks(recv, dtype=v.dtype, n=chunk, width=width,
                                 block=block)
        return acc_row + vals[0].astype(jnp.float32), f

    # reduce-scatter phase: hop h sends the chunk owned by (idx - h)
    send = jnp.take(acc, (idx - 0) % n_dev, axis=0)
    for h in range(n_dev - 1):
        slot = (idx - h - 1) % n_dev
        send, f = send_recv_reduce(send.astype(x.dtype),
                                   jnp.take(acc, slot, axis=0))
        flag = jnp.maximum(flag, f)
        acc = acc.at[slot].set(send)
    # all-gather phase: circulate the fully-reduced chunk
    for h in range(n_dev - 1):
        got, f = send_recv(send.astype(out_dtype))
        flag = jnp.maximum(flag, f)
        slot = (idx - n_dev - h) % n_dev
        acc = acc.at[slot].set(got.astype(jnp.float32))
        send = got.astype(jnp.float32)
    return acc.reshape(-1)[:n].reshape(x.shape).astype(out_dtype), flag


def psum_compressed_hierarchical(
    x: jax.Array, *, intra_axis: str = "data", inter_axis: str = "pod",
    policy: CompressionPolicy, tensor_class: str = "gradient",
    out_dtype=None,
):
    """Pod-aware two-level compressed all-reduce (beyond-paper, DESIGN §8).

    Cross-pod (DCN-class) links are the scarce resource on multi-pod
    meshes.  Instead of one flat two-shot over (pod × data) — whose wire
    crosses pods with 1/(pod·data) chunking — reduce WITHIN the pod first,
    so only the (1/data)-sized reduced shards cross pods:

        RS(intra, compressed) → two-shot(inter, compressed) → AG(intra)

    Cross-pod bytes drop by the intra-axis size (16× on the production
    mesh) at the cost of one extra intra-pod phase.  Returns (sum, flag).
    """
    out_dtype = out_dtype or x.dtype
    if not policy.should_compress(x, (intra_axis, inter_axis),
                                  tensor_class=tensor_class):
        return psum_raw_twoshot(x, (intra_axis, inter_axis)).astype(
            out_dtype), jnp.int32(0)
    width = policy.width_for(tensor_class)
    block = policy.profile.block
    exc = policy.profile.exc_frac
    fused = policy.fused_decode_reduce
    fenc = policy.fused_encode
    n = int(np.prod(x.shape))
    # 1. intra-pod reduce-scatter: each device owns 1/data of the pod sum
    shard, f1 = reduce_scatter_compressed(
        x, intra_axis, width=width, block=block, exc_frac=exc,
        use_fused=fused, fused_encode=fenc)
    # 2. cross-pod all-reduce of the shard (two-shot, compressed)
    shard = shard.astype(out_dtype)
    red, f2 = reduce_scatter_compressed(
        shard, inter_axis, width=width, block=block, exc_frac=exc,
        use_fused=fused, fused_encode=fenc)
    gat, f3 = all_gather_compressed(
        red.astype(out_dtype), inter_axis, width=width, block=block,
        exc_frac=exc, fused_encode=fenc)
    shard_full = gat.reshape(-1)[: shard.shape[0]].astype(out_dtype)
    # 3. intra-pod all-gather of the fully-reduced shards
    out, f4 = all_gather_compressed(
        shard_full, intra_axis, width=width, block=block, exc_frac=exc,
        fused_encode=fenc)
    out = out.reshape(-1)[:n].reshape(x.shape).astype(out_dtype)
    flag = jnp.maximum(jnp.maximum(f1, f2), jnp.maximum(f3, f4))
    return out, flag


# ---------------------------------------------------------------------------
# all_to_all (MoE dispatch) and P2P
# ---------------------------------------------------------------------------

def all_to_all_compressed(
    x: jax.Array, axis_name, *, policy: CompressionPolicy,
    tensor_class: str = "activation",
):
    """Compressed all_to_all over leading axis (n_dev, ...) -> (n_dev, ...).

    Used by MoE expert dispatch/return over the EP axis (paper Fig. 8a)."""
    n_dev = _axis_size(axis_name)
    assert x.shape[0] == n_dev, (x.shape, n_dev)
    if not policy.should_compress(x, axis_name, tensor_class=tensor_class):
        return raw_all_to_all(x, axis_name, 0, 0), jnp.int32(0)
    width = policy.width_for(tensor_class)
    block = policy.profile.block
    inner = int(np.prod(x.shape[1:]))
    x2d = jax.vmap(lambda r: _pad_flat(r.reshape(-1), block))(x.reshape(n_dev, inner))
    wire = _encode_chunks(
        x2d, width=width, block=block, exc_frac=policy.profile.exc_frac,
        fused=policy.fused_encode,
    )
    recv = jax.tree.map(
        lambda a: jax.lax.all_to_all(a, axis_name, 0, 0, tiled=False), wire
    )
    _record_collective(
        "all_to_all", axis_name, raw_bytes=x2d.size * x.dtype.itemsize,
        wire=wire, fused=False, decoded_elems=0,
        encoded_elems=x2d.size, itemsize=x.dtype.itemsize,
        encode_fused=policy.fused_encode,
    )
    vals, flag = _decode_chunks(
        recv, dtype=x.dtype, n=x2d.shape[1], width=width, block=block
    )
    out = vals[:, :inner].reshape(x.shape).astype(x.dtype)
    return out, flag


def ppermute_compressed(
    x: jax.Array, axis_name, perm, *, policy: CompressionPolicy,
    tensor_class: str = "weight",
):
    """Compressed point-to-point transfer (encode-send; see split_send.py for
    the overlapped pipeline)."""
    if not policy.should_compress(x, axis_name, tensor_class=tensor_class):
        return raw_ppermute(x, axis_name, perm), jnp.int32(0)
    width = policy.width_for(tensor_class)
    block = policy.profile.block
    xf = _pad_flat(x.reshape(-1), block)
    wire = _encode_chunks(
        xf[None], width=width, block=block, exc_frac=policy.profile.exc_frac,
        fused=policy.fused_encode,
    )
    recv = jax.tree.map(lambda a: jax.lax.ppermute(a, axis_name, perm), wire)
    _record_collective(
        "ppermute", axis_name, raw_bytes=xf.size * x.dtype.itemsize,
        wire=wire, fused=False, decoded_elems=0,
        encoded_elems=xf.size, itemsize=x.dtype.itemsize,
        encode_fused=policy.fused_encode,
    )
    vals, flag = _decode_chunks(
        recv, dtype=x.dtype, n=xf.shape[0], width=width, block=block
    )
    n = int(np.prod(x.shape))
    return vals[0, :n].reshape(x.shape), flag


# ---------------------------------------------------------------------------
# Pytree gradient bucket sync (the production entry point for DP)
# ---------------------------------------------------------------------------

def tree_psum_compressed(
    tree, axis_name, *, policy: CompressionPolicy, tensor_class: str = "gradient"
):
    """Fuse policy-eligible leaves into per-dtype flat buckets and all-reduce
    each with one compressed two-shot; remaining leaves use raw psum.

    Bucketing applies the paper's core granularity lesson (Property 1:
    compression efficiency needs large blocks) to the whole gradient pytree.
    Buckets are grouped BY DTYPE: casting every leaf to the first leaf's
    dtype would silently round wider leaves (e.g. f32 norms in a bf16-first
    gradient tree), violating the losslessness guarantee.  One two-shot per
    dtype group keeps each leaf bit-exact at its own precision.
    Returns (tree, overflow_flag).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    groups: dict = {}  # dtype name -> leaf indices, in tree order
    small_ix = []
    for i, l in enumerate(leaves):
        # bucket-eligible: supported dtype; the bucket as a whole passes the
        # size threshold, so per-leaf size doesn't gate membership.
        if hasattr(l, "dtype") and jnp.dtype(l.dtype).name in codec.LAYOUTS:
            groups.setdefault(jnp.dtype(l.dtype).name, []).append(i)
        else:
            small_ix.append(i)
    out = list(leaves)
    flag = jnp.int32(0)
    for name in sorted(groups):
        ixs = groups[name]
        parts = [leaves[i].reshape(-1) for i in ixs]
        sizes = [p.shape[0] for p in parts]
        bucket = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        red, f = psum_compressed(
            bucket, axis_name, policy=policy, tensor_class=tensor_class
        )
        flag = jnp.maximum(flag, f)
        offs = np.cumsum([0] + sizes)
        for k, i in enumerate(ixs):
            out[i] = red[offs[k] : offs[k + 1]].reshape(leaves[i].shape)
    for i in small_ix:
        out[i] = psum_safe(leaves[i], axis_name)
    return jax.tree_util.tree_unflatten(treedef, out), flag
