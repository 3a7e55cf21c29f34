"""Split-send P2P pipeline (paper §3.2, Fig. 4d) on TPU collective-permute.

The paper's observation: after the cheap split stage, the lo plane (sign +
mantissa — half of a bf16 tensor, 3/4 of f32) is *final* and can hit the
wire immediately, overlapping with the compute-heavy exponent encode.

On TPU the same overlap is obtained structurally: the lo-plane
``collective_permute`` has **no data dependence** on the exponent-encode
ops, so XLA's latency-hiding scheduler issues it while the VPU packs
exponents.  The naive *encode-send* baseline (paper Fig. 4a) is expressed
with an ``optimization_barrier`` that forces the lo transfer to wait for
the full encode — exactly the serialization the paper ascribes to naive
designs.  The *chunked pipeline* baseline (Fig. 4b/c) splits the tensor
into C chunks, each fully encoded then sent, chained with barriers.

All three return bit-identical tensors; they differ only in the lowered
schedule (benchmarks/fig15_strategies.py derives the overlap windows, and
tests assert the HLO dependence structure).

Reducing receivers (``reduce_into=``): when the consumer immediately
accumulates the received tensor (gradient accumulation across pipeline
stages), ``split_send`` streams the wire through the fused decode+reduce
pass instead of the pure bit-merge decode — the P2P analogue of the
two-shot's modified CopyReducePacks (paper §3.4).

Plan-driven replay (paper §3.3 extended to P2P): everything ``p2p_send``
decides per call — the policy gate, codec width, chunk grid, fused
knobs — can be compiled ONCE into a kind-"p2p" ``CommPlan``
(``sched/compile.compile_p2p_plan``) and replayed by
``sched.p2p_send_with_plan`` through the same ``p2p_dispatch`` seam, so
the plan-driven path is bit-identical to the planless one by
construction.  Kind-"kv" plans replay the same strategies bucket-wise for
KV-cache pytrees (``serve/kv_transfer.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec, packing
from repro.core.compressed_collectives import (
    _decode_chunks,
    _decode_reduce_chunks,
    _encode_chunks,
    _pad_flat,
    encode_hbm_bytes_for,
)
from repro.core.policy import (CompressionPolicy, WireReport,
                               record_wire_report)


def _record_p2p(name: str, axis_name, *, n_elems: int, dtype,
                lo_planes, exp_wire: dict, fused: bool = False,
                decoded_elems: int = 0, encode_fused: bool = False) -> None:
    """Trace-time WireReport for a P2P strategy.  When the receive is a
    pure decode (``decoded_elems=0``) there is no decoded-float round-trip
    to account; a reducing receiver (``reduce_into``) materializes the
    decoded floats between decode and add unless it runs fused.

    ``encode_fused`` mirrors that on the transmit side: ``split_send``
    always PAYS the split-plane round-trip (the early lo transfer requires
    the materialized split — that is the strategy), while ``encode_send``
    eliminates it with the one-pass fused encode."""
    itemsize = jnp.dtype(dtype).itemsize
    wire_bytes = int(lo_planes.size * 4) + sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in exp_wire.values())
    record_wire_report(WireReport(
        name=name, axis=str(axis_name),
        raw_bytes=int(n_elems) * itemsize,
        wire_bytes=wire_bytes, fused=fused,
        decode_hbm_bytes=int(8 * decoded_elems),
        encode_fused=encode_fused,
        encode_hbm_bytes=encode_hbm_bytes_for(n_elems, itemsize),
    ))


def _permute(a, axis_name, perm):
    return jax.lax.ppermute(a, axis_name, perm)


def split_send(
    x: jax.Array, axis_name, perm, *, width: int, block: int = 512,
    exc_frac: float = 0.02, reduce_into: jax.Array | None = None,
    use_fused: bool = True, use_pallas: bool | None = None,
):
    """Split-send pipeline: lo plane transfers while exponents encode.

    Returns (received tensor, overflow_flag).  Lossless: the received
    tensor is bit-identical to ``ppermute(x)``.  Replayed by kind-"p2p"/
    "kv" CommPlans (strategy "split_send") with identical arguments.

    ``reduce_into`` is the FUSED RECEIVER for reducing consumers (gradient
    accumulation across pipeline stages): instead of the pure bit-merge
    decode, the received wire streams through the fused decode+reduce pass
    (``_decode_reduce_chunks`` -> ``kernels/ops.decode_reduce``) straight
    into the caller's f32 accumulator — the P2P analogue of the two-shot's
    modified CopyReducePacks (paper §3.4), eliminating the decoded-float
    HBM round-trip of decode-then-add.  Returns
    (reduce_into + received, f32, shaped like x).  Bit-identical to the
    unfused decode-then-add (``use_fused=False``) — same accumulation op,
    same exception patch-up order."""
    lay = codec.layout_of(x.dtype)
    n = int(np.prod(x.shape))
    xf = _pad_flat(x.reshape(-1), block)
    exp, lo = codec.split_planes(xf)

    # Stage A (early transmission): the lo plane is final after the split —
    # pack to lo_bits and put it on the wire with NO dependence on stage B.
    lo_planes = packing.bitplane_pack(
        packing._pad_to(lo, packing.GROUP, "zero"), lay.lo_bits)
    lo_recv = _permute(lo_planes, axis_name, perm)

    # Stage B (overlapped): block-pack the exponent plane, then transfer.
    pk = packing.pack_exponents(exp, width=width, block=block, exc_frac=exc_frac)
    exp_wire = {
        "payload": pk.payload, "bases": pk.bases, "exc_idx": pk.exc_idx,
        "exc_raw": pk.exc_raw, "overflow": pk.overflow,
    }
    exp_recv = jax.tree.map(lambda a: _permute(a, axis_name, perm), exp_wire)
    fused = reduce_into is not None and use_fused
    _record_p2p("split_send", axis_name, n_elems=xf.shape[0], dtype=x.dtype,
                lo_planes=lo_planes, exp_wire=exp_wire, fused=fused,
                decoded_elems=xf.shape[0] if reduce_into is not None else 0)

    if fused:
        # Fused reducing receiver: one streaming pass over the wire into
        # the padded f32 accumulator (exceptions patched exactly inside).
        acc = _pad_flat(reduce_into.reshape(-1).astype(jnp.float32), block)
        wire = {
            "lo": lo_recv[None], "payload": exp_recv["payload"][None],
            "bases": exp_recv["bases"][None],
            "exc_idx": exp_recv["exc_idx"][None],
            "exc_raw": exp_recv["exc_raw"][None],
            "overflow": exp_recv["overflow"][None],
        }
        acc, flag = _decode_reduce_chunks(
            wire, dtype=x.dtype, n=xf.shape[0], width=width, block=block,
            acc=acc, use_pallas=use_pallas,
        )
        return acc[:n].reshape(x.shape), flag

    # Receiver: decode (the split's inverse is a pure bit-merge).
    rpk = packing.PackedPlane(
        payload=exp_recv["payload"], bases=exp_recv["bases"],
        exc_idx=exp_recv["exc_idx"], exc_raw=exp_recv["exc_raw"],
        overflow=exp_recv["overflow"], width=width, block=block,
        n=xf.shape[0], exp_bits=lay.exp_bits,
    )
    exp_out = packing.unpack_exponents(rpk)
    lo_out = packing.bitplane_unpack(lo_recv, lay.lo_bits,
                                     lay.uint_dtype)[: xf.shape[0]]
    out = codec.merge_planes(exp_out, lo_out, lay.dtype, (xf.shape[0],))
    if reduce_into is not None:  # unfused reducing receiver (A/B baseline)
        acc = reduce_into.reshape(-1).astype(jnp.float32)
        acc = acc + out[:n].astype(jnp.float32)
        return acc.reshape(x.shape), exp_recv["overflow"]
    return out[:n].reshape(x.shape), exp_recv["overflow"]


def encode_send(
    x: jax.Array, axis_name, perm, *, width: int, block: int = 512,
    exc_frac: float = 0.02, fused_encode: bool = True,
    use_pallas: bool | None = None,
):
    """Naive baseline (paper Fig. 4a): transmit only after FULL compression.

    Lossless (bit-identical to ``ppermute(x)``); replayed by kind-"p2p"/
    "kv" CommPlans (strategy "encode_send") with identical arguments.
    The ``optimization_barrier`` ties the lo-plane transfer to the encoded
    exponent payload, forcing the serialization the paper measures.  Since
    nothing ships early anyway, the encode itself routes through the fused
    one-pass split+pack (``kernels/ops.encode_fused``) by default — the
    serialization under study is transfer-vs-encode ordering, not the
    encode's internal HBM traffic.  ``fused_encode=False`` keeps the
    three-pass composition (bit-identical)."""
    lay = codec.layout_of(x.dtype)
    n = int(np.prod(x.shape))
    xf = _pad_flat(x.reshape(-1), block)
    if fused_encode:
        from repro.kernels import ops as kernel_ops

        w = kernel_ops.encode_fused(xf, width, block=block, exc_frac=exc_frac,
                                    use_pallas=use_pallas)
        lo_planes = w["lo"]
        wire = {
            "payload": w["payload"], "bases": w["bases"],
            "exc_idx": w["exc_idx"], "exc_raw": w["exc_raw"],
            "overflow": w["overflow"],
        }
    else:
        exp, lo = codec.split_planes(xf)
        lo_planes = packing.bitplane_pack(
            packing._pad_to(lo, packing.GROUP, "zero"), lay.lo_bits)
        pk = packing.pack_exponents(exp, width=width, block=block,
                                    exc_frac=exc_frac)
        wire = {
            "payload": pk.payload, "bases": pk.bases, "exc_idx": pk.exc_idx,
            "exc_raw": pk.exc_raw, "overflow": pk.overflow,
        }
    # serialize: nothing ships until the whole message is encoded
    lo_planes, payload = jax.lax.optimization_barrier(
        (lo_planes, wire["payload"]))
    wire = dict(wire, payload=payload)  # barriered payload ships
    lo_recv = _permute(lo_planes, axis_name, perm)
    recv = jax.tree.map(lambda a: _permute(a, axis_name, perm), wire)
    _record_p2p("encode_send", axis_name, n_elems=xf.shape[0], dtype=x.dtype,
                lo_planes=lo_planes, exp_wire=wire, encode_fused=fused_encode)
    rpk = packing.PackedPlane(
        payload=recv["payload"], bases=recv["bases"], exc_idx=recv["exc_idx"],
        exc_raw=recv["exc_raw"], overflow=recv["overflow"], width=width,
        block=block, n=xf.shape[0], exp_bits=lay.exp_bits,
    )
    exp_out = packing.unpack_exponents(rpk)
    lo_out = packing.bitplane_unpack(lo_recv, lay.lo_bits,
                                     lay.uint_dtype)[: xf.shape[0]]
    out = codec.merge_planes(exp_out, lo_out, lay.dtype, (xf.shape[0],))
    return out[:n].reshape(x.shape), recv["overflow"]


def chunked_pipeline_send(
    x: jax.Array, axis_name, perm, *, width: int, chunks: int = 4,
    block: int = 512, exc_frac: float = 0.02, fused_encode: bool = True,
):
    """Chunk-based pipelining baseline (paper Fig. 4b/c): C chunks, each
    fully encoded then sent, chained so chunk k+1's encode waits on chunk
    k's send being issued.  Lossless (bit-identical to ``ppermute(x)``);
    replayed by kind-"p2p"/"kv" CommPlans (strategy "chunked").  The paper shows this LOSES on GPUs because
    compression latency is sub-linear in size (Property 1); on TPU the
    analogous cost is per-chunk kernel/collective overhead and worse
    VPU utilization at small block counts."""
    n = int(np.prod(x.shape))
    if n == 0:
        raise ValueError("chunked_pipeline_send: empty tensor")
    # degenerate-size guard: with n < chunks*block (or block-rounding of the
    # per-chunk length) the trailing chunks would be pure padding — an
    # encode+send of all-zero rows per chunk.  Derive the per-chunk length
    # first, then the effective chunk count, so every chunk carries data.
    ideal = -(-n // max(chunks, 1))  # ceil(n / chunks)
    per = -(-ideal // block) * block  # rounded up to a block multiple
    chunks = -(-n // per)
    xf = _pad_flat(x.reshape(-1), chunks * per)
    parts = xf.reshape(chunks, per)
    assert per * (chunks - 1) < n <= per * chunks, (x.shape, chunks, block)
    outs, flag = [], jnp.int32(0)
    token = None
    for k in range(chunks):
        part = parts[k]
        if token is not None:  # chain: serialize chunk pipeline stages
            part, _ = jax.lax.optimization_barrier((part, token))
        got, f = encode_send(
            part, axis_name, perm, width=width, block=block,
            exc_frac=exc_frac, fused_encode=fused_encode,
        )
        token = got
        outs.append(got)
        flag = jnp.maximum(flag, f)
    out = jnp.concatenate(outs)[:n].reshape(x.shape)
    return out, flag


def p2p_dispatch(
    x: jax.Array, axis_name, perm, *, compressed: bool, width: int,
    block: int = 512, exc_frac: float = 0.02,
    strategy: str = "split_send", reduce_into: jax.Array | None = None,
    fused: bool = True, encode_fused: bool = True,
    use_pallas: bool | None = None,
):
    """Decision-free P2P dispatch: route ``x`` through one strategy with
    every schedule choice (gate, width, fused knobs) supplied by the
    caller.

    BOTH entry points call this — ``p2p_send`` derives the arguments from
    a ``CompressionPolicy`` per call, ``sched/executor.p2p_send_with_plan``
    replays them from a compiled kind-"p2p"/"kv" ``CommPlan`` — so the
    plan-driven and planless paths are bit-identical by construction (the
    same primitives receive the same arguments).

    ``reduce_into``: reducing receiver — return ``reduce_into + received``
    in f32 instead of the received tensor (pipeline-stage gradient
    accumulation).  The split_send strategy fuses the add into the wire
    decode when ``fused``; other strategies and the raw path
    decode-then-add (bit-identical)."""
    if not compressed:
        from repro.core.compressed_collectives import raw_ppermute
        got = raw_ppermute(x, axis_name, perm)
        if reduce_into is not None:
            got = (reduce_into.reshape(-1).astype(jnp.float32)
                   + got.reshape(-1).astype(jnp.float32)).reshape(x.shape)
        return got, jnp.int32(0)
    kw = dict(width=width, block=block, exc_frac=exc_frac)
    if strategy == "split_send":
        return split_send(x, axis_name, perm, reduce_into=reduce_into,
                          use_fused=fused, use_pallas=use_pallas, **kw)
    kw["fused_encode"] = encode_fused
    if strategy == "encode_send":  # chunked takes no kernel-dispatch knob
        kw["use_pallas"] = use_pallas
    fn = {"encode_send": encode_send, "chunked": chunked_pipeline_send}[strategy]
    if reduce_into is None:
        return fn(x, axis_name, perm, **kw)
    # Reducing receiver on a pure-decode strategy: the decoded floats are
    # materialized between decode and add, so patch the strategy's own
    # WireReports (which assumed no reduction follows) to carry the PAID
    # decoded-HBM round-trip — keeps accounting comparable with split_send.
    import dataclasses
    from repro.core.policy import capture_wire_reports
    itemsize = jnp.dtype(x.dtype).itemsize
    with capture_wire_reports() as caught:
        got, flag = fn(x, axis_name, perm, **kw)
    for r in caught:
        record_wire_report(dataclasses.replace(
            r, fused=False, decode_hbm_bytes=8 * (r.raw_bytes // itemsize)))
    got = (reduce_into.reshape(-1).astype(jnp.float32)
           + got.reshape(-1).astype(jnp.float32)).reshape(x.shape)
    return got, flag


def delta_send(
    x: jax.Array, base: jax.Array, axis_name, perm, *, width: int,
    lo_width: int, block: int = 512, exc_frac: float = 0.02,
):
    """XOR-delta P2P send (weight sync, paper §5.3.1 extended): both ends
    hold ``base``; only the encoded delta crosses the wire.

    The sender XORs ``x`` against ``base`` and ships the delta through the
    split+pack wire (``packing.encode_delta``: exponent-delta plane on the
    standard block packer at ``width``, lo-delta plane width-packed at
    ``lo_width`` with element-exact exceptions); the receiver decodes and
    XORs against ITS copy of ``base`` — bit-identical to ``ppermute(x)``
    whenever the returned flag is 0.  A nonzero flag means the delta did
    not fit the calibrated widths (exception overflow): the caller must
    fall back to a full send (``sync/engine.py`` does this automatically;
    the version protocol guarantees both ends agree on ``base``).

    Replayed by kind-"wsync" CommPlans through the shared
    :func:`wsync_dispatch` seam with identical arguments."""
    n = int(np.prod(x.shape))
    # pad in the uint domain: float concat can quiet sNaN payloads, and the
    # delta wire's contract is exact down to NaN payload bits
    xf = codec.pad_flat_bits(x.reshape(-1), block)
    bf = codec.pad_flat_bits(base.reshape(-1).astype(x.dtype), block)
    m = packing.encode_delta(xf, bf, width=width, lo_width=lo_width,
                             block=block, exc_frac=exc_frac)
    recv = jax.tree.map(lambda a: _permute(a, axis_name, perm), m)
    itemsize = jnp.dtype(x.dtype).itemsize
    # the delta encode is the three-pass split-then-pack composition: the
    # split-plane HBM round-trip is paid (encode_fused=False); the receive
    # is a pure decode (no reduction follows), so decoded-HBM is 0.
    record_wire_report(WireReport(
        name="delta_send", axis=str(axis_name),
        raw_bytes=int(xf.shape[0]) * itemsize,
        wire_bytes=m.wire_bytes(),
        encode_hbm_bytes=encode_hbm_bytes_for(xf.shape[0], itemsize),
    ))
    out = packing.decode_delta(recv, bf)
    flag = recv.overflow
    return codec.slice_bits(out, 0, n).reshape(x.shape), flag


def wsync_dispatch(
    x: jax.Array, base, axis_name, perm, *, compressed: bool,
    width: int, delta_width: int, delta_lo_width: int, block: int = 512,
    exc_frac: float = 0.02, strategy: str = "split_send",
    fused: bool = True, encode_fused: bool = True,
    use_pallas: bool | None = None,
):
    """Decision-free weight-sync dispatch: one bucket, every schedule
    choice supplied by the caller (the wsync analogue of
    :func:`p2p_dispatch`, and the shared seam that makes plan-driven and
    planless sync bit-identical by construction).

    Routing: a compressed bucket WITH a base version rides
    :func:`delta_send` at the recorded delta widths; everything else —
    full sends (no base: first contact, stale ack, epoch fence) and
    policy-gated raw buckets — funnels through :func:`p2p_dispatch`
    unchanged."""
    if compressed and base is not None and delta_width:
        return delta_send(x, base, axis_name, perm, width=delta_width,
                          lo_width=delta_lo_width, block=block,
                          exc_frac=exc_frac)
    return p2p_dispatch(
        x, axis_name, perm, compressed=compressed, width=width, block=block,
        exc_frac=exc_frac, strategy=strategy, fused=fused,
        encode_fused=encode_fused, use_pallas=use_pallas)


def p2p_send(
    x: jax.Array, axis_name, perm, *, policy: CompressionPolicy,
    tensor_class: str = "weight", strategy: str = "split_send",
    reduce_into: jax.Array | None = None, plan=None,
):
    """Policy-gated P2P entry point (RL weight sync, KV-cache transfer).

    The planless reference: gate, width and fused knobs are re-derived
    from ``policy`` on every call, then dispatched via ``p2p_dispatch``.
    Passing a compiled kind-"p2p" ``CommPlan`` (``plan=``) replays the
    recorded schedule instead (``sched/executor.execute_p2p``) —
    bit-identical to the planless path for the policy the plan was
    compiled from, since both routes call ``p2p_dispatch`` with the same
    arguments.  Callers with a stable send signature should prefer
    ``sched.p2p_send_with_plan``, which adds the keyed plan cache.

    ``reduce_into``: reducing receiver — return ``reduce_into + received``
    in f32 instead of the received tensor (pipeline-stage gradient
    accumulation).  The split_send strategy fuses the add into the wire
    decode (``policy.fused_decode_reduce``); other strategies and the raw
    path decode-then-add (bit-identical)."""
    if plan is not None:
        from repro.sched.executor import execute_p2p
        return execute_p2p(plan, x, axis_name, perm, reduce_into=reduce_into)
    return p2p_dispatch(
        x, axis_name, perm,
        compressed=policy.should_compress(x, axis_name,
                                          tensor_class=tensor_class),
        width=policy.width_for(tensor_class), block=policy.profile.block,
        exc_frac=policy.profile.exc_frac, strategy=strategy,
        reduce_into=reduce_into, fused=policy.fused_decode_reduce,
        encode_fused=policy.fused_encode)
