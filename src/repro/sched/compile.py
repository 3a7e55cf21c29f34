"""CommPlan compiler: pytree spec + CompressionPolicy + axis -> schedule.

Everything ``tree_psum_compressed`` / ``zero1_step`` / the FSDP gathers /
``p2p_send`` / ``transfer_cache`` decide per call — dtype bucketing,
compress-vs-raw gating, widths, chunk grids, fused receive, backend
dispatch — is decided HERE, once, from abstract shapes.  The executor then
replays the recorded schedule against the existing collective / P2P
primitives, so plan-driven and planless paths are bit-identical by
construction (same primitives, same arguments, same order).

``PLAN_KINDS`` (bottom of this module) is the authoritative registry of
every plan kind and its compiler; ``docs/ARCHITECTURE.md`` documents the
same table and a tier-1 test cross-checks the two.

Expected wire bytes are derived by ``jax.eval_shape`` over the real
encoder (``_encode_chunks``): the wire format's static shape arithmetic is
reused rather than duplicated, so plan accounting always matches what the
collectives' WireReports record.

Width selection defaults to the policy profile (bit-parity with the
planless paths).  When live data is supplied (``sample=``), the compiler
runs the compressibility probe instead: ``calibrate.choose_width`` per
bucket, recording the estimated escape rate / ratio / entropy floor in
``BucketPlan.probe`` — the paper's offline-calibration story (§3.4, Fig.
12 stability) folded into plan compilation.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import calibrate, codec
from repro.core import compressed_collectives as cc
from repro.sched.plan import (BROADCAST_KINDS, BROADCAST_PIPELINE,
                              BROADCAST_STAR, BROADCAST_TREE,
                              PATH_COMPRESSED, PATH_RAW, PATH_RAW_PSUM,
                              PATH_RAW_TWOSHOT, PATH_RING, PATH_TWO_SHOT,
                              BroadcastSchedule, BucketPlan, CommPlan,
                              PhasePair, policy_fingerprint, tree_signature)


def axis_tuple(axis_name) -> tuple:
    return tuple(axis_name) if isinstance(axis_name, (tuple, list)) else (axis_name,)


def probe_backend() -> tuple:
    """(backend name, use_pallas) from the kernel-package probe."""
    from repro import kernels

    return kernels.backend(), kernels.default_use_pallas()


def _pad_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def encoded_wire_bytes(n_chunks: int, chunk: int, dtype, *, width: int,
                       block: int, exc_frac: float) -> int:
    """Static wire size of encoding (n_chunks, chunk) at the given width —
    eval_shape over the real encoder, so this IS the wire format's size."""
    wire = jax.eval_shape(
        partial(cc._encode_chunks, width=width, block=block, exc_frac=exc_frac),
        jax.ShapeDtypeStruct((n_chunks, chunk), jnp.dtype(dtype)),
    )
    return cc.wire_nbytes(wire)


def _group_leaves(leaves):
    """tree_psum_compressed's bucketing: codec-supported dtypes bucket per
    dtype name; everything else syncs raw."""
    groups: dict = {}
    raw_ix = []
    for i, l in enumerate(leaves):
        if hasattr(l, "dtype") and jnp.dtype(l.dtype).name in codec.LAYOUTS:
            groups.setdefault(jnp.dtype(l.dtype).name, []).append(
                (i, tuple(l.shape), int(np.prod(l.shape))))
        else:
            raw_ix.append(i)
    return groups, tuple(raw_ix)


def _probe_bucket(sample_parts, block: int):
    """Compressibility probe on live bucket data -> (width_choice or None)."""
    if sample_parts is None:
        return None
    flat = (jnp.concatenate(sample_parts) if len(sample_parts) > 1
            else sample_parts[0])
    return calibrate.choose_width(flat, block=block)


def compile_psum_plan(tree, axis_name, *, policy, tensor_class: str = "gradient",
                      n_dev: int, sample=None, key: tuple = None) -> CommPlan:
    """Compile the two-shot pytree all-reduce schedule.

    Mirrors ``tree_psum_compressed`` + ``psum_compressed`` dispatch exactly;
    ``tree`` may hold arrays or ShapeDtypeStructs (gating uses shapes/dtypes
    only).  ``sample`` (optional, concrete arrays) switches width selection
    to the calibrate probe."""
    backend, use_pallas = probe_backend()
    axis = axis_tuple(axis_name)
    leaves, _ = jax.tree_util.tree_flatten(tree)
    sample_leaves = (jax.tree_util.tree_leaves(sample)
                    if sample is not None else None)
    groups, raw_ix = _group_leaves(leaves)
    buckets = []
    for name in sorted(groups):
        members = tuple(groups[name])
        L = sum(m[2] for m in members)
        dt = codec.LAYOUTS[name].dtype
        itemsize = jnp.dtype(dt).itemsize
        struct = jax.ShapeDtypeStruct((L,), dt)
        base = dict(dtype_name=name, members=members, length=L, n_dev=n_dev)
        if not policy.should_compress(struct, axis_name, tensor_class=tensor_class):
            path = (PATH_RAW_TWOSHOT if L * itemsize >= policy.min_bytes
                    else PATH_RAW_PSUM)
            buckets.append(BucketPlan(path=path, raw_bytes=L * itemsize, **base))
            continue
        width = policy.width_for(tensor_class)
        block = policy.profile.block
        exc = policy.profile.exc_frac
        probe = None
        if sample_leaves is not None:
            choice = _probe_bucket([sample_leaves[i].reshape(-1)
                                    for i, _, _ in members], block)
            width = choice.width
            probe = (choice.est_exc_rate, choice.est_ratio, choice.entropy_bits)
        padded = _pad_up(L, n_dev * block)
        chunk = padded // n_dev
        if policy.allreduce_algorithm == "ring":
            hop = encoded_wire_bytes(1, chunk, dt, width=width, block=block,
                                     exc_frac=exc)
            buckets.append(BucketPlan(
                path=PATH_RING, width=width, block=block, exc_frac=exc,
                fused=policy.fused_decode_reduce,
                encode_fused=policy.fused_encode, chunk=chunk,
                wire_bytes=2 * (n_dev - 1) * hop,
                raw_bytes=2 * (n_dev - 1) * chunk * itemsize,
                probe=probe, **base))
            continue
        ag_width = min(width + policy.profile.ag_extra_bits, 8)
        rs_wire = encoded_wire_bytes(n_dev, chunk, dt, width=width,
                                     block=block, exc_frac=exc)
        ag_wire = n_dev * encoded_wire_bytes(1, chunk, dt, width=ag_width,
                                             block=block, exc_frac=exc)
        buckets.append(BucketPlan(
            path=PATH_TWO_SHOT, width=width, ag_width=ag_width, block=block,
            exc_frac=exc, fused=policy.fused_decode_reduce,
            encode_fused=policy.fused_encode, chunk=chunk,
            wire_bytes=rs_wire + ag_wire,
            raw_bytes=(padded + n_dev * chunk) * itemsize,
            probe=probe, **base))
    if key is None:
        key = psum_plan_key(tree, axis_name, policy, tensor_class, n_dev)
    return CommPlan(key=key, kind="psum", axis=axis, n_dev=n_dev,
                    backend=backend, use_pallas=use_pallas,
                    buckets=tuple(buckets), raw_leaf_ix=raw_ix,
                    n_leaves=len(leaves))


def psum_plan_key(tree, axis_name, policy, tensor_class: str, n_dev: int) -> tuple:
    # probe_backend() is part of EVERY plan key: a cached plan must never
    # replay stale kernel dispatch after the probe changes (REPRO_USE_PALLAS
    # flip + probe_cache_clear) — same invariant as policy_fingerprint.
    return ("psum", tree_signature(tree), axis_tuple(axis_name), int(n_dev),
            policy_fingerprint(policy, tensor_class), probe_backend())


def reduce_scatter_plan_key(length: int, dtype_name: str, axis_name, policy,
                            tensor_class: str, n_dev: int) -> tuple:
    return ("reduce_scatter", (int(length), str(dtype_name)),
            axis_tuple(axis_name), int(n_dev),
            policy_fingerprint(policy, tensor_class), probe_backend())


def all_gather_plan_key(length: int, dtype_name: str, axis_name, policy,
                        tensor_class: str, n_dev: int) -> tuple:
    return ("all_gather", (int(length), str(dtype_name)),
            axis_tuple(axis_name), int(n_dev),
            policy_fingerprint(policy, tensor_class), probe_backend())


# ---------------------------------------------------------------------------
# flat single-phase plans (ZeRO-1's RS/AG gating rule: global bucket bytes)
# ---------------------------------------------------------------------------

def compile_reduce_scatter_plan(length: int, dtype_name: str, axis_name, *,
                                policy, n_dev: int,
                                tensor_class: str = "gradient",
                                key: tuple = None) -> CommPlan:
    """Flat reduce-scatter schedule for a local bucket of ``length`` elems.

    Gate: compressed iff the policy is enabled and the GLOBAL bytes (local
    bucket × n_dev) clear ``min_bytes`` — the ZeRO-1 rule (the paper's 1 MB
    threshold applied to the whole wire, not the per-device slice)."""
    backend, use_pallas = probe_backend()
    axis = axis_tuple(axis_name)
    dt = codec.LAYOUTS[dtype_name].dtype
    itemsize = jnp.dtype(dt).itemsize
    members = ((0, (length,), length),)
    if key is None:
        key = reduce_scatter_plan_key(length, dtype_name, axis_name, policy,
                                      tensor_class, n_dev)
    if not (policy.enabled and length * itemsize * n_dev >= policy.min_bytes):
        bucket = BucketPlan(dtype_name=dtype_name, members=members,
                            length=length, path=PATH_RAW, n_dev=n_dev,
                            raw_bytes=length * itemsize)
    else:
        width = policy.width_for(tensor_class)
        block = policy.profile.block
        padded = _pad_up(length, n_dev * block)
        chunk = padded // n_dev
        bucket = BucketPlan(
            dtype_name=dtype_name, members=members, length=length,
            path=PATH_COMPRESSED, width=width, block=block,
            exc_frac=policy.profile.exc_frac,
            fused=policy.fused_decode_reduce,
            encode_fused=policy.fused_encode, n_dev=n_dev, chunk=chunk,
            wire_bytes=encoded_wire_bytes(
                n_dev, chunk, dt, width=width, block=block,
                exc_frac=policy.profile.exc_frac),
            raw_bytes=padded * itemsize)
    return CommPlan(key=key, kind="reduce_scatter", axis=axis, n_dev=n_dev,
                    backend=backend, use_pallas=use_pallas,
                    buckets=(bucket,), n_leaves=1)


def compile_all_gather_plan(length: int, dtype_name: str, axis_name, *,
                            policy, n_dev: int, tensor_class: str = "weight",
                            key: tuple = None) -> CommPlan:
    """Flat all-gather schedule for a local shard of ``length`` elements
    (ZeRO-1's AG phase: weight-class width + ag_extra_bits headroom)."""
    backend, use_pallas = probe_backend()
    axis = axis_tuple(axis_name)
    dt = codec.LAYOUTS[dtype_name].dtype
    itemsize = jnp.dtype(dt).itemsize
    members = ((0, (length,), length),)
    if key is None:
        key = all_gather_plan_key(length, dtype_name, axis_name, policy,
                                  tensor_class, n_dev)
    if not (policy.enabled and length * itemsize * n_dev >= policy.min_bytes):
        bucket = BucketPlan(dtype_name=dtype_name, members=members,
                            length=length, path=PATH_RAW, n_dev=n_dev,
                            fused=False, raw_bytes=n_dev * length * itemsize)
    else:
        width = min(policy.width_for(tensor_class)
                    + policy.profile.ag_extra_bits, 8)
        block = policy.profile.block
        padded = _pad_up(length, block)
        bucket = BucketPlan(
            dtype_name=dtype_name, members=members, length=length,
            path=PATH_COMPRESSED, width=width, block=block,
            exc_frac=policy.profile.exc_frac, fused=False,
            encode_fused=policy.fused_encode, n_dev=n_dev,
            chunk=padded,
            wire_bytes=n_dev * encoded_wire_bytes(
                1, padded, dt, width=width, block=block,
                exc_frac=policy.profile.exc_frac),
            raw_bytes=n_dev * padded * itemsize)
    return CommPlan(key=key, kind="all_gather", axis=axis, n_dev=n_dev,
                    backend=backend, use_pallas=use_pallas,
                    buckets=(bucket,), n_leaves=1)


# ---------------------------------------------------------------------------
# ZeRO-1: per-dtype RS/AG phase pairs around the optimizer update
# ---------------------------------------------------------------------------

def compile_zero1_plan(meta, *, policy, axis_name, n_dev: int,
                       key: tuple = None) -> CommPlan:
    """Compile the ZeRO-1 sync schedule from a ``BucketMeta``.

    One PhasePair per dtype bucket: the RS phase carries gradient-class
    packed planes, the AG phase weight-class planes (paper Table 1's
    distinct calibrated widths).  Gating matches ``zero1_step``'s planless
    rules bit-for-bit."""
    backend, use_pallas = probe_backend()
    axis = axis_tuple(axis_name)
    if key is None:
        key = zero1_plan_key(meta, axis_name, policy, n_dev)
    pairs = []
    for name, members, Lp, sl in zip(meta.dtype_names, meta.members,
                                     meta.padded, meta.shard_lens):
        rs = compile_reduce_scatter_plan(
            Lp, name, axis_name, policy=policy, n_dev=n_dev,
            tensor_class="gradient", key=key + ("rs", name)).buckets[0]
        rs = _with_members(rs, members)
        ag = compile_all_gather_plan(
            sl, name, axis_name, policy=policy, n_dev=n_dev,
            tensor_class="weight", key=key + ("ag", name)).buckets[0]
        pairs.append(PhasePair(rs=rs, ag=ag))
    return CommPlan(key=key, kind="zero1", axis=axis, n_dev=n_dev,
                    backend=backend, use_pallas=use_pallas,
                    buckets=tuple(pairs), n_leaves=sum(
                        len(m) for m in meta.members))


def zero1_plan_key(meta, axis_name, policy, n_dev: int) -> tuple:
    return ("zero1", meta.dtype_names, meta.padded, meta.shard_lens,
            meta.block, axis_tuple(axis_name), int(n_dev),
            policy_fingerprint(policy), probe_backend())


def _with_members(bucket: BucketPlan, members) -> BucketPlan:
    import dataclasses

    return dataclasses.replace(bucket, members=tuple(members))


# ---------------------------------------------------------------------------
# FSDP gather: custom-vjp weight AG (forward) + gradient RS (backward)
# ---------------------------------------------------------------------------

def compile_fsdp_gather_plan(local_shape: tuple, dtype_name: str, axis_name,
                             *, policy, n_dev: int,
                             key: tuple = None) -> CommPlan:
    """Schedule for one FSDP leaf gather.  ``width`` is the backward
    (gradient-class reduce-scatter) width, ``ag_width`` the forward
    (weight-class all-gather) width — ``optim/fsdp._make_gather``'s
    (w_bwd, w_fwd) in plan-IR terms.  Sharded-vs-replicated is the train
    step's plan (``plan_fsdp_tree``); this plan only schedules the wire."""
    backend, use_pallas = probe_backend()
    axis = axis_tuple(axis_name)
    length = int(np.prod(local_shape))
    dt = jnp.dtype(dtype_name)
    itemsize = dt.itemsize
    block = policy.profile.block
    if key is None:
        key = fsdp_gather_plan_key(local_shape, dtype_name, axis_name,
                                   policy, n_dev)
    members = ((0, tuple(local_shape), length),)
    if not policy.enabled:
        bucket = BucketPlan(dtype_name=dtype_name, members=members,
                            length=length, path=PATH_RAW, width=8, ag_width=8,
                            fused=False, n_dev=n_dev,
                            raw_bytes=(n_dev + 1) * length * itemsize)
    else:
        w_bwd = policy.width_for("gradient")
        w_fwd = policy.width_for("weight")
        ag_len = _pad_up(length, block)
        rs_chunk = _pad_up(length, block)  # per-destination row, block-padded
        bucket = BucketPlan(
            dtype_name=dtype_name, members=members, length=length,
            path=PATH_COMPRESSED, width=w_bwd, ag_width=w_fwd, block=block,
            exc_frac=policy.profile.exc_frac,
            fused=policy.fused_decode_reduce,
            encode_fused=policy.fused_encode, n_dev=n_dev, chunk=rs_chunk,
            wire_bytes=(n_dev * encoded_wire_bytes(
                1, ag_len, dt, width=w_fwd, block=block,
                exc_frac=policy.profile.exc_frac)
                + encoded_wire_bytes(
                    n_dev, rs_chunk, dt, width=w_bwd, block=block,
                    exc_frac=policy.profile.exc_frac)),
            raw_bytes=(n_dev * ag_len + n_dev * rs_chunk) * itemsize)
    return CommPlan(key=key, kind="fsdp_gather", axis=axis, n_dev=n_dev,
                    backend=backend, use_pallas=use_pallas,
                    buckets=(bucket,), n_leaves=1)


def fsdp_gather_plan_key(local_shape, dtype_name, axis_name, policy,
                         n_dev: int) -> tuple:
    return ("fsdp_gather", tuple(local_shape), str(dtype_name),
            axis_tuple(axis_name), int(n_dev), policy_fingerprint(policy),
            probe_backend())


# ---------------------------------------------------------------------------
# P2P: the split-send pipeline compiled into the IR (paper §3.2) — what
# ``p2p_send`` re-decides per call (gate, width, chunking, fused flags)
# recorded once per (shape, dtype, strategy, policy) signature
# ---------------------------------------------------------------------------

P2P_STRATEGIES = ("split_send", "encode_send", "chunked")
_P2P_PIPELINE_CHUNKS = 4  # chunked_pipeline_send's default chunk count


def p2p_wire_bytes(n_padded: int, dtype, *, width: int, block: int,
                   exc_frac: float) -> int:
    """Static wire size of ONE P2P message of ``n_padded`` (block-padded)
    elements: eval_shape over the real split+pack composition, so this IS
    the wire the strategies ship (packed lo plane + exponent wire incl.
    the overflow scalar — exactly what ``split_send._record_p2p`` sums)."""
    from repro.core import packing

    lay = codec.layout_of(dtype)

    def enc(xf):
        exp, lo = codec.split_planes(xf)
        lo_planes = packing.bitplane_pack(
            packing._pad_to(lo, packing.GROUP, "zero"), lay.lo_bits)
        pk = packing.pack_exponents(exp, width=width, block=block,
                                    exc_frac=exc_frac)
        return {"lo": lo_planes, "payload": pk.payload, "bases": pk.bases,
                "exc_idx": pk.exc_idx, "exc_raw": pk.exc_raw,
                "overflow": pk.overflow}

    wire = jax.eval_shape(enc,
                          jax.ShapeDtypeStruct((n_padded,), jnp.dtype(dtype)))
    return sum(int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
               for v in wire.values())


def _p2p_bucket(length: int, dtype_name: str, axis_name, *, policy,
                n_dev: int, tensor_class: str, strategy: str) -> BucketPlan:
    """One flat P2P message's schedule: ``p2p_send``'s gate + width choice
    + the strategy's chunk grid, recorded as a BucketPlan.  ``chunk`` is
    the block-padded length of one send ("chunked": one pipeline chunk)."""
    # gate BEFORE any layout lookup: codec-unsupported dtypes (int32, f64)
    # must compile to the raw path exactly like p2p_send routes them
    dt = jnp.dtype(dtype_name)
    itemsize = dt.itemsize
    members = ((0, (length,), length),)
    struct = jax.ShapeDtypeStruct((length,), dt)
    base = dict(dtype_name=dtype_name, members=members, length=length,
                n_dev=n_dev)
    if not policy.should_compress(struct, axis_name,
                                  tensor_class=tensor_class):
        return BucketPlan(path=PATH_RAW, raw_bytes=length * itemsize, **base)
    dt = codec.LAYOUTS[dtype_name].dtype
    width = policy.width_for(tensor_class)
    block = policy.profile.block
    exc = policy.profile.exc_frac
    # split_send ALWAYS pays the split-plane round-trip (the early lo-plane
    # transfer requires the materialized split); the other strategies fuse
    # the encode per the policy knob.
    encode_fused = policy.fused_encode and strategy != "split_send"
    if strategy == "chunked":
        # chunked_pipeline_send's degenerate-chunk guard: derive the
        # per-chunk length first, then the effective chunk count.
        ideal = -(-length // _P2P_PIPELINE_CHUNKS)
        per = _pad_up(ideal, block)
        n_chunks = -(-length // per)
        wire = n_chunks * p2p_wire_bytes(per, dt, width=width, block=block,
                                         exc_frac=exc)
        return BucketPlan(path=PATH_COMPRESSED, width=width, block=block,
                          exc_frac=exc, fused=policy.fused_decode_reduce,
                          encode_fused=encode_fused, chunk=per,
                          wire_bytes=wire,
                          raw_bytes=n_chunks * per * itemsize, **base)
    padded = _pad_up(length, block)
    return BucketPlan(path=PATH_COMPRESSED, width=width, block=block,
                      exc_frac=exc, fused=policy.fused_decode_reduce,
                      encode_fused=encode_fused, chunk=padded,
                      wire_bytes=p2p_wire_bytes(padded, dt, width=width,
                                                block=block, exc_frac=exc),
                      raw_bytes=padded * itemsize, **base)


def compile_p2p_plan(x, axis_name, *, policy, n_dev: int,
                     tensor_class: str = "weight",
                     strategy: str = "split_send",
                     key: tuple = None) -> CommPlan:
    """Compile the schedule of one P2P send (kind "p2p").

    Mirrors ``core/split_send.p2p_send``'s dispatch bit-for-bit: the same
    policy gate, width, block and fused knobs, decided once from the
    abstract (shape, dtype) instead of per call.  ``x`` may be an array or
    a ShapeDtypeStruct.  The executor replays it through the identical
    strategy primitives (``sched/executor.p2p_send_with_plan``)."""
    if strategy not in P2P_STRATEGIES:
        raise ValueError(f"unknown P2P strategy {strategy!r}")
    backend, use_pallas = probe_backend()
    axis = axis_tuple(axis_name)
    shape = tuple(x.shape)
    dtype_name = jnp.dtype(x.dtype).name
    length = int(np.prod(shape))
    if key is None:
        key = p2p_plan_key(shape, dtype_name, axis_name, policy,
                           tensor_class, strategy, n_dev)
    bucket = _p2p_bucket(length, dtype_name, axis_name, policy=policy,
                         n_dev=n_dev, tensor_class=tensor_class,
                         strategy=strategy)
    bucket = _with_members(bucket, ((0, shape, length),))
    return CommPlan(key=key, kind="p2p", axis=axis, n_dev=n_dev,
                    backend=backend, use_pallas=use_pallas,
                    buckets=(bucket,), n_leaves=1, strategy=strategy)


def p2p_plan_key(shape, dtype_name, axis_name, policy, tensor_class: str,
                 strategy: str, n_dev: int) -> tuple:
    return ("p2p", (tuple(shape), str(dtype_name)), str(strategy),
            axis_tuple(axis_name), int(n_dev),
            policy_fingerprint(policy, tensor_class), probe_backend())


# ---------------------------------------------------------------------------
# serve KV: the cache-pytree shipment compiled into the IR (paper §5.3.2) —
# per-dtype bucket plans from serve/kv_transfer's leaf bucketing
# ---------------------------------------------------------------------------

def compile_kv_plan(cache, axis_name, *, policy, n_dev: int,
                    strategy: str = "split_send",
                    key: tuple = None) -> CommPlan:
    """Compile a KV-cache transfer schedule (kind "kv").

    Mirrors ``serve/kv_transfer.transfer_cache`` bit-for-bit: leaves are
    split with its ``_bucket_leaves`` rule, compressible leaves fuse into
    one flat message per dtype (in first-seen leaf order — the planless
    grouping order), each gated/sized like a ``p2p_send`` of the
    concatenated bucket at tensor_class "activation".  ``cache`` may hold
    arrays or ShapeDtypeStructs.  The executor replays it through the
    identical wire primitives (``sched/executor.transfer_cache_with_plan``);
    a decode loop with a signature-stable cache hits the plan cache on
    every transfer after the first."""
    from repro.serve.kv_transfer import _bucket_leaves

    if strategy not in P2P_STRATEGIES:
        raise ValueError(f"unknown P2P strategy {strategy!r}")
    backend, use_pallas = probe_backend()
    axis = axis_tuple(axis_name)
    leaves, comp, raw = _bucket_leaves(cache)
    groups: dict = {}
    for i in comp:
        groups.setdefault(jnp.dtype(leaves[i].dtype).name, []).append(i)
    buckets = []
    for name, idxs in groups.items():
        members = tuple((i, tuple(leaves[i].shape),
                         int(np.prod(leaves[i].shape))) for i in idxs)
        L = sum(m[2] for m in members)
        bucket = _p2p_bucket(L, name, axis_name, policy=policy, n_dev=n_dev,
                             tensor_class="activation", strategy=strategy)
        buckets.append(_with_members(bucket, members))
    if key is None:
        key = kv_plan_key(cache, axis_name, policy, strategy, n_dev)
    return CommPlan(key=key, kind="kv", axis=axis, n_dev=n_dev,
                    backend=backend, use_pallas=use_pallas,
                    buckets=tuple(buckets), raw_leaf_ix=tuple(raw),
                    n_leaves=len(leaves), strategy=strategy)


def kv_plan_key(cache, axis_name, policy, strategy: str, n_dev: int) -> tuple:
    return ("kv", tree_signature(cache), str(strategy),
            axis_tuple(axis_name), int(n_dev),
            policy_fingerprint(policy, "activation"), probe_backend())


# ---------------------------------------------------------------------------
# weight sync: the versioned trainer->replica broadcast compiled into the IR
# (paper §5.3.1, the RL weight-sync workload) — per-dtype leaf buckets with
# XOR-delta-vs-full gating and both wires' widths/bytes recorded per bucket
# ---------------------------------------------------------------------------

def delta_wire_bytes(n_padded: int, dtype, *, width: int, lo_width: int,
                     block: int, exc_frac: float) -> int:
    """Static wire size of ONE XOR-delta message of ``n_padded``
    (block-padded) elements: eval_shape over the real delta encoder
    (``packing.encode_delta``), so this IS the wire ``delta_send`` ships."""
    from repro.core import packing

    struct = jax.ShapeDtypeStruct((n_padded,), jnp.dtype(dtype))
    m = jax.eval_shape(
        partial(packing.encode_delta, width=width, lo_width=lo_width,
                block=block, exc_frac=exc_frac),
        struct, struct)
    return sum(int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
               for v in jax.tree_util.tree_leaves(m))


def compile_broadcast_schedule(n_receivers: int, *, kind: str = BROADCAST_TREE,
                               fanout: int = 2) -> BroadcastSchedule:
    """Normalize (fleet size, requested kind, requested fan-out) into the
    frozen :class:`BroadcastSchedule` record a wsync plan carries.

    The effective fan-out is what makes all three kinds one arithmetic
    family: ``star`` widens to ``n_receivers`` (every receiver a direct
    trainer child), ``pipeline`` narrows to 1 (a forwarding chain), and
    ``tree`` keeps the requested ``fanout`` (clamped to the fleet —
    a 3-replica fleet at fanout 8 IS a star-shaped tree)."""
    if kind not in BROADCAST_KINDS:
        raise ValueError(f"unknown broadcast kind {kind!r}; expected one "
                         f"of {BROADCAST_KINDS}")
    if fanout < 1:
        raise ValueError(f"fanout must be >= 1, got {fanout}")
    n = int(n_receivers)
    if kind == BROADCAST_STAR:
        eff = max(n, 1)
    elif kind == BROADCAST_PIPELINE:
        eff = 1
    else:
        eff = min(int(fanout), max(n, 1))
    return BroadcastSchedule(kind=kind, fanout=eff, n_receivers=n)


def compile_wsync_plan(tree, axis_name, *, policy, n_dev: int,
                       strategy: str = "split_send",
                       broadcast: str = None, fanout: int = 2,
                       n_receivers: int = 0,
                       key: tuple = None) -> CommPlan:
    """Compile a weight-sync broadcast schedule (kind "wsync").

    Mirrors ``sync/wire.sync_weights`` bit-for-bit: codec-supported leaves
    fuse into one flat bucket per dtype (``_group_leaves``, the psum rule),
    each gated/width'd like a ``p2p_send`` of the concatenated bucket at
    tensor_class "weight", PLUS the XOR-delta schedule — the delta codec
    widths (``policy.delta_widths``) and the expected delta wire bytes —
    recorded per compressed bucket.  Delta-vs-full is a RUNTIME choice per
    receiver (does the receiver hold an acked, epoch-current base
    version?); the plan records the schedule of BOTH paths so neither
    re-derives anything.  ``tree`` may hold arrays or ShapeDtypeStructs.
    The executor replays it through ``split_send.wsync_dispatch``
    (``sched/executor.sync_weights_with_plan``).

    ``broadcast``/``fanout``/``n_receivers`` compile the host fan-out
    topology into the plan (``CommPlan.broadcast``): who forwards the
    encoded wire to whom when the fleet broadcasts one publish to
    ``n_receivers`` same-base replicas.  ``broadcast=None`` (default)
    leaves the plan receiver-count-agnostic — the legacy star behaviour
    where the distributor sends every copy itself."""
    if strategy not in P2P_STRATEGIES:
        raise ValueError(f"unknown P2P strategy {strategy!r}")
    schedule = None
    if broadcast is not None:
        schedule = compile_broadcast_schedule(
            n_receivers, kind=broadcast, fanout=fanout)
    backend, use_pallas = probe_backend()
    axis = axis_tuple(axis_name)
    leaves, _ = jax.tree_util.tree_flatten(tree)
    groups, raw_ix = _group_leaves(leaves)
    buckets = []
    for name in sorted(groups):
        members = tuple(groups[name])
        L = sum(m[2] for m in members)
        bucket = _p2p_bucket(L, name, axis_name, policy=policy, n_dev=n_dev,
                             tensor_class="weight", strategy=strategy)
        bucket = _with_members(bucket, members)
        if bucket.path == PATH_COMPRESSED:
            w_d, w_lo = policy.delta_widths(name)
            dt = codec.LAYOUTS[name].dtype
            padded = _pad_up(L, policy.profile.block)
            bucket = dataclasses.replace(
                bucket, delta_width=w_d, delta_lo_width=w_lo,
                delta_wire_bytes=delta_wire_bytes(
                    padded, dt, width=w_d, lo_width=w_lo,
                    block=policy.profile.block,
                    exc_frac=policy.profile.exc_frac))
        buckets.append(bucket)
    if key is None:
        key = wsync_plan_key(tree, axis_name, policy, strategy, n_dev,
                             broadcast=schedule)
    return CommPlan(key=key, kind="wsync", axis=axis, n_dev=n_dev,
                    backend=backend, use_pallas=use_pallas,
                    buckets=tuple(buckets), raw_leaf_ix=raw_ix,
                    n_leaves=len(leaves), strategy=strategy,
                    broadcast=schedule)


def wsync_plan_key(tree, axis_name, policy, strategy: str, n_dev: int,
                   broadcast: "BroadcastSchedule | None" = None) -> tuple:
    # the schedule triple is part of the key: a fleet-size or fan-out
    # change MUST miss and recompile — replaying a stale topology would
    # mis-route the broadcast (route_for also fails loudly at runtime)
    sched_key = (None if broadcast is None else
                 (broadcast.kind, broadcast.fanout, broadcast.n_receivers))
    return ("wsync", tree_signature(tree), str(strategy),
            axis_tuple(axis_name), int(n_dev),
            policy_fingerprint(policy, "weight"), probe_backend(),
            sched_key)


# ---------------------------------------------------------------------------
# cached compile helpers (the step builders' entry points)
# ---------------------------------------------------------------------------

def cached_zero1_plan(meta, *, policy, axis_name, n_dev: int, cache=None):
    from repro.sched.cache import default_cache

    cache = default_cache() if cache is None else cache
    key = zero1_plan_key(meta, axis_name, policy, n_dev)
    return cache.get_or_compile(
        key, lambda: compile_zero1_plan(meta, policy=policy,
                                        axis_name=axis_name, n_dev=n_dev,
                                        key=key))


def cached_fsdp_gather_plan(local_shape, dtype_name, axis_name, *, policy,
                            n_dev: int, cache=None):
    from repro.sched.cache import default_cache

    cache = default_cache() if cache is None else cache
    key = fsdp_gather_plan_key(local_shape, dtype_name, axis_name, policy,
                               n_dev)
    return cache.get_or_compile(
        key, lambda: compile_fsdp_gather_plan(
            tuple(local_shape), dtype_name, axis_name, policy=policy,
            n_dev=n_dev, key=key))


def cached_p2p_plan(x, axis_name, *, policy, n_dev: int,
                    tensor_class: str = "weight",
                    strategy: str = "split_send", cache=None):
    from repro.sched.cache import default_cache

    cache = default_cache() if cache is None else cache
    key = p2p_plan_key(tuple(x.shape), jnp.dtype(x.dtype).name, axis_name,
                       policy, tensor_class, strategy, n_dev)
    return cache.get_or_compile(
        key, lambda: compile_p2p_plan(
            x, axis_name, policy=policy, n_dev=n_dev,
            tensor_class=tensor_class, strategy=strategy, key=key))


def cached_wsync_plan(tree, axis_name, *, policy, n_dev: int,
                      strategy: str = "split_send", broadcast: str = None,
                      fanout: int = 2, n_receivers: int = 0, cache=None):
    """Keyed-cache wrapper for :func:`compile_wsync_plan` — the sync
    engine's entry point (a stable weight-tree signature hits the cached
    schedule on every publish after the first; zero re-derived decisions
    per broadcast).  ``broadcast``/``fanout``/``n_receivers`` select the
    fan-out topology: a stable fleet size hits, a changed one misses and
    recompiles the schedule."""
    from repro.sched.cache import default_cache

    cache = default_cache() if cache is None else cache
    schedule = None
    if broadcast is not None:
        schedule = compile_broadcast_schedule(
            n_receivers, kind=broadcast, fanout=fanout)
    key = wsync_plan_key(tree, axis_name, policy, strategy, n_dev,
                         broadcast=schedule)
    return cache.get_or_compile(
        key, lambda: compile_wsync_plan(
            tree, axis_name, policy=policy, n_dev=n_dev, strategy=strategy,
            broadcast=broadcast, fanout=fanout, n_receivers=n_receivers,
            key=key))


def cached_kv_plan(cache, axis_name, *, policy, n_dev: int,
                   strategy: str = "split_send", plan_cache=None):
    """Keyed-cache wrapper for :func:`compile_kv_plan` — the serve engine's
    entry point (``plan_cache`` defaults to the process cache, so repeated
    transfers of a signature-stable cache skip recompilation; a restarted
    engine reloads via ``sched.cache.load_plans`` and hits immediately)."""
    from repro.sched.cache import default_cache

    plan_cache = default_cache() if plan_cache is None else plan_cache
    key = kv_plan_key(cache, axis_name, policy, strategy, n_dev)
    return plan_cache.get_or_compile(
        key, lambda: compile_kv_plan(
            cache, axis_name, policy=policy, n_dev=n_dev, strategy=strategy,
            key=key))


# ---------------------------------------------------------------------------
# kind registry: CommPlan.kind -> compiler.  docs/ARCHITECTURE.md documents
# this table and tests/test_docs.py cross-checks the two, so the doc cannot
# silently rot.  New wire features register here instead of growing their
# own per-call decision logic (ROADMAP plan-IR unification).
# ---------------------------------------------------------------------------

PLAN_KINDS = {
    "psum": compile_psum_plan,
    "reduce_scatter": compile_reduce_scatter_plan,
    "all_gather": compile_all_gather_plan,
    "zero1": compile_zero1_plan,
    "fsdp_gather": compile_fsdp_gather_plan,
    "p2p": compile_p2p_plan,
    "kv": compile_kv_plan,
    "wsync": compile_wsync_plan,
}
