"""Planless in-mesh weight broadcast (the kind-"wsync" reference path).

One trainer rank ships its weight pytree to inference replicas across a
mesh axis permutation.  Codec-supported leaves fuse into flat buckets of
one dtype, cut at leaf boundaries at ``sched.compile.WSYNC_BUCKET_BYTES``
(``sched.compile.wsync_buckets``, shared with the plan — paper Property
1: large blocks keep the codec efficient); each bucket is gated/width'd
like a ``p2p_send`` at tensor_class "weight", and — when both ends hold a shared ``base``
version — ships a bitwise XOR delta instead of the full tensor
(``core/split_send.delta_send``), which is dramatically more compressible
for consecutive optimizer steps while staying exactly lossless.

This module re-derives every decision from the ``CompressionPolicy`` per
call; ``sched.sync_weights_with_plan`` replays the identical schedule from
a compiled kind-"wsync" ``CommPlan``.  Both routes funnel through
``core/split_send.wsync_dispatch``, so plan-driven == planless
bit-identically by construction.  Version bookkeeping (who holds which
base) lives one level up in ``sync/store.py`` / ``sync/engine.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import CompressionPolicy
from repro.core.split_send import wsync_dispatch


def sync_weights(tree, axis_name, perm, *, policy: CompressionPolicy,
                 base=None, strategy: str = "split_send"):
    """Broadcast a weight pytree across ``perm`` on mesh axis ``axis_name``.

    ``base=None`` ships full tensors (first contact / stale receiver);
    ``base`` a pytree of ``tree``'s structure ships XOR deltas on every
    compressed bucket — the receiver reconstructs against its own copy of
    the base version, bit-identical to a raw ppermute of ``tree`` whenever
    the returned flag is 0 (a nonzero flag = delta exception overflow:
    retry with ``base=None``).  Raw-gated buckets and codec-unsupported
    leaves always ship full.

    The planless reference: gating/widths are re-derived from ``policy``
    per call.  Callers with a stable weight signature should prefer
    ``sched.sync_weights_with_plan`` (adds the keyed plan cache).  Returns
    (tree_at_dest, flag)."""
    from repro.core import codec
    from repro.core.compressed_collectives import raw_ppermute
    from repro.sched.compile import wsync_buckets

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    base_leaves = None
    if base is not None:
        base_leaves, base_def = jax.tree_util.tree_flatten(base)
        if base_def != treedef:
            raise ValueError("base tree structure != weight tree")
    groups, raw_ix = wsync_buckets(leaves)
    out = list(leaves)
    flag = jnp.int32(0)
    for name, members in groups:
        L = sum(m[2] for m in members)
        bucket = codec.concat_members(leaves, members)
        bucket_base = (codec.concat_members(base_leaves, members)
                       if base_leaves is not None else None)
        struct = jax.ShapeDtypeStruct((L,), bucket.dtype)
        compressed = policy.should_compress(struct, axis_name,
                                            tensor_class="weight")
        w_d, w_lo = policy.delta_widths(name)
        got, f = wsync_dispatch(
            bucket, bucket_base, axis_name, perm, compressed=compressed,
            width=policy.width_for("weight"), delta_width=w_d,
            delta_lo_width=w_lo, block=policy.profile.block,
            exc_frac=policy.profile.exc_frac, strategy=strategy,
            fused=policy.fused_decode_reduce,
            encode_fused=policy.fused_encode)
        flag = jnp.maximum(flag, f)
        for i, leaf in codec.split_members(got, members):
            out[i] = leaf
    for i in raw_ix:
        out[i] = raw_ppermute(
            leaves[i][None] if leaves[i].ndim == 0 else leaves[i],
            axis_name, perm)
        if leaves[i].ndim == 0:
            out[i] = out[i][0]
    return jax.tree_util.tree_unflatten(treedef, out), flag


def broadcast_weights(tree, axis_name, schedule, ranks, *,
                      policy: CompressionPolicy, base=None,
                      strategy: str = "split_send"):
    """Planless in-mesh replay of a :class:`~repro.sched.plan.
    BroadcastSchedule`: one :func:`sync_weights` per hop level, each
    level's perm forwarding from the previous level's receivers
    (``sched.executor.wsync_hop_perms`` lowers the topology; this is its
    policy-re-deriving reference twin, bit-identical to
    ``sched.executor.execute_wsync_broadcast`` by construction).

    The host fleet (``sync/fleet.SyncFleet``) is where the schedule's
    zero-re-encode forwarding lives; in-mesh every level re-runs the
    dispatch at its sources.  Returns (tree_at_leaves, ORed flag)."""
    from repro.sched.executor import wsync_hop_perms

    current, flag = tree, jnp.int32(0)
    for level in wsync_hop_perms(schedule, ranks):
        current, f = sync_weights(current, axis_name, list(level),
                                  policy=policy, base=base,
                                  strategy=strategy)
        flag = jnp.maximum(flag, f)
    return current, flag
