"""Persistent plan executor: drive the compressed collectives from a CommPlan.

The executor is deliberately thin: every wire still goes through the
``compressed_collectives`` / ``kernels.ops`` primitives (so plan-driven
and planless execution are bit-identical — same ops, same arguments, same
device-index accumulation order).  What changes is WHERE decisions happen:
the planless paths re-derive bucketing/gating/widths inside every trace,
the executor replays a schedule compiled once and cached per signature
(``sched/cache.py``).

Wire accounting: a plan execution emits ONE consolidated ``WireReport``
(name ``plan:<kind>``) instead of N per-bucket records — the per-wire
reports of the buckets are captured (``policy.capture_wire_reports``) and
folded, preserving raw/wire totals and the fused/unfused decoded-HBM
split, so ``summarize_wire_reports`` sees the same totals either way.

Entry points:
  * ``psum_with_plan``            — pytree two-shot all-reduce (the plan
    twin of ``tree_psum_compressed``)
  * ``reduce_scatter_with_plan``  — flat local bucket -> reduced shard
  * ``all_gather_with_plan``      — flat local shard -> stacked full
  * ``execute_zero1_pairs``       — ZeRO-1 phase driver (optim/zero1.py)
  * ``gather_from_plan``          — FSDP custom-vjp gather (optim/fsdp.py)
  * ``p2p_send_with_plan``        — split-send P2P pipeline (the plan twin
    of ``core/split_send.p2p_send``, kind "p2p")
  * ``transfer_cache_with_plan``  — KV-cache pytree shipment (the plan
    twin of ``serve/kv_transfer.transfer_cache``, kind "kv")
  * ``sync_weights_with_plan``    — versioned weight broadcast with
    XOR-delta-vs-full routing (the plan twin of
    ``sync/wire.sync_weights``, kind "wsync")
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compressed_collectives import (
    _axis_size,
    all_gather_compressed,
    psum_compressed_ring,
    psum_raw_twoshot,
    psum_safe,
    reduce_scatter_compressed,
)
from repro import obs
from repro.core.policy import (WireReport, capture_wire_reports,
                               record_wire_report)
from repro.sched import compile as sched_compile
from repro.sched.cache import PlanCache, default_cache
from repro.sched.plan import (PATH_COMPRESSED, PATH_RAW_PSUM,
                              PATH_RAW_TWOSHOT, PATH_RING, PATH_TWO_SHOT,
                              BucketPlan, CommPlan)


def consolidate_reports(plan: CommPlan, caught) -> WireReport | None:
    """Fold the per-wire reports of one plan execution into one record.

    ``fused`` is uniform across a plan's reduce-side wires (it comes from
    one policy knob), so a single flag classifies the whole decoded-HBM
    sum the same way ``summarize_wire_reports`` would classify the
    individual records."""
    if not caught:
        return None
    fused = any(r.fused and r.decode_hbm_bytes for r in caught)
    encode_fused = any(r.encode_fused and r.encode_hbm_bytes for r in caught)
    return WireReport(
        name=f"plan:{plan.kind}",
        axis=str(plan.axis if len(plan.axis) > 1 else plan.axis[0]),
        raw_bytes=sum(r.raw_bytes for r in caught),
        wire_bytes=sum(r.wire_bytes for r in caught),
        fused=fused,
        decode_hbm_bytes=sum(r.decode_hbm_bytes for r in caught),
        encode_fused=encode_fused,
        encode_hbm_bytes=sum(r.encode_hbm_bytes for r in caught),
    )


def _plan_span(plan: CommPlan):
    """Trace span for one plan execution (``plan:<kind>``, fires at trace
    time — plan replay is pure Python, so the wall clock is the schedule-
    replay cost, not device time)."""
    return obs.span(f"plan:{plan.kind}",
                    plan_key=f"{hash(plan.key) & 0xFFFFFFFF:08x}",
                    buckets=len(plan.buckets))


def _emit(plan: CommPlan, caught) -> None:
    """Record the consolidated WireReport AND mirror it into the metrics
    registry — both views are fed from the SAME record, so the snapshot's
    per-kind wire totals agree exactly with ``summarize_wire_reports``
    over the ``plan:*`` reports of the same run."""
    rep = consolidate_reports(plan, caught)
    if rep is not None:
        record_wire_report(rep)
    obs.metric("plan_exec_total").inc(kind=plan.kind)
    if rep is not None:
        obs.metric("plan_wire_raw_bytes_total").inc(rep.raw_bytes,
                                                    kind=plan.kind)
        obs.metric("plan_wire_bytes_total").inc(rep.wire_bytes,
                                                kind=plan.kind)
        obs.metric("plan_wire_ratio").set(rep.ratio, kind=plan.kind)
        obs.metric("plan_wire_ratio_hist").observe(rep.ratio, kind=plan.kind)


# ---------------------------------------------------------------------------
# bucket-level drivers (shared by every entry point)
# ---------------------------------------------------------------------------

def _exec_reduce_scatter(b: BucketPlan, x, axis_name, use_pallas):
    """One RS bucket: compressed (plan widths) or the byte-exact raw RS.
    Returns (f32 shard, flag) either way — zero1's contract."""
    if b.path == PATH_COMPRESSED:
        return reduce_scatter_compressed(
            x, axis_name, width=b.width, block=b.block, exc_frac=b.exc_frac,
            use_fused=b.fused, use_pallas=use_pallas,
            fused_encode=b.encode_fused)
    from repro.optim.zero1 import _raw_reduce_scatter

    return _raw_reduce_scatter(x, axis_name, b.n_dev), jnp.int32(0)


def _exec_all_gather(b: BucketPlan, y, axis_name, use_pallas=None):
    """One AG bucket.  Returns (stacked (n_dev, chunk) or raw-gathered,
    flag); the caller reshapes per its own layout (matching the planless
    call sites exactly)."""
    if b.path == PATH_COMPRESSED:
        return all_gather_compressed(
            y, axis_name, width=b.width, block=b.block, exc_frac=b.exc_frac,
            fused_encode=b.encode_fused, use_pallas=use_pallas)
    from repro.optim.zero1 import _raw_all_gather

    return _raw_all_gather(y, axis_name), jnp.int32(0)


def _exec_psum_bucket(b: BucketPlan, bucket, axis_name, use_pallas):
    """One psum bucket: the exact dispatch of ``psum_compressed``."""
    dt = bucket.dtype
    if b.path == PATH_RAW_PSUM:
        return psum_safe(bucket, axis_name).astype(dt), jnp.int32(0)
    if b.path == PATH_RAW_TWOSHOT:
        return psum_raw_twoshot(bucket, axis_name).astype(dt), jnp.int32(0)
    if b.path == PATH_RING:
        return psum_compressed_ring(
            bucket, axis_name, width=b.width, block=b.block,
            exc_frac=b.exc_frac, out_dtype=dt, use_fused=b.fused,
            fused_encode=b.encode_fused, use_pallas=use_pallas)
    assert b.path == PATH_TWO_SHOT, b.path
    red, f1 = reduce_scatter_compressed(
        bucket, axis_name, width=b.width, block=b.block, exc_frac=b.exc_frac,
        use_fused=b.fused, use_pallas=use_pallas,
        fused_encode=b.encode_fused)
    gath, f2 = all_gather_compressed(
        red.astype(dt), axis_name, width=b.ag_width, block=b.block,
        exc_frac=b.exc_frac, fused_encode=b.encode_fused,
        use_pallas=use_pallas)
    out = gath.reshape(-1)[: b.length].astype(dt)
    return out, jnp.maximum(f1, f2)


# ---------------------------------------------------------------------------
# pytree all-reduce
# ---------------------------------------------------------------------------

def execute_psum(plan: CommPlan, tree, axis_name):
    """Run a compiled psum plan over a concrete pytree.

    Bit-identical to ``tree_psum_compressed(tree, axis_name, policy=...)``
    for the policy the plan was compiled from.  Returns (tree, flag)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    assert len(leaves) == plan.n_leaves, (len(leaves), plan.n_leaves)
    out = list(leaves)
    flag = jnp.int32(0)
    with _plan_span(plan), capture_wire_reports() as caught:
        for b in plan.buckets:
            parts = [leaves[i].reshape(-1) for i, _, _ in b.members]
            bucket = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            red, f = _exec_psum_bucket(b, bucket, axis_name, plan.use_pallas)
            flag = jnp.maximum(flag, f)
            offs = np.cumsum([0] + [m[2] for m in b.members])
            for k, (i, shape, _) in enumerate(b.members):
                out[i] = red[offs[k]: offs[k + 1]].reshape(shape)
        for i in plan.raw_leaf_ix:
            out[i] = psum_safe(leaves[i], axis_name)
    _emit(plan, caught)
    return jax.tree_util.tree_unflatten(treedef, out), flag


def psum_with_plan(tree, axis_name, *, policy=None, tensor_class: str = "gradient",
                   plan: CommPlan = None, cache: PlanCache = None):
    """Plan-driven pytree all-reduce.

    With ``plan=None`` this is the cached thin wrapper: the plan is looked
    up by (pytree signature, axis, n_dev, policy fingerprint) and compiled
    on first sight — a repeated step signature re-traces straight off the
    cached schedule.  Returns (tree, overflow_flag)."""
    if plan is None:
        assert policy is not None, "psum_with_plan needs policy= or plan="
        n_dev = _axis_size(axis_name)
        cache = default_cache() if cache is None else cache
        key = sched_compile.psum_plan_key(tree, axis_name, policy,
                                          tensor_class, n_dev)
        plan = cache.get_or_compile(
            key, lambda: sched_compile.compile_psum_plan(
                tree, axis_name, policy=policy, tensor_class=tensor_class,
                n_dev=n_dev, key=key))
    return execute_psum(plan, tree, axis_name)


# ---------------------------------------------------------------------------
# flat phases
# ---------------------------------------------------------------------------

def reduce_scatter_with_plan(x, axis_name, *, policy=None,
                             tensor_class: str = "gradient",
                             plan: CommPlan = None, cache: PlanCache = None):
    """Plan-driven flat reduce-scatter (ZeRO-1 gating rules).

    Returns (f32 local shard, flag) — bit-identical to the planless
    ``reduce_scatter_compressed`` (compressed path, fused or unfused) or
    ``zero1._raw_reduce_scatter`` (gated off)."""
    if plan is None:
        assert policy is not None
        n_dev = _axis_size(axis_name)
        cache = default_cache() if cache is None else cache
        name = jnp.dtype(x.dtype).name
        key = sched_compile.reduce_scatter_plan_key(
            int(np.prod(x.shape)), name, axis_name, policy, tensor_class,
            n_dev)
        plan = cache.get_or_compile(
            key, lambda: sched_compile.compile_reduce_scatter_plan(
                int(np.prod(x.shape)), name, axis_name, policy=policy,
                n_dev=n_dev, tensor_class=tensor_class, key=key))
    with _plan_span(plan), capture_wire_reports() as caught:
        b = plan.buckets[0]
        out, flag = _exec_reduce_scatter(b, x, axis_name, plan.use_pallas)
    _emit(plan, caught)
    return out, flag


def all_gather_with_plan(y, axis_name, *, policy=None,
                         tensor_class: str = "weight",
                         plan: CommPlan = None, cache: PlanCache = None):
    """Plan-driven flat all-gather.  Returns (gathered, flag)."""
    if plan is None:
        assert policy is not None
        n_dev = _axis_size(axis_name)
        cache = default_cache() if cache is None else cache
        name = jnp.dtype(y.dtype).name
        key = sched_compile.all_gather_plan_key(
            int(np.prod(y.shape)), name, axis_name, policy, tensor_class,
            n_dev)
        plan = cache.get_or_compile(
            key, lambda: sched_compile.compile_all_gather_plan(
                int(np.prod(y.shape)), name, axis_name, policy=policy,
                n_dev=n_dev, tensor_class=tensor_class, key=key))
    with _plan_span(plan), capture_wire_reports() as caught:
        b = plan.buckets[0]
        out, flag = _exec_all_gather(b, y, axis_name, plan.use_pallas)
    _emit(plan, caught)
    return out, flag


# ---------------------------------------------------------------------------
# ZeRO-1 phase driver
# ---------------------------------------------------------------------------

class Zero1Execution:
    """Context for one plan-driven ZeRO-1 sync: the optimizer update runs
    BETWEEN the RS and AG phases, so the executor exposes the two phases
    separately and consolidates the wire accounting when closed."""

    def __init__(self, plan: CommPlan, axis_name):
        self.plan = plan
        self.axis_name = axis_name
        self._cap = capture_wire_reports()
        self._caught = None
        self._span = None

    def __enter__(self):
        self._span = _plan_span(self.plan)
        self._span.__enter__()
        self._caught = self._cap.__enter__()
        return self

    def __exit__(self, *exc):
        self._cap.__exit__(*exc)
        self._span.__exit__(*exc)
        if exc[0] is None:
            _emit(self.plan, self._caught)
        return False

    def reduce_scatter(self, i: int, gbucket):
        b = self.plan.buckets[i].rs
        return _exec_reduce_scatter(b, gbucket, self.axis_name,
                                    self.plan.use_pallas)

    def all_gather(self, i: int, shard):
        b = self.plan.buckets[i].ag
        return _exec_all_gather(b, shard, self.axis_name,
                                self.plan.use_pallas)


# ---------------------------------------------------------------------------
# P2P + serve KV wires (kinds "p2p"/"kv")
# ---------------------------------------------------------------------------

def _exec_p2p_bucket(b: BucketPlan, x, axis_name, perm, *, strategy,
                     use_pallas, reduce_into=None):
    """One P2P message from its BucketPlan: the exact dispatch of
    ``p2p_send``, with the gate/width/fused decisions read off the plan
    (``core/split_send.p2p_dispatch`` is the shared seam — bit-identical
    to the planless call by construction).  ``use_pallas`` replays the
    plan's recorded backend probe, same contract as the collective
    kinds (the key invalidates on probe changes, so it equals a live
    probe for any plan the cache hands out)."""
    from repro.core.split_send import p2p_dispatch

    return p2p_dispatch(
        x, axis_name, perm, compressed=b.path == PATH_COMPRESSED,
        width=b.width, block=b.block, exc_frac=b.exc_frac,
        strategy=strategy, reduce_into=reduce_into, fused=b.fused,
        encode_fused=b.encode_fused, use_pallas=use_pallas)


def execute_p2p(plan: CommPlan, x, axis_name, perm, *, reduce_into=None):
    """Run a compiled kind-"p2p" plan on a concrete tensor.

    Bit-identical to ``p2p_send(x, axis_name, perm, policy=...)`` for the
    (policy, tensor_class, strategy) the plan was compiled from.  Returns
    (received tensor, flag) — or (reduce_into + received, flag) for a
    reducing receiver.  Emits ONE consolidated ``plan:p2p`` WireReport."""
    assert plan.kind == "p2p", plan.kind
    _, shape, _ = plan.buckets[0].members[0]
    assert tuple(x.shape) == tuple(shape) and \
        jnp.dtype(x.dtype).name == plan.buckets[0].dtype_name, (
            f"tensor {x.shape}/{jnp.dtype(x.dtype).name} does not match the "
            f"plan's signature {shape}/{plan.buckets[0].dtype_name}")
    with _plan_span(plan), capture_wire_reports() as caught:
        b = plan.buckets[0]
        out, flag = _exec_p2p_bucket(b, x, axis_name, perm,
                                     strategy=plan.strategy,
                                     use_pallas=plan.use_pallas,
                                     reduce_into=reduce_into)
    _emit(plan, caught)
    return out, flag


def p2p_send_with_plan(x, axis_name, perm, *, policy=None,
                       tensor_class: str = "weight",
                       strategy: str = "split_send", reduce_into=None,
                       plan: CommPlan = None, cache: PlanCache = None):
    """Plan-driven P2P send (the cached thin wrapper over ``execute_p2p``).

    With ``plan=None`` the plan is looked up by (shape, dtype, strategy,
    axis, n_dev, policy fingerprint) in the keyed cache and compiled on
    first sight — a repeated send signature replays the cached schedule
    with zero re-derivation.  Bit-identical to the planless ``p2p_send``."""
    if plan is None:
        assert policy is not None, "p2p_send_with_plan needs policy= or plan="
        n_dev = _axis_size(axis_name)
        cache = default_cache() if cache is None else cache
        key = sched_compile.p2p_plan_key(
            tuple(x.shape), jnp.dtype(x.dtype).name, axis_name, policy,
            tensor_class, strategy, n_dev)
        plan = cache.get_or_compile(
            key, lambda: sched_compile.compile_p2p_plan(
                x, axis_name, policy=policy, n_dev=n_dev,
                tensor_class=tensor_class, strategy=strategy, key=key))
    return execute_p2p(plan, x, axis_name, perm, reduce_into=reduce_into)


def execute_kv_transfer(plan: CommPlan, cache, axis_name, perm):
    """Run a compiled kind-"kv" plan on a concrete KV-cache pytree.

    Bit-identical to ``transfer_cache(cache, axis_name, perm, policy=...)``
    for the (policy, strategy) the plan was compiled from: the recorded
    per-dtype buckets concatenate the same leaves in the same order and
    ride the same wire primitives; raw leaves ship with the same raw
    ppermute.  Returns (cache_at_dest, flag) and emits ONE consolidated
    ``plan:kv`` WireReport."""
    from repro.core.compressed_collectives import raw_ppermute

    assert plan.kind == "kv", plan.kind
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    assert len(leaves) == plan.n_leaves, (len(leaves), plan.n_leaves)
    for b in plan.buckets:  # a stale plan must fail loudly, not mis-scatter
        for i, shape, _ in b.members:
            assert tuple(leaves[i].shape) == tuple(shape) and \
                jnp.dtype(leaves[i].dtype).name == b.dtype_name, (
                    f"cache leaf {i} is {leaves[i].shape}/"
                    f"{jnp.dtype(leaves[i].dtype).name} but the plan "
                    f"recorded {shape}/{b.dtype_name}")
    out = list(leaves)
    flag = jnp.int32(0)
    with _plan_span(plan), capture_wire_reports() as caught:
        for b in plan.buckets:
            parts = [leaves[i].reshape(-1) for i, _, _ in b.members]
            bucket = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            got, f = _exec_p2p_bucket(b, bucket, axis_name, perm,
                                      strategy=plan.strategy,
                                      use_pallas=plan.use_pallas)
            flag = jnp.maximum(flag, f)
            offs = np.cumsum([0] + [m[2] for m in b.members])
            for k, (i, shape, _) in enumerate(b.members):
                out[i] = got[offs[k]: offs[k + 1]].reshape(shape)
        for i in plan.raw_leaf_ix:
            out[i] = raw_ppermute(
                leaves[i][None] if leaves[i].ndim == 0 else leaves[i],
                axis_name, perm)
            if leaves[i].ndim == 0:
                out[i] = out[i][0]
    _emit(plan, caught)
    return jax.tree_util.tree_unflatten(treedef, out), flag


def transfer_cache_with_plan(cache, axis_name, perm, *, policy=None,
                             strategy: str = "split_send",
                             plan: CommPlan = None,
                             plan_cache: PlanCache = None):
    """Plan-driven KV-cache transfer (the cached thin wrapper over
    ``execute_kv_transfer``).

    With ``plan=None`` the plan is looked up by the cache pytree's
    signature (treedef + per-leaf shape/dtype) in the keyed plan cache —
    a serve decode loop whose cache signature is stable hits the cached
    schedule on every transfer after the first (zero recompiles).
    Bit-identical to the planless ``transfer_cache``."""
    if plan is None:
        assert policy is not None, \
            "transfer_cache_with_plan needs policy= or plan="
        n_dev = _axis_size(axis_name)
        plan_cache = default_cache() if plan_cache is None else plan_cache
        key = sched_compile.kv_plan_key(cache, axis_name, policy, strategy,
                                        n_dev)
        plan = plan_cache.get_or_compile(
            key, lambda: sched_compile.compile_kv_plan(
                cache, axis_name, policy=policy, n_dev=n_dev,
                strategy=strategy, key=key))
    return execute_kv_transfer(plan, cache, axis_name, perm)


# ---------------------------------------------------------------------------
# weight sync (kind "wsync"): versioned trainer->replica broadcast with
# per-bucket XOR-delta-vs-full routing
# ---------------------------------------------------------------------------

def execute_wsync(plan: CommPlan, tree, axis_name, perm, *, base=None):
    """Run a compiled kind-"wsync" plan on a concrete weight pytree.

    Bit-identical to ``sync/wire.sync_weights(tree, ..., base=base)`` for
    the (policy, strategy) the plan was compiled from: both routes call
    ``split_send.wsync_dispatch`` with the same arguments.  ``base`` is
    the receiver-acked weight version both ends hold — ``None`` broadcasts
    full tensors (first contact / stale ack / epoch fence), a pytree of
    ``tree``'s structure ships XOR deltas on every delta-eligible bucket.
    Returns (tree_at_dest, flag); a nonzero flag on a delta execution
    means exception overflow — the caller must retry full.  Emits ONE
    consolidated ``plan:wsync`` WireReport."""
    from repro.core import codec
    from repro.core.compressed_collectives import raw_ppermute
    from repro.core.split_send import wsync_dispatch

    assert plan.kind == "wsync", plan.kind
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    assert len(leaves) == plan.n_leaves, (len(leaves), plan.n_leaves)
    base_leaves = None
    if base is not None:
        base_leaves, base_def = jax.tree_util.tree_flatten(base)
        assert base_def == treedef, "base tree structure != weight tree"
    for b in plan.buckets:  # a stale plan must fail loudly, not mis-scatter
        for i, shape, _ in b.members:
            assert tuple(leaves[i].shape) == tuple(shape) and \
                jnp.dtype(leaves[i].dtype).name == b.dtype_name, (
                    f"weight leaf {i} is {leaves[i].shape}/"
                    f"{jnp.dtype(leaves[i].dtype).name} but the plan "
                    f"recorded {shape}/{b.dtype_name}")
    out = list(leaves)
    flag = jnp.int32(0)
    with _plan_span(plan), capture_wire_reports() as caught:
        for b in plan.buckets:
            bucket = codec.concat_members(leaves, b.members)
            bucket_base = (codec.concat_members(base_leaves, b.members)
                           if base_leaves is not None else None)
            got, f = wsync_dispatch(
                bucket, bucket_base, axis_name, perm,
                compressed=b.path == PATH_COMPRESSED, width=b.width,
                delta_width=b.delta_width,
                delta_lo_width=b.delta_lo_width,
                block=b.block, exc_frac=b.exc_frac,
                strategy=plan.strategy, fused=b.fused,
                encode_fused=b.encode_fused, use_pallas=plan.use_pallas)
            flag = jnp.maximum(flag, f)
            for i, leaf in codec.split_members(got, b.members):
                out[i] = leaf
        for i in plan.raw_leaf_ix:
            out[i] = raw_ppermute(
                leaves[i][None] if leaves[i].ndim == 0 else leaves[i],
                axis_name, perm)
            if leaves[i].ndim == 0:
                out[i] = out[i][0]
    _emit(plan, caught)
    return jax.tree_util.tree_unflatten(treedef, out), flag


def sync_weights_with_plan(tree, axis_name, perm, *, policy=None, base=None,
                           strategy: str = "split_send",
                           plan: CommPlan = None, cache: PlanCache = None):
    """Plan-driven weight sync (the cached thin wrapper over
    ``execute_wsync``).

    With ``plan=None`` the plan is looked up by the weight pytree's
    signature in the keyed plan cache — a trainer publishing a
    signature-stable tree hits the cached schedule on every broadcast
    after the first.  Bit-identical to the planless
    ``sync/wire.sync_weights``."""
    if plan is None:
        assert policy is not None, \
            "sync_weights_with_plan needs policy= or plan="
        n_dev = _axis_size(axis_name)
        cache = default_cache() if cache is None else cache
        key = sched_compile.wsync_plan_key(tree, axis_name, policy, strategy,
                                           n_dev)
        plan = cache.get_or_compile(
            key, lambda: sched_compile.compile_wsync_plan(
                tree, axis_name, policy=policy, n_dev=n_dev,
                strategy=strategy, key=key))
    return execute_wsync(plan, tree, axis_name, perm, base=base)


def wsync_hop_perms(schedule, ranks) -> tuple:
    """Lower a :class:`~repro.sched.plan.BroadcastSchedule` to per-level
    ppermute perm lists for the in-mesh wire.

    ``ranks[0]`` is the trainer's device rank, ``ranks[1:]`` the receiver
    ranks in slot order (the distributor's sorted-name order).  Level
    ``h``'s perm forwards from the hop-``h-1`` holders to the hop-``h``
    receivers, so replaying the levels in order delivers every rank
    exactly once — star lowers to one wide level, a pipeline to a chain
    of single-pair levels.  A rank list that disagrees with the schedule's
    compiled fleet size fails loudly (the stale-schedule guard)."""
    ranks = tuple(ranks)
    if len(ranks) != schedule.n_receivers + 1:
        raise ValueError(
            f"stale broadcast schedule: compiled for "
            f"{schedule.n_receivers} receivers, got {len(ranks) - 1} ranks")
    return tuple(tuple((ranks[p], ranks[c]) for p, c in level)
                 for level in schedule.levels())


def execute_wsync_broadcast(plan: CommPlan, tree, axis_name, ranks, *,
                            base=None):
    """Run a schedule-carrying kind-"wsync" plan as its sequence of
    in-mesh hop levels: level h re-sends what the hop-h-1 holders received
    along that level's perm (``wsync_hop_perms``).

    The in-mesh twin of the fleet's host broadcast — the SAME
    ``BroadcastSchedule`` drives both.  The difference is the forwarding
    medium: the host fleet forwards the encoded ``SyncUpdate`` wire
    verbatim (zero re-encodes), while each in-mesh hop replays the full
    ``wsync_dispatch`` (an SPMD program re-encodes at every level's
    sources — XLA owns that wire).  Returns (tree_at_leaves, flag); the
    flag ORs every level's overflow flag, so a nonzero means some hop's
    delta overflowed and the caller must retry full."""
    assert plan.kind == "wsync", plan.kind
    if plan.broadcast is None:
        raise ValueError("plan carries no BroadcastSchedule; use "
                         "execute_wsync with an explicit perm")
    current, flag = tree, jnp.int32(0)
    for level in wsync_hop_perms(plan.broadcast, ranks):
        current, f = execute_wsync(plan, current, axis_name, list(level),
                                   base=base)
        flag = jnp.maximum(flag, f)
    return current, flag


# ---------------------------------------------------------------------------
# FSDP gather
# ---------------------------------------------------------------------------

def gather_from_plan(plan: CommPlan):
    """Custom-vjp FSDP gather driven by a compiled plan (forward weight AG
    at ``ag_width``, backward gradient RS at ``width``, fused receive per
    plan).  Returns the gather fn — the heavy lifting stays in
    ``optim/fsdp._make_gather`` (lru-cached on exactly the plan fields)."""
    from repro.optim import fsdp as fsdp_lib

    b = plan.buckets[0]
    local_shape = b.members[0][1]
    return fsdp_lib._make_gather(
        plan.axis, b.ag_width, b.width, b.block, b.exc_frac,
        b.path == PATH_COMPRESSED, local_shape, b.dtype_name, b.fused,
        b.encode_fused)
