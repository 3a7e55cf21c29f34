"""Host-path weight-sync engine: one trainer, N inference replicas.

The paper's headline P2P workload (§5.3.1, Fig. 10) is RL weight
synchronization — the trainer pushes updated policy weights to rollout /
inference workers every iteration.  ``WeightSyncEngine`` owns that
workload end to end on the host path (out-of-band, separate-process
replicas; the in-mesh twin is ``sync/wire.sync_weights`` /
``sched.sync_weights_with_plan``):

  * the schedule — per-dtype leaf buckets, compress-vs-raw gates, full and
    XOR-delta codec widths, expected wire bytes — comes from a compiled
    kind-"wsync" ``CommPlan`` cached on the weight tree's signature: the
    first publish compiles it, every later publish is a plan-cache hit
    (zero re-derived decisions per broadcast);
  * version bookkeeping (``sync/store.VersionedStore``) decides delta-vs-
    full per replica: deltas are sent against the replica's acked version
    when the trainer still retains it AND the ack is epoch-current;
    otherwise (late joiner, pruned history, post-restart fence) the full
    tensors go out;
  * losslessness is unconditional: a delta whose exceptions overflow the
    calibrated widths falls back to a full encode of that bucket before
    anything ships, and every path reconstructs bit-identically —
    including NaN/Inf payloads.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import codec, integrity, packing
from repro.core.policy import CompressionPolicy
from repro.sched.plan import PATH_COMPRESSED
from repro.sync.store import VersionedStore

MODE_DELTA = "delta"
MODE_FULL = "full"
MODE_RAW = "raw"

# recovery-escalation ladder (sync/fleet.py): a rejected delta re-sends
# full; a rejected full re-sends raw — the simplest possible wire last
FORCE_MODES = (None, MODE_FULL, MODE_RAW)


def _count_exceptions(msg: packing.DeltaMessage) -> None:
    """Occupancy of a host-copied delta's two exception lists: each
    ``exc_idx`` is sorted, its unused slots at the plane's fill value."""
    lo_fill = msg.lo.payload.size // msg.lo.width * packing.GROUP
    for plane, p, fill in (("lo", msg.lo, lo_fill),
                           ("exp", msg.exp, msg.exp.n_blocks)):
        obs.metric("sync_delta_exceptions_total").inc(
            int(np.searchsorted(p.exc_idx, fill)), plane=plane)
        obs.metric("sync_delta_exception_slots_total").inc(
            int(p.exc_idx.size), plane=plane)


def _raw_wire(bucket, dtype_name):
    """Raw bucket -> wire ndarray.  Codec float dtypes travel as their
    uint bit patterns: converting sub-f32 floats through host numpy can
    canonicalize signaling-NaN payloads, and the raw path must be just as
    bit-exact as the coded ones (the host twin of the collectives'
    ``_to_wire`` bitcast)."""
    lay = codec.LAYOUTS.get(dtype_name)
    if lay is None:
        return np.asarray(bucket)
    return np.asarray(jax.lax.bitcast_convert_type(bucket, lay.uint_dtype))


def _raw_unwire(msg, dtype_name):
    lay = codec.LAYOUTS.get(dtype_name)
    if lay is None:
        return jnp.asarray(msg)
    return jax.lax.bitcast_convert_type(jnp.asarray(msg), lay.dtype)


@dataclasses.dataclass
class SyncUpdate:
    """One encoded trainer->replica weight shipment.

    ``base_version`` is None for a pure full send; otherwise every
    ``MODE_DELTA`` bucket must be decoded against that version's bits (the
    receiver's current weights — ``apply_update(base_params=...)``).
    ``buckets`` carry (dtype_name, members, mode, message) per plan
    bucket; ``raw_leaves`` the codec-unsupported leaves.

    ``checksum`` is the CRC-32 integrity envelope over the PAYLOAD
    (bucket schedule + packed planes + raw leaves — see
    :func:`update_checksum`); receivers must verify it before applying
    (``verify_update``).  The (version, epoch, base) fields are excluded
    on purpose: they are fenced against the receiver's own state, which
    a checksum could not strengthen."""

    version: int
    epoch: int
    base_version: Optional[int]
    treedef: Any
    n_leaves: int
    buckets: tuple  # ((dtype_name, members, mode, message), ...)
    raw_leaves: tuple  # ((leaf_index, ndarray), ...)
    wire_bytes: int
    raw_bytes: int
    checksum: Optional[int] = None

    @property
    def mode(self) -> str:
        """"delta" if any bucket shipped a delta, else "full"."""
        return (MODE_DELTA if any(m == MODE_DELTA for _, _, m, _ in
                                  self.buckets) else MODE_FULL)

    @property
    def ratio(self) -> float:
        return self.wire_bytes / max(self.raw_bytes, 1)


def apply_update(update: SyncUpdate, base_params=None):
    """Reconstruct the published weights from a :class:`SyncUpdate`.

    Bit-identical to the trainer's published tree.  ``base_params`` (the
    receiver's weights at ``update.base_version``) is required iff the
    update carries delta buckets."""
    leaves: list = [None] * update.n_leaves
    base_leaves = None
    if base_params is not None:
        base_leaves = jax.tree_util.tree_flatten(base_params)[0]
    for dtype_name, members, mode, msg in update.buckets:
        if mode == MODE_DELTA:
            if base_leaves is None:
                raise ValueError(
                    f"update v{update.version} deltas against "
                    f"v{update.base_version}; apply_update needs "
                    f"base_params")
            base_bucket = codec.pad_flat_bits(
                codec.concat_members(base_leaves, members),
                int(np.prod(msg.shape)))
            got = packing.decode_delta(msg, base_bucket)
        elif mode == MODE_FULL:
            got = packing.decode_message(msg)
        else:
            got = _raw_unwire(msg, dtype_name)
        for i, leaf in codec.split_members(got, members):
            leaves[i] = leaf
    for i, arr in update.raw_leaves:
        leaves[i] = jnp.asarray(arr)
    return jax.tree_util.tree_unflatten(update.treedef, leaves)


def update_checksum(update: SyncUpdate) -> int:
    """CRC-32 over the update's payload: bucket schedule (dtype, members,
    mode), every message array, and the raw leaves.  Cheap relative to
    the encode it protects, and a single flipped wire bit changes it."""
    c = integrity.crc32_tree(update.n_leaves)
    for dtype_name, members, mode, msg in update.buckets:
        c = integrity.crc32_tree((dtype_name, members, mode, msg), seed=c)
    return integrity.crc32_tree(update.raw_leaves, seed=c)


def verify_update(update: SyncUpdate) -> bool:
    """True iff the update carries a checksum and its payload still
    matches it.  Receivers (fleet replicas, ``ServeEngine.
    ingest_weights``) call this BEFORE ``apply_update`` — a False means
    reject-and-renegotiate (nack, escalate delta -> full -> raw), never
    apply."""
    return (update.checksum is not None
            and update_checksum(update) == update.checksum)


class WeightSyncEngine:
    """Trainer-side broadcast engine with versioned XOR-delta encoding."""

    def __init__(self, *, policy: CompressionPolicy = None,
                 axis_name: str = "data", strategy: str = "split_send",
                 history: int = 4, plan_cache=None) -> None:
        self.policy = CompressionPolicy() if policy is None else policy
        self.axis_name = axis_name
        self.strategy = strategy
        self.store = VersionedStore(history=history)
        self.plan_cache = plan_cache
        # encoded updates of the LATEST version, keyed by base_version:
        # replicas that acked the same base receive byte-identical updates,
        # so broadcasting to N replicas encodes once, not N times
        self._updates: dict = {}

    # -- trainer side --------------------------------------------------------

    def publish(self, params) -> int:
        """Retain ``params`` as the next weight version (the train-step
        publish hook's target — ``train/step.make_publish_hook``)."""
        self._updates.clear()  # encoded updates are per-version
        with obs.span("sync:publish"):
            version = self.store.publish(params)
        obs.metric("sync_publish_total").inc()
        self._export_lag()  # every replica just fell one version behind
        return version

    def _export_lag(self) -> None:
        """Per-replica version-lag gauges (latest - acked, epoch-current)."""
        if not obs.enabled():
            return
        gauge = obs.metric("sync_replica_version_lag")
        latest = self.store.version
        for r in self.store.acked_replicas():
            acked = self.store.acked_version(r)
            gauge.set(latest - acked, replica=str(r))

    def plan_for(self, params, *, broadcast: Optional[str] = None,
                 fanout: int = 2, n_receivers: int = 0):
        """The cached kind-"wsync" CommPlan of ``params``' signature.

        ``broadcast``/``fanout``/``n_receivers`` additionally compile the
        fan-out topology into the plan (``CommPlan.broadcast``) — the
        fleet's distributor asks for the schedule of each same-base
        receiver group here, so a stable fleet size is a cache hit and a
        changed one recompiles (the schedule triple is part of the key).
        The default (no broadcast) is the receiver-count-agnostic plan
        ``_encode_update`` uses: the encode schedule is identical across
        topologies — forwarding must never change the bits."""
        from repro import sched

        return sched.cached_wsync_plan(
            params, self.axis_name, policy=self.policy, n_dev=1,
            strategy=self.strategy, cache=self.plan_cache,
            broadcast=broadcast, fanout=fanout, n_receivers=n_receivers)

    def update_for(self, replica, *, force: Optional[str] = None
                   ) -> SyncUpdate:
        """Encode the latest version for ``replica``: XOR delta against its
        acked base when possible (a replica that is already current gets
        the all-zero delta — far cheaper than a full re-send), full
        otherwise (stale/absent/fenced ack, raw-gated buckets, or
        per-bucket delta overflow).  Updates are memoized per (latest
        version, base version, force): broadcasting to N replicas with
        the same ack encodes once.

        ``force`` is the recovery-escalation override (``sync/fleet.py``):
        ``"full"`` skips the delta route even when a base is acked (the
        receiver rejected or lost a delta); ``"raw"`` additionally ships
        every bucket uncompressed — the last-resort wire after repeated
        integrity failures."""
        if force not in FORCE_MODES:
            raise ValueError(f"force must be one of {FORCE_MODES}, "
                             f"got {force!r}")
        with obs.span("sync:update", replica=str(replica)) as sp:
            params, version = self.store.latest()
            base_version = (None if force is not None
                            else self.store.base_for(replica))
            sp.args["version"] = version
            key = (base_version, force)
            cached = self._updates.get(key)
            if cached is not None:
                obs.instant("sync:memo_hit", version=version,
                            base=base_version)
                obs.metric("sync_memo_hits_total").inc()
                return cached
            update = self._encode_update(params, version, base_version,
                                         force=force)
            self._updates[key] = update
        obs.metric("sync_updates_total").inc(mode=update.mode)
        obs.metric("sync_update_wire_bytes_total").inc(update.wire_bytes,
                                                       mode=update.mode)
        return update

    def _encode_update(self, params, version: int, base_version,
                       force: Optional[str] = None) -> SyncUpdate:
        base = self.store.get(base_version) if base_version is not None \
            else None
        plan = self.plan_for(params)
        leaves = jax.tree_util.tree_flatten(params)[0]
        base_leaves = (jax.tree_util.tree_flatten(base)[0]
                       if base is not None else None)
        buckets = []
        wire = 0
        used_delta = False
        bucket_counter = obs.metric("sync_buckets_total")
        with obs.span("sync:encode", version=version,
                      base=base_version if base_version is not None else -1):
            for b in plan.buckets:
                bucket = codec.concat_members(leaves, b.members)
                mode, msg = MODE_RAW, None
                if b.path == PATH_COMPRESSED and force != MODE_RAW:
                    # pad to the block grid like the in-mesh wire, so the
                    # plan's eval_shape accounting IS this wire's size (and
                    # overflow thresholds match delta_send exactly)
                    bucket = codec.pad_flat_bits(bucket, b.block)
                    if base_leaves is not None and b.delta_width:
                        base_bucket = codec.pad_flat_bits(
                            codec.concat_members(base_leaves, b.members),
                            b.block)
                        with obs.span("sync:codec", mode=MODE_DELTA):
                            m = packing.encode_delta(
                                bucket, base_bucket, width=b.delta_width,
                                lo_width=b.delta_lo_width, block=b.block,
                                exc_frac=b.exc_frac)
                            overflow = int(m.overflow)
                        if not overflow:  # else: fall through to full
                            with obs.span("sync:d2h", mode=MODE_DELTA):
                                mode, msg = MODE_DELTA, jax.device_get(m)
                            wire += m.wire_bytes()
                            used_delta = True
                    if msg is None:
                        with obs.span("sync:codec", mode=MODE_FULL):
                            m = packing.encode_message(
                                bucket, width=b.width, block=b.block,
                                exc_frac=b.exc_frac, fused=b.encode_fused)
                            overflow = int(m.exp.overflow)
                        if overflow:
                            # even the full wire's exceptions overflowed
                            # (pathological exponent spread): ship the bucket
                            # raw — the host twin of the runtime's
                            # retry-uncompressed guard.  Never corrupt.
                            with obs.span("sync:d2h", mode=MODE_RAW):
                                mode, msg = (MODE_RAW,
                                             _raw_wire(bucket, b.dtype_name))
                            wire += msg.nbytes
                        else:
                            with obs.span("sync:d2h", mode=MODE_FULL):
                                mode, msg = MODE_FULL, jax.device_get(m)
                            wire += m.wire_bytes()
                else:
                    with obs.span("sync:d2h", mode=MODE_RAW):
                        msg = _raw_wire(bucket, b.dtype_name)
                    wire += msg.nbytes
                with obs.span("obs:sample"):
                    bucket_counter.inc(mode=mode)
                    if mode == MODE_DELTA:
                        _count_exceptions(msg)
                buckets.append((b.dtype_name, b.members, mode, msg))
            with obs.span("sync:d2h", mode="leaves"):
                raw_leaves = tuple((i, np.asarray(leaves[i]))
                                   for i in plan.raw_leaf_ix)
        wire += sum(arr.nbytes for _, arr in raw_leaves)
        raw_total = sum(l.size * jnp.dtype(l.dtype).itemsize
                        for l in leaves if hasattr(l, "dtype"))
        update = SyncUpdate(
            version=version, epoch=self.store.epoch,
            base_version=base_version if used_delta else None,
            treedef=jax.tree_util.tree_structure(params),
            n_leaves=len(leaves), buckets=tuple(buckets),
            raw_leaves=raw_leaves, wire_bytes=int(wire),
            raw_bytes=int(raw_total))
        with obs.span("sync:checksum"):
            update.checksum = update_checksum(update)
        return update

    def ack(self, replica, version: int, epoch: Optional[int] = None) -> bool:
        """Record a replica's applied version (epoch-fenced)."""
        ok = self.store.ack(replica, version, epoch)
        if ok:
            obs.metric("sync_replica_version_lag").set(
                self.store.version - version, replica=str(replica))
        return ok

    def advance_epoch(self) -> int:
        """Fence all acks (trainer restart/restore): next sends go full."""
        self._updates.clear()  # cached updates carry the old epoch
        return self.store.advance_epoch()
