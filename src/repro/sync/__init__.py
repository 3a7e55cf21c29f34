"""Weight-sync subsystem (paper §5.3.1: RL weight synchronization).

Broadcasts versioned model weights from one trainer to N inference
replicas over the compressed host/P2P wire, with a lossless XOR-delta
transform against the receiver's acked base version (``core/codec.
xor_bits`` + the split+pack delta wire in ``core/packing.py``) and
automatic fallback to full-tensor sends when the base is stale, absent,
or epoch-fenced.  The schedule compiles ONCE into a kind-"wsync"
``CommPlan`` (``sched/compile.compile_wsync_plan``) and is replayed by
``sched.sync_weights_with_plan`` (in-mesh) or :class:`WeightSyncEngine`
(host path) — bit-identical to the planless ``sync/wire.sync_weights``
by construction.  ``serve/engine.ServeEngine.ingest_weights`` hot-swaps
a running decode loop from the stream; ``train/step.make_publish_hook``
bridges the trainer side.

Robustness: every ``SyncUpdate`` carries a payload CRC envelope
(``update_checksum``/``verify_update``), and :class:`SyncFleet`
(``sync/fleet.py``) drives trainer + N replicas through straggler-
tolerant publish/distribute/ack rounds with bounded retries, the
delta -> full -> raw escalation ladder, mid-epoch join/leave, and
checkpointed trainer failover — deterministically replayable under an
injected ``runtime/faults.FaultPlan``.
"""
from repro.sync.engine import (SyncUpdate, WeightSyncEngine, apply_update,
                               update_checksum, verify_update)
from repro.sync.fleet import FleetConfig, Replica, RoutedUpdate, SyncFleet
from repro.sync.store import VersionedStore
from repro.sync.wire import broadcast_weights, sync_weights

__all__ = ["FleetConfig", "Replica", "RoutedUpdate", "SyncFleet",
           "SyncUpdate", "VersionedStore", "WeightSyncEngine",
           "apply_update", "broadcast_weights", "sync_weights",
           "update_checksum", "verify_update"]
