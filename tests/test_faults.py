"""Chaos harness: deterministic fault injection, wire integrity, fleet
recovery.

Quick-gate coverage:
  * ``FaultPlan`` determinism: same seed -> same lifecycle schedule and
    the same per-message fault sequence; different seeds differ;
  * ``FaultyWire`` with ``plan=None`` is a transparent pass-through;
    scripted drop/corrupt/delay behave exactly as pinned;
  * every ``SyncUpdate`` (delta/full/raw) carries a payload checksum that
    survives the round trip and catches a single flipped bit;
  * forced full/raw escalation encodes remain bit-exact;
  * ``crc32_tree`` equals the copying formula (dtype, shape, C-order
    bytes) for every dtype and layout, and at every size around the
    pooled chunks' boundaries; reads C-contiguous arrays in place (one
    copy for any other layout); its pool stays under its thread cap;
  * KV wires (``pack_cache``) verify their checksum before decode;
    ``ServeEngine`` rejects corrupt ingests and retries corrupt KV
    shipments within a bounded budget;
  * ``SyncFleet`` recovery: dropped updates/acks retry with backoff,
    corrupted deltas nack -> escalate full -> converge, kill/join,
    trainer restart (checkpoint rewind + epoch fence), quarantine after
    the retry budget, and a full seeded chaos run that replays its
    recovery trace identically and ends bit-exact with zero silent
    corruptions.
"""
import dataclasses
import os
import shutil
import threading
import tracemalloc
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec, integrity
from repro.core.integrity import (WireIntegrityError, crc32_tree, flip_bit)
from repro.core.policy import CompressionPolicy
from repro.runtime.faults import (FaultConfig, FaultEvent, FaultPlan,
                                  FaultyWire, corrupt_payload)
from repro.sync import (FleetConfig, RoutedUpdate, SyncFleet,
                        WeightSyncEngine, apply_update, update_checksum,
                        verify_update)

POL = CompressionPolicy(min_bytes=0)


def make_params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(0, 0.02, (2048,)), jnp.bfloat16),
        "b": jnp.asarray(rng.normal(0, 1, (300,)), jnp.float32),
        "step": jnp.asarray(7, jnp.int32),  # codec-unsupported: raw path
    }


def perturb(params, seed=1):
    rng = np.random.default_rng(seed)

    def f(l):
        lay = codec.LAYOUTS.get(jnp.dtype(l.dtype).name)
        if lay is None:
            return l
        u = lay.uint_dtype
        mask = rng.integers(0, 8, l.shape).astype(np.uint64)
        mask[rng.random(l.shape) > 0.3] = 0
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(l, u) ^ jnp.asarray(mask, u),
            l.dtype)

    return jax.tree.map(f, params)


def bits(a):
    lay = codec.LAYOUTS.get(jnp.dtype(a.dtype).name)
    if lay is not None:
        return jax.lax.bitcast_convert_type(a, lay.uint_dtype)
    return a


def tree_bits_equal(a, b):
    return all(bool(jnp.all(bits(x) == bits(y))) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------------------
# FaultPlan determinism
# ---------------------------------------------------------------------------

def test_fault_plan_deterministic():
    cfg = FaultConfig(seed=3, rounds=10, drop_rate=0.2, corrupt_rate=0.2,
                      delay_rate=0.2, kills=2, joins=1, trainer_restarts=1,
                      replicas=("a", "b", "c"))
    p1, p2 = FaultPlan.generate(cfg), FaultPlan.generate(cfg)
    assert p1.events == p2.events and len(p1.events) == 4
    seq1 = [p1.message_fault(r) for r in range(1, 9) for _ in range(6)]
    seq2 = [p2.message_fault(r) for r in range(1, 9) for _ in range(6)]
    assert seq1 == seq2
    assert any(f is not None for f in seq1)
    p3 = FaultPlan.generate(dataclasses.replace(cfg, seed=4))
    seq3 = [p3.message_fault(r) for r in range(1, 9) for _ in range(6)]
    assert seq1 != seq3 or p1.events != p3.events


def test_fault_plan_horizon_and_scripted():
    cfg = FaultConfig(seed=0, rounds=4, drop_rate=1.0)
    plan = FaultPlan.generate(cfg)
    assert plan.message_fault(1) == ("drop", 0)
    assert plan.message_fault(5) is None  # past the horizon: quiet wire
    sp = FaultPlan.scripted({0: "drop", 2: ("delay", 3), 3: "corrupt"})
    assert sp.message_fault(1) == ("drop", 0)
    assert sp.message_fault(1) is None
    assert sp.message_fault(1) == ("delay", 3)
    assert sp.message_fault(1) == ("corrupt", 0)
    with pytest.raises(ValueError):
        FaultPlan.scripted({0: "explode"})


# ---------------------------------------------------------------------------
# FaultyWire
# ---------------------------------------------------------------------------

def test_faulty_wire_disabled_is_passthrough():
    w = FaultyWire(None)
    w.send("r0", {"x": 1})
    w.send("r0", {"x": 2})
    w.send("r1", {"x": 3})
    assert w.drain("r0") == [{"x": 1}, {"x": 2}]
    assert w.drain("r1", with_flags=True) == [({"x": 3}, False)]
    assert w.drain("r0") == [] and w.pending() == 0
    assert all(c == 0 for c in w.counts.values())


def test_faulty_wire_drop_and_delay():
    w = FaultyWire(FaultPlan.scripted({0: "drop", 1: ("delay", 2)}))
    w.send("r0", "lost")
    w.send("r0", "late")
    w.send("r0", "now")
    assert w.drain("r0") == ["now"]
    w.advance_round()  # round 1: delay not yet mature
    assert w.drain("r0") == []
    w.advance_round()  # round 2: matures
    assert w.drain("r0") == ["late"]
    assert w.counts == {"drop": 1, "corrupt": 0, "delay": 1}
    assert w.pending() == 0


def test_faulty_wire_corrupts_copies_not_originals():
    eng = WeightSyncEngine(policy=POL)
    params = make_params()
    eng.publish(params)
    update = eng.update_for("r0")
    w = FaultyWire(FaultPlan.scripted({0: "corrupt"}))
    w.send("r0", update)
    [(bad, flag)] = w.drain("r0", with_flags=True)
    assert flag and not verify_update(bad)
    # the memoized original must be untouched (it is shared across sends)
    assert verify_update(update)
    assert tree_bits_equal(apply_update(update), params)


def test_corrupt_payload_control_messages_pass_through():
    rng = np.random.default_rng(0)
    assert corrupt_payload({"type": "ack", "version": 3}, rng) is None


# ---------------------------------------------------------------------------
# SyncUpdate integrity envelope + escalation encodes
# ---------------------------------------------------------------------------

def test_update_checksum_roundtrip_all_modes():
    eng = WeightSyncEngine(policy=POL)
    params = make_params()
    v1 = eng.publish(params)
    for force in (None, "full", "raw"):
        u = eng.update_for("r0", force=force)
        assert u.checksum is not None and verify_update(u)
        assert tree_bits_equal(apply_update(u), params)
    eng.ack("r0", v1)
    p2 = perturb(params)
    eng.publish(p2)
    d = eng.update_for("r0")
    assert d.mode == "delta" and verify_update(d)
    assert tree_bits_equal(apply_update(d, base_params=params), p2)


def test_forced_raw_ships_every_bucket_raw():
    eng = WeightSyncEngine(policy=POL)
    params = make_params()
    eng.publish(params)
    u = eng.update_for("r0", force="raw")
    assert all(mode == "raw" for _, _, mode, _ in u.buckets)
    assert tree_bits_equal(apply_update(u), params)
    with pytest.raises(ValueError, match="force"):
        eng.update_for("r0", force="banana")


def test_corrupted_update_fails_verify():
    eng = WeightSyncEngine(policy=POL)
    eng.publish(make_params())
    u = eng.update_for("r0")
    rng = np.random.default_rng(5)
    for _ in range(8):  # any flipped bit must be caught
        bad = corrupt_payload(u, rng)
        assert bad is not None
        assert not verify_update(bad)
    assert verify_update(u)  # original untouched


def test_crc32_tree_sensitivity():
    a = {"x": np.arange(8, dtype=np.float32), "y": (1, "s")}
    assert crc32_tree(a) == crc32_tree(
        {"x": np.arange(8, dtype=np.float32), "y": (1, "s")})
    b = {"x": flip_bit(a["x"], 17), "y": (1, "s")}
    assert crc32_tree(a) != crc32_tree(b)
    # dtype/shape are covered, not just bytes
    assert crc32_tree(np.zeros(4, np.float32)) != crc32_tree(
        np.zeros(2, np.float64))


def _crc32_tree_copying(obj, seed=0):
    """The checksum's formula as first written: each array copied into C
    order and then into a ``bytes`` object.  ``crc32_tree`` must agree
    with it bit for bit, whatever way it reads the bytes."""
    c = seed & 0xFFFFFFFF

    def visit(o):
        nonlocal c
        if o is None or isinstance(o, (bool, int, float, str)):
            c = zlib.crc32(repr(o).encode(), c)
        elif isinstance(o, bytes):
            c = zlib.crc32(o, c)
        elif isinstance(o, (list, tuple)):
            for x in o:
                visit(x)
        elif isinstance(o, dict):
            for k in sorted(o, key=repr):
                visit(k)
                visit(o[k])
        elif hasattr(o, "shape") and hasattr(o, "dtype"):
            arr = np.ascontiguousarray(np.asarray(o))
            c = zlib.crc32(str(arr.dtype).encode(), c)
            c = zlib.crc32(repr(arr.shape).encode(), c)
            c = zlib.crc32(arr.tobytes(), c)
        elif dataclasses.is_dataclass(o) and not isinstance(o, type):
            for f in dataclasses.fields(o):
                visit(getattr(o, f.name))
        else:
            c = zlib.crc32(repr(o).encode(), c)

    visit(obj)
    return c


CRC_DTYPES = (np.uint8, np.uint16, np.uint32, np.int32, np.bool_,
              np.float32, jnp.bfloat16, jnp.float8_e4m3fn)


def _random_array(dtype, shape, seed):
    """Random bit patterns of ``dtype`` (NaN payloads included)."""
    dtype = np.dtype(dtype)
    n = int(np.prod(shape))
    raw = np.random.default_rng(seed).integers(
        0, 256, n * dtype.itemsize, dtype=np.uint8)
    if dtype == np.bool_:
        raw &= 1
    return raw.view(dtype).reshape(shape)


CRC_LAYOUTS = {
    "0d": lambda d, s: _random_array(d, (), s),
    "empty": lambda d, s: _random_array(d, (0, 5), s),
    "1d": lambda d, s: _random_array(d, (37,), s),
    "2d_c": lambda d, s: _random_array(d, (6, 7), s),
    "fortran": lambda d, s: np.asfortranarray(_random_array(d, (6, 7), s)),
    "strided": lambda d, s: _random_array(d, (12, 14), s)[::2, 1::3],
}


@pytest.mark.parametrize("layout", sorted(CRC_LAYOUTS))
@pytest.mark.parametrize("dtype", CRC_DTYPES,
                         ids=lambda d: np.dtype(d).name)
def test_crc32_tree_matches_copying_formula(dtype, layout):
    a = CRC_LAYOUTS[layout](dtype, 3)
    b = CRC_LAYOUTS[layout](dtype, 4)
    assert crc32_tree(a) == _crc32_tree_copying(a)
    # seeds chain exactly as the formula's do, across arrays and trees
    chained = crc32_tree(b, seed=crc32_tree(a, seed=0xDEADBEEF))
    assert chained == _crc32_tree_copying(
        b, seed=_crc32_tree_copying(a, seed=0xDEADBEEF))
    assert crc32_tree({"a": a, "b": (b, 1)}) == _crc32_tree_copying(
        {"a": a, "b": (b, 1)})


def test_crc32_tree_matches_copying_formula_jax_array():
    x = jnp.asarray(_random_array(np.float32, (5, 7), 6), jnp.bfloat16)
    assert crc32_tree(x) == _crc32_tree_copying(x)
    assert crc32_tree(x) == crc32_tree(np.asarray(x))


def test_crc32_tree_matches_copying_formula_delta_message():
    from repro.core import packing

    rng = np.random.default_rng(7)
    base = jnp.asarray(rng.normal(0, 0.02, 4096), jnp.bfloat16)
    new = perturb({"w": base})["w"]
    msg = jax.device_get(packing.encode_delta(new, base, width=2,
                                              lo_width=3))
    assert crc32_tree(msg) == _crc32_tree_copying(msg)
    assert crc32_tree(msg, seed=11) == _crc32_tree_copying(msg, seed=11)


SMALL_CHUNK = 4096

CRC_CHUNKED = {
    "0": lambda s: _random_array(np.uint8, (0,), s),
    "1": lambda s: _random_array(np.uint8, (1,), s),
    "chunk-1": lambda s: _random_array(np.uint8, (SMALL_CHUNK - 1,), s),
    "chunk": lambda s: _random_array(np.uint8, (SMALL_CHUNK,), s),
    "chunk+1": lambda s: _random_array(np.uint8, (SMALL_CHUNK + 1,), s),
    "2chunk": lambda s: _random_array(np.uint8, (2 * SMALL_CHUNK,), s),
    "2chunk+1": lambda s: _random_array(np.uint8, (2 * SMALL_CHUNK + 1,), s),
    "7chunk+13": lambda s: _random_array(np.uint8, (7 * SMALL_CHUNK + 13,), s),
    "bf16_2d": lambda s: _random_array(jnp.bfloat16, (96, 130), s),
    "fortran": lambda s: np.asfortranarray(
        _random_array(np.float32, (96, 130), s)),
    "0d": lambda s: _random_array(np.float32, (), s),
}


@pytest.mark.parametrize("case", sorted(CRC_CHUNKED))
def test_crc32_tree_matches_copying_formula_chunked(case, monkeypatch):
    """Arrays of two or more whole chunks are hashed on the pool and the
    chunk CRCs folded: the value is the single call's, at every size
    around the chunk boundaries, for every layout, and chained from any
    seed."""
    monkeypatch.setattr(integrity, "_CRC_CHUNK", SMALL_CHUNK)
    a = CRC_CHUNKED[case](5)
    b = CRC_CHUNKED[case](6)
    assert crc32_tree(a) == _crc32_tree_copying(a)
    chained = crc32_tree(b, seed=crc32_tree(a, seed=0xDEADBEEF))
    assert chained == _crc32_tree_copying(
        b, seed=_crc32_tree_copying(a, seed=0xDEADBEEF))
    assert crc32_tree({"a": a, "b": (b, 1)}, seed=0x12345678) == \
        _crc32_tree_copying({"a": a, "b": (b, 1)}, seed=0x12345678)


def test_crc32_tree_matches_copying_formula_real_chunk():
    a = _random_array(np.uint8, (3 * integrity._CRC_CHUNK + 5,), 8)
    assert crc32_tree(a) == _crc32_tree_copying(a)
    assert crc32_tree(a, seed=0xC0FFEE) == _crc32_tree_copying(
        a, seed=0xC0FFEE)


@pytest.mark.parametrize("nbytes", [1, 5, SMALL_CHUNK, SMALL_CHUNK + 3,
                                    "real"])
def test_crc32_append_zeros_combines_chunks(nbytes):
    """``M_L(c) ^ crc(B) == crc(B, c)`` for ``len(B) == L``, over random
    seeds and contents: the fold of chunk CRCs is exact."""
    if nbytes == "real":
        nbytes = integrity._CRC_CHUNK
    rng = np.random.default_rng(nbytes)
    for _ in range(4):
        chunk = rng.integers(0, 256, nbytes, dtype=np.uint8)
        for c in (0, 0xFFFFFFFF, *rng.integers(0, 1 << 32, 6).tolist()):
            assert (integrity._append_zeros(c, nbytes) ^ zlib.crc32(chunk)
                    == zlib.crc32(chunk, c))


def test_crc32_pool_stays_under_its_cap(monkeypatch):
    monkeypatch.setattr(integrity, "_CRC_CHUNK", SMALL_CHUNK)
    cap = min(integrity._POOL_CAP, len(os.sched_getaffinity(0)))
    assert integrity._POOL._max_workers == cap
    a = _random_array(np.uint8, (64 * SMALL_CHUNK + 1,), 9)
    for _ in range(3):
        assert crc32_tree(a) == _crc32_tree_copying(a)
    assert len(integrity._POOL._threads) <= cap
    assert sum(t.name.startswith("wire-crc")
               for t in threading.enumerate()) <= cap


def test_crc32_tree_concurrent_callers_share_the_pool(monkeypatch):
    """Callers on several threads at once share the one pool and each
    gets its own array's single-call value."""
    import sys

    monkeypatch.setattr(integrity, "_CRC_CHUNK", SMALL_CHUNK)
    arrays = [_random_array(np.uint8, ((9 + i) * SMALL_CHUNK + i,), 20 + i)
              for i in range(12)]
    want = [_crc32_tree_copying(a) for a in arrays]
    got = [None] * len(arrays)

    def hash_all(i):
        for _ in range(5):
            got[i] = crc32_tree(arrays[i])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=hash_all, args=(i,))
                   for i in range(len(arrays))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == want


def test_crc32_tree_counts_pooled_bytes(monkeypatch):
    """Only the whole chunks of an array of two or more are hashed on the
    pool; a smaller array takes the single call and counts nothing."""
    from repro import obs

    monkeypatch.setattr(integrity, "_CRC_CHUNK", SMALL_CHUNK)
    obs.set_enabled(True)
    obs.reset()
    try:
        crc32_tree(np.zeros(2 * SMALL_CHUNK - 1, np.uint8))
        counters = obs.snapshot()["counters"]
        assert "wire_crc_pooled_bytes_total" not in counters
        assert counters["wire_crc_bytes_total"] == {
            "path=view": 2 * SMALL_CHUNK - 1}
        crc32_tree(np.zeros(3 * SMALL_CHUNK + 7, np.uint8))
        counters = obs.snapshot()["counters"]
        assert counters["wire_crc_pooled_bytes_total"] == {
            "": 3 * SMALL_CHUNK}
        assert counters["wire_crc_bytes_total"] == {
            "path=view": 5 * SMALL_CHUNK + 6}
    finally:
        obs.set_enabled(None)
        obs.reset()


def test_crc32_tree_hashes_in_place():
    """A C-contiguous payload is read where it lies: no copy of its bytes.
    Any other layout is copied into C order once, never twice."""
    from repro import obs

    n = 1 << 24  # 64 MiB of uint32
    flat = np.arange(n, dtype=np.uint32)
    fortran = np.asfortranarray(flat.reshape(4096, 4096))
    obs.set_enabled(True)
    obs.reset()
    try:
        peaks = []
        for arr in (flat, fortran):
            tracemalloc.start()
            try:
                crc32_tree(arr)
                peaks.append(tracemalloc.get_traced_memory()[1])
            finally:
                tracemalloc.stop()
        assert peaks[0] < 1 << 20, peaks
        assert fortran.nbytes <= peaks[1] < 1.5 * fortran.nbytes, peaks
        counted = obs.snapshot()["counters"]["wire_crc_bytes_total"]
        assert counted == {"path=view": flat.nbytes,
                           "path=copy": fortran.nbytes}
    finally:
        obs.set_enabled(None)
        obs.reset()


# ---------------------------------------------------------------------------
# KV-wire integrity + serve-side recovery
# ---------------------------------------------------------------------------

def _kv_cache():
    rng = np.random.default_rng(2)
    return {"k": jnp.asarray(rng.normal(0, 1, (4, 64)), jnp.bfloat16),
            "v": jnp.asarray(rng.normal(0, 1, (4, 64)), jnp.bfloat16),
            "pos": jnp.asarray(3, jnp.int32)}


def test_kv_wire_checksum_detects_corruption():
    from repro.p2p.engine import Compressor
    from repro.serve.kv_transfer import pack_cache, unpack_cache, verify_wire

    cache = _kv_cache()
    comp = Compressor(codec_name="packed")
    wire = pack_cache(cache, comp)
    assert verify_wire(wire)
    out = unpack_cache(wire, comp)
    assert tree_bits_equal(out, cache)
    bad = corrupt_payload(wire, np.random.default_rng(1))
    assert bad is not None and not verify_wire(bad)
    with pytest.raises(WireIntegrityError):
        unpack_cache(bad, comp)
    # original survives its corrupted copy
    assert verify_wire(wire)


def test_serve_ingest_rejects_corrupt_update():
    from repro import configs
    from repro.models import transformer
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = configs.get_smoke("smollm_135m")
    p = transformer.init(jax.random.PRNGKey(0), cfg)
    serve = ServeEngine(cfg, p, ServeConfig(batch_slots=1, max_len=32))
    sync = WeightSyncEngine(policy=POL)
    sync.publish(p)
    u = sync.update_for("serve")
    bad = corrupt_payload(u, np.random.default_rng(3))
    with pytest.raises(WireIntegrityError):
        serve.ingest_weights(bad)
    assert serve.weight_version is None  # nothing applied
    serve.ingest_weights(u)  # the intact original still lands
    assert serve.weight_version == u.version


def test_serve_kv_ship_retries_on_corruption():
    from repro import configs
    from repro.models import transformer
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = configs.get_smoke("smollm_135m")
    p = transformer.init(jax.random.PRNGKey(0), cfg)
    serve = ServeEngine(cfg, p, ServeConfig(batch_slots=1, max_len=32,
                                            pd_disaggregated=True))
    cache = transformer.init_cache(cfg, 1, 32)
    hits = {"n": 0}
    rng = np.random.default_rng(4)

    def injector(wire):  # corrupt the first shipment only
        hits["n"] += 1
        if hits["n"] == 1:
            return corrupt_payload(wire, rng) or wire
        return wire

    serve.kv_fault_injector = injector
    out = serve._ship_kv(cache)
    assert hits["n"] == 2  # one reject, one clean retry
    assert tree_bits_equal(out, cache)

    # exhaustion: every try corrupted -> bounded failure, no silent apply
    hits["n"] = 0
    serve.kv_fault_injector = lambda w: corrupt_payload(w, rng) or w
    with pytest.raises(WireIntegrityError, match="times"):
        serve._ship_kv(cache)


# ---------------------------------------------------------------------------
# SyncFleet recovery
# ---------------------------------------------------------------------------

def fleet_fixture(tmpdir, names=("r0", "r1"), plan=None, **cfg_kw):
    eng = WeightSyncEngine(policy=POL)
    cfg = FleetConfig(ckpt_dir=str(tmpdir), **cfg_kw)
    return SyncFleet(eng, names, cfg=cfg, fault_plan=plan)


def test_fleet_happy_path_delta_after_ack(tmp_path):
    fleet = fleet_fixture(tmp_path)
    p1 = make_params()
    fleet.publish(p1)
    assert fleet.settle() == 1
    assert fleet.verify_bitexact()
    p2 = perturb(p1)
    fleet.publish(p2)
    fleet.settle()
    assert fleet.verify_bitexact()
    # second round trip rode the delta wire (both replicas had acked v1)
    assert all(r.applied == 2 for r in fleet.replicas.values())
    assert fleet.stats["retries"] == 0 and fleet.stats["nacks"] == 0


def test_fleet_dropped_update_times_out_and_retries(tmp_path):
    # 2 replicas; round 1 msgs: 0,1 = updates, 2,3 = acks.  Drop r0's
    # update: r0 times out, backs off one round, then recovers.
    plan = FaultPlan.scripted({0: "drop"})
    fleet = fleet_fixture(tmp_path, plan=plan)
    fleet.publish(make_params())
    rounds = fleet.settle()
    assert rounds >= 2  # the drop cost at least one extra round
    assert fleet.verify_bitexact()
    assert fleet.stats["timeouts"] == 1 and fleet.stats["retries"] == 1
    assert fleet.stats["escalations"] == 0  # timeouts do not escalate


def test_fleet_dropped_ack_is_reacked_idempotently(tmp_path):
    # drop r0's ACK (msg 2): the trainer re-sends; the replica holds the
    # version already and must re-ack without re-applying
    plan = FaultPlan.scripted({2: "drop"})
    fleet = fleet_fixture(tmp_path, plan=plan)
    fleet.publish(make_params())
    fleet.settle()
    assert fleet.verify_bitexact()
    r0 = fleet.replicas["r0"]
    assert r0.applied == 1 and r0.stale_seen == 1


def test_fleet_corrupted_delta_escalates_to_full(tmp_path):
    # round 1 clean (both ack v1); corrupt a delta of v2: nack ->
    # escalate to full -> converge
    plan = FaultPlan.scripted({4: "corrupt"})
    fleet = fleet_fixture(tmp_path)
    fleet.wire.plan = plan  # message faults only from round 2 on
    p1 = make_params()
    fleet.publish(p1)
    fleet.settle()
    fleet.publish(perturb(p1))
    fleet.settle()
    assert fleet.verify_bitexact()
    led = fleet.integrity_ledger()
    assert led["seen"] == led["detected"] == 1 and led["silent"] == 0
    assert fleet.stats["escalations"] == 1
    assert any("escalate" in e for _, e in fleet.trace)


def test_fleet_kill_join_and_full_send_to_joiner(tmp_path):
    plan = FaultPlan(events=[FaultEvent(2, "kill", "r1"),
                             FaultEvent(3, "join", "r2")])
    fleet = fleet_fixture(tmp_path, plan=plan)
    fleet.publish(make_params())
    fleet.settle()  # round 1: both converge
    fleet.round()  # round 2: r1 killed
    assert fleet.live_replicas() == ("r0",)
    fleet.round()  # round 3: r2 joins, receives the full wire
    fleet.settle()
    assert fleet.live_replicas() == ("r0", "r2")
    assert fleet.verify_bitexact()
    assert fleet.replicas["r2"].applied == 1
    assert fleet.replicas["r1"].params is None  # its memory is gone


def test_fleet_trainer_restart_rewinds_and_fences(tmp_path):
    plan = FaultPlan(events=[FaultEvent(4, "trainer_restart")])
    fleet = fleet_fixture(tmp_path, plan=plan, ckpt_every_publishes=2)
    p = make_params()
    versions = []
    for i in range(3):  # snapshots at publish 2 only
        p = perturb(p, seed=10 + i)
        versions.append(fleet.publish(p))
        fleet.round()
    assert fleet.engine.store.version == 3
    fleet.round()  # round 4: restart -> restore rewinds v3 -> v2
    assert fleet.engine.store.version == 2
    assert fleet.engine.store.epoch == 1  # fenced
    fleet.settle()
    assert fleet.stats["trainer_restarts"] == 1
    assert fleet.verify_bitexact()  # replicas rolled back to v2 bits
    for r in fleet.replicas.values():
        assert r.epoch == 1  # every survivor re-acked under the new epoch


def test_fleet_quarantine_bounds_retries(tmp_path):
    # every update corrupted forever: the replica nacks until the budget
    # is spent, then is quarantined; the fleet converges trivially
    # (no replicas left owed) instead of wedging
    plan = FaultPlan.scripted({i: "corrupt" for i in range(0, 200, 2)})
    fleet = fleet_fixture(tmp_path, names=("r0",), max_retries=3,
                          backoff_base=0, backoff_cap=1, plan=plan)
    fleet.publish(make_params())
    fleet.settle(max_rounds=50)
    assert fleet.stats["quarantines"] == 1
    assert fleet._links["r0"].quarantined
    assert fleet.stats["max_link_failures"] == 4  # budget + the last straw
    led = fleet.integrity_ledger()
    assert led["silent"] == 0 and led["detected"] == led["seen"]


def _chaos_run(tmpdir, seed):
    shutil.rmtree(tmpdir, ignore_errors=True)
    names = ("r0", "r1", "r2")
    cfg = FaultConfig(seed=seed, rounds=10, drop_rate=0.12,
                      corrupt_rate=0.12, delay_rate=0.12, max_delay=2,
                      kills=1, joins=1, trainer_restarts=1, replicas=names)
    fleet = fleet_fixture(tmpdir, names=names,
                          plan=FaultPlan.generate(cfg),
                          ckpt_every_publishes=2)
    p = make_params(seed=seed)
    for r in range(10):
        if r % 2 == 0:
            p = perturb(p, seed=100 + r)
            fleet.publish(p)
        fleet.round()
    fleet.settle()
    return fleet


def test_fleet_chaos_is_deterministic_and_lossless(tmp_path):
    f1 = _chaos_run(str(tmp_path / "a"), seed=13)
    f2 = _chaos_run(str(tmp_path / "b"), seed=13)
    # same seed -> the same injected faults and the SAME recovery trace
    assert f1.trace == f2.trace
    assert f1.stats == f2.stats and f1.wire.counts == f2.wire.counts
    for fleet in (f1, f2):
        assert fleet.converged() and fleet.verify_bitexact()
        led = fleet.integrity_ledger()
        assert led["silent"] == 0
        assert led["injected"] == led["seen"] + led["lost"]
        assert fleet.stats["quarantines"] == 0
        assert fleet.stats["max_link_failures"] <= fleet.cfg.max_retries
        assert fleet.stats["trainer_restarts"] == 1
    # a different seed yields a different schedule
    f3 = _chaos_run(str(tmp_path / "c"), seed=14)
    assert f3.trace != f1.trace or f3.wire.counts != f1.wire.counts


def test_fleet_obs_accounting(tmp_path):
    # every injected fault is visible in the obs counters
    from repro import obs

    obs.set_enabled(True)
    obs.reset()
    try:
        # msg 0 = r0's update (corrupt -> nack -> escalate), msg 3 =
        # r1's ack (drop -> timeout retry)
        plan = FaultPlan.scripted({0: "corrupt", 3: "drop"})
        fleet = fleet_fixture(tmp_path, plan=plan)
        fleet.publish(make_params())
        fleet.settle()
        assert fleet.verify_bitexact()
        counters = obs.snapshot()["counters"]
        assert counters["fault_injected_total"]["kind=corrupt"] == 1
        assert counters["fault_injected_total"]["kind=drop"] == 1
        assert counters["sync_integrity_failures_total"][
            "reason=checksum"] == 1
        assert counters["fleet_retries_total"][""] == fleet.stats["retries"]
        assert counters["fleet_escalations_total"]["to=full"] == 1
    finally:
        obs.set_enabled(None)
        obs.reset()


# ---------------------------------------------------------------------------
# Broadcast schedules under chaos: forwarded hops, dead interiors
# ---------------------------------------------------------------------------

def test_corrupt_payload_routed_envelope_targets_inner_wire():
    # corruption of a scheduled delivery damages the forwarded BITS, not
    # the routing envelope — exactly what the next hop's CRC must catch
    eng = WeightSyncEngine(policy=POL)
    eng.publish(make_params())
    update = eng.update_for("r0")
    ru = RoutedUpdate(update, (("r1", ()),), hop=1)
    bad = corrupt_payload(ru, np.random.default_rng(0))
    assert isinstance(bad, RoutedUpdate)
    assert bad.route == ru.route and bad.hop == ru.hop
    assert not verify_update(bad.update)
    assert verify_update(update)  # the shared original is untouched


def test_fleet_corrupted_forward_rejected_at_next_hop(tmp_path):
    # 3-replica pipeline, round-1 ordinals: 0 trainer->r0, 1 r0 ack,
    # 2 r0->r1 forward, 3 r1 ack, 4 r1->r2 forward, 5 r2 ack.  Corrupt
    # the FORWARDED hop (ordinal 2): r1's own CRC check rejects it, and
    # the damage is NOT forwarded on to r2.
    plan = FaultPlan.scripted({2: "corrupt"})
    fleet = fleet_fixture(tmp_path, names=("r0", "r1", "r2"),
                          broadcast="pipeline", plan=plan)
    fleet.publish(make_params())
    fleet.settle()
    assert fleet.verify_bitexact()
    assert fleet.replicas["r1"].rejects["checksum"] == 1
    assert fleet.replicas["r2"].rejects["checksum"] == 0  # never spread
    led = fleet.integrity_ledger()
    assert led["injected"] == led["seen"] == led["detected"] == 1
    assert led["silent"] == 0 and led["lost"] == 0
    assert fleet.stats["escalations"] == 1  # r1 nacked -> full


def test_fleet_dead_interior_reparents_subtree(tmp_path):
    # white-box mid-round kill: the interior node dies AFTER the trainer
    # wired its envelope but BEFORE delivery, so the whole subtree's
    # copies evaporate with it and must re-parent to direct trainer sends
    fleet = fleet_fixture(tmp_path, names=("r0", "r1", "r2"),
                          broadcast="pipeline")
    p1 = make_params()
    fleet.publish(p1)
    fleet.settle()
    fleet.publish(perturb(p1))
    fleet._round += 1
    fleet.wire.advance_round()
    sent = fleet._send_updates()  # one envelope: r0, route r1 -> r2
    assert sent == {"r0", "r1", "r2"}
    fleet.kill("r0")
    fleet._deliver_to_replicas()  # evaporates at dead r0
    fleet._drain_trainer()
    assert fleet._orphans == {"r1", "r2"}
    assert fleet.stats["reparents"] == 2
    assert sum(1 for _, e in fleet.trace if e.startswith("reparent")) == 2
    fleet.settle()  # orphans served direct full sends, then rejoin
    assert fleet._orphans == set()
    assert fleet.verify_bitexact()
    assert fleet.replicas["r0"].params is None  # still dead
    assert fleet.integrity_ledger()["silent"] == 0


def test_fleet_delayed_forward_times_out_then_converges(tmp_path):
    # delay the r1->r2 forwarded envelope one round: r2 times out, the
    # retry and the matured envelope both arrive, the duplicate re-acks
    plan = FaultPlan.scripted({4: ("delay", 1)})
    fleet = fleet_fixture(tmp_path, names=("r0", "r1", "r2"),
                          broadcast="pipeline", plan=plan)
    fleet.publish(make_params())
    assert fleet.settle() == 2
    assert fleet.verify_bitexact()
    assert fleet.stats["timeouts"] == 1
    r2 = fleet.replicas["r2"]
    assert r2.applied == 1 and r2.stale_seen == 1
    assert fleet.integrity_ledger()["silent"] == 0


def test_fleet_delayed_envelope_matures_at_killed_interior(tmp_path):
    # the root envelope is delayed a round, and its holder is killed in
    # the meantime: the matured delivery evaporates at the dead interior
    # and orphans the subtree, which converges through direct re-sends
    plan = FaultPlan.scripted({0: ("delay", 1)},
                              events=[FaultEvent(2, "kill", "r0")])
    fleet = fleet_fixture(tmp_path, names=("r0", "r1", "r2"),
                          broadcast="pipeline", plan=plan)
    fleet.publish(make_params())
    fleet.settle()
    assert fleet.stats["reparents"] == 2  # r1, r2 re-parented via dead r0
    assert fleet.live_replicas() == ("r1", "r2")
    assert fleet.verify_bitexact()
    assert fleet.stats["timeouts"] == 3  # the whole round-1 wave stalled
    assert fleet.integrity_ledger()["silent"] == 0


def test_fleet_corrupt_envelope_lost_at_dead_interior(tmp_path):
    # corrupt + kill on the same envelope: the corruption never reaches a
    # CRC check (the holder is dead) and must be accounted as LOST, while
    # the orphaned subtree still converges bit-exactly
    plan = FaultPlan.scripted({0: "corrupt"})
    fleet = fleet_fixture(tmp_path, names=("r0", "r1", "r2"),
                          broadcast="pipeline", plan=plan)
    fleet.publish(make_params())
    fleet._round += 1
    fleet.wire.advance_round()
    fleet._send_updates()  # ordinal 0: the corrupted envelope to r0
    fleet.kill("r0")
    fleet._deliver_to_replicas()
    fleet._drain_trainer()
    led = fleet.integrity_ledger()
    assert led["injected"] == led["lost"] == 1
    assert led["seen"] == led["detected"] == 0 and led["silent"] == 0
    assert fleet._orphans == {"r1", "r2"}
    fleet.settle()
    assert fleet.verify_bitexact()


@pytest.mark.parametrize("kind,fanout", [("tree", 2), ("pipeline", 1)])
def test_fleet_chaos_broadcast_lossless(tmp_path, kind, fanout):
    # the chaos gate over a scheduled fleet: generated drops/corruptions/
    # delays + lifecycle events across forwarded hops, and still zero
    # silent corruptions, an exact ledger, and bit-exact convergence
    names = ("r0", "r1", "r2", "r3", "r4")
    cfg = FaultConfig(seed=29, rounds=12, drop_rate=0.1, corrupt_rate=0.1,
                      delay_rate=0.1, max_delay=2, kills=1, joins=1,
                      replicas=names)
    fleet = fleet_fixture(tmp_path, names=names, broadcast=kind,
                          fanout=fanout, max_retries=30, backoff_cap=2,
                          plan=FaultPlan.generate(cfg))
    p = make_params()
    for i in range(4):
        p = perturb(p, seed=40 + i)
        fleet.publish(p)
        fleet.settle(max_rounds=60)
    assert fleet.converged() and fleet.verify_bitexact()
    led = fleet.integrity_ledger()
    assert led["silent"] == 0
    assert led["injected"] == led["seen"] + led["lost"]
    assert fleet.stats["forwards"] > 0  # the schedule actually routed
