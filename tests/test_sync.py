"""Weight-sync subsystem: delta codec, wsync plans, version protocol.

Quick-gate coverage (1-device meshes + host path):
  * ``codec.xor_bits`` is a bit-exact involution across dtypes, on
    arbitrary bit patterns (NaN payloads / Inf / subnormals included);
  * the delta wire (``packing.encode_delta``/``decode_delta``) round-trips
    warm deltas exactly, degrades to an overflow flag (never silent
    corruption) on cold ones, and its static wire size matches the plan
    compiler's ``eval_shape`` accounting;
  * planless ``sync.wire.sync_weights`` == plan-driven
    ``sched.sync_weights_with_plan``, bit-for-bit, full and delta;
  * kind-"wsync" compiler gating mirrors the policy; plans round-trip
    through ``save_plans``/``load_plans``; repeated broadcasts hit the
    plan cache with zero recompiles;
  * ``VersionedStore`` ack/history/epoch fencing; ``WeightSyncEngine``
    full->ack->delta protocol with late-join, pruned-history, overflow and
    epoch-fence fallbacks; ``ServeEngine.ingest_weights`` hot swap;
    ``train/step.make_publish_hook`` cadence.

8-device broadcast/delta parity lives in tests/drivers/multidev.py
(``wsync`` section, slow gate).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import sched
from repro.core import calibrate, codec, packing
from repro.core import policy as policy_lib
from repro.core.policy import CompressionPolicy
from repro.sync import (VersionedStore, WeightSyncEngine, apply_update,
                        sync_weights)

IDPERM = [(0, 0)]
DTYPES = ["float32", "bfloat16", "float16"]


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1,), ("data",))


def bits(a):
    lay = codec.LAYOUTS.get(jnp.dtype(a.dtype).name)
    if lay is not None:
        return jax.lax.bitcast_convert_type(a, lay.uint_dtype)
    return a


def bits_equal(a, b):
    return bool(jnp.all(bits(a) == bits(b)))


def tree_bits_equal(a, b):
    return all(bits_equal(x, y) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def random_bits(dtype_name, n, seed=0):
    """Arbitrary bit patterns of a float dtype: uniformly covers normals,
    subnormals, zeros, infinities and NaN payloads."""
    lay = codec.LAYOUTS[dtype_name]
    rng = np.random.default_rng(seed)
    npdt = {8: np.uint8, 16: np.uint16, 32: np.uint32}[lay.total_bits]
    raw = rng.integers(0, 2 ** lay.total_bits, n, dtype=np.uint64).astype(npdt)
    return jax.lax.bitcast_convert_type(jnp.asarray(raw), lay.dtype)


def warm_pair(dtype_name, n, seed=0, flip_bits=3):
    """(new, base): base + a sparse low-mantissa-bit XOR — the consecutive-
    optimizer-step shape the delta wire targets."""
    lay = codec.LAYOUTS[dtype_name]
    rng = np.random.default_rng(seed)
    base = jnp.asarray(rng.normal(0, 0.02, n), lay.dtype)
    mask = rng.integers(0, 1 << flip_bits, n).astype(np.uint64)
    mask[rng.random(n) > 0.3] = 0  # most weights unchanged
    u = lay.uint_dtype
    new = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(base, u) ^ jnp.asarray(mask, u),
        lay.dtype)
    return new, base


def make_params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "wq": jnp.asarray(rng.normal(0, 0.02, (64, 48)), jnp.bfloat16),
        "wk": jnp.asarray(rng.normal(0, 0.02, (1536,)), jnp.bfloat16),
        "norm": jnp.asarray(rng.normal(0, 1, (300,)), jnp.float32),
        "step": jnp.asarray(7, jnp.int32),  # codec-unsupported: raw path
    }


def perturb_params(params, seed=1, flip_bits=3):
    rng = np.random.default_rng(seed)

    def f(l):
        lay = codec.LAYOUTS.get(jnp.dtype(l.dtype).name)
        if lay is None:
            return l
        u = lay.uint_dtype
        mask = rng.integers(0, 1 << flip_bits, l.shape).astype(np.uint64)
        mask[rng.random(l.shape) > 0.3] = 0
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(l, u) ^ jnp.asarray(mask, u),
            l.dtype)

    return jax.tree.map(f, params)


def _shmap(fn, mesh, n_in=1, n_out=2):
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * n_in,
                         out_specs=(P(),) * n_out, axis_names={"data"},
                         check_vma=False)


POL = CompressionPolicy(min_bytes=0)


# ---------------------------------------------------------------------------
# xor_bits: bit-exact involution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype_name", DTYPES + ["float8_e4m3fn",
                                                 "float8_e5m2"])
def test_xor_delta_involution_arbitrary_bits(dtype_name):
    x = random_bits(dtype_name, 4096, seed=1)
    b = random_bits(dtype_name, 4096, seed=2)
    d = codec.xor_bits(x, b)
    assert d.dtype == codec.LAYOUTS[dtype_name].uint_dtype
    assert bool(jnp.all((d ^ bits(b)) == bits(x)))
    # delta against self is exactly zero bits
    assert bool(jnp.all(codec.xor_bits(x, x) == 0))


def test_xor_delta_rejects_mismatch():
    with pytest.raises(ValueError):
        codec.xor_bits(jnp.zeros((4,), jnp.float32),
                       jnp.zeros((4,), jnp.bfloat16))
    with pytest.raises(ValueError):
        codec.xor_bits(jnp.zeros((4,), jnp.float32),
                       jnp.zeros((8,), jnp.float32))


# ---------------------------------------------------------------------------
# delta wire: roundtrip, specials, degenerate + overflow semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype_name", DTYPES)
@pytest.mark.parametrize("n", [512, 4096, 5000])  # incl. non-block-multiple
def test_delta_message_roundtrip_warm(dtype_name, n):
    new, base = warm_pair(dtype_name, n)
    w, wl = POL.delta_widths(dtype_name)
    m = packing.encode_delta(new, base, width=w, lo_width=wl)
    assert int(m.overflow) == 0
    assert bits_equal(packing.decode_delta(m, base), new)


@pytest.mark.parametrize("dtype_name", DTYPES)
def test_delta_message_nan_inf_subnormal_payloads(dtype_name):
    """Specials in EITHER operand survive bitwise: NaN payloads, signed
    infinities, subnormals, signed zeros."""
    lay = codec.LAYOUTS[dtype_name]
    u = lay.uint_dtype
    rng = np.random.default_rng(3)
    base = jnp.asarray(rng.normal(0, 0.02, 2048), lay.dtype)
    new_bits = np.asarray(jax.lax.bitcast_convert_type(base, u)).copy()
    exp_mask = ((1 << lay.exp_bits) - 1) << lay.mant_bits
    new_bits[7] = exp_mask | 0b101  # NaN with a payload
    new_bits[100] = exp_mask  # +Inf
    new_bits[200] = (1 << (lay.total_bits - 1)) | exp_mask  # -Inf
    new_bits[300] = 1  # smallest subnormal
    new_bits[400] = 1 << (lay.total_bits - 1)  # -0.0
    new = jax.lax.bitcast_convert_type(jnp.asarray(new_bits), lay.dtype)
    # the specials differ from base in high bits -> they ride exceptions
    m = packing.encode_delta(new, base, width=2, lo_width=2)
    assert int(m.overflow) == 0
    assert bits_equal(packing.decode_delta(m, base), new)
    # and specials in the BASE cancel exactly too
    m2 = packing.encode_delta(new, new, width=1, lo_width=1)
    assert bits_equal(packing.decode_delta(m2, new), new)


@pytest.mark.parametrize("dtype_name", DTYPES)
def test_delta_message_zero_delta_degenerate(dtype_name):
    """Identical versions: the delta is all-zero, packs at the minimum
    widths with zero exceptions, and round-trips."""
    x = random_bits(dtype_name, 4096, seed=5)
    m = packing.encode_delta(x, x, width=1, lo_width=1)
    assert int(m.overflow) == 0
    assert int(jnp.sum(m.lo.exc_idx < 4096)) == 0  # no lo exceptions used
    assert bits_equal(packing.decode_delta(m, x), x)


def test_delta_message_overflow_flag_on_cold_delta():
    """Uncorrelated versions at warm widths: the exception lists overflow
    and the flag says so — the sender must fall back to a full send."""
    x = random_bits("bfloat16", 8192, seed=6)
    b = random_bits("bfloat16", 8192, seed=7)
    m = packing.encode_delta(x, b, width=1, lo_width=1)
    assert int(m.overflow) == 1


def _float_bitcasts(fn, *args) -> int:
    """Number of uint->float bitcasts in ``fn``'s jaxpr, nested ones too."""
    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "bitcast_convert_type"
                    and jnp.issubdtype(eqn.outvars[0].aval.dtype,
                                       jnp.floating)):
                n += 1
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    n += walk(inner)
        return n
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("dtype_name", DTYPES)
def test_delta_codec_never_holds_the_delta_as_a_float(dtype_name):
    """The XOR delta of two nearby floats is mostly subnormal as a float,
    and a TPU flushes subnormals where a program unpacks them: the codec
    keeps the delta as bits, and only decode's result is a float again."""
    new, base = warm_pair(dtype_name, 1024)
    w, wl = POL.delta_widths(dtype_name)
    enc = lambda a, b: packing.encode_delta(a, b, width=w, lo_width=wl)
    assert _float_bitcasts(enc, new, base) == 0
    m = enc(new, base)
    assert _float_bitcasts(packing.decode_delta, m, base) == 1


def test_delta_wire_bytes_matches_eval_shape():
    """The plan compiler's eval_shape accounting IS the encoder's output."""
    n = 2048
    from repro.sched.compile import delta_wire_bytes

    new, base = warm_pair("bfloat16", n)
    m = packing.encode_delta(new, base, width=2, lo_width=4)
    assert delta_wire_bytes(n, jnp.bfloat16, width=2, lo_width=4, block=512,
                            exc_frac=0.02) == m.wire_bytes()


def test_pack_delta_plane_exceptions_exact():
    """Element-granular exceptions restore outliers exactly."""
    rng = np.random.default_rng(8)
    vals = rng.integers(0, 4, 2048).astype(np.uint32)
    vals[[3, 77, 500]] = [1 << 20, (1 << 24) - 1, 5000]  # carry-tail outliers
    p = packing.pack_delta_plane(jnp.asarray(vals), 2)
    assert int(p.overflow) == 0
    assert np.array_equal(np.asarray(packing.unpack_delta_plane(p)), vals)


def test_choose_delta_widths_warm_vs_cold():
    new, base = warm_pair("bfloat16", 1 << 15, flip_bits=2)
    w, wl = calibrate.choose_delta_widths(new, base)
    assert 1 <= w <= 3 and 1 <= wl <= 4  # warm: narrow widths
    cold = random_bits("bfloat16", 1 << 15, seed=9)
    w2, wl2 = calibrate.choose_delta_widths(cold, base)
    assert wl2 >= 7  # cold: the lo plane is incompressible


# ---------------------------------------------------------------------------
# in-mesh wires: delta_send + sync_weights, planless vs plan-driven
# ---------------------------------------------------------------------------

def test_delta_send_bit_exact(mesh):
    from repro.core.split_send import delta_send

    new, base = warm_pair("bfloat16", 5000)  # ragged: pads to block
    out, flag = jax.jit(_shmap(
        lambda x, b: delta_send(x, b, "data", IDPERM, width=2, lo_width=4),
        mesh, n_in=2))(new, base)
    assert bits_equal(out, new) and int(flag) == 0


def test_sync_weights_full_and_delta_bit_exact(mesh):
    params = make_params()
    new = perturb_params(params)

    full, f1 = jax.jit(_shmap(
        lambda t: sync_weights(t, "data", IDPERM, policy=POL), mesh))(new)
    assert tree_bits_equal(full, new) and int(f1) == 0

    delta, f2 = jax.jit(_shmap(
        lambda t, b: sync_weights(t, "data", IDPERM, policy=POL, base=b),
        mesh, n_in=2))(new, params)
    assert tree_bits_equal(delta, new) and int(f2) == 0


def test_sync_weights_arbitrary_bits_full_and_max_width_delta(mesh):
    """End-to-end bit preservation on pathological payloads: a tree of
    arbitrary bit patterns (sNaN payloads included) survives the full
    in-mesh broadcast, and the delta wire at MAXIMUM widths is lossless on
    ANY data (every element fits, no exceptions needed)."""
    lay = codec.LAYOUTS["bfloat16"]
    tree = {"a": random_bits("bfloat16", 1024, seed=21).reshape(32, 32),
            "b": random_bits("bfloat16", 512, seed=22)}
    base = {"a": random_bits("bfloat16", 1024, seed=23).reshape(32, 32),
            "b": random_bits("bfloat16", 512, seed=24)}
    full, f1 = jax.jit(_shmap(
        lambda t: sync_weights(t, "data", IDPERM, policy=POL), mesh))(tree)
    assert tree_bits_equal(full, tree) and int(f1) == 0
    prof = dataclasses.replace(POL.profile, widths=dict(
        POL.profile.widths, delta=lay.exp_bits, delta_lo=lay.lo_bits))
    wide = dataclasses.replace(POL, profile=prof)
    delta, f2 = jax.jit(_shmap(
        lambda t, b: sync_weights(t, "data", IDPERM, policy=wide, base=b),
        mesh, n_in=2))(tree, base)
    assert tree_bits_equal(delta, tree) and int(f2) == 0


def test_sync_weights_plan_parity(mesh):
    """Plan-driven == planless, bit-for-bit, full AND delta — the wsync
    bit-parity contract (shared wsync_dispatch seam)."""
    params = make_params()
    new = perturb_params(params)
    cache = sched.PlanCache()

    def f(t, b):
        a1, f1 = sync_weights(t, "data", IDPERM, policy=POL)
        a2, f2 = sched.sync_weights_with_plan(t, "data", IDPERM, policy=POL,
                                              cache=cache)
        d1, f3 = sync_weights(t, "data", IDPERM, policy=POL, base=b)
        d2, f4 = sched.sync_weights_with_plan(t, "data", IDPERM, policy=POL,
                                              base=b, cache=cache)
        flag = jnp.maximum(jnp.maximum(f1, f2), jnp.maximum(f3, f4))
        return a1, a2, d1, d2, flag

    a1, a2, d1, d2, flag = jax.jit(_shmap(f, mesh, n_in=2, n_out=5))(
        new, params)
    assert tree_bits_equal(a1, a2) and tree_bits_equal(d1, d2)
    assert tree_bits_equal(a1, new) and tree_bits_equal(d1, new)
    assert int(flag) == 0
    # full and delta share ONE plan (delta-vs-full is runtime routing)
    assert cache.stats.misses == 1 and cache.stats.hits >= 1


def test_sync_weights_plan_consolidated_report(mesh):
    params = make_params()
    new = perturb_params(params)
    policy_lib.clear_wire_reports()
    jax.jit(_shmap(
        lambda t, b: sched.sync_weights_with_plan(
            t, "data", IDPERM, policy=POL, base=b, cache=sched.PlanCache()),
        mesh, n_in=2))(new, params)
    reps = [r for r in policy_lib.wire_reports() if r.name == "plan:wsync"]
    assert len(reps) == 1
    # totals equal the planless per-wire records
    policy_lib.clear_wire_reports()
    jax.jit(_shmap(
        lambda t, b: sync_weights(t, "data", IDPERM, policy=POL, base=b),
        mesh, n_in=2))(new, params)
    loose = policy_lib.wire_reports()
    assert reps[0].wire_bytes == sum(r.wire_bytes for r in loose)
    assert reps[0].raw_bytes == sum(r.raw_bytes for r in loose)
    policy_lib.clear_wire_reports()


def test_execute_wsync_rejects_mismatched_tree(mesh):
    params = make_params()
    plan = sched.compile_wsync_plan(params, "data", policy=POL, n_dev=1)
    bad = dict(params, wk=jnp.zeros((64,), jnp.bfloat16))
    with pytest.raises(AssertionError, match="plan"):
        jax.jit(_shmap(
            lambda t: sched.execute_wsync(plan, t, "data", IDPERM),
            mesh))(bad)


# ---------------------------------------------------------------------------
# wsync plan compiler
# ---------------------------------------------------------------------------

def test_wsync_plan_structure_and_gating():
    params = make_params()
    plan = sched.compile_wsync_plan(params, "data", policy=POL, n_dev=1)
    assert plan.kind == "wsync" and plan.strategy == "split_send"
    assert plan.n_leaves == 4 and len(plan.raw_leaf_ix) == 1  # int32 step
    by_dt = {b.dtype_name: b for b in plan.buckets}
    assert set(by_dt) == {"bfloat16", "float32"}
    for name, b in by_dt.items():
        assert b.path == "compressed"
        assert b.width == POL.width_for("weight")
        assert (b.delta_width, b.delta_lo_width) == POL.delta_widths(name)
        assert 0 < b.delta_wire_bytes < b.raw_bytes
    s = plan.summary()
    assert s["n_delta"] == 2 and s["delta_wire_bytes"] == sum(
        b.delta_wire_bytes for b in plan.buckets)
    # gated off: below min_bytes -> raw path, no delta schedule
    raw_plan = sched.compile_wsync_plan(
        params, "data", policy=CompressionPolicy(min_bytes=1 << 30), n_dev=1)
    assert all(b.path == "raw" and b.delta_width == 0
               for b in raw_plan.buckets)
    # raw axis -> raw path
    raw2 = sched.compile_wsync_plan(params, "model", policy=POL, n_dev=1)
    assert all(b.path == "raw" for b in raw2.buckets)
    # works from abstract shapes
    structs = jax.eval_shape(lambda: params)
    assert sched.compile_wsync_plan(
        structs, "data", policy=POL, n_dev=1).summary() == s


def test_wsync_plan_key_misses_on_delta_width_change():
    params = make_params()
    k1 = sched.compile.wsync_plan_key(params, "data", POL, "split_send", 1)
    prof = dataclasses.replace(
        POL.profile, widths=dict(POL.profile.widths, delta_lo=7))
    pol2 = dataclasses.replace(POL, profile=prof)
    k2 = sched.compile.wsync_plan_key(params, "data", pol2, "split_send", 1)
    assert k1 != k2  # a stale delta schedule must never replay


def test_wsync_plan_persistence_roundtrip(tmp_path):
    params = make_params()
    cache = sched.PlanCache()
    plan = sched.cached_wsync_plan(params, "data", policy=POL, n_dev=1,
                                   cache=cache)
    path = str(tmp_path / "plans.pkl")
    assert sched.save_plans(path, cache) == 1
    fresh = sched.PlanCache()
    assert sched.load_plans(path, fresh) == 1
    assert fresh.get_or_compile(plan.key, lambda: None) == plan
    assert fresh.stats.hits == 1 and fresh.stats.misses == 0


# ---------------------------------------------------------------------------
# version store
# ---------------------------------------------------------------------------

def test_versioned_store_ack_history_and_fencing():
    st = VersionedStore(history=2)
    assert st.version == 0
    with pytest.raises(ValueError):
        st.latest()
    v1 = st.publish({"w": jnp.ones(4)})
    v2 = st.publish({"w": jnp.ones(4) * 2})
    assert (v1, v2) == (1, 2) and st.retained() == (1, 2)
    # acks gate on plausible versions and the current epoch
    assert not st.ack("r", 3)  # unpublished
    assert not st.ack("r", 0)
    assert st.ack("r", v1)
    assert st.base_for("r") == v1
    # history pruning invalidates the base (stale ack -> full send)
    v3 = st.publish({"w": jnp.ones(4) * 3})
    assert st.retained() == (2, 3) and st.get(v1) is None
    assert st.acked_version("r") == v1 and st.base_for("r") is None
    # epoch fencing drops ALL acks, and stale-epoch acks are rejected
    st.ack("r", v3)
    old_epoch = st.epoch
    assert st.advance_epoch() == old_epoch + 1
    assert st.acked_version("r") is None
    assert not st.ack("r", v3, epoch=old_epoch)
    assert st.ack("r", v3, epoch=st.epoch)
    assert st.base_for("r") == v3


def test_versioned_store_owns_published_buffers():
    """publish() snapshots by default: mutating (or deleting) the caller's
    arrays must not corrupt the retained version."""
    st = VersionedStore()
    arr = jax.device_put(jnp.arange(8, dtype=jnp.float32))
    st.publish({"w": arr})
    arr.delete()  # simulates a donated train step consuming the buffer
    kept = st.latest()[0]["w"]
    assert np.array_equal(np.asarray(kept), np.arange(8, dtype=np.float32))


# ---------------------------------------------------------------------------
# host engine protocol
# ---------------------------------------------------------------------------

def test_engine_full_then_delta_then_prune_fallback():
    params = make_params()
    eng = WeightSyncEngine(policy=POL, history=2,
                           plan_cache=sched.PlanCache())
    v1 = eng.publish(params)
    u1 = eng.update_for("r0")
    assert u1.mode == "full" and u1.base_version is None
    held = apply_update(u1)
    assert tree_bits_equal(held, params)
    assert eng.ack("r0", u1.version, u1.epoch)

    p2 = perturb_params(params, seed=2)
    eng.publish(p2)
    u2 = eng.update_for("r0")
    assert u2.mode == "delta" and u2.base_version == v1
    assert u2.wire_bytes < u1.wire_bytes  # the delta is the smaller wire
    # the plan's eval_shape accounting IS the host wire (both block-padded)
    raw_leaf_b = 4  # the int32 "step" scalar rides raw
    assert u2.wire_bytes == eng.plan_for(p2).delta_wire_bytes + raw_leaf_b
    held = apply_update(u2, base_params=held)
    assert tree_bits_equal(held, p2)
    assert eng.ack("r0", u2.version, u2.epoch)

    # publish past the history bound without acks: base pruned -> full
    p3, p4 = perturb_params(p2, seed=3), perturb_params(p2, seed=4)
    eng.publish(p3)
    eng.publish(p4)
    u4 = eng.update_for("r0")
    assert u4.mode == "full" and u4.base_version is None
    assert tree_bits_equal(apply_update(u4), p4)


def test_engine_current_replica_gets_zero_delta():
    """A replica already at the latest version re-syncs via the all-zero
    delta — far cheaper than a full re-send, and still bit-exact."""
    eng = WeightSyncEngine(policy=POL, plan_cache=sched.PlanCache())
    params = make_params()
    v = eng.publish(params)
    full = eng.update_for("r")  # before the ack: full send
    eng.ack("r", v)
    u = eng.update_for("r")
    assert u.mode == "delta" and u.base_version == v
    assert u.wire_bytes < full.wire_bytes
    assert tree_bits_equal(apply_update(u, base_params=params), params)


def test_engine_memoizes_updates_per_base():
    """Broadcasting one version to N replicas with the same acked base
    encodes once: update_for returns the identical SyncUpdate object."""
    eng = WeightSyncEngine(policy=POL, plan_cache=sched.PlanCache())
    v = eng.publish(make_params())
    u_a, u_b = eng.update_for("a"), eng.update_for("b")
    assert u_a is u_b
    eng.ack("a", v)
    assert eng.update_for("a") is not u_a  # different base -> new encode
    eng.publish(perturb_params(make_params()))
    assert eng.update_for("b") is not u_b  # new version -> memo cleared


def test_engine_overflow_falls_back_to_full_per_bucket():
    """A cold delta (uncorrelated versions) overflows the warm widths: the
    engine must ship FULL buckets, not a corrupt delta."""
    eng = WeightSyncEngine(policy=POL, plan_cache=sched.PlanCache())
    params = make_params()
    v1 = eng.publish(params)
    eng.ack("r", v1)
    cold = jax.tree.map(
        lambda l: (random_bits(jnp.dtype(l.dtype).name, l.size,
                               seed=11).reshape(l.shape)
                   if jnp.dtype(l.dtype).name in codec.LAYOUTS else l),
        params)
    eng.publish(cold)
    u = eng.update_for("r")
    assert u.mode == "full" and u.base_version is None
    assert tree_bits_equal(apply_update(u), cold)


def test_engine_epoch_fence_forces_full():
    eng = WeightSyncEngine(policy=POL, plan_cache=sched.PlanCache())
    params = make_params()
    v1 = eng.publish(params)
    eng.ack("r", v1)
    eng.advance_epoch()
    eng.publish(perturb_params(params))
    u = eng.update_for("r")
    assert u.mode == "full" and u.base_version is None


def test_engine_plan_cache_zero_recompiles():
    cache = sched.PlanCache()
    eng = WeightSyncEngine(policy=POL, plan_cache=cache)
    params = make_params()
    held = {}
    for i in range(4):
        params = perturb_params(params, seed=20 + i)
        eng.publish(params)
        for r in ("a", "b"):
            u = eng.update_for(r)
            held[r] = apply_update(u, base_params=held.get(r)
                                   if u.base_version is not None else None)
            eng.ack(r, u.version, u.epoch)
    assert all(tree_bits_equal(h, params) for h in held.values())
    # zero recompiles after the first publish; the update memo means one
    # plan lookup per distinct (version, base) encode, all hits
    assert cache.stats.misses == 1 and cache.stats.hits == 3


# ---------------------------------------------------------------------------
# serve ingestion + train publish hook
# ---------------------------------------------------------------------------

def test_serve_engine_ingest_weights_hot_swap():
    from repro import configs
    from repro.models import transformer
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = configs.get_smoke("smollm_135m")
    p_old = transformer.init(jax.random.PRNGKey(0), cfg)
    p_new = perturb_params(p_old, seed=30)
    serve = ServeEngine(cfg, p_old, ServeConfig(batch_slots=2, max_len=32))
    assert serve.weight_version is None

    sync = WeightSyncEngine(policy=POL, plan_cache=sched.PlanCache())
    v1 = sync.publish(p_old)
    assert serve.ingest_weights(sync.update_for("serve")) == v1
    sync.ack("serve", v1)
    v2 = sync.publish(p_new)
    u = sync.update_for("serve")
    assert u.mode == "delta"
    assert serve.ingest_weights(u) == v2
    assert serve.weight_version == v2 and serve.weight_epoch == u.epoch
    assert tree_bits_equal(serve.params, p_new)
    # a delta against a version this engine does not hold must be fenced
    stale = dataclasses.replace(u, base_version=v1 - 1)
    with pytest.raises(ValueError, match="full send"):
        serve.ingest_weights(stale)
    # and a delta from another epoch likewise
    fenced = dataclasses.replace(u, epoch=u.epoch + 1)
    with pytest.raises(ValueError, match="full send"):
        serve.ingest_weights(fenced)


def test_make_publish_hook_cadence():
    from repro.train.step import make_publish_hook

    eng = WeightSyncEngine(policy=POL, plan_cache=sched.PlanCache())
    hook = make_publish_hook(eng, every=2)
    params = make_params()
    out = [hook({"params": params, "step": jnp.asarray(s)})
           for s in (1, 2, 3, 4)]
    assert out == [None, 1, None, 2]
    assert eng.store.version == 2


@pytest.mark.slow
def test_fig_sync_smoke_gates():
    """The benchmark's CI gate: >= 3x warm-delta wire reduction, >= 90%
    wsync plan-cache hit rate, zero recompiles (asserted inside run)."""
    from benchmarks.fig_sync import run

    out = run(smoke=True)
    assert out["loop"]["warm_reduction"] >= 3.0
