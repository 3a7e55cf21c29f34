"""The wsync schedule's bucket limit (``sched.compile.WSYNC_BUCKET_BYTES``),
lowered per test so that a small tree splits: buckets cut at leaf
boundaries, every round bit-exact through the host engine, the planless
in-mesh wire and the plan executor, the checksum catching a flipped byte
in any bucket, the ``sync:bucket`` spans, and a tree under the limit
encoded exactly as the one-bucket-per-dtype schedule encoded it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import obs, sched
from repro.core import codec, integrity
from repro.core.policy import CompressionPolicy
from repro.sched import compile as sched_compile
from repro.sync import WeightSyncEngine, apply_update, sync_weights
from repro.sync.engine import verify_update

POL = CompressionPolicy(min_bytes=0)
RAW_POL = CompressionPolicy(min_bytes=1 << 40)  # every bucket raw-gated
LIMIT = 8 << 10


def make_params(seed=0):
    rng = np.random.default_rng(seed)

    def w(shape, dtype, scale=0.02):
        return jnp.asarray(rng.normal(0, scale, shape), dtype)

    return {
        "a": w((64, 48), jnp.bfloat16),  # 6 KiB
        "b": w((1024,), jnp.bfloat16),  # 2 KiB: one bucket with "a"
        "c": w((5000,), jnp.bfloat16),  # 10 KiB: over the limit, alone
        "d": w((2048,), jnp.bfloat16),  # 4 KiB
        "e": w((300,), jnp.float32, 1.0),
        "f": w((3000,), jnp.float32, 1.0),  # 12 KiB: alone
        "step": jnp.asarray(7, jnp.int32),  # codec-unsupported: raw leaf
    }


def perturb(params, seed=1):
    rng = np.random.default_rng(seed)

    def f(l):
        lay = codec.LAYOUTS.get(jnp.dtype(l.dtype).name)
        if lay is None:
            return l
        mask = rng.integers(0, 8, l.shape).astype(np.uint64)
        mask[rng.random(l.shape) > 0.3] = 0
        u = lay.uint_dtype
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(l, u) ^ jnp.asarray(mask, u),
            l.dtype)

    return jax.tree.map(f, params)


def bits(a):
    lay = codec.LAYOUTS.get(jnp.dtype(a.dtype).name)
    return a if lay is None else jax.lax.bitcast_convert_type(
        a, lay.uint_dtype)


def same_bits(a, b) -> bool:
    return all(bool(jnp.all(bits(x) == bits(y))) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


@pytest.fixture
def small_limit(monkeypatch):
    monkeypatch.setattr(sched_compile, "WSYNC_BUCKET_BYTES", LIMIT)


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1,), ("data",))


def _shmap(fn, mesh, n_in):
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * n_in,
                         out_specs=(P(), P()), axis_names={"data"},
                         check_vma=False)


def test_buckets_cut_at_leaf_boundaries(small_limit):
    params = make_params()
    leaves = jax.tree_util.tree_leaves(params)
    buckets, raw_ix = sched_compile.wsync_buckets(leaves)
    groups, want_raw = sched_compile._group_leaves(leaves)
    assert raw_ix == want_raw
    for name in groups:  # each dtype's leaves, in order, cut into runs
        runs = [m for n, m in buckets if n == name]
        assert [x for run in runs for x in run] == groups[name]
        item = jnp.dtype(name).itemsize
        sizes = [sum(x[2] for x in run) * item for run in runs]
        for run, size in zip(runs, sizes):
            assert size <= LIMIT or len(run) == 1
        for run, nxt in zip(runs, runs[1:]):  # cut only where it must be
            assert (sum(x[2] for x in run) + nxt[0][2]) * item > LIMIT
    by_leaf = {m[0]: len(run) for _, run in buckets for m in run}
    assert by_leaf[2] == 1 and by_leaf[5] == 1  # "c" and "f", alone
    assert len(buckets) == 5
    plan = sched.compile_wsync_plan(params, "data", policy=POL, n_dev=1)
    assert [(b.dtype_name, b.members) for b in plan.buckets] == list(buckets)


@pytest.mark.parametrize("mode", ["full", "delta", "raw"])
def test_rounds_bit_exact_through_every_entry_point(small_limit, mesh, mode):
    """The host engine, the planless in-mesh wire and the plan executor
    each deliver the published tree bit for bit, over several buckets."""
    params = make_params()
    new = perturb(params)
    pol = RAW_POL if mode == "raw" else POL
    eng = WeightSyncEngine(policy=pol, plan_cache=sched.PlanCache())
    eng.ack("r", eng.publish(params))
    eng.publish(new)
    u = eng.update_for("r", force="full" if mode == "full" else None)
    assert len(u.buckets) == 5
    assert {m for _, _, m, _ in u.buckets} == {mode}
    base = params if mode == "delta" else None
    assert verify_update(u) and same_bits(apply_update(u, base), new)

    cache = sched.PlanCache()

    def both(t, b):
        b = b if mode == "delta" else None
        a1, f1 = sync_weights(t, "data", [(0, 0)], policy=pol, base=b)
        a2, f2 = sched.sync_weights_with_plan(t, "data", [(0, 0)],
                                              policy=pol, base=b, cache=cache)
        return (a1, a2), jnp.maximum(f1, f2)

    (a1, a2), flag = jax.jit(_shmap(both, mesh, 2))(new, params)
    assert int(flag) == 0
    assert same_bits(a1, new) and same_bits(a2, new)


def test_verify_update_catches_a_flipped_byte_in_every_bucket(small_limit):
    params = make_params()
    eng = WeightSyncEngine(policy=POL, plan_cache=sched.PlanCache())
    eng.ack("r", eng.publish(params))
    eng.publish(perturb(params))
    u = eng.update_for("r")
    assert u.mode == "delta" and len(u.buckets) == 5
    for k, (name, members, mode, msg) in enumerate(u.buckets):
        arrays, tdef = jax.tree_util.tree_flatten(msg)
        arrays[0] = integrity.flip_bit(arrays[0], 13)
        bad = list(u.buckets)
        bad[k] = (name, members, mode, jax.tree_util.tree_unflatten(
            tdef, arrays))
        assert not verify_update(dataclasses.replace(u, buckets=tuple(bad)))
    assert verify_update(u)


def test_one_sync_bucket_span_per_bucket(small_limit):
    obs.set_enabled(True)
    obs.reset()
    try:
        params = make_params()
        eng = WeightSyncEngine(policy=POL, plan_cache=sched.PlanCache())
        eng.publish(params)
        u = eng.update_for("r")
        spans = [s for s in obs.spans() if s.name == "sync:bucket"]
        assert [s.args["bucket"] for s in spans] == list(range(5))
        raw = [sum(m[2] for m in members) * jnp.dtype(name).itemsize
               for name, members, _, _ in u.buckets]
        assert [s.args["raw_bytes"] for s in spans] == raw
        assert {s.args["mode"] for s in spans} == {"full"}
        assert obs.snapshot()["counters"]["sync_bucket_raw_bytes_total"] \
            == {"mode=full": sum(raw)}
    finally:
        obs.set_enabled(None)
        obs.reset()


@pytest.mark.parametrize("force", [None, "full", "raw"])
def test_a_tree_under_the_limit_keeps_one_bucket_per_dtype(monkeypatch,
                                                           force):
    """At the module's limit a small tree is one bucket per dtype: the same
    wire bytes and checksum as with no limit at all."""
    params = make_params()
    new = perturb(params)

    def encode():
        eng = WeightSyncEngine(policy=POL, plan_cache=sched.PlanCache())
        eng.ack("r", eng.publish(params))
        eng.publish(new)
        return eng.update_for("r", force=force)

    u = encode()
    monkeypatch.setattr(sched_compile, "WSYNC_BUCKET_BYTES", 1 << 62)
    one = encode()
    assert len(u.buckets) == len(one.buckets) == 2  # bfloat16, float32
    assert (u.wire_bytes, u.checksum) == (one.wire_bytes, one.checksum)
