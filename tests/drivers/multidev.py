"""Multi-device correctness driver (run in a subprocess: the XLA host-device
flag must be set before jax init, and the main pytest process must keep the
default 1-device view per the assignment).

Each section runs independently (a lowering failure in one records an error
for its keys instead of killing the rest).  Prints one JSON line with all
results; a value of the form {"skip": reason} marks a check the installed
jax/jaxlib cannot lower (the suite skips instead of failing).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import json
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.compressed_collectives import (
    all_to_all_compressed, psum_compressed, psum_raw_twoshot,
    reduce_scatter_compressed, tree_psum_compressed)
from repro.core.policy import CompressionPolicy
from repro.core.split_send import (chunked_pipeline_send, encode_send,
                                   p2p_send, split_send)
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.optim import optimizers as opt_lib
from repro.serve.kv_transfer import transfer_cache
from repro.train.step import TrainConfig, build_train_state, build_train_step

res = {}
# model axis kept trivial (=1): dp spans all 8 devices, which is what the
# compressed collectives under test ride on.
mesh3 = make_mesh((2, 4, 1), ("pod", "data", "model"))
mesh1 = make_mesh((8,), ("data",))
policy = CompressionPolicy(min_bytes=0)
rng = np.random.default_rng(0)


def section(name, keys):
    """Decorator: run a section, mapping exceptions to per-key skip records."""
    def deco(fn):
        try:
            fn()
        except Exception as e:  # record per-key skip, keep other sections
            first = str(e).splitlines()[0][:200] if str(e) else ""
            err = f"{type(e).__name__}: {first}"
            for k in keys:
                res.setdefault(k, {"skip": err})
            print(f"SECTION {name} failed: {err}", file=sys.stderr)
            traceback.print_exc(limit=2, file=sys.stderr)
    return deco


def bits_equal(a, b):
    if a.dtype in (jnp.bfloat16, jnp.float16):
        u = jnp.uint16
        return bool(jnp.all(jax.lax.bitcast_convert_type(a, u)
                            == jax.lax.bitcast_convert_type(b, u)))
    return bool(jnp.all(a == b))


# -- 1. psum_compressed == raw psum (both algorithms) -------------------------
# two-shot: ONE f32 reduction -> bit-equal to the f32 reference.
# ring: every hop re-encodes the partial sum in the wire dtype (bf16), so
# intermediate sums round — numerically close but NOT bit-equal.  This is
# the re-compression overhead the paper ascribes to ring (Fig. 9b).
@section("psum", ["psum_two_shot_exact", "psum_two_shot_flag",
                  "psum_ring_exact", "psum_ring_flag"])
def _psum():
    x = jnp.asarray(rng.normal(0, 0.02, (1 << 16,)), jnp.bfloat16)
    for algo in ["two_shot", "ring"]:
        pol = dataclasses.replace(policy, allreduce_algorithm=algo)

        def f(v):
            out, flag = psum_compressed(v, "data", policy=pol)
            return out, flag

        out, flag = jax.jit(jax.shard_map(
            f, mesh=mesh1, in_specs=(P(),), out_specs=(P(), P()),
            axis_names={"data"}, check_vma=False))(x)
        ref = (x.astype(jnp.float32) * 8).astype(jnp.bfloat16)
        if algo == "two_shot":
            res[f"psum_{algo}_exact"] = bits_equal(out, ref)
        else:
            rel = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                        - ref.astype(jnp.float32)))) / \
                float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
            res[f"psum_{algo}_exact"] = rel < 5e-2  # bf16 per-hop rounding
        res[f"psum_{algo}_flag"] = int(flag)


# -- 1b. fused vs unfused reduce-scatter: bit-identical across 8 devices ------
@section("rs_fused", ["rs_fused_bitexact_bfloat16", "rs_fused_bitexact_float32"])
def _rs_fused():
    for dt in [jnp.bfloat16, jnp.float32]:
        x = jnp.asarray(rng.normal(0, 0.02, (1 << 15,)), dt)

        def f(v):
            a, fa = reduce_scatter_compressed(v, "data", width=5,
                                              use_fused=True)
            b, fb = reduce_scatter_compressed(v, "data", width=5,
                                              use_fused=False)
            return a, b, jnp.maximum(fa, fb)

        a, b, flag = jax.jit(jax.shard_map(
            f, mesh=mesh1, in_specs=(P(),), out_specs=(P(), P(), P()),
            axis_names={"data"}, check_vma=False))(x)
        name = jnp.dtype(dt).name
        res[f"rs_fused_bitexact_{name}"] = (
            bits_equal(a, b) and int(flag) == 0)


# -- 2. all_to_all_compressed == raw all_to_all --------------------------------
@section("a2a", ["a2a_exact", "a2a_flag"])
def _a2a():
    a = jnp.asarray(rng.normal(0, 1, (8, 4096)), jnp.bfloat16)

    def a2a_pair(v):
        vl = v.reshape(8, -1)  # local rows: one destination per device
        got, flag = all_to_all_compressed(vl, "data", policy=policy)
        want = jax.lax.all_to_all(vl.astype(jnp.float32), "data", 0, 0,
                                  tiled=False).astype(vl.dtype)
        return got.reshape(v.shape), want.reshape(v.shape), flag

    g, w, flag = jax.jit(jax.shard_map(
        a2a_pair, mesh=mesh1, in_specs=(P("data", None),),
        out_specs=(P("data", None),) * 2 + (P(),),
        axis_names={"data"}, check_vma=False))(a)
    res["a2a_exact"] = bits_equal(g, w)
    res["a2a_flag"] = int(flag)


# -- 3. split_send / encode_send / chunked == raw ppermute ---------------------
perm = [(i, (i + 1) % 8) for i in range(8)]


@section("p2p", [f"p2p_{s}_{k}" for s in ("split", "encode", "chunked")
                 for k in ("exact", "flag")])
def _p2p():
    t = jnp.asarray(rng.normal(0, 0.02, (1 << 15,)), jnp.bfloat16)
    for name, fn in [("split", split_send), ("encode", encode_send),
                     ("chunked", chunked_pipeline_send)]:
        def f(v, _fn=fn):
            got, flag = _fn(v, "data", perm, width=5)
            want = jax.lax.ppermute(v, "data", perm)
            return got, want, flag

        g, w, flag = jax.jit(jax.shard_map(
            f, mesh=mesh1, in_specs=(P(),), out_specs=(P(), P(), P()),
            axis_names={"data"}, check_vma=False))(t)
        res[f"p2p_{name}_exact"] = bits_equal(g, w)
        res[f"p2p_{name}_flag"] = int(flag)


# -- 4. tree_psum_compressed on a mixed pytree ---------------------------------
# bf16-first tree with an f32 leaf: per-dtype bucketing must keep the f32
# leaf bit-exact at f32 precision (casting it into a bf16 bucket was the
# old lossy bug).  The reference is the DEVICE-ORDER sequential f32 sum —
# the collectives' accumulation order — not `x * 8`: sequential partial
# sums of identical f32 values legitimately round (3v, 5v, 7v need more
# mantissa bits), and losslessness means "no error beyond the uncompressed
# reduction in the same order".
@section("tree_psum", ["tree_psum_exact", "tree_psum_f32_exact"])
def _tree():
    tree = {"w": jnp.asarray(rng.normal(0, 0.02, (256, 64)), jnp.bfloat16),
            "b": jnp.asarray(rng.normal(0, 1, (64,)), jnp.float32),
            "n": jnp.asarray(rng.integers(0, 5, (3,)), jnp.int32)}

    def tf(tr):
        out, flag = tree_psum_compressed(tr, "data", policy=policy)
        return out, flag

    out, flag = jax.jit(jax.shard_map(
        tf, mesh=mesh1, in_specs=(P(),), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))(tree)

    def seq_ref(leaf):
        acc = jnp.zeros(leaf.shape, jnp.float32)
        for _ in range(8):
            acc = acc + leaf.astype(jnp.float32)
        return acc

    ok_w = bits_equal(out["w"], seq_ref(tree["w"]).astype(jnp.bfloat16))
    ok_b = bool(jnp.all(out["b"] == seq_ref(tree["b"])))  # exact f32 bits
    ok_n = bool(jnp.all(out["n"] == tree["n"] * 8))
    res["tree_psum_exact"] = ok_w and ok_b and ok_n
    res["tree_psum_f32_exact"] = ok_b


# -- 4b. sched executor: psum_with_plan == tree_psum_compressed on 8 devs ------
@section("sched", ["sched_psum_exact", "sched_cache_hit",
                   "sched_rs_exact"])
def _sched():
    from repro import sched
    from repro.core.compressed_collectives import reduce_scatter_compressed

    tree = {"w": jnp.asarray(rng.normal(0, 0.02, (256, 64)), jnp.bfloat16),
            "b": jnp.asarray(rng.normal(0, 1, (4096,)), jnp.float32),
            "n": jnp.asarray(rng.integers(0, 5, (3,)), jnp.int32)}
    cache = sched.PlanCache()

    def planned(tr):
        return sched.psum_with_plan(tr, "data", policy=policy, cache=cache)

    def planless(tr):
        return tree_psum_compressed(tr, "data", policy=policy)

    sm = lambda f: jax.jit(jax.shard_map(
        f, mesh=mesh1, in_specs=(P(),), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))
    a, fa = sm(planned)(tree)
    b, fb = sm(planless)(tree)
    ok = all(bits_equal(x, y) if x.dtype != jnp.int32 else
             bool(jnp.all(x == y))
             for x, y in zip(jax.tree_util.tree_leaves(a),
                             jax.tree_util.tree_leaves(b)))
    res["sched_psum_exact"] = ok and int(fa) == int(fb) == 0
    sm(planned)(tree)  # same signature: second trace must hit the cache
    res["sched_cache_hit"] = (cache.stats.hits >= 1
                              and cache.stats.misses == 1)

    x = jnp.asarray(rng.normal(0, 0.02, (1 << 15,)), jnp.bfloat16)
    a2, f2 = jax.jit(jax.shard_map(
        lambda v: sched.reduce_scatter_with_plan(
            v, "data", policy=policy, cache=sched.PlanCache()),
        mesh=mesh1, in_specs=(P(),), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))(x)
    b2, g2 = jax.jit(jax.shard_map(
        lambda v: reduce_scatter_compressed(
            v, "data", width=policy.width_for("gradient"),
            block=policy.profile.block, exc_frac=policy.profile.exc_frac,
            use_fused=policy.fused_decode_reduce),
        mesh=mesh1, in_specs=(P(),), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))(x)
    res["sched_rs_exact"] = (bool(jnp.all(
        jax.lax.bitcast_convert_type(a2, jnp.uint32)
        == jax.lax.bitcast_convert_type(b2, jnp.uint32)))
        and int(f2) == int(g2))


# -- 4b2. fused transmit-side encode: knob + plan parity across 8 devices ------
@section("enc_fused", ["enc_fused_bitexact", "enc_fused_plan_exact",
                       "enc_fused_plan_recorded"])
def _enc_fused():
    from repro import sched
    tree = {"w": jnp.asarray(rng.normal(0, 0.02, (1 << 15,)), jnp.bfloat16),
            "b": jnp.asarray(rng.normal(0, 1, (4096,)), jnp.float32)}
    pol_f = policy  # fused_encode=True default
    pol_u = dataclasses.replace(policy, fused_encode=False)
    sm = lambda f: jax.jit(jax.shard_map(
        f, mesh=mesh1, in_specs=(P(),), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))
    a, fa = sm(lambda t: tree_psum_compressed(t, "data", policy=pol_f))(tree)
    b, fb = sm(lambda t: tree_psum_compressed(t, "data", policy=pol_u))(tree)
    res["enc_fused_bitexact"] = (
        all(bits_equal(a[k], b[k]) for k in tree)
        and int(fa) == int(fb) == 0)
    cache = sched.PlanCache()
    c, fc = sm(lambda t: sched.psum_with_plan(t, "data", policy=pol_f,
                                              cache=cache))(tree)
    res["enc_fused_plan_exact"] = (
        all(bits_equal(a[k], c[k]) for k in tree) and int(fc) == 0)
    plan = next(iter(cache._plans.values()))
    res["enc_fused_plan_recorded"] = all(
        bk.encode_fused for bk in plan.buckets)


# -- 4b3. p2p/kv plan executors: bit-parity + cache reuse across 8 devices -----
@section("p2p_plan", ["p2p_plan_bitexact", "p2p_plan_reduce_exact",
                      "p2p_plan_cache_hit", "kv_plan_bitexact"])
def _p2p_plan():
    from repro import sched

    t = jnp.asarray(rng.normal(0, 0.02, (1 << 15,)), jnp.bfloat16)
    acc0 = jnp.asarray(rng.normal(0, 1, (1 << 15,)), jnp.float32)
    cache = sched.PlanCache()

    def f(v, a):
        planned, f1 = sched.p2p_send_with_plan(v, "data", perm, policy=policy,
                                               cache=cache)
        planless, f2 = p2p_send(v, "data", perm, policy=policy)
        pr, f3 = sched.p2p_send_with_plan(v, "data", perm, policy=policy,
                                          reduce_into=a, cache=cache)
        ur, f4 = p2p_send(v, "data", perm, policy=policy, reduce_into=a)
        return planned, planless, pr, ur, jnp.maximum(jnp.maximum(f1, f2),
                                                      jnp.maximum(f3, f4))

    mk = lambda: jax.jit(jax.shard_map(
        f, mesh=mesh1, in_specs=(P(), P()), out_specs=(P(),) * 5,
        axis_names={"data"}, check_vma=False))
    planned, planless, pr, ur, flag = mk()(t, acc0)
    res["p2p_plan_bitexact"] = bits_equal(planned, planless) and int(flag) == 0
    res["p2p_plan_reduce_exact"] = bool(jnp.all(
        jax.lax.bitcast_convert_type(pr, jnp.uint32)
        == jax.lax.bitcast_convert_type(ur, jnp.uint32)))
    mk()(t, acc0)  # fresh jit wrapper: re-trace -> pure plan-cache hits
    # send and reducing send share one signature (reduce_into is a runtime
    # argument, not a schedule decision): 1 compile, everything else hits
    res["p2p_plan_cache_hit"] = (cache.stats.misses == 1
                                 and cache.stats.hits >= 3)

    from repro.models import transformer
    kcfg = configs.get_smoke("smollm_135m")
    kv_cache = transformer.init_cache(kcfg, 2, 64)
    params2 = transformer.init(jax.random.PRNGKey(0), kcfg)
    _, kv_cache = transformer.prefill(
        params2, registry.make_batch(kcfg, 2, 32), kcfg, kv_cache)

    def kvf(c):
        a, f1 = sched.transfer_cache_with_plan(c, "data", perm, policy=policy,
                                               plan_cache=sched.PlanCache())
        b, f2 = transfer_cache(c, "data", perm, policy=policy)
        return a, b, jnp.maximum(f1, f2)

    got, want, flag = jax.jit(jax.shard_map(
        kvf, mesh=mesh1, in_specs=(P(),), out_specs=(P(), P(), P()),
        axis_names={"data"}, check_vma=False))(kv_cache)
    res["kv_plan_bitexact"] = all(
        bits_equal(a, b) for a, b in zip(jax.tree_util.tree_leaves(got),
                                         jax.tree_util.tree_leaves(want)))


# -- 4c. split_send fused reducing receiver across 8 devices -------------------
@section("p2p_reduce", ["p2p_reduce_into_exact"])
def _p2p_reduce():
    t = jnp.asarray(rng.normal(0, 0.02, (1 << 14,)), jnp.bfloat16)
    acc0 = jnp.asarray(rng.normal(0, 1, (1 << 14,)), jnp.float32)

    def f(v, a):
        fused, f1 = split_send(v, "data", perm, width=5, reduce_into=a,
                               use_fused=True)
        unfused, f2 = split_send(v, "data", perm, width=5, reduce_into=a,
                                 use_fused=False)
        want = a + jax.lax.ppermute(v, "data", perm).astype(jnp.float32)
        return fused, unfused, want, jnp.maximum(f1, f2)

    fused, unfused, want, flag = jax.jit(jax.shard_map(
        f, mesh=mesh1, in_specs=(P(), P()), out_specs=(P(),) * 4,
        axis_names={"data"}, check_vma=False))(t, acc0)
    res["p2p_reduce_into_exact"] = (
        bool(jnp.all(jax.lax.bitcast_convert_type(fused, jnp.uint32)
                     == jax.lax.bitcast_convert_type(unfused, jnp.uint32)))
        and bool(jnp.all(jax.lax.bitcast_convert_type(fused, jnp.uint32)
                         == jax.lax.bitcast_convert_type(want, jnp.uint32)))
        and int(flag) == 0)


# -- 5. train-step losslessness on the 3-axis mesh (zero1 + fsdp) --------------
cfg = configs.get_smoke("smollm_135m")


def _train_part(part, extra):
    batch = registry.make_batch(cfg, 16, 32)
    batch = {k: jax.device_put(v, NamedSharding(mesh3, P(("pod", "data"),
                                                        None)))
             for k, v in batch.items()}
    tc = TrainConfig(microbatches=2, policy=CompressionPolicy(min_bytes=0),
                     optim=opt_lib.OptimConfig(lr=1e-3, warmup_steps=2),
                     partition=part, **extra)
    tr = dataclasses.replace(tc, policy=CompressionPolicy.disabled())
    s1, _ = build_train_state(cfg, tc, mesh3, jax.random.PRNGKey(1))
    s2, _ = build_train_state(cfg, tr, mesh3, jax.random.PRNGKey(1))
    f1, _ = build_train_step(cfg, tc, mesh3)
    f2, _ = build_train_step(cfg, tr, mesh3)
    j1, j2 = jax.jit(f1), jax.jit(f2)
    for _ in range(2):
        s1, m1 = j1(s1, batch)
        s2, m2 = j2(s2, batch)
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        s1["params"], s2["params"])
    res[f"train_{part}_bitexact"] = max(
        jax.tree_util.tree_leaves(diffs)) == 0.0
    res[f"train_{part}_loss_drop"] = float(m2["loss"]) < 6.0


@section("train_zero1", ["train_zero1_bitexact", "train_zero1_loss_drop"])
def _train_zero1():
    _train_part("zero1", {})


@section("train_fsdp", ["train_fsdp_bitexact", "train_fsdp_loss_drop"])
def _train_fsdp():
    # per-layer compressed gathers inside the rematted forward scan; a
    # lowering failure is recorded as a skip by the section decorator
    _train_part("fsdp", {"fsdp_min_bytes": 0})


# -- 6. KV-cache transfer over a mesh axis --------------------------------------
@section("kv", ["kv_transfer_exact"])
def _kv():
    from repro.models import transformer
    cache = transformer.init_cache(cfg, 2, 64)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    _, cache2 = transformer.prefill(
        params, registry.make_batch(cfg, 2, 32), cfg, cache)

    def kv(c):
        got, flag = transfer_cache(c, "data", perm, policy=policy)

        def raw(l):
            if l.ndim == 0:
                return jax.lax.ppermute(l[None], "data", perm)[0]
            return jax.lax.ppermute(l, "data", perm)

        want = jax.tree.map(raw, c)
        return got, want, flag

    got, want, flag = jax.jit(jax.shard_map(
        kv, mesh=mesh1, in_specs=(P(),), out_specs=(P(), P(), P()),
        axis_names={"data"}, check_vma=False))(cache2)
    res["kv_transfer_exact"] = all(
        bits_equal(a, b) for a, b in zip(jax.tree_util.tree_leaves(got),
                                         jax.tree_util.tree_leaves(want)))


# -- 7. weight sync: XOR-delta broadcast + wsync plan parity across 8 devices --
@section("wsync", ["wsync_full_bitexact", "wsync_delta_bitexact",
                   "wsync_plan_parity", "wsync_plan_cache_hit"])
def _wsync():
    from repro import sched
    from repro.sync import sync_weights

    tree = {
        "wq": jnp.asarray(rng.normal(0, 0.02, (1 << 14,)), jnp.bfloat16),
        "wk": jnp.asarray(rng.normal(0, 0.02, (1 << 13,)), jnp.bfloat16),
        "norm": jnp.asarray(rng.normal(0, 1, (4096,)), jnp.float32),
        "step": jnp.asarray(3, jnp.int32),
    }
    # next version: sparse low-mantissa-bit XOR (a warm optimizer step)
    def xor_mask(l, bits_n):
        if jnp.dtype(l.dtype).name not in ("bfloat16", "float32"):
            return l
        u = jnp.uint16 if l.dtype == jnp.bfloat16 else jnp.uint32
        mask = rng.integers(0, 1 << bits_n, l.shape).astype(np.uint64)
        mask[rng.random(l.shape) > 0.3] = 0
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(l, u) ^ jnp.asarray(mask, u),
            l.dtype)

    new = {k: xor_mask(v, 3) for k, v in tree.items()}
    cache = sched.PlanCache()

    def f(t, b):
        full, f1 = sync_weights(t, "data", perm, policy=policy)
        delta, f2 = sync_weights(t, "data", perm, policy=policy, base=b)
        planned, f3 = sched.sync_weights_with_plan(
            t, "data", perm, policy=policy, base=b, cache=cache)
        pfull, f4 = sched.sync_weights_with_plan(
            t, "data", perm, policy=policy, cache=cache)
        flag = jnp.maximum(jnp.maximum(f1, f2), jnp.maximum(f3, f4))
        return full, delta, planned, pfull, flag

    mk = lambda: jax.jit(jax.shard_map(
        f, mesh=mesh1, in_specs=(P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        axis_names={"data"}, check_vma=False))
    full, delta, planned, pfull, flag = mk()(new, tree)
    teq = lambda a, b: all(
        bits_equal(x, y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                         jax.tree_util.tree_leaves(b)))
    res["wsync_full_bitexact"] = teq(full, new) and int(flag) == 0
    res["wsync_delta_bitexact"] = teq(delta, new)
    res["wsync_plan_parity"] = teq(planned, delta) and teq(pfull, full)
    mk()(new, tree)  # fresh jit wrapper: re-trace -> pure plan-cache hits
    # delta and full replay ONE plan (delta-vs-full is runtime routing)
    res["wsync_plan_cache_hit"] = (cache.stats.misses == 1
                                   and cache.stats.hits >= 3)


print("RESULT " + json.dumps(res))
