"""Weight sync: a trainer publishes, one replica applies each publish.

Set-up builds the version chain on the device from the seed
(``perfbench/chain.py``), calibrates the delta widths on its first delta as
the program's own RL example does, builds a ``WeightSyncEngine`` and a
``ServeEngine`` replica, and warms up with one full publish (v0) and one
delta round (v1).  The window publishes the chain ping-pong, one round at a
time: ``publish`` -> ``update_for`` -> ``ingest_weights`` ->
``block_until_ready``, then ``ack``.  The round in progress when the window
ends is finished and counted.

Every round's replica weights, the warm-up's included, are compared bit for
bit with the chain version that was published; the counts are read once
the window has closed.
"""
from __future__ import annotations

import sys
import time
import traceback

import jax
import jax.numpy as jnp

from perfbench import archcfg, chain, harness

REPLICA = "replica-0"
CHAIN_KEYS = ("versions", "lr", "beta1", "beta2", "eps", "weight_decay",
              "init_std")


def _bits(x):
    """A leaf as unsigned integers of its width: NaN payloads and
    subnormals compare as the bits they are."""
    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    return jax.lax.bitcast_convert_type(x, uint)


@jax.jit
def mismatches(got, want):
    """Elements of ``got`` whose bits differ from ``want``'s."""
    pairs = zip(jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want))
    return sum(jnp.sum(_bits(a) != _bits(b), dtype=jnp.int32)
               for a, b in pairs)


def _ingest(replica, update, want):
    replica.ingest_weights(update)


# the control: the reference in the program's place, one precision below
# the configuration's (float8 for bfloat16, bfloat16 for float32)
LOWER = {"bfloat16": jnp.float8_e4m3fn, "float16": jnp.float8_e4m3fn,
         "float32": jnp.bfloat16}


def lower_precision(tree):
    return jax.tree.map(lambda x: x.astype(LOWER[x.dtype.name]).astype(
        x.dtype), tree)


def _control_apply(replica, update, want):
    """The published version, rounded through the precision below, takes
    the place of the replica's apply; the version bookkeeping is kept."""
    replica.params = lower_precision(want)
    replica.weight_version = update.version
    replica.weight_epoch = update.epoch


def control_hooks() -> dict:
    return {"apply": _control_apply}


def run(ctx: harness.RunContext) -> harness.Outcome:
    from repro.core import calibrate
    from repro.core.policy import CompressionPolicy
    from repro.models import transformer
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.sync import WeightSyncEngine

    mix = ctx.cell.mix
    apply = ctx.hooks.get("apply", _ingest)
    cfg = archcfg.arch_config(ctx.cell.config)
    shapes = jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), cfg))
    versions = chain.make_chain(shapes, ctx.key, device=ctx.devices[0],
                                **{k: mix[k] for k in CHAIN_KEYS})

    def flat(tree):
        return jnp.concatenate(
            [l.reshape(-1) for l in jax.tree_util.tree_leaves(tree)])

    w_d, w_lo = calibrate.choose_delta_widths(flat(versions[1]),
                                              flat(versions[0]))
    base = calibrate.CompressionProfile.default(cfg.dtype)
    profile = calibrate.CompressionProfile(
        widths={**base.widths, "delta": w_d, "delta_lo": w_lo})
    engine = WeightSyncEngine(policy=CompressionPolicy(profile=profile))
    replica = ServeEngine(cfg, jax.tree.map(jnp.zeros_like, versions[0]),
                          ServeConfig(**mix["replica"]))

    def one_round(i):
        want = versions[i]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.sync.round"):
            with jax.profiler.TraceAnnotation("bench.sync.encode"):
                engine.publish(want)
                update = engine.update_for(REPLICA)
            with jax.profiler.TraceAnnotation("bench.sync.apply"):
                apply(replica, update, want)
                jax.block_until_ready(replica.params)
        dt = time.perf_counter() - t0
        engine.ack(REPLICA, update.version, update.epoch)
        return dt, update, mismatches(replica.params, want)

    order = chain.ping_pong(len(versions))
    checked = [one_round(next(order))[2] for _ in range(2)]  # full, delta
    jax.block_until_ready(checked)
    rounds, attempted, failed = [], 0, 0
    ctx.end_setup()
    with ctx.window():
        t_end = time.perf_counter() + ctx.seconds
        while True:
            attempted += 1
            try:
                dt, update, bad = one_round(next(order))
            except Exception:  # the round never came: count it, stop
                traceback.print_exc()
                failed += 1
                break
            checked.append(bad)
            rounds.append({"s": dt, "mode": update.mode,
                           "wire_bytes": update.wire_bytes,
                           "raw_bytes": update.raw_bytes})
            if time.perf_counter() >= t_end:
                break
    memory_peak = harness.memory_peak(ctx.devices)
    bad_elements = sum(int(b) for b in jax.device_get(checked))
    sync_ms = (1e3 * sum(r["s"] for r in rounds) / len(rounds)
               if rounds else None)
    n_delta = sum(r["mode"] == "delta" for r in rounds)
    print(f"info: rounds {len(rounds)} delta {n_delta} widths {w_d}/{w_lo} "
          f"round_s {[r['s'] for r in rounds]}", file=sys.stderr)
    return harness.Outcome(
        end_to_end={"sync_ms": sync_ms},
        counters={"rounds": rounds},
        attempted=attempted, failed=failed,
        checks=[("mismatched_elements", bad_elements, 0)],
        memory_peak_bytes=memory_peak, plan_kinds=("wsync",))
