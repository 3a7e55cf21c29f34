"""Chip smoke: the compressed train -> weight-sync -> serve loop on a TPU.

    python chip_smoke.py             # one chip: train, weight sync, serve
    python chip_smoke.py --chips 4   # four chips: the ZeRO-1 phase only

Drives the system's main path once through its normal entry points, at the
full published width of ``smollm_135m`` (random weights from a seed):

1. train: ZeRO-1 steps with compressed gradient reduce-scatter and
   parameter all-gather (``launch/train.build``), against the raw twin
   (``CompressionPolicy.disabled()``) from the same seed and batches; the
   losses must agree bit for bit;
2. weight sync (one chip): two publishes through ``sync.WeightSyncEngine``,
   the second an XOR delta, each applied to a ``ServeEngine`` replica via
   ``ingest_weights``; the replica's weights must equal the published ones
   bit for bit;
3. serve (one chip): the replica answers a few PD-disaggregated requests;
   its tokens must equal those of a colocated engine.

With ``--chips 4`` only phase 1 runs, on a ``data=4, model=1`` mesh, and
the script checks that the state is spread over all four devices.

Every phase runs in this one process: a chip belongs to one process, so
nothing here spawns a child that needs it.  Progress goes to stdout as one
JSON object per line; the last line is ``{"ok": true, "device": {...}}``.
The script exits non-zero, and prints no such line, when JAX finds no TPU,
when a phase raises, when a bit-exactness check fails, when a compressed
plan recorded another backend or ``use_pallas=False``, or when every
compressed step was retried raw.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "smollm_135m"
SMOKE_CONFIG = False  # the published widths; the CPU tests flip this
SEED = 0
BATCH, SEQ = 8, 512
WARMUP_STEPS, TIMED_STEPS = 2, 4
N_REQUESTS, PROMPT_LEN, MAX_NEW = 4, 64, 16
CKPT_DIR = os.path.join(ROOT, ".scratch", "chip_smoke_ckpt")  # never written


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


class CompileCounter:
    """Counts XLA compilations (and persistent-cache hits) process-wide."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _uint_bits(x):
    import jax
    import jax.numpy as jnp
    from repro.core import codec

    lay = codec.LAYOUTS.get(jnp.dtype(x.dtype).name)
    return jax.lax.bitcast_convert_type(x, lay.uint_dtype) if lay else x


def bits_equal(a, b) -> bool:
    """Pytree equality in the uint domain (NaN payloads compare too)."""
    import jax
    import jax.numpy as jnp

    same = jax.tree.map(
        lambda x, y: bool(jnp.all(_uint_bits(x) == _uint_bits(y))), a, b)
    return all(jax.tree_util.tree_leaves(same))


def _loss_bits(losses) -> list:
    import numpy as np

    return [int(v) for v in np.asarray(losses, np.float32).view(np.uint32)]


def _train_args(compressed: bool):
    return argparse.Namespace(
        arch=ARCH, smoke=SMOKE_CONFIG, batch=BATCH, seq=SEQ, microbatches=1,
        partition="zero1", optimizer="adamw", lr=3e-4, warmup=20, seed=SEED,
        pods=1, no_compress=not compressed, compress_min_bytes=0,
        ckpt_dir=CKPT_DIR, ckpt_every=1 << 30, heartbeat=None, sigterm=False)


def train_twins(mesh, counter, kind: str):
    """Phase 1: the raw twin, then the compressed one, from the same seed
    and batches.  Returns (cfg, compressed state, its StepRunner)."""
    import jax
    from repro.launch import train as launch_train

    losses = {}
    n_steps = WARMUP_STEPS + TIMED_STEPS
    for name in ("raw", "compressed"):
        t0 = time.perf_counter()
        c0 = counter.compiles
        cfg, _, _, state, runner = launch_train.build(
            _train_args(name == "compressed"), mesh=mesh)
        state, warm = runner.train(state, num_steps=WARMUP_STEPS, log_every=0)
        setup_s = time.perf_counter() - t0
        c1 = counter.compiles
        t1 = time.perf_counter()
        state, timed = runner.train(state, start_step=WARMUP_STEPS,
                                    num_steps=TIMED_STEPS, log_every=0)
        jax.block_until_ready(state)
        losses[name] = warm + timed
        report("train", twin=name, device_kind=kind,
               mesh=dict(mesh.shape), batch=BATCH, seq=SEQ,
               setup_s_incl_compile=setup_s,
               timed_steps=TIMED_STEPS,
               timed_s_info_only=time.perf_counter() - t1,
               compiles_setup=c1 - c0, compiles_timed=counter.compiles - c1,
               retries=runner.retries, losses=losses[name])
        if name == "raw":
            del state, runner  # free the raw twin before the compressed one
    same = _loss_bits(losses["raw"]) == _loss_bits(losses["compressed"])
    report("train_check", losses_bit_identical=same,
           compressed_retries=runner.retries)
    if not same:
        raise SmokeFailure(
            f"compressed and raw losses differ: {losses['compressed']} vs "
            f"{losses['raw']}")
    if runner.retries >= n_steps:
        raise SmokeFailure(f"every compressed step was retried raw "
                           f"({runner.retries} retries in {n_steps} steps)")
    return cfg, state, runner


def check_spread(state, devices) -> None:
    """The sharded optimizer state lives on every device, each holding its
    own ZeRO-1 slice (not replicas, and not all on ``devices()[0]``)."""
    import numpy as np

    master = state["opt"]["buckets"][0]["master"]
    shards = sorted(master.addressable_shards, key=lambda s: s.device.id)
    on = sorted(s.device.id for s in shards)
    want = sorted(d.id for d in devices)
    distinct = len({np.asarray(s.data).tobytes() for s in shards})
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    report("spread", shard_devices=on, distinct_shards=distinct,
           bytes_in_use=in_use)
    if on != want or distinct != len(want):
        raise SmokeFailure(f"ZeRO-1 state not spread over {want}: shards on "
                           f"{on}, {distinct} distinct")
    if any(b is not None and b <= 0 for b in in_use):
        raise SmokeFailure(f"a device holds no memory: {in_use}")


def _serve_config(pd: bool):
    from repro.serve.engine import ServeConfig

    return ServeConfig(batch_slots=N_REQUESTS, max_len=PROMPT_LEN + MAX_NEW,
                       prefill_chunk=PROMPT_LEN, pd_disaggregated=pd)


def weight_sync(cfg, state, runner, kind: str):
    """Phase 2: publish the trained weights (full), take one more
    compressed step, publish again (XOR delta); apply both to a replica.
    Returns (replica ServeEngine, trainer params it must now equal)."""
    import jax
    import jax.numpy as jnp
    from repro.core import calibrate
    from repro.core.policy import CompressionPolicy
    from repro.models import transformer
    from repro.serve.engine import ServeEngine
    from repro.sync import WeightSyncEngine

    t0 = time.perf_counter()
    v1 = jax.tree.map(lambda l: l.copy(), state["params"])
    step = WARMUP_STEPS + TIMED_STEPS
    state, _ = runner.train(state, start_step=step, num_steps=1, log_every=0)
    if runner.retries >= step + 1:
        raise SmokeFailure("every compressed step was retried raw")
    v2 = state["params"]
    # calibrate the delta widths on this very publish-to-publish delta
    flat = lambda t: jnp.concatenate(
        [l.reshape(-1) for l in jax.tree_util.tree_leaves(t)])
    w_d, w_lo = calibrate.choose_delta_widths(flat(v2), flat(v1))
    prof = calibrate.CompressionProfile(widths={
        "gradient": 5, "weight": 5, "activation": 5,
        "delta": w_d, "delta_lo": w_lo})
    engine = WeightSyncEngine(policy=CompressionPolicy(min_bytes=0,
                                                       profile=prof))
    replica = ServeEngine(
        cfg, transformer.init(jax.random.PRNGKey(SEED + 1), cfg),
        _serve_config(pd=True))
    setup_s = time.perf_counter() - t0
    for params, want_mode in ((v1, "full"), (v2, "delta")):
        t1 = time.perf_counter()
        version = engine.publish(params)
        upd = engine.update_for("replica-0")
        t2 = time.perf_counter()
        replica.ingest_weights(upd)
        jax.block_until_ready(replica.params)
        t3 = time.perf_counter()
        engine.ack("replica-0", upd.version, upd.epoch)
        exact = bits_equal(replica.params, params)
        report("weight_sync", device_kind=kind, version=version,
               mode=upd.mode, wire_bytes=upd.wire_bytes,
               raw_bytes=upd.raw_bytes, delta_widths=[w_d, w_lo],
               bit_exact=exact, setup_s=setup_s,
               encode_s_info_only=t2 - t1, apply_s_info_only=t3 - t2)
        if not exact:
            raise SmokeFailure(f"replica differs from published v{version}")
        if upd.mode != want_mode:
            raise SmokeFailure(f"publish v{version} took the {upd.mode} "
                               f"path, expected {want_mode}")
    return replica, v2


def serve(cfg, replica, params, kind: str) -> None:
    """Phase 3: the PD-disaggregated replica against a colocated engine."""
    import numpy as np
    from repro.serve.engine import Request, ServeEngine

    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab, (N_REQUESTS, PROMPT_LEN)).astype(np.int32)
    tokens = {}
    for name, eng in (("pd_disaggregated", replica),
                      ("colocated", ServeEngine(cfg, params,
                                                _serve_config(pd=False)))):
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=MAX_NEW))
        done = sorted(eng.run(), key=lambda r: r.rid)
        tokens[name] = [r.out for r in done]
        report("serve", engine=name, device_kind=kind,
               requests=len(done), new_tokens=sum(len(t) for t in
                                                  tokens[name]),
               wall_s_incl_compile=time.perf_counter() - t0)
    same = tokens["pd_disaggregated"] == tokens["colocated"]
    report("serve_check", tokens_identical=same,
           first_request=tokens["pd_disaggregated"][0])
    if not same:
        raise SmokeFailure("PD-disaggregated tokens differ from colocated")
    if [len(t) for t in tokens["colocated"]] != [MAX_NEW] * N_REQUESTS:
        raise SmokeFailure(f"expected {N_REQUESTS} answers of {MAX_NEW} "
                           f"tokens, got {tokens['colocated']}")


def check_plans(platform: str, want_kinds) -> None:
    """Every compressed plan recorded this backend and compiled kernels."""
    from repro import sched

    plans = [p for p in sched.default_cache().plans() if p.raw_bytes > 0]
    rows = sorted({(p.kind, p.backend, p.use_pallas) for p in plans})
    report("plans", compressed=[list(r) for r in rows],
           plan_cache=sched.cache_info())
    bad = [r for r in rows if r[1] != platform or not r[2]]
    if bad:
        raise SmokeFailure(f"compressed plans off the {platform} kernels: "
                           f"{bad}")
    missing = set(want_kinds) - {r[0] for r in rows}
    if missing:
        raise SmokeFailure(f"no compressed plan of kind {sorted(missing)}")


def run(chips: int) -> dict:
    """All phases for ``chips`` devices; returns the device record."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found {dev.platform}")
    if len(devices) < chips:
        raise SmokeFailure(f"--chips {chips} but JAX sees {len(devices)}")
    devices = devices[:chips]

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import kernels
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh

    cache = use_compile_cache()
    counter = CompileCounter()
    kind = dev.device_kind
    report("start", device_kind=kind, chips=chips, compile_cache=cache,
           backend=kernels.backend(), use_pallas=kernels.default_use_pallas())
    t0 = time.perf_counter()
    mesh = make_mesh((chips, 1), ("data", "model"), devices=devices)
    cfg, state, runner = train_twins(mesh, counter, kind)
    want_kinds = ["zero1"]
    if chips > 1:
        check_spread(state, devices)
    else:
        replica, params = weight_sync(cfg, state, runner, kind)
        del state, runner
        serve(cfg, replica, params, kind)
        want_kinds += ["wsync", "kv"]
    check_plans(dev.platform, want_kinds)
    report("done", device_kind=kind, wall_s=time.perf_counter() - t0,
           compiles=counter.compiles,
           persistent_cache_hits=counter.cache_hits,
           fallback_counts=kernels.fallback_counts())
    return {"platform": dev.platform, "kind": kind, "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the ZeRO-1 phase, on a data=4 mesh")
    args = ap.parse_args(argv)
    try:
        device = run(args.chips)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
