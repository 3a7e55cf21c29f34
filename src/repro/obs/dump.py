"""Obs dump CLI: drive a smoke workload, export snapshot + Chrome trace.

    PYTHONPATH=src python -m repro.obs.dump [--target train_sync|sync|serve]
                                            [--out DIR] [--steps N]

Runs a small instrumented workload end to end and writes three artifacts
to ``--out`` (default ``REPRO_TRACE_DIR``):

  * ``trace_<target>.json``   — Chrome-trace/Perfetto timeline of the run
  * ``metrics_<target>.json`` — the metrics-registry snapshot
  * ``metrics_<target>.md``   — the same snapshot as a markdown table

Targets are pluggable (``TARGETS``); the default ``train_sync`` runs the
smollm smoke model through the fault-tolerant step runner and then a
publish/update/ack weight-sync loop — one file that shows nested
``train:step`` / ``plan:*`` / ``sync:*`` spans on a common clock.  Also
registered in ``benchmarks/run.py`` (key ``obs``) so the bench sweep
exercises the full telemetry path.
"""
from __future__ import annotations

import argparse
import os
import tempfile


def _run_train(steps: int) -> None:
    """A few fault-tolerant train steps on the smoke smollm config."""
    import jax

    from repro import configs
    from repro.core.policy import CompressionPolicy
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import registry
    from repro.optim import optimizers as opt_lib
    from repro.runtime.fault_tolerance import RunnerConfig, StepRunner
    from repro.train import step as step_lib

    cfg = configs.get_smoke("smollm_135m")
    tcfg = step_lib.TrainConfig(
        microbatches=1, policy=CompressionPolicy(min_bytes=0),
        optim=opt_lib.OptimConfig(lr=1e-3, warmup_steps=2))
    mesh = make_smoke_mesh(1)
    step, _ = step_lib.build_train_step(cfg, tcfg, mesh)
    state, _ = step_lib.build_train_state(cfg, tcfg, mesh,
                                          jax.random.PRNGKey(0))
    batch = registry.make_batch(cfg, 2, 32)
    jstep = jax.jit(step, donate_argnums=(0,))
    with tempfile.TemporaryDirectory() as ckpt_dir:
        runner = StepRunner(jstep, None, RunnerConfig(ckpt_dir=ckpt_dir))
        for _ in range(steps):
            state, _ = runner.run_step(state, batch)


def _run_sync(publishes: int) -> None:
    """A publish -> update -> ack weight-sync loop with two replicas."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.calibrate import CompressionProfile
    from repro.core.policy import CompressionPolicy
    from repro.sync.engine import WeightSyncEngine, apply_update

    rng = np.random.default_rng(0)
    params = {
        "wq": jnp.asarray(rng.normal(0, 0.02, (1 << 14,)), jnp.bfloat16),
        "wk": jnp.asarray(rng.normal(0, 0.02, (1 << 13,)), jnp.bfloat16),
        "step": jnp.asarray(0, jnp.int32),
    }
    prof = CompressionProfile(widths={"weight": 5, "delta": 2,
                                      "delta_lo": 4})
    eng = WeightSyncEngine(policy=CompressionPolicy(min_bytes=0,
                                                    profile=prof))
    replicas = {"r0": None, "r1": None}
    for i in range(publishes):
        version = eng.publish(params)
        for r in replicas:
            if r == "r1" and i < 2:
                continue  # r1 joins late: exercises the full-send path
            upd = eng.update_for(r)
            base = replicas[r] if upd.base_version is not None else None
            replicas[r] = apply_update(upd, base_params=base)
            eng.ack(r, version)
        # a small simulated optimizer step between publishes: sub-ULP
        # relative updates, so most bf16 weights round to NO change and
        # the warm XOR delta stays within the calibrated widths
        params = jax.tree.map(
            lambda l: jnp.asarray(
                np.asarray(l, np.float32)
                * (1 + rng.normal(0, 2e-4, l.shape)), l.dtype)
            if l.dtype == jnp.bfloat16 else l, params)
        params["step"] = params["step"] + 1


def _run_serve(steps: int) -> None:
    """A tiny PD-disaggregated serve loop (admission + decode)."""
    import jax
    import numpy as np

    from repro import configs
    from repro.models import transformer
    from repro.serve.engine import Request, ServeConfig, ServeEngine

    cfg = configs.get_smoke("smollm_135m")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=64, prefill_chunk=8, pd_disaggregated=True))
    rng = np.random.default_rng(0)
    for rid in range(3):
        prompt = rng.integers(0, cfg.vocab, 8).astype(np.int32)
        engine.submit(Request(rid=rid, prompt=prompt, max_new=4))
    engine.run(max_steps=max(steps, 16))


def _target_train_sync(steps: int) -> None:
    _run_train(steps)
    _run_sync(max(steps, 3))


TARGETS = {
    "train_sync": _target_train_sync,  # default: train steps + sync loop
    "sync": _run_sync,
    "serve": _run_serve,
}


def dump(target: str = "train_sync", out: str = None,
         steps: int = 3) -> dict:
    """Run ``target`` and write trace + metric artifacts; returns paths."""
    from repro import obs

    if target not in TARGETS:
        raise KeyError(f"unknown target {target!r}; have {sorted(TARGETS)}")
    obs.reset()
    TARGETS[target](steps)
    out = obs.trace_dir() if out is None else out
    os.makedirs(out, exist_ok=True)
    trace_path = obs.export_chrome_trace(
        os.path.join(out, f"trace_{target}.json"))
    json_path = os.path.join(out, f"metrics_{target}.json")
    with open(json_path, "w") as f:
        f.write(obs.registry().to_json(indent=2))
    md_path = os.path.join(out, f"metrics_{target}.md")
    with open(md_path, "w") as f:
        f.write(obs.registry().to_markdown() + "\n")
    return {"trace": trace_path, "metrics_json": json_path,
            "metrics_md": md_path}


def run() -> None:
    """benchmarks/run.py entry point (key "obs"): the default smoke
    dump."""
    paths = dump()
    print(f"obs dump: trace -> {paths['trace']}")
    print(f"obs dump: metrics -> {paths['metrics_json']}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--target", default="train_sync",
                    choices=sorted(TARGETS))
    ap.add_argument("--out", default=None,
                    help="output directory (default: REPRO_TRACE_DIR)")
    ap.add_argument("--steps", type=int, default=3,
                    help="workload size (train steps / publishes / "
                         "decode steps)")
    args = ap.parse_args()
    paths = dump(args.target, args.out, args.steps)
    for k, v in paths.items():
        print(f"{k}: {v}")


if __name__ == "__main__":
    main()
