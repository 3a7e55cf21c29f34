"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm_135m --smoke \
        --steps 200 --batch 8 --seq 128

Wires together: config registry → data pipeline → compressed train step
(+ its compression-disabled fallback twin for overflow retry) → fault-
tolerant StepRunner (checkpoint/resume, straggler detection, heartbeat).
"""
from __future__ import annotations

import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.policy import CompressionPolicy
from repro.data.pipeline import DataConfig, DataPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.optim import optimizers as opt_lib
from repro.runtime.fault_tolerance import RunnerConfig, StepRunner
from repro.train import step as step_lib


def build(args, mesh=None):
    """``mesh`` defaults to the smoke mesh over every visible device."""
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    if mesh is None:
        mesh = make_smoke_mesh(pods=args.pods)
    dp = step_lib.dp_axes_of(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    policy = (CompressionPolicy(min_bytes=args.compress_min_bytes)
              if not args.no_compress else CompressionPolicy.disabled())
    tcfg = step_lib.TrainConfig(
        microbatches=args.microbatches,
        partition=args.partition,
        optim=opt_lib.OptimConfig(name=args.optimizer, lr=args.lr,
                                  warmup_steps=args.warmup),
        policy=policy,
        loss_chunk=min(1024, args.seq),
    )
    step, _ = step_lib.build_train_step(cfg, tcfg, mesh)
    fallback = None
    if policy.enabled:
        tcfg_raw = dataclasses.replace(tcfg,
                                       policy=CompressionPolicy.disabled())
        fallback, _ = step_lib.build_train_step(cfg, tcfg_raw, mesh)
    state, _ = step_lib.build_train_state(cfg, tcfg, mesh,
                                          jax.random.PRNGKey(args.seed))
    pipe = DataPipeline(DataConfig(vocab=cfg.vocab, global_batch=args.batch,
                                   seq_len=args.seq, seed=args.seed))

    dpax = dp if len(dp) > 1 else dp[0]
    bshard = NamedSharding(mesh, P(dpax, None))

    def wrap(fn):
        # a ZeRO-1 step is two jitted programs (train/step.py), donating
        # its state; another jit around it would fuse them back into one
        jfn = fn if tcfg.partition == "zero1" else jax.jit(
            fn, donate_argnums=(0,))

        def run(state, batch):
            batch = {k: jax.device_put(jnp.asarray(v), bshard)
                     for k, v in batch.items()}
            return jfn(state, batch)
        return run

    runner = StepRunner(
        wrap(step), wrap(fallback) if fallback else None,
        RunnerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     heartbeat_path=args.heartbeat,
                     install_sigterm=args.sigterm),
        pipeline=pipe,
    )
    return cfg, tcfg, mesh, state, runner


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--partition", default="zero1", choices=["zero1", "fsdp"])
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--compress-min-bytes", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--heartbeat", default=None)
    ap.add_argument("--sigterm", action="store_true")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    use_compile_cache()
    cfg, tcfg, mesh, state, runner = build(args)
    start = 0
    if args.resume:
        resumed, start = runner.try_resume(state)
        if resumed is not None:
            state = resumed
            print(f"resumed from step {start}")
    print(f"arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"params={cfg.param_count()/1e6:.1f}M partition={tcfg.partition} "
          f"compressed={tcfg.policy.enabled}")
    state, history = runner.train(state, start_step=start,
                                  num_steps=args.steps)
    print(f"final loss {history[-1]:.4f} | stragglers {runner.stragglers} "
          f"| retries {runner.retries}")


if __name__ == "__main__":
    main()
