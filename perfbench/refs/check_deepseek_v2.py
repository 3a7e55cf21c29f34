"""The deepseek_v2_lite replica's logits after one delta round, against
the reference, on the chip.

    python3 perfbench/refs/check_deepseek_v2.py --seeds 7 8 9

For each seed, in one process: builds the ``deepseek_v2_lite.wsync_rl``
cell's version chain from the seed at the published widths, publishes v0
in full and v1 as a delta to a ``ServeEngine`` replica under the cell's
``ServeConfig`` (the cell's own calibrated widths), then prefills a seeded
60-token prompt from the vocabulary slice and takes 4 greedy decode steps
through the replica's latent cache, which holds the cell's 64 positions.
The reference (``perfbench/refs/deepseek_v2.py``, float32) computes the
logits of the same 64 tokens from v1, routing over all 64 experts and
adding the held experts' part, as the program does.  Prints one JSON line
a seed: the replica's largest logit error over the reference logits' RMS
beside ``deepseek_v2.TOL_STAGE_BF16``, whether the replica holds v1's
bits, and the same error for the control, the replica run on v1 rounded
through float8.  Exits 1 if, on any seed, the replica fails the tolerance
or the control meets it.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepseek_v2_lite.wsync_rl"
PROMPT, STEPS = 60, 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import archcfg, chain, harness
    from perfbench.refs import deepseek_v2 as ref
    from repro.core import calibrate
    from repro.core.policy import CompressionPolicy
    from repro.models import transformer
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.sync import WeightSyncEngine

    cell = harness.resolve(ROOT, CELL)
    wsync = harness.generator_of(ROOT, cell)
    cfg = archcfg.arch_config(cell.config)
    mix = cell.mix
    scfg = ServeConfig(**mix["replica"])
    shapes = jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), cfg))

    def check(seed: int) -> bool:
        versions = chain.make_chain(
            shapes, chain.seed_key(seed),
            **{k: mix[k] for k in wsync.CHAIN_KEYS})
        flat = [jnp.concatenate([l.reshape(-1) for l in
                                 jax.tree_util.tree_leaves(v)])
                for v in versions[:2]]
        w_d, w_lo = calibrate.choose_delta_widths(flat[1], flat[0])
        del flat
        base = calibrate.CompressionProfile.default(cfg.dtype)
        engine = WeightSyncEngine(policy=CompressionPolicy(
            profile=calibrate.CompressionProfile(
                widths={**base.widths, "delta": w_d, "delta_lo": w_lo})))
        replica = ServeEngine(cfg, jax.tree.map(jnp.zeros_like, versions[0]),
                              scfg)
        modes = []
        for v in versions[:2]:
            engine.publish(v)
            update = engine.update_for("replica-0")
            replica.ingest_weights(update)
            engine.ack("replica-0", update.version, update.epoch)
            modes.append(update.mode)
        held = int(wsync.mismatches(replica.params, versions[1]))

        prompt = np.random.default_rng(seed).integers(
            0, cfg.vocab, PROMPT).astype(np.int32)

        def serve(params):
            cache = transformer.init_cache(cfg, 1, scfg.max_len)
            lg, cache = replica.prefill_step(
                params, {"tokens": jnp.asarray(prompt[None])}, cache)
            got, seq = [lg[0, -1]], list(prompt)
            for _ in range(STEPS):
                seq.append(int(jnp.argmax(got[-1])))
                lg, cache = replica.decode_step(
                    params, jnp.asarray([[seq[-1]]], jnp.int32), cache)
                got.append(lg[0, -1])
            return jnp.stack(got).astype(jnp.float32), seq

        def rel_err(params):
            got, seq = serve(params)
            want = ref.forward(versions[1], jnp.asarray([seq]),
                               cell.config)[0, PROMPT - 1:]
            return float(jnp.max(jnp.abs(got - want))
                         / jnp.sqrt(jnp.mean(want * want)))

        err = rel_err(replica.params)
        control = rel_err(wsync.lower_precision(versions[1]))
        ok = held == 0 and err < ref.TOL_STAGE_BF16 < control
        print(json.dumps({"seed": seed, "modes": modes,
                          "widths": [w_d, w_lo], "mismatched_elements": held,
                          "rel_err": err, "tolerance": ref.TOL_STAGE_BF16,
                          "control_rel_err": control, "ok": ok,
                          "device": jax.devices()[0].device_kind}),
              flush=True)
        return ok

    oks = [check(seed) for seed in args.seeds]
    return 0 if all(oks) else 1


if __name__ == "__main__":
    sys.exit(main())
