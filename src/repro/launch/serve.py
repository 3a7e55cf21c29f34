"""Serving driver: slot-based continuous batching over a smoke model.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm_135m --smoke \
        --requests 16 --max-new 24
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer
from repro.serve.engine import Request, ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    if cfg.enc_dec:
        raise SystemExit("enc-dec serving demo not wired in this driver; "
                         "see tests/test_serve.py for whisper decode")
    params = transformer.init(jax.random.PRNGKey(args.seed), cfg)
    eng = ServeEngine(cfg, params, ServeConfig(
        batch_slots=args.slots, max_len=args.max_len,
        prefill_chunk=args.prompt_len))
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    for i in range(args.requests):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab,
                                               args.prompt_len).astype(np.int32),
                           max_new=args.max_new))
    done = eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s, slots={args.slots})")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out[:8]}...")


if __name__ == "__main__":
    main()
