"""Run one benchmark cell once and print its result as the last line.

    python3 perfbench/run.py --workload smollm_135m.wsync_rl --seed 7 \
        --seconds 51 --trace 0

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics read from a
profiler trace of the window.  Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.

JAX's persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when
that is set, else ``<checkout>/.jax_cache``, so that only a cell's first
run in a checkout compiles.
"""
import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_process() -> None:
    """Import paths, the compile cache and the compiler's logs: before
    JAX is imported."""
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    import jax

    # every program of the cell goes into the cache, the eager codec ops
    # (each compiles in under a second) included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    setup_process()
    from perfbench import harness

    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_start=T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
