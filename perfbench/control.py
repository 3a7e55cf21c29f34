"""The readings the limits of ``correct`` are set from, on the chip.

    python3 perfbench/control.py --workload smollm_135m.wsync_rl \
        --seconds 51 --control 1 --seeds 11 12 13

Runs the cell once per seed, all in this one process (a chip belongs to
one process), and prints one JSON line per seed: the seed, whether the
control ran, ``correct`` and the numbers compared.  With ``--control 0``
these are the program's readings.  With ``--control 1`` the generator's
``control_hooks()`` put the reference in the program's place, computed one
precision below the configuration's; it has to come out not correct.  The
benchmark's own runs never run the control.
"""
import argparse
import io
import json
import sys

import run  # perfbench/run.py, beside this file


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    run.setup_process()
    from perfbench import harness

    cell = harness.resolve(run.ROOT, args.workload)
    hooks = (harness.generator_of(run.ROOT, cell).control_hooks()
             if args.control else {})
    for seed in args.seeds:
        err = io.StringIO()
        try:
            r = harness.run_cell(run.ROOT, args.workload, seed, args.seconds,
                                 False, hooks=hooks, err=err)
        except harness.NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 2
        sys.stderr.write(err.getvalue())
        print(json.dumps({"seed": seed, "control": bool(args.control),
                          "correct": r["correct"], "checks": r["checks"],
                          "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
