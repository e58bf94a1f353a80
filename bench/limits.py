"""Readings that a correctness limit is set from: the widest logit gap of
the program and of the control (the reference computed in the
configuration's ``correct.control`` format, in the program's place), on
many seeds, at a cell's own sizes and load, in one process.

    python bench/limits.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

Each seed is one run as ``bench/run.py`` makes it (a shorter window is
enough: every run compares the same number of served tokens); the first
``--control-seeds`` of them run as ``bench/run.py --control 1`` does, so
their ``correct`` is the control's, and they read the program's gap on
the same sample beside it. The benchmark's own runs never run the
control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control")
    args = ap.parse_args()
    from bench.harness import runner, spec
    import jax
    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    rows = []
    for i, seed in enumerate(args.seeds):
        out = runner.run_cell(cell, seed, args.seconds, False,
                              time.perf_counter(),
                              control=i < args.control_seeds)
        r = out.result
        row = {"seed": seed, "correct": r["correct"],
               "attempted": r["attempted"], "failed": r["failed"],
               "gap": out.readings["program_logit_gap"],
               "control_gap": out.readings.get("control_logit_gap")}
        print("READING " + json.dumps(row), flush=True)
        rows.append(row)
    gaps = [r["gap"] for r in rows]
    ctrl = [r["control_gap"] for r in rows if r["control_gap"] is not None]
    print("SUMMARY " + json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "lower_max_program_gap": max(gaps),
        "upper_min_control_gap": min(ctrl) if ctrl else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
