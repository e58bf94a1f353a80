"""The benchmark's one command: one run of one cell on the chips of this
machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones), ``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``,
each compared number beside its limit. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.

``--control 1`` checks the check: the reference computed in the
configuration's control format (``correct.control``) takes the program's
place in the comparison, and the run has to come out not correct. The
benchmark's own runs leave it at 0.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench.harness import runner, spec
    t_proc0 = T0 - (runner.process_age_s() - (time.perf_counter() - T0))
    cell = spec.load_cell(args.workload)
    # the cell's files pin every setting: no environment overrides
    for var in ("REPRO_ATTN_BACKEND", "REPRO_WINDOW_GATHER",
                "REPRO_FAST_ATTN", "REPRO_SCAN_UNROLL"):
        os.environ.pop(var, None)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"needs {cell.chips} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    out = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_proc0, control=bool(args.control))
    print(json.dumps(out.result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
