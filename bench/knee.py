"""The knee sweep of an open-loop cell: its traffic at a series of fixed
rates, in one process.

    python bench/knee.py --workload <cell> --seconds <s> --seed <n> \\
        --rates 0.2 0.7 0.8 ...

The first rate is taken as a request alone and runs once; every other
rate runs ``REPEATS`` times, on seeds ``n``, ``n + 1``, ... Every rate
offers the cell's own schedule (the same requests in the same order),
its gaps scaled to the rate. For every run it prints the tails of time to
first token and of time per output token, the requests due, the failed
ones, and how fast the queue grew. A rate passes when each of its runs
keeps both tails within the multiples of the lone request's medians that
the traffic file's ``knee`` entry states, fails nothing, and grows no
queue. The knee is the highest rate that passed with every lower rate;
the cell then runs at a fixed rate below it. The sweep ends after
``STOP_AFTER`` rates in a row have failed. The benchmark's own runs
never sweep.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

KEYS = ("due_in_window", "ttft_p50_ms", "ttft_p90_ms", "ttft_p95_ms",
        "tpot_p50_ms", "tpot_p90_ms", "tpot_p95_ms", "output_tokens_per_s",
        "backlog_growth_per_s")
REPEATS = 2         # runs per rate, so that one noisy run cannot set the knee
STOP_AFTER = 2      # failing rates in a row after which higher rates are moot


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    from bench.harness import runner, spec
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    base = spec.load_cell(args.workload)
    lead = float(base.traffic.get("lead_s", 0))
    for rate in args.rates:
        if rate * (lead + args.seconds) >= base.traffic["requests"]:
            raise ValueError(f"rate {rate}: the cell's {base.traffic['requests']}"
                             " requests end before the window closes")
    rows, failing = [], 0
    for i, rate in enumerate(args.rates):
        cell = copy.copy(base)
        cell.traffic = dict(base.traffic, rate_per_s=rate)
        runs = []
        for k in range(1 if i == 0 else REPEATS):
            out = runner.run_cell(cell, args.seed + k, args.seconds, False,
                                  time.perf_counter())
            row = {"rate_per_s": rate, "seed": args.seed + k,
                   "failed": out.result["failed"],
                   "correct": out.result["correct"]}
            row.update({k2: out.readings.get(k2) for k2 in KEYS})
            if i:
                row["ok"] = passes(row, rows[0], base.traffic["knee"],
                                   args.seconds)
            print("KNEE " + json.dumps(row), flush=True)
            runs.append(row)
        rows += runs
        failing = 0 if i == 0 or all(r["ok"] for r in runs) else failing + 1
        if failing >= STOP_AFTER:
            break
    knee = judge(rows, base.traffic["knee"], args.seconds)
    print("KNEE_RATE " + json.dumps(knee), flush=True)
    return 0


def limits_of(alone: dict, limits: dict):
    return (limits["ttft_p90_multiple"] * alone["ttft_p50_ms"],
            limits["tpot_p90_multiple"] * alone["tpot_p50_ms"])


def passes(r: dict, alone: dict, limits: dict, seconds: float) -> bool:
    """Both tails within their multiples of the lone request's medians,
    nothing failed, and the queue grew by no more than the allowance over
    the window."""
    ttft_lim, tpot_lim = limits_of(alone, limits)
    grew = r["backlog_growth_per_s"] * seconds
    allowed = max(limits["max_queue_growth_requests"],
                  limits["max_queue_growth_share"] * r["due_in_window"])
    return (r["failed"] == 0 and r["ttft_p90_ms"] <= ttft_lim
            and r["tpot_p90_ms"] <= tpot_lim and grew <= allowed)


def judge(rows, limits: dict, seconds: float) -> dict:
    """The highest rate at which this and every lower rate passed on each
    of its runs; ``rows[0]`` is the lone request, the others are grouped
    by rate in the order they ran."""
    alone = rows[0]
    ttft_lim, tpot_lim = limits_of(alone, limits)
    knee = None
    for rate, runs in itertools.groupby(rows[1:],
                                        key=lambda r: r["rate_per_s"]):
        if not all(passes(r, alone, limits, seconds) for r in runs):
            break
        knee = rate
    return {"ttft_p90_limit_ms": ttft_lim, "tpot_p90_limit_ms": tpot_lim,
            "knee_rate_per_s": knee,
            "cell_rate_per_s": None if knee is None
            else round(limits["fraction"] * knee, 3)}


if __name__ == "__main__":
    sys.exit(main())
