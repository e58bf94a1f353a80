"""Two sets of runs of one cell on the same seeds, one process per run,
and the spread of each metric: what a bound is set from.

    python bench/sets.py --workload <cell> --seconds <s> --seeds 1 2 3 4 5 6 \\
        [--traced 3] [--out runs.jsonl]

Runs set A over the seeds, then set B over the same seeds, then
``--traced`` runs with ``--trace 1`` on further seeds; appends every
result line to ``--out`` and prints, for every metric, each set's median
and its quartile spread (``statistics.quantiles(n=4)``, the distance
between the first and third quartile as a share of the median). This
process never imports JAX, so each run holds the chip alone.
"""
import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).with_name("run.py")


def run_once(cell, seed, seconds, trace):
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, str(RUN), "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        result = None
    return {"seed": seed, "trace": trace, "rc": p.returncode,
            "wall_s": time.time() - t0, "result": result,
            "notes": lines[:-1], "stderr_tail": p.stderr[-2000:]}


def spread(xs):
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)


def summary(records):
    sets = {}
    for r in records:
        if r["trace"] or r["result"] is None:
            continue
        for k, v in r["result"]["metrics"].items():
            sets.setdefault(k, {}).setdefault(r["set"], []).append(v["value"])
    out = {}
    for k, by in sorted(sets.items()):
        if len(by) == 2 and all(len(v) >= 2 for v in by.values()):
            out[k] = {s: {"median": statistics.median(v), "spread": spread(v)}
                      for s, v in sorted(by.items())}
            out[k]["widest_spread"] = max(x["spread"] for x in
                                          out[k].values())
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--out", default="runs.jsonl")
    args = ap.parse_args()
    plan = [("A", s, 0) for s in args.seeds] + \
        [("B", s, 0) for s in args.seeds] + \
        [("T", s + 100, 1) for s in args.seeds[:args.traced]]
    records = []
    with open(args.out, "a") as f:
        for tag, seed, trace in plan:
            rec = dict(run_once(args.workload, seed, args.seconds, trace),
                       set=tag)
            records.append(rec)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            res = rec["result"] or {}
            print(tag, seed, "rc", rec["rc"], "correct", res.get("correct"),
                  json.dumps(res.get("metrics", {})), flush=True)
    print("SPREAD " + json.dumps(summary(records)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
