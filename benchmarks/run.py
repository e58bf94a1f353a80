"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks.common.emit).

  fig3_makespan        Fig. 3  device- vs host-resident scheduling makespan
  table6_presaturation Table 6 pre-saturation P99 TTFT/TPOT + throughput
  table7_interference  Table 7 / Fig. 1 CPU-interference retention
  fig4_tokenizer       Fig. 4  DPU tokenizer throughput vs naive baseline
  kernels              §4.2    Pallas kernels vs oracles
  decode_attn          §4.2    decode attention backends: gather vs pallas
  prefill_attn         §4.2    prefill attention backends: gather vs flash
  unified_attn         §4.2    unified ragged dispatch: 1 vs 2 launches/step
  prefix_cache         §4.2    radix prefix reuse: hit rate vs TTFT / pages
  tpot_under_load      Table 6 P99 inter-token gap: mixed-phase vs
                               phase-exclusive scheduling under admission
  roofline             (g)     dry-run roofline table

REPRO_BENCH_SMOKE=1 shrinks the attention-backend sweeps to one tiny point
(the CI dry-run mode that keeps these scripts from rotting).
"""
from __future__ import annotations

import sys
import time
import traceback

from benchmarks import (decode_attn, fig3_makespan, fig4_tokenizer,
                        kernels, prefill_attn, prefix_cache, roofline,
                        table6_presaturation, table7_interference,
                        tpot_under_load, unified_attn)
from benchmarks.common import emit

MODULES = [
    ("fig4_tokenizer", fig4_tokenizer),
    ("kernels", kernels),
    ("decode_attn", decode_attn),
    ("prefill_attn", prefill_attn),
    ("unified_attn", unified_attn),
    ("prefix_cache", prefix_cache),
    ("tpot_under_load", tpot_under_load),
    ("fig3_makespan", fig3_makespan),
    ("table6_presaturation", table6_presaturation),
    ("table7_interference", table7_interference),
    ("roofline", roofline),
]


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in MODULES:
        if only and only != name:
            continue
        t0 = time.time()
        try:
            mod.main()
            emit(f"_{name}_total", (time.time() - t0) * 1e6, "ok")
        except Exception as e:
            traceback.print_exc()
            emit(f"_{name}_total", (time.time() - t0) * 1e6,
                 f"FAILED:{type(e).__name__}")
            failures += 1
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
