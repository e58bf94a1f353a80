import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# Unroll layer scans so cost_analysis counts every layer (XLA counts
# while-loop bodies once; see models.transformer.layer_scan).
os.environ["REPRO_SCAN_UNROLL"] = "1"

"""Multi-pod dry-run (deliverable e) + roofline-term extraction (g).

For every (architecture x input shape) the production step function is
jit-compiled against ShapeDtypeStruct stand-ins on the 16x16 single-pod mesh
and the 2x16x16 multi-pod mesh:

    lowered  = jax.jit(step, in_shardings=...).lower(*input_specs)
    compiled = lowered.compile()
    compiled.memory_analysis()   # proves it fits
    compiled.cost_analysis()     # FLOPs / bytes for the roofline

Collective bytes are parsed from the compiled HLO text (all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute output sizes)
— they are not part of cost_analysis.

Usage:
    python -m repro.launch.dryrun --arch gemma2-9b --shape decode_32k
    python -m repro.launch.dryrun --arch gemma2-9b --shape decode_32k --multi-pod
    python -m repro.launch.dryrun --all            # everything, both meshes
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import re
import time
import traceback
from typing import Dict

import jax
import numpy as np

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ARCHS
from repro.launch import mesh as mesh_lib
from repro.launch.specs import build_plan
from repro.models.transformer import active_param_count, count_params

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def scatter_output_bytes(hlo_text: str) -> int:
    """Sum output sizes of scatter ops. XLA cost_analysis charges each
    scatter 2x its full operand (read+write); an in-place scatter on TPU
    touches only the indexed rows, so the roofline reports an adjusted
    memory term = bytes_accessed - 2 * scatter_bytes (update bytes are
    negligible). Verified with a micro-probe (see EXPERIMENTS.md)."""
    total = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?[%\w\.\-]+\s*=\s*(.*)", stripped)
        if not m:
            continue
        rhs = m.group(1)
        if re.search(r"\bscatter\(", rhs):
            head = rhs.split("scatter", 1)[0]
            total += sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head))
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output bytes of collective ops in the HLO, by collective kind."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?[%\w\.\-]+\s*=\s*(.*)", stripped)
        if not m:
            continue
        rhs = m.group(1)
        for kind in _COLLECTIVES:
            # match the op invocation, e.g. "all-reduce(" or "all-gather-start("
            if re.search(rf"\b{kind}(-start)?\(", rhs):
                # output type(s) = everything before the op name
                head = rhs.split(kind)[0]
                nbytes = sum(_shape_bytes(d, s)
                             for d, s in _SHAPE_RE.findall(head))
                out[kind] += nbytes
                out["count"] += 1
                break
    return out


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode D = batch
    tokens (one step), prefill/train D = batch*seq tokens; train x3 for
    fwd+bwd (6ND already counts fwd+bwd; serve uses 2ND)."""
    n_active = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch          # one token per lane
    return 2.0 * n_active * tokens


def run_one(arch: str, shape_name: str, multi_pod: bool,
            *, kv_dtype: str = "bfloat16", tag: str = "",
            expert_parallel: bool = False) -> dict:
    cfg = ARCHS[arch]
    shape = INPUT_SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh_lib.mesh_num_chips(mesh)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "kind": shape.kind, "kv_dtype": kv_dtype, "tag": tag,
        "params": count_params(cfg), "active_params": active_param_count(cfg),
    }
    plan = build_plan(cfg, shape, mesh, kv_dtype=kv_dtype)
    if plan.kind == "skip":
        rec["status"] = "skipped"
        rec["skip_reason"] = plan.skip_reason
        return rec

    fn = plan.fn
    t0 = time.time()
    with mesh, jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=plan.in_shardings)
        lowered = jitted.lower(*plan.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    rec["status"] = "ok"
    rec["lower_s"] = round(t_lower, 2)
    rec["compile_s"] = round(t_compile, 2)

    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
    except Exception as e:  # CPU backend may not expose everything
        rec["memory"] = {"error": str(e)}

    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float))
                       and k in ("flops", "bytes accessed", "transcendentals",
                                 "bytes accessed output", "optimal_seconds")}
    except Exception as e:
        rec["cost"] = {"error": str(e)}

    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(hlo)
    rec["scatter_bytes"] = scatter_output_bytes(hlo)
    rec["hlo_lines"] = hlo.count("\n")

    # roofline terms (per DESIGN/EXPERIMENTS methodology)
    flops = rec.get("cost", {}).get("flops", 0.0) or 0.0
    bytes_acc = rec.get("cost", {}).get("bytes accessed", 0.0) or 0.0
    coll = sum(v for k, v in rec["collectives"].items() if k != "count")
    # SSM/hybrid prefill+train keep an inner chunk scan (trip count nc =
    # T/64) that cost_analysis counts once; correct multiplicatively.
    # Layer scans are fully unrolled (REPRO_SCAN_UNROLL), so this is the
    # only rolled loop left. Upper bound: the non-loop epilogue (embedding,
    # unembed, loss) is overcorrected by the same factor.
    if cfg.arch_type in ("ssm", "hybrid") and shape.kind in ("prefill",
                                                             "train"):
        nc = max(shape.seq_len // 64, 1)
        rec["chunk_loop_correction"] = nc
        flops *= nc
        bytes_acc *= nc
        coll *= nc
    # cost_analysis reports whole-program numbers for the SPMD program,
    # which is per-device already under jit-SPMD.
    scatter_adj = rec.get("scatter_bytes", 0)
    if cfg.arch_type in ("ssm", "hybrid") and shape.kind in ("prefill",
                                                             "train"):
        scatter_adj *= rec.get("chunk_loop_correction", 1)
    bytes_adj = max(bytes_acc - 2 * scatter_adj, 0.0)
    rec["roofline"] = {
        "compute_s": flops / mesh_lib.PEAK_FLOPS_BF16,
        "memory_s": bytes_adj / mesh_lib.HBM_BW,
        "memory_raw_s": bytes_acc / mesh_lib.HBM_BW,
        "collective_s": coll / mesh_lib.ICI_BW,
        "model_flops_total": model_flops(cfg, shape),
    }
    terms = {k: rec["roofline"][k] for k in
             ("compute_s", "memory_s", "collective_s")}
    rec["roofline"]["bottleneck"] = max(terms, key=terms.get)
    return rec


def result_path(arch, shape, mesh_name, tag=""):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape}__{mesh_name}{suffix}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--kv-dtype", default="bfloat16")
    ap.add_argument("--tag", default="")
    ap.add_argument("--force", action="store_true")
    # §Perf hillclimb switches (see EXPERIMENTS.md §Perf)
    ap.add_argument("--rolled", action="store_true",
                    help="keep layer scans rolled (workaround for an XLA "
                         "crash with shard_map+unroll; loop bodies counted "
                         "once — use only for baseline/optimized RATIOS "
                         "with a matching --rolled baseline)")
    ap.add_argument("--fast-attn", action="store_true",
                    help="REPRO_FAST_ATTN: no f32 KV upcast materialization")
    ap.add_argument("--window-gather", action="store_true",
                    help="REPRO_WINDOW_GATHER: gather only the live window")
    args = ap.parse_args()

    if args.rolled:
        os.environ["REPRO_SCAN_UNROLL"] = "0"
    if args.fast_attn:
        os.environ["REPRO_FAST_ATTN"] = "1"
    if args.window_gather:
        os.environ["REPRO_WINDOW_GATHER"] = "1"

    combos = []
    if args.all:
        for arch in ARCHS:
            for shape in INPUT_SHAPES:
                for mp in (False, True):
                    combos.append((arch, shape, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape, args.multi_pod)]

    failures = 0
    for arch, shape, mp in combos:
        mesh_name = "2x16x16" if mp else "16x16"
        path = result_path(arch, shape, mesh_name, args.tag)
        if os.path.exists(path) and not args.force:
            print(f"[cached] {arch} {shape} {mesh_name}")
            continue
        print(f"[dryrun] {arch} {shape} {mesh_name} ...", flush=True)
        try:
            rec = run_one(arch, shape, mp, kv_dtype=args.kv_dtype,
                          tag=args.tag)
        except Exception as e:
            rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                   "status": "error", "error": str(e),
                   "traceback": traceback.format_exc()}
            failures += 1
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" compute={r['compute_s']*1e3:.2f}ms"
                     f" memory={r['memory_s']*1e3:.2f}ms"
                     f" collective={r['collective_s']*1e3:.2f}ms"
                     f" bottleneck={r['bottleneck']}")
        print(f"  -> {status}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run combos failed")


if __name__ == "__main__":
    main()
