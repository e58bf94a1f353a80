import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
os.environ["REPRO_SCAN_UNROLL"] = "1"

"""Per-op HLO byte/flop profile for a dry-run combo — the §Perf "profiler".

Parses the compiled HLO text: every op line contributes output bytes plus
the sizes of its operands (resolved from their definition sites). Groups by
op kind and prints the top contributors — this is how the hillclimb
enumerates candidates ("look for redundant converts/copies, gather/scatter
volume, collective placement").

    python -m repro.launch.hlo_profile --arch gemma2-9b --shape long_500k \
        [--window-gather --fast-attn --kv-dtype int8]
"""
import argparse
import re
from collections import defaultdict

import jax

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ARCHS
from repro.launch import mesh as mesh_lib
from repro.launch.specs import build_plan

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}
_TYPE_RE = re.compile(
    r"((?:f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)"
    r"\[[0-9,]*\])")
_DEF_RE = re.compile(r"^(?:ROOT\s+)?(%[\w\.\-]+)\s*=\s*(.*)$")
_OP_RE = re.compile(r"([a-z][\w\-]*)\(")
_OPERAND_RE = re.compile(r"(%[\w\.\-]+)")


def _bytes_of(type_str: str) -> int:
    dt, dims = type_str.split("[")
    dims = dims.rstrip("]")
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def profile_hlo(hlo: str, top: int = 20):
    sizes = {}
    by_kind = defaultdict(lambda: [0, 0])   # kind -> [bytes, count]
    for line in hlo.splitlines():
        m = _DEF_RE.match(line.strip())
        if not m:
            continue
        name, rhs = m.groups()
        types = _TYPE_RE.findall(rhs.split(" ", 1)[0] if False else rhs[:rhs.find("(")] if "(" in rhs else rhs)
        out_bytes = sum(_bytes_of(t) for t in types)
        sizes[name] = out_bytes
        om = _OP_RE.search(rhs)
        kind = om.group(1) if om else "const"
        if kind in ("parameter", "constant"):
            continue
        operand_bytes = sum(sizes.get(o, 0)
                            for o in _OPERAND_RE.findall(
                                rhs[rhs.find("("):] if "(" in rhs else ""))
        by_kind[kind][0] += out_bytes + operand_bytes
        by_kind[kind][1] += 1
    rows = sorted(by_kind.items(), key=lambda kv: -kv[1][0])[:top]
    total = sum(v[0] for v in by_kind.values())
    print(f"{'op kind':28s} {'GB':>10s} {'%':>6s} {'count':>8s}")
    for kind, (b, c) in rows:
        print(f"{kind:28s} {b/1e9:10.2f} {100*b/max(total,1):6.1f} {c:8d}")
    print(f"{'TOTAL (out+operands)':28s} {total/1e9:10.2f}")
    return by_kind


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--kv-dtype", default="bfloat16")
    ap.add_argument("--fast-attn", action="store_true")
    ap.add_argument("--window-gather", action="store_true")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args()
    if args.fast_attn:
        os.environ["REPRO_FAST_ATTN"] = "1"
    if args.window_gather:
        os.environ["REPRO_WINDOW_GATHER"] = "1"

    cfg = ARCHS[args.arch]
    shape = INPUT_SHAPES[args.shape]
    mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
    plan = build_plan(cfg, shape, mesh, kv_dtype=args.kv_dtype)
    with mesh, jax.set_mesh(mesh):
        compiled = jax.jit(plan.fn, in_shardings=plan.in_shardings).lower(
            *plan.args).compile()
    profile_hlo(compiled.as_text(), top=args.top)


if __name__ == "__main__":
    main()
