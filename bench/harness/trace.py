"""Reduction of a JAX profiler trace to device busy time and kernel time.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation run on the chip, with a start and a duration in
nanoseconds on the trace's common clock. Host planes hold the benchmark's
own ``TraceAnnotation`` spans on the same clock.

- busy: the union of a device's operation intervals inside the traced
  window, averaged over the devices;
- kernel time: the summed durations of operations whose name contains the
  kernel's name (the ``name=`` given to its ``pallas_call``), which names
  the HLO instruction of its custom call;
- idle time by what the host was doing: the stretches of the window in
  which no operation ran, each labelled by the innermost benchmark span
  (``bench.*``) around its middle, summed per label.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced_window"
# control-flow operations whose events enclose the operations they run:
# they count towards busy time, but not as operations of their own
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over devices
    devices: int
    op_seconds: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)  # by label

    def kernel_seconds(self, kernel: str) -> float:
        """Summed device time of operations named after ``kernel``, per
        device."""
        return sum(s for n, s in self.op_seconds.items() if kernel in n) \
            / max(self.devices, 1)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        per = [(k, v / max(self.devices, 1))
               for k, v in self.op_seconds.items()]
        return sorted(per, key=lambda kv: -kv[1])[:n]


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals, lo, hi) -> List[Tuple[float, float]]:
    """Stretches of ``[lo, hi)`` that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _label(gap, spans) -> str:
    """The innermost benchmark span around the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    inside = [(e - s, n) for n, s, e in spans if s <= mid < e]
    return min(inside)[1] if inside else "no benchmark span"


def op_kind(event_name: str) -> str:
    """``%fusion.123 = bf16[...] fusion(...)`` -> ``fusion``: the HLO
    instruction's name without its number, so the events of one kernel
    (``%ragged_attention.10 = ...``) sum under the kernel's name."""
    name = event_name.split(" = ", 1)[0].strip().lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_planes(planes) -> Reduced:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events``; events with ``name``, ``start_ns``, ``duration_ns``),
    as ``jax.profiler.ProfileData`` gives them."""
    window, spans = None, []
    device_ops = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.append([(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events])
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    if not device_ops:
        raise ValueError(f"trace holds no device plane with {OPS_LINE!r}")
    lo, hi = window
    busy, op_ns, all_gaps = 0.0, defaultdict(float), []
    for ops in device_ops:
        inside = [(max(s, lo), min(e, hi), n) for n, s, e in ops
                  if e > lo and s < hi]
        iv = [(s, e) for s, e, _ in inside]
        busy += union_length(iv)
        for s, e, n in inside:
            kind = op_kind(n)
            if kind not in CONTAINERS:
                op_ns[kind] += e - s
        all_gaps.extend(gaps(iv, lo, hi))
    n_dev = len(device_ops)
    idle = defaultdict(float)
    for g in all_gaps:
        idle[_label(g, spans)] += (g[1] - g[0]) / n_dev
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy / n_dev * 1e-9,
        devices=n_dev,
        op_seconds={k: v * 1e-9 for k, v in op_ns.items()},
        idle_gaps=sorted(((k, v * 1e-9) for k, v in idle.items()),
                         key=lambda kv: -kv[1])[:10])


def reduce_dir(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(find_xplane(trace_dir)).planes)
