"""Serving SLO metrics: TTFT / TPOT / ITL percentiles (paper §6 metrics).

Two sources:
  * wall-clock (frontend polling) — what a client observes;
  * device step stamps (ring.token_step / submit_step, plus the telemetry
    event log) — per-step-exact, mapped to wall time window by window
    through the host recorder's step→wall map (``telemetry/host.py``);
    used for the fine-grained engine comparisons (window polling
    granularity would otherwise floor wall-clock TTFT at one window).

Records cover every terminal request, not just DECODE_COMPLETED ones: a
CANCELLED or FAULTED slot with partial output still produced tokens the
client saw, so its TTFT and inter-token gaps belong in the tail
percentiles. Each record is tagged with its terminal state so callers
can slice either way.

When the telemetry event log is supplied, preempt→resume stalls are
subtracted from any inter-token gap that spans them: ITL/TPOT then
measure decode cadence, not scheduler-induced pauses (which surface
separately as `preempted`/`resumed` counters and trace instants).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core import ring_buffer as rb
from repro.telemetry import state as tel_lib

#: Terminal slot states that yield a request record.
TERMINAL_RING_STATES = (rb.DECODE_COMPLETED, rb.CANCELLED, rb.FAULTED)


def percentiles(xs: Sequence[float], ps=(50, 95, 99, 99.9)) -> Dict[str, float]:
    xs = np.asarray([x for x in xs if np.isfinite(x)], np.float64)
    if xs.size == 0:
        return {f"p{p}": float("nan") for p in ps} | {"mean": float("nan")}
    out = {f"p{p}": float(np.percentile(xs, p)) for p in ps}
    out["mean"] = float(xs.mean())
    return out


@dataclass
class StepMetrics:
    """Metrics derived from device step stamps."""
    ttft_steps: List[int]
    tpot_steps: List[float]
    itl_steps: List[int]


def _slot_events(events, slot: int) -> List:
    """Normalize an event source to ``[(name, step), ...]`` for one slot.

    ``events`` is anything exposing ``ev_code`` / ``ev_step`` / ``ev_count``
    — a device ``TelemetryState`` or a host mirror (any object or a
    3-tuple of arrays)."""
    if events is None:
        return []
    if isinstance(events, tuple):
        code, step, count = events
    else:
        code, step, count = events.ev_code, events.ev_step, events.ev_count
    return tel_lib.events_of_slot(np.asarray(code), np.asarray(step),
                                  np.asarray(count), slot)


def _preempt_stalls(events: List) -> List:
    """Closed ``(preempted_step, back_on_lane_step)`` episodes for a slot.

    An episode opens at ``preempted`` and closes at the next ``resumed``
    (a restored-from-offload request still waits in DECODE_PAUSED until a
    lane re-admits it, which is another ``resumed``). Open episodes — the
    request never got a lane again — are ignored; no token gap can span
    them."""
    stalls, open_at = [], None
    for name, step in events:
        if name == "preempted":
            open_at = step
        elif name == "resumed" and open_at is not None:
            stalls.append((open_at, step))
            open_at = None
    return stalls


def _stall_within(stalls: List, t0: int, t1: int) -> int:
    """Total stalled steps from episodes fully inside the gap [t0, t1]."""
    return sum(r - p for p, r in stalls if t0 <= p and r <= t1)


def request_records(ring, slots: Optional[Sequence[int]] = None,
                    events=None) -> List[dict]:
    """Per-request metric records from ring stamps (+ optional event log).

    With ``slots=None`` every slot currently in a terminal state
    (completed, cancelled, faulted) is included. Each record carries the
    terminal tag, token count, TTFT, per-gap ITL with preempt stalls
    excluded, TPOT (mean of kept gaps), and the raw event timeline."""
    token_step = np.asarray(ring.token_step)
    submit = np.asarray(ring.submit_step)
    gen = np.asarray(ring.generated)
    rid = np.asarray(ring.request_id)
    st = np.asarray(ring.slot_state)
    if slots is None:
        slots = [s for s in range(st.shape[0])
                 if int(st[s]) in TERMINAL_RING_STATES]
    recs = []
    for s in slots:
        n = int(gen[s])
        ev = _slot_events(events, int(s))
        stalls = _preempt_stalls(ev)
        rec = {
            "slot": int(s),
            "request_id": int(rid[s]),
            "terminal": rb.STATE_NAMES.get(int(st[s]), str(int(st[s]))),
            "n_tokens": n,
            "submit_step": int(submit[s]),
            "events": ev,
            "ttft_steps": None,
            "tpot_steps": None,
            "itl_steps": [],
        }
        if n > 0:
            steps = token_step[s, :n].astype(np.int64)
            rec["ttft_steps"] = int(steps[0] - submit[s])
            if n > 1:
                gaps = [int(steps[i + 1] - steps[i])
                        - _stall_within(stalls, int(steps[i]),
                                        int(steps[i + 1]))
                        for i in range(n - 1)]
                rec["itl_steps"] = gaps
                rec["tpot_steps"] = float(sum(gaps) / (n - 1))
        recs.append(rec)
    return recs


def from_ring(ring, slots: Optional[Sequence[int]] = None,
              events=None) -> StepMetrics:
    """Aggregate step-based metrics across terminal requests.

    Unlike the original completed-only version, partial-output CANCELLED
    and FAULTED requests contribute their TTFT and gaps too; pass an
    explicit ``slots`` list to restrict. Pass the telemetry event log as
    ``events`` to exclude preempt→resume stalls from ITL/TPOT."""
    ttft, tpot, itl = [], [], []
    for rec in request_records(ring, slots=slots, events=events):
        if rec["ttft_steps"] is not None:
            ttft.append(rec["ttft_steps"])
        if rec["tpot_steps"] is not None:
            tpot.append(rec["tpot_steps"])
        itl.extend(rec["itl_steps"])
    return StepMetrics(ttft, tpot, itl)
