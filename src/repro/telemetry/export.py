"""Telemetry exporters: Prometheus text exposition and Perfetto traces.

The device telemetry plane produces two raw streams (see
``telemetry/state.py``): per-step counter rows and a per-slot event log
of ``(code, step)`` stamps. The host recorder (``telemetry/host.py``)
adds host spans and the per-window step→wall map. This module turns
those into the two standard observability formats without touching the
hot path:

  * :func:`prometheus_text` — the Prometheus text exposition format
    (counters summed over every drained row, gauges from the latest row,
    optional latency summaries as quantile-labelled gauges);
  * :func:`perfetto_trace` — a Chrome-trace / Perfetto JSON object that
    holds the recorder's host spans and the request spans on one
    timeline. Event step stamps become wall time through the step→wall
    map: uniform inside one window, so a stamp is off by at most one
    window's step spread, and boundary waits are not spread over steps;
  * :func:`span_summaries` — compact per-request phase durations for the
    CLI final report.

Everything here runs on the host after drain; nothing is jitted.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from repro.telemetry import state as tel_lib
from repro.telemetry.metrics import percentiles

#: Row columns exported as monotonically increasing counters (summed
#: over drained rows). The rest are point-in-time gauges.
_COUNTER_COLS = (
    "tokens", "chunk_tokens", "chunk_dispatches", "admitted", "cancelled",
    "preempted", "resumed", "faulted", "watchdog_fires", "trie_hit_tokens",
    "expert_rows", "experts_hit",
)
_GAUGE_COLS = ("decode_lanes", "free_pages")

_HELP = {
    "tokens": "Decode tokens produced across all lanes.",
    "chunk_tokens": "Prompt tokens prefetched into KV by chunked prefill.",
    "chunk_dispatches": "Steps that launched a prefill chunk dispatch.",
    "admitted": "Requests admitted from the submission ring.",
    "cancelled": "Requests cancelled (deadline or explicit).",
    "preempted": "Decode-lane preemptions by the overload controller.",
    "resumed": "Paused requests re-admitted onto a decode lane.",
    "faulted": "Requests terminated by fault containment.",
    "watchdog_fires": "Watchdog liveness expirations.",
    "trie_hit_tokens": "Prompt tokens served from the prefix trie.",
    "expert_rows": "Routed (token, choice) pairs on held experts, summed "
                   "over expert layers.",
    "experts_hit": "Held experts given at least one row, summed over "
                   "expert layers and calls.",
    "decode_lanes": "Decode lanes active in the most recent step.",
    "free_pages": "Free KV pages after the most recent step.",
    "steps": "Engine steps covered by the drained telemetry rows.",
}


def prometheus_text(totals: tel_lib.CounterTotals, *,
                    records: Optional[List[dict]] = None,
                    recorder=None, owner: Optional[int] = None,
                    prefix: str = "blink") -> str:
    """Render drained counter totals in Prometheus text exposition format.

    ``totals`` holds every drained row's sums and the latest row. When
    ``records`` (from ``metrics.request_records`` or
    ``BlinkServer.telemetry_records``) and ``recorder`` (whose
    ``step_end`` / ``step_seconds`` map step stamps to the host clock,
    through ``owner``'s windows only when given) are supplied, TTFT/TPOT quantiles are appended as labelled gauges in
    seconds: TTFT from the mapped submit and first-token steps, TPOT as
    the record's steps per token times the step time of the window that
    emitted its first token."""
    lines: List[str] = []

    def emit(name: str, help_key: str, kind: str, value) -> None:
        lines.append(f"# HELP {prefix}_{name} {_HELP.get(help_key, help_key)}")
        lines.append(f"# TYPE {prefix}_{name} {kind}")
        lines.append(f"{prefix}_{name} {value}")

    emit("steps_total", "steps", "counter", int(totals.steps))
    for col in _COUNTER_COLS:
        emit(f"{col}_total", col, "counter",
             int(totals.sums[tel_lib.COL[col]]))
    for col in _GAUGE_COLS:
        emit(col, col, "gauge", int(totals.last[tel_lib.COL[col]]))

    if records is not None and recorder is not None:
        ttft, tpot = [], []
        for r in records:
            if r["ttft_steps"] is None:
                continue
            sub = r["submit_step"]
            first = sub + r["ttft_steps"]
            t_sub = recorder.step_end(sub, owner)
            t_first = recorder.step_end(first, owner)
            if t_sub is not None and t_first is not None:
                ttft.append(t_first - t_sub)
            step_s = recorder.step_seconds(first, owner)
            if r["tpot_steps"] is not None and step_s is not None:
                tpot.append(r["tpot_steps"] * step_s)
        for metric, xs in (("ttft", ttft), ("tpot", tpot)):
            if not xs:
                continue
            stats = percentiles(xs)
            name = f"{metric}_seconds"
            lines.append(f"# HELP {prefix}_{name} Step-stamp {metric.upper()}"
                         " mapped to wall time window by window.")
            lines.append(f"# TYPE {prefix}_{name} gauge")
            for q, v in stats.items():
                if np.isfinite(v):
                    lines.append(f'{prefix}_{name}{{quantile="{q}"}} {v:.6g}')
    return "\n".join(lines) + "\n"


def _span(name: str, ts_us: float, dur_us: float, tid: int, args: dict,
          pid: int = 1, cat: str = "request") -> dict:
    return {"name": name, "ph": "X", "ts": ts_us, "dur": max(dur_us, 0.0),
            "pid": pid, "tid": tid, "cat": cat, "args": args}


def request_spans(record: dict, recorder, origin: float,
                  owner: Optional[int] = None) -> List[dict]:
    """Chrome-trace events for one request record, in microseconds since
    ``origin`` on the host clock.

    Phases are cut at the canonical lifecycle stamps: ``queued`` =
    submitted→admitted, ``prefill`` = admitted→first token, ``decode`` =
    first token→terminal; each stamp is placed at the end of its step
    through the recorder's step→wall map (``owner``'s windows, when
    given), and a phase with an unmapped
    stamp is left out. Preempt/offload/restore/resume show up as instant
    markers inside the decode span rather than splitting it — the stall
    is already visible in the counter rows and excluded from ITL by the
    metrics layer."""
    def us(step):
        t = recorder.step_end(step, owner)
        return None if t is None else (t - origin) * 1e6

    ev: dict = {}
    for name, step in record["events"]:
        ev.setdefault(name, step)
    tid = record["slot"]
    args = {"request_id": record["request_id"],
            "terminal": record["terminal"], "n_tokens": record["n_tokens"]}
    terminal_step = None
    for name in ("completed", "cancelled", "faulted"):
        if name in ev:
            terminal_step = ev[name]
    out: List[dict] = []

    def phase(name, a, b):
        ta, tb = us(a), us(b)
        if ta is not None and tb is not None:
            out.append(_span(name, ta, tb - ta, tid, args))

    sub = ev.get("submitted", record["submit_step"])
    adm = ev.get("admitted")
    ft = ev.get("first_token")
    if adm is not None:
        phase("queued", sub, adm)
        end = ft if ft is not None else terminal_step
        if end is not None:
            phase("prefill", adm, end)
    if ft is not None and terminal_step is not None:
        phase("decode", ft, terminal_step)
    for name, step in record["events"]:
        ts = us(step)
        if ts is not None and name in ("preempted", "offloaded", "restored",
                                       "resumed", "watchdog", "chunk"):
            out.append({"name": name, "ph": "i", "ts": ts, "pid": 1,
                        "tid": tid, "s": "t", "cat": "request", "args": args})
    return out


def perfetto_trace(records: Sequence[dict], recorder,
                   owner: Optional[int] = None) -> dict:
    """Chrome-trace / Perfetto JSON object: the recorder's host spans
    (process ``blink-host``) and the request records' spans (process
    ``blink-engine``, one track per ring slot) on one timeline, in
    microseconds since the earliest recorded span or window. Given an
    ``owner``, only that server's spans and windows.

    Load the result (``json.dump``-ed) in ``ui.perfetto.dev`` or
    ``chrome://tracing``."""
    spans = recorder.closed() if owner is None else recorder.owned(owner)
    windows = [w for w in recorder.windows
               if owner is None or w.owner == owner]
    starts = [sp.t0 for sp in spans] + [w.t_launch for w in windows[:1]]
    origin = min(starts) if starts else 0.0
    events: List[dict] = []
    for sp in spans:
        events.append(_span(sp.name, (sp.t0 - origin) * 1e6,
                            sp.seconds * 1e6, 0,
                            dict(sp.attrs, compiles=sp.compiles),
                            pid=2, cat="host"))
    seen_tids = set()
    for rec in records:
        events.extend(request_spans(rec, recorder, origin, owner))
        tid = rec["slot"]
        if tid not in seen_tids:
            seen_tids.add(tid)
            events.append({"name": "thread_name", "ph": "M", "pid": 1,
                           "tid": tid,
                           "args": {"name": f"slot {tid}"}})
    events.append({"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
                   "args": {"name": "blink-engine"}})
    events.append({"name": "process_name", "ph": "M", "pid": 2, "tid": 0,
                   "args": {"name": "blink-host"}})
    return {"traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"clock": "time.perf_counter", "origin_s": origin}}


def span_summaries(records: Sequence[dict]) -> List[str]:
    """One compact line per request for the CLI final report."""
    out = []
    for rec in sorted(records, key=lambda r: r["request_id"]):
        ev = {}
        for name, step in rec["events"]:
            ev.setdefault(name, step)
        terminal_step = None
        for name in ("completed", "cancelled", "faulted"):
            if name in ev:
                terminal_step = ev[name]
        sub = ev.get("submitted", rec["submit_step"])
        adm, ft = ev.get("admitted"), ev.get("first_token")
        queued = (adm - sub) if adm is not None else None
        prefill = (ft - adm) if (adm is not None and ft is not None) else None
        decode = ((terminal_step - ft)
                  if (ft is not None and terminal_step is not None) else None)
        fmt = lambda v: "-" if v is None else f"{v}"
        out.append(
            f"req {rec['request_id']:>3} slot {rec['slot']:>2} "
            f"{rec['terminal']:<16} tokens={rec['n_tokens']:>4} "
            f"queued={fmt(queued)} prefill={fmt(prefill)} "
            f"decode={fmt(decode)} (steps)")
    return out
