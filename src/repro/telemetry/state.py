"""Device-resident telemetry plane (CPU-free observability).

Blink's steady-state path never touches the CPU, so observability must
not either: any ``io_callback``/``debug.callback`` inside the persistent
window would reintroduce exactly the host round-trips the architecture
removes. This module keeps all measurement state in ``TelemetryState`` —
SoA int32 arrays carried INSIDE ``EngineState`` (so it rides window
donation, ``lax.fori_loop`` and crash-recovery snapshots for free) —
and derives every increment with pure jnp ops from (top-of-step,
end-of-step) ring snapshots, the same diff technique the watchdog's
progress accounting already uses. Nothing is written from inside the
scheduler branches, so the instrumented step compiles to the identical
Pallas dispatch count and the token streams stay bitwise-identical with
the event log on or off (``tests/test_telemetry.py`` pins both).

Two surfaces, drained at window boundaries like ``token_reader``:

* **per-step counter rows**, always on: decode batch size, tokens
  emitted, prefill chunk tokens + dispatches, admissions, cancellations,
  preemptions, lane resumes, faults, watchdog fires, free pages, trie hit
  tokens, and the expert layers' routed rows on held experts and held
  experts hit, summed over layers and calls (zero without experts;
  drained from the cache's ``experts`` accumulator) — the raw material
  for Prometheus exposition (``telemetry.export``) and the benchmark's
  counter readers. ``rows``
  is window-deep, so a boundary drain never loses a row. How a step's
  row reaches it follows the attention path (``stacks_rows``): on the
  unified path each step writes ``rows[step % depth]``; on the split
  path each step writes ``row`` and the window loop stacks them, in
  step order. On a TPU v5e, XLA's memory-space assignment made the
  first form copy a whole KV-pool layer before every chunk-prefill call
  of the split path, and the second cost the unified path 1.7% per
  step. Compiled for a v5e, each path's own form leaves the window's
  large copies as they are without the rows, for both benchmark models
  (PERF.md).
* **per-slot event log** (``ev_code``/``ev_step``, bounded at
  ``serve.telemetry_events_per_slot``), behind ``ServeConfig.telemetry``
  (its fields are ``None`` when off): (event code, step stamp) pairs
  generalizing ``token_step``/``submit_step`` into full request
  timelines — submitted, validated, admitted, chunk-advanced,
  first-token, resumed, preempted, offloaded, restored, and a tagged
  terminal (completed / cancelled / faulted). Writes beyond the bound
  are dropped; ``ev_count`` keeps counting so drops are visible.

Events the engine cannot see happen in-step — submission, KV offload,
offload restore, offload-drop cancellation — are DPU-plane boundary
transitions. The step PROLOGUE catches them by diffing the live ring
against ``last_state`` (the previous step's end-of-step snapshot) and
stamps them with the first step that observes them (submission keeps its
true ``submit_step`` stamp).

``HostEngine`` mirrors every row and event through the same shared
candidate functions (numpy in, numpy out), so the differential harness
can demand identical telemetry streams device-vs-host.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ring_buffer as rb

# Counter-row columns, in storage order. "step" makes drained rows
# self-describing; free_pages/decode_lanes are gauges, the rest are
# per-step deltas (cumulative counters = column sums over drained rows).
COUNTERS = (
    "step", "decode_lanes", "tokens", "chunk_tokens", "chunk_dispatches",
    "admitted", "cancelled", "preempted", "resumed", "faulted",
    "watchdog_fires", "free_pages", "trie_hit_tokens",
    "expert_rows", "experts_hit",
)
N_COUNTERS = len(COUNTERS)
COL = {name: i for i, name in enumerate(COUNTERS)}


class CounterTotals:
    """Running totals of drained counter rows, in constant memory however
    long the server runs: rows drained, per-column sums (the counters) and
    the latest row (the gauges)."""

    def __init__(self):
        self.steps = 0
        self.sums = np.zeros(N_COUNTERS, np.int64)
        self.last = np.zeros(N_COUNTERS, np.int64)

    def add(self, rows) -> None:
        rows = np.asarray(rows, np.int64).reshape(-1, N_COUNTERS)
        if len(rows):
            self.steps += len(rows)
            self.sums += rows.sum(axis=0)
            self.last = rows[-1].copy()

    @classmethod
    def of(cls, rows) -> "CounterTotals":
        totals = cls()
        totals.add(rows)
        return totals


# Event taxonomy. 0 is reserved (= empty log cell).
EV_SUBMITTED = 1
EV_VALIDATED = 2
EV_ADMITTED = 3
EV_CHUNK = 4
EV_FIRST_TOKEN = 5
EV_RESUMED = 6
EV_PREEMPTED = 7
EV_OFFLOADED = 8
EV_RESTORED = 9
EV_COMPLETED = 10
EV_CANCELLED = 11
EV_FAULTED = 12

EVENT_NAMES = {
    EV_SUBMITTED: "submitted", EV_VALIDATED: "validated",
    EV_ADMITTED: "admitted", EV_CHUNK: "chunk", EV_FIRST_TOKEN:
    "first_token", EV_RESUMED: "resumed", EV_PREEMPTED: "preempted",
    EV_OFFLOADED: "offloaded", EV_RESTORED: "restored",
    EV_COMPLETED: "completed", EV_CANCELLED: "cancelled",
    EV_FAULTED: "faulted",
}
TERMINAL_EVENTS = (EV_COMPLETED, EV_CANCELLED, EV_FAULTED)


@jax.tree_util.register_dataclass
@dataclass
class TelemetryState:
    """SoA telemetry arrays carried inside ``EngineState``."""
    row: Optional[jax.Array]  # [N_COUNTERS] the step's row (stacks_rows)
    rows: jax.Array        # [window, N_COUNTERS] int32 counter rows
    # the event log: None unless ServeConfig.telemetry is on
    ev_code: Optional[jax.Array] = None   # [S, E] int32 codes (0 = empty)
    ev_step: Optional[jax.Array] = None   # [S, E] int32 stamps (-1 = empty)
    ev_count: Optional[jax.Array] = None  # [S] int32 OBSERVED (>= E drop)
    ev_seq: Optional[jax.Array] = None    # [S] int32 seq being logged
    last_state: Optional[jax.Array] = None  # [S] end-of-previous-step state


def stacks_rows(serve) -> bool:
    """Whether the window loop stacks each step's ``row`` (the split
    attention path) rather than each step writing ``rows[step % depth]``
    (the unified path)."""
    return not serve.attn_unified


def make_telemetry_state(serve) -> TelemetryState:
    row = jnp.zeros((N_COUNTERS,), jnp.int32) if stacks_rows(serve) \
        else None
    rows = jnp.zeros((max(serve.window, 1), N_COUNTERS), jnp.int32)
    if not serve.telemetry:
        return TelemetryState(row=row, rows=rows)
    S = serve.num_slots
    E = serve.telemetry_events_per_slot
    return TelemetryState(
        row=row, rows=rows,
        ev_code=jnp.zeros((S, E), jnp.int32),
        ev_step=jnp.full((S, E), -1, jnp.int32),
        ev_count=jnp.zeros((S,), jnp.int32),
        ev_seq=jnp.full((S,), -1, jnp.int32),
        last_state=jnp.zeros((S,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Shared candidate math (jnp on the device plane, numpy on the host mirror)
# ---------------------------------------------------------------------------


def boundary_candidates(xp, *, last_state, cur_state, cur_seq, ev_seq,
                        submit_step, step):
    """Prologue events: DPU-plane transitions that happened BETWEEN steps,
    detected by diffing the live ring against the previous end-of-step
    snapshot. At most one fires per slot (the current states are mutually
    exclusive). Returns ``(mask, code, stamp, submitted)``."""
    submitted = (cur_state == rb.PREFILL_PENDING) & (cur_seq != ev_seq)
    offloaded = (last_state == rb.PREEMPTED) & (cur_state == rb.OFFLOADED)
    restored = (last_state == rb.OFFLOADED) & (cur_state == rb.DECODE_PAUSED)
    dropped = (last_state == rb.OFFLOADED) & (cur_state == rb.CANCELLED)
    mask = submitted | offloaded | restored | dropped
    code = xp.where(submitted, EV_SUBMITTED,
                    xp.where(offloaded, EV_OFFLOADED,
                             xp.where(restored, EV_RESTORED, EV_CANCELLED)))
    stamp = xp.where(submitted, submit_step, step)
    return mask, code, stamp, submitted


def step_candidates(xp, *, mixed: bool, top_state, top_pd, top_gen, top_val,
                    end_state, end_pd, end_gen, end_val, cached, prompt_len):
    """In-step events + counter deltas from a (top-of-step, end-of-step)
    ring snapshot pair — the watchdog's ``moved`` diff generalized. Pure
    elementwise integer math: identical results under jnp and numpy.

    Returns ``(masks, codes, counters)`` where ``masks``/``codes`` are
    K-long lists of per-slot arrays in the canonical within-step event
    order and ``counters`` maps counter names to scalar deltas.
    """
    validated = (top_val == 0) & (end_val > 0)
    # PENDING -> past-the-gate. A slot admitted and poisoned in the same
    # step ends FAULTED but shows chunk-cursor progress; an intake- or
    # watchdog-faulted PENDING slot shows none.
    past_gate = ((end_state == rb.PREFILLING)
                 | (end_state == rb.DECODE_PROCESSING)
                 | (end_state == rb.DECODE_COMPLETED)
                 | ((end_state == rb.FAULTED) & (end_pd > top_pd)))
    admitted = (top_state == rb.PREFILL_PENDING) & past_gate
    if mixed:
        # chunk-cursor progress beyond the admission jump to cached_len
        chunk_adv = (end_pd - top_pd - xp.where(admitted, cached, 0)) > 0
    else:
        # phase-exclusive prefills the whole suffix at admission
        chunk_adv = admitted
    first_tok = (top_gen == 0) & (end_gen > 0)
    resumed = (top_state == rb.DECODE_PAUSED) \
        & (end_state == rb.DECODE_PROCESSING)
    preempted = (top_state != rb.PREEMPTED) & (end_state == rb.PREEMPTED)
    cancelled = (top_state != rb.CANCELLED) & (end_state == rb.CANCELLED)
    faulted = (top_state != rb.FAULTED) & (end_state == rb.FAULTED)
    completed = (top_state != rb.DECODE_COMPLETED) \
        & (end_state == rb.DECODE_COMPLETED)
    terminal = completed | cancelled | faulted
    term_code = xp.where(completed, EV_COMPLETED,
                         xp.where(cancelled, EV_CANCELLED, EV_FAULTED))

    trie_hits = xp.sum(xp.where(admitted, cached, 0))
    if mixed:
        chunk_tokens = xp.sum(xp.maximum(end_pd - top_pd, 0)) - trie_hits
    else:
        chunk_tokens = xp.sum(xp.where(admitted, prompt_len - cached, 0))
    counters = {
        "tokens": xp.sum(xp.maximum(end_gen - top_gen, 0)),
        "chunk_tokens": chunk_tokens,
        "admitted": xp.sum(admitted),
        "cancelled": xp.sum(cancelled),
        "preempted": xp.sum(preempted),
        "resumed": xp.sum(resumed),
        "faulted": xp.sum(faulted),
        "trie_hit_tokens": trie_hits,
    }
    ev = xp.full_like(top_state, 0)
    masks = [validated, admitted, chunk_adv, first_tok, resumed, preempted,
             terminal]
    codes = [ev + EV_VALIDATED, ev + EV_ADMITTED, ev + EV_CHUNK,
             ev + EV_FIRST_TOKEN, ev + EV_RESUMED, ev + EV_PREEMPTED,
             term_code]
    return masks, codes, counters


# ---------------------------------------------------------------------------
# Device plane (traced; pure jnp, zero host callbacks)
# ---------------------------------------------------------------------------


def device_prologue(tel: TelemetryState, ring, step) -> TelemetryState:
    """Record boundary transitions and reset the log of resubmitted slots
    (new occupant = new ``seq``). Runs before any scheduler sub-phase."""
    mask, code, stamp, submitted = boundary_candidates(
        jnp, last_state=tel.last_state, cur_state=ring.slot_state,
        cur_seq=ring.seq, ev_seq=tel.ev_seq, submit_step=ring.submit_step,
        step=step)
    E = tel.ev_code.shape[1]
    count = jnp.where(submitted, 0, tel.ev_count)
    pos = jnp.where(mask & (count < E), count, E)   # E = out of range: drop
    sidx = jnp.arange(tel.ev_count.shape[0])
    ev_code = tel.ev_code.at[sidx, pos].set(code.astype(jnp.int32),
                                            mode="drop")
    ev_step = tel.ev_step.at[sidx, pos].set(stamp.astype(jnp.int32),
                                            mode="drop")
    return dataclasses.replace(
        tel, ev_code=ev_code, ev_step=ev_step,
        ev_count=count + mask.astype(jnp.int32),
        ev_seq=jnp.where(submitted, ring.seq, tel.ev_seq))


def device_epilogue(tel: TelemetryState, ring_top, ring, step, *,
                    mixed: bool, wd_fired, decode_lanes, chunk_dispatch,
                    free_pages, experts=None) -> TelemetryState:
    """Write this step's counter row and, when the event log is on,
    scatter its in-step events. ``ring_top`` is the (post-prologue)
    top-of-step snapshot; ``ring`` the end-of-step ring. ``experts`` is
    the step's ``[expert_rows, experts_hit]`` (None: no expert layer).
    Runs after every scheduler sub-phase."""
    masks, codes, counters = step_candidates(
        jnp, mixed=mixed,
        top_state=ring_top.slot_state, top_pd=ring_top.prefill_done_len,
        top_gen=ring_top.generated, top_val=ring_top.validated,
        end_state=ring.slot_state, end_pd=ring.prefill_done_len,
        end_gen=ring.generated, end_val=ring.validated,
        cached=ring.cached_len, prompt_len=ring.prompt_len)
    if experts is None:
        experts = jnp.zeros((2,), jnp.int32)
    row = jnp.concatenate([jnp.stack([
        step, decode_lanes, counters["tokens"], counters["chunk_tokens"],
        chunk_dispatch, counters["admitted"], counters["cancelled"],
        counters["preempted"], counters["resumed"], counters["faulted"],
        wd_fired, free_pages, counters["trie_hit_tokens"],
    ]).astype(jnp.int32), experts.astype(jnp.int32)])
    if tel.row is not None:
        tel = dataclasses.replace(tel, row=row)
    else:
        tel = dataclasses.replace(
            tel, rows=tel.rows.at[jnp.mod(step, tel.rows.shape[0])].set(row))
    if tel.ev_code is None:
        return tel

    mask = jnp.stack(masks, axis=1)                       # [S, K] bool
    code = jnp.stack(codes, axis=1).astype(jnp.int32)     # [S, K]
    m32 = mask.astype(jnp.int32)
    S, K = mask.shape
    E = tel.ev_code.shape[1]
    pos = tel.ev_count[:, None] + jnp.cumsum(m32, axis=1) - m32
    wpos = jnp.where(mask & (pos < E), pos, E)            # E: drop
    sidx = jnp.broadcast_to(jnp.arange(S)[:, None], (S, K))
    stamp = jnp.broadcast_to(step.astype(jnp.int32), (S, K))
    return dataclasses.replace(
        tel,
        ev_code=tel.ev_code.at[sidx, wpos].set(code, mode="drop"),
        ev_step=tel.ev_step.at[sidx, wpos].set(stamp, mode="drop"),
        ev_count=tel.ev_count + jnp.sum(m32, axis=1),
        last_state=ring.slot_state)


# ---------------------------------------------------------------------------
# Host mirror (numpy twins of the prologue/epilogue scatter)
# ---------------------------------------------------------------------------


def host_scatter(ev_code: np.ndarray, ev_step: np.ndarray,
                 ev_count: np.ndarray, mask, code, stamp) -> None:
    """In-place numpy twin of the device event scatter: append each
    masked (code, stamp) at the slot's cursor, dropping writes past the
    bound but still counting them."""
    E = ev_code.shape[1]
    mask = np.asarray(mask)
    if mask.ndim == 1:
        mask, code, stamp = mask[:, None], \
            np.asarray(code)[:, None], np.asarray(stamp)[:, None]
    for s, k in zip(*np.nonzero(mask)):
        p = int(ev_count[s])
        if p < E:
            ev_code[s, p] = code[s, k]
            ev_step[s, p] = stamp[s, k]
        ev_count[s] += 1


def events_of_slot(ev_code, ev_step, ev_count, slot: int):
    """Decode one slot's log into ``[(name, step), ...]`` (drops beyond
    the bound are simply absent)."""
    n = min(int(ev_count[slot]), ev_code.shape[1])
    return [(EVENT_NAMES.get(int(ev_code[slot, i]), "?"),
             int(ev_step[slot, i])) for i in range(n)]
