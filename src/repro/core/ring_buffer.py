"""GPU(device)-resident ring buffer — the sole DPU<->engine rendezvous.

Paper §4.2: "The ring buffer resides in GPU memory and is the only shared
data structure between the DPU and GPU ... It consists of a fixed set of
slots plus shared arenas for input and generated tokens. Each slot records
per-request metadata and offsets into the token arenas. The scheduler
advances each slot through a lifecycle state machine EMPTY ->
PREFILL_PENDING -> PREFILL_PROCESSING -> DECODE_PROCESSING ->
DECODE_COMPLETED -> EMPTY and uses a DECODE_PAUSED state to support
preemption and continuous batching."

The state machine here is bit-for-bit that protocol. Atomic CAS is not
needed on TPU: slot transitions happen inside a single XLA program
(data-race-free by construction); the frontend only writes EMPTY slots and
only reads COMPLETED ones, so the cross-plane protocol keeps the same
ownership discipline the CAS enforced on GPU.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ServeConfig

# --- slot lifecycle states (paper §4.2) -----------------------------------
EMPTY = 0
PREFILL_PENDING = 1
PREFILL_PROCESSING = 2
DECODE_PROCESSING = 3
DECODE_PAUSED = 4
DECODE_COMPLETED = 5
# Mixed-phase extension (ServeConfig.prefill_chunk_tokens > 0): an admitted
# slot whose prompt K/V is being built chunk-by-chunk ACROSS steps while
# decode keeps running. Unlike the transient PREFILL_PROCESSING marker (set
# and overwritten inside one phase-exclusive step), PREFILLING persists at
# window boundaries; its progress cursor is ``prefill_done_len``. The slot
# holds a decode lane (admission reserved it) but emits no tokens until the
# cursor reaches prompt_len — then the first token is sampled and the slot
# moves to DECODE_PROCESSING (or DECODE_COMPLETED for max_new == 1).
PREFILLING = 6
# SLO-aware overload control (ROADMAP: graceful degradation, paper Table
# 6/7): terminal + transit states for deadline cancellation and
# decode-lane preemption. CANCELLED is terminal like DECODE_COMPLETED —
# the slot's deadline expired (queued, mid-PREFILLING, or mid-decode);
# whatever partial output exists stays readable in the arena and the
# frontend drains the slot through the same refcounted release path.
CANCELLED = 7
# A victim chosen by the in-window preemption policy: its decode lane is
# already freed but its KV pages are still resident — the DPU plane spills
# them to the host offload buffer at the next window boundary
# (core.offload.service_overload) and moves the slot to OFFLOADED.
PREEMPTED = 8
# KV spilled to the host buffer; the slot holds no pages and no lane. The
# DPU plane restores it (pages re-allocated, bytes copied back, slot ->
# DECODE_PAUSED awaiting a lane) when capacity allows.
OFFLOADED = 9
# Fault plane (ring is an UNTRUSTED transport boundary — the SmartNIC
# RDMA-writes entries with no host in the loop): terminal state for
# quarantined slots. An entry lands here when intake validation rejects it
# (checksum mismatch, duplicate/stale sequence, out-of-range payload), when
# the watchdog sees no progress for ``watchdog_steps`` (a torn write whose
# commit flag never arrived, or a wedged lane), or when the poison guard
# catches non-finite logits. Terminal like DECODE_COMPLETED/CANCELLED:
# whatever partial output exists stays readable and the slot drains through
# the same refcounted release path — zero page/lane leaks by construction.
FAULTED = 10

STATE_NAMES = {
    EMPTY: "EMPTY",
    PREFILL_PENDING: "PREFILL_PENDING",
    PREFILL_PROCESSING: "PREFILL_PROCESSING",
    DECODE_PROCESSING: "DECODE_PROCESSING",
    DECODE_PAUSED: "DECODE_PAUSED",
    DECODE_COMPLETED: "DECODE_COMPLETED",
    PREFILLING: "PREFILLING",
    CANCELLED: "CANCELLED",
    PREEMPTED: "PREEMPTED",
    OFFLOADED: "OFFLOADED",
    FAULTED: "FAULTED",
}

# Distinct odd 32-bit salts, one per checksummed field (xxhash/murmur
# constants — any odd constants work; they only need to be the SAME on the
# DPU plane (python ints) and the device plane (uint32 lanes)).
_SALT_SEQ = 0x9E3779B1
_SALT_PLEN = 0x85EBCA77
_SALT_MAXNEW = 0xC2B2AE3D
_SALT_ARRIVAL = 0x27D4EB2F
_SALT_CACHED = 0x165667B1
_SALT_CLASS = 0x1B873593
_SALT_DEADLINE = 0xCC9E2D51
_SALT_TOKSUM = 0x9E3779B9
_SALT_PAGESUM = 0x85EBCA6B

_INT32_MAX = 2**31 - 1
_U32 = 0xFFFFFFFF


@jax.tree_util.register_dataclass
@dataclass
class RingState:
    """All arrays are device-resident and survive window re-instantiation."""
    slot_state: jax.Array     # [S] int32 lifecycle code
    arrival: jax.Array        # [S] int32 admission ticket (smaller = earlier)
    request_id: jax.Array     # [S] int32 frontend request id
    prompt_len: jax.Array     # [S] int32
    max_new: jax.Array        # [S] int32
    generated: jax.Array      # [S] int32 tokens generated so far
    last_token: jax.Array     # [S] int32 most recent token (decode input)
    temperature: jax.Array    # [S] f32 (0 = greedy)
    # prefix-reuse metadata (written by the frontend at submit, read by the
    # engine at admission): cached_len tokens of the prompt are already
    # resident in the paged pool; shared_pages holds the page chain covering
    # them (-1 padded). 0 / all -1 = no reuse — the default protocol.
    cached_len: jax.Array     # [S] int32 (page-aligned, < prompt_len)
    shared_pages: jax.Array   # [S, pages_per_req] int32
    # mixed-phase chunk cursor: prompt tokens whose K/V is resident (cached
    # prefix + completed chunks). Engine-owned: set to cached_len at
    # admission, advanced once per chunk, == prompt_len when the slot
    # leaves PREFILLING. Doubles as the suffix-page high-water mark —
    # pages beyond ceil(prefill_done_len / page_size) hold no live K/V.
    prefill_done_len: jax.Array  # [S] int32
    # SLO metadata (written by the frontend at submit, read by every pure
    # policy decision in the engine): slo_class 0 is the highest-priority
    # (interactive) class; deadline_step is the absolute engine step by
    # which the request must meet its target (INT32_MAX = no deadline).
    slo_class: jax.Array      # [S] int32 (0 = interactive, higher = batch)
    deadline_step: jax.Array  # [S] int32 absolute deadline (INT_MAX = none)
    input_arena: jax.Array    # [S, max_prompt] int32
    output_arena: jax.Array   # [S, max_new_tokens] int32
    # device step stamps; the host maps them to wall time through the
    # per-window step→wall map (telemetry/host.py)
    submit_step: jax.Array    # [S] int32 step at which prompt was submitted
    # step whose prefill (last chunk) emitted the first token; equals
    # token_step[:, 0], and the poll reads it as Request.first_token_step
    prefill_step: jax.Array   # [S] int32
    token_step: jax.Array     # [S, max_new_tokens] int32 publish step/token
    # --- ring integrity protocol (untrusted-transport ingress) -------------
    # seq: per-entry monotone sequence number assigned at submit. The device
    # validates each entry exactly once, at first sight: a seq at or below
    # ``seq_seen`` (the high-water mark of every seq ever observed) is a
    # duplicate or stale replay and faults; intra-step collisions resolve to
    # the lowest slot index / already-validated claimant.
    seq: jax.Array            # [S] int32 (-1 = no entry)
    # checksum over the entry payload (entry_checksum), written by the
    # submitter; the device recomputes and compares during intake
    # validation (``ServeConfig.ring_checksum``).
    checksum: jax.Array       # [S] int32
    # commit flag — written LAST by the submitter (the RDMA-visibility
    # fence of §4.2 made explicit): the device skips entries whose commit
    # flag has not landed (a torn write), leaving them invisible to
    # admission until the watchdog quarantines them.
    committed: jax.Array      # [S] int32 (0 = torn/unwritten, 1 = complete)
    # device-side validation verdict: 1 once intake validation accepted the
    # entry (admission only ever sees validated entries). Engine-owned.
    validated: jax.Array      # [S] int32
    # watchdog: consecutive engine steps without observable progress
    # (lifecycle transition, chunk-cursor advance, token emission, or
    # validation verdict). Engine-owned; ``watchdog_steps`` faults on it.
    stall_steps: jax.Array    # [S] int32
    # high-water mark of every sequence number the validator has observed
    # (scalar). Duplicate/stale detection is a pure function of this plus
    # the top-of-step snapshot.
    seq_seen: jax.Array       # [] int32

    @property
    def num_slots(self) -> int:
        return self.slot_state.shape[0]


def make_ring(serve: ServeConfig) -> RingState:
    S = serve.num_slots
    return RingState(
        slot_state=jnp.zeros((S,), jnp.int32),
        arrival=jnp.full((S,), jnp.iinfo(jnp.int32).max, jnp.int32),
        request_id=jnp.full((S,), -1, jnp.int32),
        prompt_len=jnp.zeros((S,), jnp.int32),
        max_new=jnp.zeros((S,), jnp.int32),
        generated=jnp.zeros((S,), jnp.int32),
        last_token=jnp.zeros((S,), jnp.int32),
        temperature=jnp.zeros((S,), jnp.float32),
        cached_len=jnp.zeros((S,), jnp.int32),
        shared_pages=jnp.full((S, serve.pages_per_req), -1, jnp.int32),
        prefill_done_len=jnp.zeros((S,), jnp.int32),
        slo_class=jnp.zeros((S,), jnp.int32),
        deadline_step=jnp.full((S,), jnp.iinfo(jnp.int32).max, jnp.int32),
        input_arena=jnp.zeros((S, serve.max_prompt_len), jnp.int32),
        output_arena=jnp.full((S, serve.max_new_tokens), -1, jnp.int32),
        submit_step=jnp.zeros((S,), jnp.int32),
        prefill_step=jnp.full((S,), -1, jnp.int32),
        token_step=jnp.full((S, serve.max_new_tokens), -1, jnp.int32),
        seq=jnp.full((S,), -1, jnp.int32),
        checksum=jnp.zeros((S,), jnp.int32),
        committed=jnp.zeros((S,), jnp.int32),
        validated=jnp.zeros((S,), jnp.int32),
        stall_steps=jnp.zeros((S,), jnp.int32),
        seq_seen=jnp.asarray(-1, jnp.int32),
    )


# ---------------------------------------------------------------------------
# Ring integrity protocol — one checksum formula, two implementations that
# must agree BITWISE: ``entry_checksum`` (python ints — the DPU plane writes
# it at submit, the host engine mirrors it) and ``entry_checksum_device``
# (uint32 lanes — the device recomputes it during intake validation).
# ---------------------------------------------------------------------------


def entry_checksum(*, seq: int, prompt_len: int, max_new: int, arrival: int,
                   cached_len: int, slo_class: int, deadline_step: int,
                   temperature: float, tokens, shared_pages=()) -> int:
    """Payload checksum of one ring entry, as a signed int32 (the storage
    dtype). Token/page sums are position-weighted so transpositions and
    single-bit flips both change the digest; page ids are offset by +1 so
    the -1 padding contributes nothing and the row width drops out."""
    c = (int(seq) & _U32) * _SALT_SEQ & _U32
    c ^= (int(prompt_len) & _U32) * _SALT_PLEN & _U32
    c ^= (int(max_new) & _U32) * _SALT_MAXNEW & _U32
    c ^= (int(arrival) & _U32) * _SALT_ARRIVAL & _U32
    c ^= (int(cached_len) & _U32) * _SALT_CACHED & _U32
    c ^= (int(slo_class) & _U32) * _SALT_CLASS & _U32
    c ^= (int(deadline_step) & _U32) * _SALT_DEADLINE & _U32
    c ^= int(np.float32(temperature).view(np.uint32))
    tok = 0
    for i, t in enumerate(tokens):
        tok = (tok + (int(t) & _U32) * (i + 1)) & _U32
    c ^= tok * _SALT_TOKSUM & _U32
    pg = 0
    for j, p in enumerate(shared_pages):
        pg = (pg + ((int(p) + 1) & _U32) * (j + 1)) & _U32
    c ^= pg * _SALT_PAGESUM & _U32
    c &= _U32
    return c - 2**32 if c >= 2**31 else c


def entry_checksum_device(ring: RingState) -> jax.Array:
    """[S] int32 — ``entry_checksum`` recomputed from the ring arrays
    (vectorised over slots; uint32 lane arithmetic wraps mod 2^32 exactly
    like the masked python ints)."""
    u = lambda x: x.astype(jnp.uint32)
    W = ring.input_arena.shape[1]
    tw = jnp.arange(1, W + 1, dtype=jnp.uint32)
    tok = jnp.sum(u(ring.input_arena) * tw[None, :], axis=1,
                  dtype=jnp.uint32)
    Pw = ring.shared_pages.shape[1]
    pw = jnp.arange(1, Pw + 1, dtype=jnp.uint32)
    pg = jnp.sum(u(ring.shared_pages + 1) * pw[None, :], axis=1,
                 dtype=jnp.uint32)
    c = u(ring.seq) * jnp.uint32(_SALT_SEQ)
    c = c ^ (u(ring.prompt_len) * jnp.uint32(_SALT_PLEN))
    c = c ^ (u(ring.max_new) * jnp.uint32(_SALT_MAXNEW))
    c = c ^ (u(ring.arrival) * jnp.uint32(_SALT_ARRIVAL))
    c = c ^ (u(ring.cached_len) * jnp.uint32(_SALT_CACHED))
    c = c ^ (u(ring.slo_class) * jnp.uint32(_SALT_CLASS))
    c = c ^ (u(ring.deadline_step) * jnp.uint32(_SALT_DEADLINE))
    c = c ^ jax.lax.bitcast_convert_type(ring.temperature, jnp.uint32)
    c = c ^ (tok * jnp.uint32(_SALT_TOKSUM))
    c = c ^ (pg * jnp.uint32(_SALT_PAGESUM))
    return c.astype(jnp.int32)


def validate_intake(ring: RingState, *, vocab: int,
                    check_checksum: bool = True) -> RingState:
    """Intake validation sub-phase — a pure function of the top-of-step
    snapshot, run by BOTH engine policies before pending selection.

    Each committed, not-yet-validated PREFILL_PENDING entry is checked
    exactly once, at first sight:

    - duplicate / stale sequence (seq <= ``seq_seen``, or the same seq held
      by an already-validated live slot or a lower-indexed same-step
      candidate)           -> FAULTED
    - checksum mismatch (``check_checksum``)                  -> FAULTED
    - payload out of range (prompt_len/max_new outside the arenas, token id
      outside [0, vocab), non-finite or negative temperature, cached_len
      not leaving a suffix)                                   -> FAULTED
    - otherwise ``validated`` = 1 (admission may now see it).

    Uncommitted entries are skipped entirely (torn writes stay invisible;
    the watchdog quarantines them if the commit flag never lands).
    ``seq_seen`` advances over every candidate observed, faulted or not.
    """
    S = ring.num_slots
    idx = jnp.arange(S)
    pending = ring.slot_state == PREFILL_PENDING
    cand = pending & (ring.committed > 0) & (ring.validated == 0)
    live = ring.slot_state != EMPTY
    # sequence claims: an already-validated live entry always beats a new
    # candidate with the same seq; among same-step candidates the lowest
    # slot index wins (deterministic — first writer by slot order).
    claimant = live & ((ring.validated > 0) | cand)
    eq = ring.seq[:, None] == ring.seq[None, :]
    j_wins = (ring.validated > 0)[None, :] | (idx[None, :] < idx[:, None])
    dup = jnp.any(eq & claimant[None, :] & j_wins
                  & (idx[None, :] != idx[:, None]), axis=1)
    stale = ring.seq <= ring.seq_seen
    bad = stale | dup
    if check_checksum:
        bad = bad | (entry_checksum_device(ring) != ring.checksum)
    W = ring.input_arena.shape[1]
    in_prompt = jnp.arange(W)[None, :] < ring.prompt_len[:, None]
    tok_bad = jnp.any(in_prompt & ((ring.input_arena < 0)
                                   | (ring.input_arena >= vocab)), axis=1)
    bad = bad | tok_bad
    bad = bad | (ring.prompt_len <= 0) | (ring.prompt_len > W)
    bad = bad | (ring.max_new <= 0) \
        | (ring.max_new > ring.output_arena.shape[1])
    bad = bad | ~jnp.isfinite(ring.temperature) | (ring.temperature < 0)
    bad = bad | (ring.cached_len < 0) \
        | (ring.cached_len >= ring.prompt_len)
    faulted = cand & bad
    ok = cand & ~bad
    seq_obs = jnp.max(jnp.where(cand, ring.seq, jnp.iinfo(jnp.int32).min))
    return dataclasses.replace(
        ring,
        slot_state=jnp.where(faulted, FAULTED, ring.slot_state),
        validated=jnp.where(ok, 1, ring.validated).astype(jnp.int32),
        seq_seen=jnp.maximum(ring.seq_seen, seq_obs).astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# Frontend-side (DPU-plane) operations. These run OUTSIDE the persistent
# window program — the simulation analogue of one-sided RDMA writes into
# device memory. They only touch EMPTY / DECODE_COMPLETED slots, preserving
# the ownership protocol.
# ---------------------------------------------------------------------------


def next_seq(ring: RingState) -> int:
    """Next monotone sequence number for a submission into ``ring``: one
    past everything the validator has observed (``seq_seen``) AND every
    in-flight entry (submitted this boundary, not yet validated)."""
    return max(int(ring.seq_seen), int(jnp.max(ring.seq))) + 1


def submit_request(ring: RingState, slot: int, *, tokens, request_id: int,
                   max_new: int, arrival: int, temperature: float = 0.0,
                   step: int = 0, cached_len: int = 0,
                   shared_pages=None, slo_class: int = 0,
                   deadline=None, seq=None, checksum=None,
                   committed: bool = True) -> RingState:
    """Write a tokenized prompt into an EMPTY slot -> PREFILL_PENDING.

    ``cached_len``/``shared_pages``: prefix-reuse metadata from the DPU
    prefix index — the first ``cached_len`` tokens' K/V already live in
    ``shared_pages`` (the frontend takes the allocator reference; the
    engine only wires them into the block table at admission).

    ``slo_class``/``deadline``: overload-control metadata. ``deadline`` is
    the ABSOLUTE step number (submitter computes it from
    ``ServeConfig.deadline_steps``); None means no deadline.

    ``seq``/``checksum``/``committed``: ring integrity protocol. By default
    the next monotone sequence number is assigned (``next_seq``), the
    payload checksum is computed (``entry_checksum``) and the commit flag
    is set — a well-formed write. Fault injection passes these explicitly
    to model duplicate/stale sequences, corrupt digests and torn writes
    (``committed=False`` leaves the entry invisible to admission)."""
    n = len(tokens)
    arena_row = jnp.zeros((ring.input_arena.shape[1],), jnp.int32)
    arena_row = arena_row.at[:n].set(jnp.asarray(tokens, jnp.int32))
    page_row = jnp.full((ring.shared_pages.shape[1],), -1, jnp.int32)
    if shared_pages is not None and len(shared_pages):
        page_row = page_row.at[:len(shared_pages)].set(
            jnp.asarray(shared_pages, jnp.int32))
    if seq is None:
        seq = next_seq(ring)
    dl = jnp.iinfo(jnp.int32).max if deadline is None else int(deadline)
    if checksum is None:
        checksum = entry_checksum(
            seq=int(seq), prompt_len=n, max_new=int(max_new),
            arrival=int(arrival), cached_len=int(cached_len),
            slo_class=int(slo_class), deadline_step=int(dl),
            temperature=float(temperature), tokens=tokens,
            shared_pages=() if shared_pages is None else shared_pages)
    return dataclasses.replace(
        ring,
        input_arena=ring.input_arena.at[slot].set(arena_row),
        prompt_len=ring.prompt_len.at[slot].set(n),
        cached_len=ring.cached_len.at[slot].set(int(cached_len)),
        shared_pages=ring.shared_pages.at[slot].set(page_row),
        prefill_done_len=ring.prefill_done_len.at[slot].set(0),
        max_new=ring.max_new.at[slot].set(max_new),
        arrival=ring.arrival.at[slot].set(arrival),
        request_id=ring.request_id.at[slot].set(request_id),
        generated=ring.generated.at[slot].set(0),
        temperature=ring.temperature.at[slot].set(temperature),
        output_arena=ring.output_arena.at[slot].set(-1),
        token_step=ring.token_step.at[slot].set(-1),
        submit_step=ring.submit_step.at[slot].set(step),
        prefill_step=ring.prefill_step.at[slot].set(-1),
        slo_class=ring.slo_class.at[slot].set(int(slo_class)),
        deadline_step=ring.deadline_step.at[slot].set(dl),
        seq=ring.seq.at[slot].set(int(seq)),
        checksum=ring.checksum.at[slot].set(int(checksum)),
        validated=ring.validated.at[slot].set(0),
        stall_steps=ring.stall_steps.at[slot].set(0),
        slot_state=ring.slot_state.at[slot].set(PREFILL_PENDING),
        # commit flag LAST (the RDMA-visibility fence of §4.2): the device
        # treats a PREFILL_PENDING entry without it as a torn write
        committed=ring.committed.at[slot].set(1 if committed else 0),
    )


def release_slot(ring: RingState, slot: int) -> RingState:
    """Frontend drained a terminal slot -> EMPTY (slot reusable)."""
    return dataclasses.replace(
        ring,
        slot_state=ring.slot_state.at[slot].set(EMPTY),
        arrival=ring.arrival.at[slot].set(jnp.iinfo(jnp.int32).max),
        cached_len=ring.cached_len.at[slot].set(0),
        shared_pages=ring.shared_pages.at[slot].set(-1),
        prefill_done_len=ring.prefill_done_len.at[slot].set(0),
        slo_class=ring.slo_class.at[slot].set(0),
        deadline_step=ring.deadline_step.at[slot].set(
            jnp.iinfo(jnp.int32).max),
        seq=ring.seq.at[slot].set(-1),
        checksum=ring.checksum.at[slot].set(0),
        committed=ring.committed.at[slot].set(0),
        validated=ring.validated.at[slot].set(0),
        stall_steps=ring.stall_steps.at[slot].set(0),
    )
