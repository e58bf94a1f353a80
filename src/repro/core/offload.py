"""Host-side KV offload/restore for decode-lane preemption (DPU plane).

Blink keeps the GPU plane CPU-free: the in-window preemption policy
(``engine.make_engine_step``) only ever DECIDES — it marks a victim
PREEMPTED and frees its lane, all as pure array updates inside the fused
step. Moving the victim's live KV pages off the device is inherently a
host interaction, so it rides the same between-window boundary as every
other DPU-plane operation (frontend flush/poll, prefix-trie eviction):
``service_overload`` runs once per window and

  1. spills each PREEMPTED slot's block-table row to a host-side
     ``KVOffloadBuffer`` (a byte-exact copy of its K/V pages + dequant
     scales + ``seq_lens`` cursor), releases the row through the same
     refcounted ``free_pages`` path as completion (shared prefix
     references included — the trie keeps its own), and parks the slot in
     OFFLOADED;
  2. cancels OFFLOADED slots whose e2e deadline passed while spilled
     (dropping the buffered bytes — nothing device-side to release);
  3. restores spilled slots earliest-deadline-first when capacity allows:
     fresh pages from the refcounted ``PageAllocator``, bytes copied back
     verbatim, block row rewired, and the slot parked in DECODE_PAUSED —
     the engine's resume sub-phase grants it a lane in-window, exactly
     like a slot finishing its last prefill chunk.

Because the spill/restore is a pure memcpy of already-computed KV (no
recompute, no requantisation) and greedy sampling is step-independent, a
preempted-then-restored request's token stream is bit-identical to the
same request served without preemption — the differential harness pins
that.

Restore is deliberately conservative ("restore from surplus"): it never
takes the last free lane count below the number of still-waiting restored
slots, and never dips into the pages the EDF-head pending admission
needs — otherwise a restore could immediately re-trigger the preemption
that caused it (offload/restore thrash).

``HostEngine`` mirrors this whole routine at the end of each host step
(equivalent to the window boundary at window=1, which is how the
differential tests drive both planes), so offload/restore/cancel
decisions are compared event-for-event across engines.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ServeConfig
from repro.core import ring_buffer as rb
from repro.models import cache as cache_lib

INT_MAX = np.iinfo(np.int32).max


@dataclass
class KVOffloadEntry:
    """One spilled request: byte-exact host copies of its KV pages."""
    request_id: int
    slot: int
    seq_len: int                       # kv cursor at spill time
    n_pages: int                       # valid pages (== lifetime need)
    k: np.ndarray                      # [L, n_pages, KV, ps, hd]
    v: np.ndarray
    k_scale: Optional[np.ndarray]      # [L, n_pages, KV, lanes] (int8)
    v_scale: Optional[np.ndarray]
    # chunked-restore cursor: pages are allocated all-or-nothing at restore
    # START (so the admission-reserve gate sees the full cost up front) but
    # copied back over several boundaries; the slot stays OFFLOADED and the
    # "restore" event fires only when the last chunk lands.
    restore_pages: Optional[np.ndarray] = None   # [n_pages] allocated ids
    restored_pages: int = 0                      # pages copied so far

    @property
    def nbytes(self) -> int:
        n = self.k.nbytes + self.v.nbytes
        if self.k_scale is not None:
            n += self.k_scale.nbytes + self.v_scale.nbytes
        return n


@dataclass
class KVOffloadBuffer:
    """Host-DRAM staging area for preempted requests' KV.

    Keyed by slot (a slot has at most one spilled image: the engine never
    re-preempts a slot that isn't decoding, and a restored slot's entry is
    dropped). Conservation contract asserted by the tests: entries are in
    bijection with OFFLOADED ring slots at every window boundary, and the
    buffer is empty at drain."""
    entries: Dict[int, KVOffloadEntry] = field(default_factory=dict)
    offloads: int = 0
    restores: int = 0
    drops: int = 0

    @property
    def pages_held(self) -> int:
        return sum(e.n_pages for e in self.entries.values())

    @property
    def nbytes_held(self) -> int:
        return sum(e.nbytes for e in self.entries.values())


def _pending_reserve(ring, serve: ServeConfig) -> int:
    """Pages the EDF-head PREFILL_PENDING request needs (0 if none):
    restore never dips into this budget, mirroring the admission gate's
    view so a restore cannot starve the very admission whose backpressure
    caused the preemption."""
    st = np.asarray(ring.slot_state)
    pend = st == rb.PREFILL_PENDING
    if not pend.any():
        return 0
    dl = np.where(pend, np.asarray(ring.deadline_step), INT_MAX)
    ar = np.where(pend, np.asarray(ring.arrival), INT_MAX)
    head = int(np.lexsort((ar, dl))[0])
    need = int(cache_lib.pages_needed(int(ring.prompt_len[head]),
                                      int(ring.max_new[head]),
                                      serve.page_size))
    if serve.prefix_cache:
        need = max(need - int(ring.cached_len[head]) // serve.page_size, 0)
    return need


def _restore_page_budget(serve: ServeConfig) -> Optional[int]:
    """Pages the boundary may copy back per service pass.

    With adaptive chunking on (``prefill_chunk_tokens_max > 0``) the
    restore burst is bounded by the same knob that bounds a prefill
    chunk — ``ceil(prefill_chunk_tokens_max / page_size)`` pages — so a
    window boundary never blocks on a host copy larger than one chunk's
    worth of KV. ``None`` means unbounded (legacy one-shot restore)."""
    if serve.prefill_chunk_tokens_max <= 0:
        return None
    return max(1, -(-serve.prefill_chunk_tokens_max // serve.page_size))


def service_overload(state, buf: KVOffloadBuffer, serve: ServeConfig
                     ) -> Tuple[Any, List[Tuple[str, int, int]]]:
    """One DPU-plane overload service pass over an ``EngineState``.

    Returns ``(state, events)`` where events is an ordered list of
    ``(kind, request_id, slot)`` with kind in {"offload", "restore",
    "drop"} — the host engine emits the identical sequence, and the
    frontend uses "drop" to surface the PREEMPTED terminal status."""
    ring, alloc = state.ring, state.alloc
    kvc = state.cache["kv"]
    # device placements of the incoming leaves: the eager spill/restore
    # scatters below run computation-follows-data, but their OUTPUT
    # placement is a compiler choice — on a tensor-parallel window
    # (sharded KV pool, mesh-replicated ring/allocator) the updated leaves
    # must land back on the exact same shardings or the next window's
    # donation layout flaps. np.asarray/device_get on the sharded pool is
    # safe as-is: a fully-addressable sharded leaf assembles byte-exact.
    in_shardings = jax.tree.map(lambda x: x.sharding, (ring, alloc, kvc))
    ps = serve.page_size
    step_now = int(state.step)
    events: List[Tuple[str, int, int]] = []

    # -- 1. spill every PREEMPTED slot (ascending slot order) ---------------
    states_np = np.asarray(ring.slot_state)
    for slot in np.flatnonzero(states_np == rb.PREEMPTED):
        slot = int(slot)
        row = np.asarray(kvc.block_table[slot])
        pages = row[row >= 0]
        idx = jnp.asarray(pages, jnp.int32)
        entry = KVOffloadEntry(
            request_id=int(ring.request_id[slot]), slot=slot,
            seq_len=int(kvc.seq_lens[slot]), n_pages=int(pages.size),
            k=np.asarray(kvc.k_pages[:, idx]),
            v=np.asarray(kvc.v_pages[:, idx]),
            k_scale=(np.asarray(kvc.k_scale[:, idx])
                     if kvc.quantized else None),
            v_scale=(np.asarray(kvc.v_scale[:, idx])
                     if kvc.quantized else None))
        buf.entries[slot] = entry
        buf.offloads += 1
        alloc = cache_lib.free_pages(alloc, jnp.asarray(row))
        kvc = dataclasses.replace(
            kvc, block_table=kvc.block_table.at[slot].set(-1))
        ring = dataclasses.replace(
            ring, slot_state=ring.slot_state.at[slot].set(rb.OFFLOADED))
        events.append(("offload", entry.request_id, slot))

    # -- 2. cancel spilled slots whose e2e deadline passed ------------------
    if serve.deadline_policy == "e2e":
        for slot in sorted(buf.entries):
            if int(ring.deadline_step[slot]) <= step_now:
                entry = buf.entries.pop(slot)
                buf.drops += 1
                if entry.restore_pages is not None:
                    # mid-restore drop: the pages were allocated at restore
                    # start but the block row was never wired — return them
                    alloc = cache_lib.free_pages(
                        alloc, jnp.asarray(entry.restore_pages, jnp.int32))
                ring = dataclasses.replace(
                    ring,
                    slot_state=ring.slot_state.at[slot].set(rb.CANCELLED))
                events.append(("drop", entry.request_id, slot))

    # -- 3. restore earliest-deadline-first, from surplus only --------------
    # Chunked: pages are allocated all-or-nothing when a restore STARTS
    # (the gate sees the full cost), but at most ``_restore_page_budget``
    # pages of KV are copied back per boundary — an in-progress slot stays
    # OFFLOADED (its lane reservation held via ``lanes_free``) until its
    # last chunk lands, and only then surfaces the "restore" event.
    states_np = np.asarray(ring.slot_state)
    in_progress = sum(1 for e in buf.entries.values()
                      if e.restore_pages is not None)
    lanes_free = int(np.sum(np.asarray(state.lane_slot) < 0)) \
        - int(np.sum(states_np == rb.DECODE_PAUSED)) - in_progress
    reserve = _pending_reserve(ring, serve)
    budget = _restore_page_budget(serve)
    order = sorted(buf.entries,
                   key=lambda s: (int(ring.deadline_step[s]),
                                  int(ring.arrival[s])))
    for slot in order:
        entry = buf.entries[slot]
        if entry.restore_pages is None:
            # not started: take the lane reservation + all pages up front
            if lanes_free <= 0 or (budget is not None and budget <= 0):
                continue
            if int(alloc.top) - entry.n_pages < reserve:
                continue       # smaller spill later in EDF order may fit
            pages, alloc, ok = cache_lib.alloc_pages(
                alloc, jnp.asarray(entry.n_pages, jnp.int32),
                serve.pages_per_req)
            assert bool(ok), \
                "restore allocation must succeed after the gate"
            entry.restore_pages = np.asarray(pages)[:entry.n_pages] \
                .astype(np.int32)
            lanes_free -= 1
        # copy the next chunk of pages (all of them when unbounded)
        done = entry.restored_pages
        n_copy = entry.n_pages - done
        if budget is not None:
            n_copy = min(n_copy, budget)
            budget -= n_copy
        if n_copy > 0:
            ids = jnp.asarray(entry.restore_pages[done:done + n_copy],
                              jnp.int32)
            kvc = dataclasses.replace(
                kvc,
                k_pages=kvc.k_pages.at[:, ids].set(
                    jnp.asarray(entry.k[:, done:done + n_copy],
                                kvc.k_pages.dtype)),
                v_pages=kvc.v_pages.at[:, ids].set(
                    jnp.asarray(entry.v[:, done:done + n_copy],
                                kvc.v_pages.dtype)))
            if kvc.quantized:
                kvc = dataclasses.replace(
                    kvc,
                    k_scale=kvc.k_scale.at[:, ids].set(
                        jnp.asarray(entry.k_scale[:, done:done + n_copy],
                                    kvc.k_scale.dtype)),
                    v_scale=kvc.v_scale.at[:, ids].set(
                        jnp.asarray(entry.v_scale[:, done:done + n_copy],
                                    kvc.v_scale.dtype)))
            entry.restored_pages = done + n_copy
        if entry.restored_pages < entry.n_pages:
            continue           # partial: keep OFFLOADED, resume next pass
        # final chunk landed: wire the row, park DECODE_PAUSED, emit
        row_ids = jnp.asarray(entry.restore_pages, jnp.int32)
        kvc = dataclasses.replace(
            kvc,
            block_table=kvc.block_table.at[slot].set(
                jnp.where(jnp.arange(kvc.max_blocks) < entry.n_pages,
                          jnp.pad(row_ids, (0, max(kvc.max_blocks
                                                   - entry.n_pages, 0))
                                  )[:kvc.max_blocks], -1)),
            seq_lens=kvc.seq_lens.at[slot].set(entry.seq_len))
        # the restored slot no longer shares prefix pages — its whole row
        # is freshly owned, so the drain path's plain row free is exact
        ring = dataclasses.replace(
            ring,
            cached_len=ring.cached_len.at[slot].set(0),
            shared_pages=ring.shared_pages.at[slot].set(-1),
            prefill_done_len=ring.prefill_done_len.at[slot].set(
                ring.prompt_len[slot]),
            slot_state=ring.slot_state.at[slot].set(rb.DECODE_PAUSED))
        del buf.entries[slot]
        buf.restores += 1
        events.append(("restore", entry.request_id, slot))

    ring, alloc, kvc = jax.tree.map(
        jax.device_put, (ring, alloc, kvc), in_shardings)
    state = dataclasses.replace(
        state, ring=ring, alloc=alloc,
        cache=dict(state.cache, kv=kvc))
    return state, events
