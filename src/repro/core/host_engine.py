"""Host-driven baseline engine (the paper's comparator class: vLLM-style).

Every scheduler iteration returns control to the HOST: slot scanning,
admission, batching and page allocation happen in Python/NumPy; sampled
tokens are copied device->host every step (the PCIe round-trip of Fig. 3's
CPU-resident scheduler); the next step is dispatched from the host.

The scheduling *policy* (FCFS, admission conditions, page accounting — and,
when ``ServeConfig.prefix_cache`` is on, radix prefix matching, refcounted
page sharing, suffix-only admission/prefill, trie commit and LRU eviction;
and, when ``ServeConfig.prefill_chunk_tokens`` is set, the mixed-phase
admit/chunk/decode step with its PREFILLING cursor) is identical to
``repro.core.engine`` — the paper's controlled-comparison requirement
("identical scheduling policy", §4.2) — so benchmark deltas isolate WHERE
control runs, not WHAT it decides. ``tests/test_scheduler_diff.py`` holds
the two engines to bitwise-identical token streams over random traces.

``jitter`` models CPU interference: a callable invoked once per *host touch*
(scheduler iteration, dispatch, copy-back). Under colocation the paper
measures host-side operation inflation of 81%-172% (§3.2); the interference
benchmark sweeps this.
"""
from __future__ import annotations

import dataclasses as dc
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ServeConfig
from repro.core import ring_buffer as rb
from repro.core.sampling import sample_tokens
from repro.frontend.prefix_index import PrefixIndex
from repro.models import cache as cache_lib
from repro.models.api import ModelApi, cache_for_serve
from repro.telemetry import state as tel_lib


class HostEngine:
    def __init__(self, api: ModelApi, serve: ServeConfig, params,
                 jitter: Optional[Callable[[], None]] = None,
                 seed: int = 0, enc_len: int = 0):
        self.api = api
        self.serve = serve
        self.params = params
        self.jitter = jitter or (lambda: None)
        self.cache = cache_for_serve(api, serve, enc_len=enc_len)
        self._enc_len = enc_len
        self.paged = api.cfg.uses_paged_kv
        from repro.core.engine import _check_mixed_phase, _check_prefix_cache
        if serve.prefix_cache:
            _check_prefix_cache(api, serve)
        _check_mixed_phase(api, serve)
        S = serve.num_slots
        # host-side scheduling state (the CPU-resident control plane)
        self.slot_state = np.zeros(S, np.int32)
        self.arrival = np.full(S, np.iinfo(np.int32).max, np.int64)
        self.prompt = [None] * S
        self.max_new = np.zeros(S, np.int32)
        self.generated = np.zeros(S, np.int32)
        self.last_token = np.zeros(S, np.int32)
        self.temperature = np.zeros(S, np.float32)
        self.outputs: List[List[int]] = [[] for _ in range(S)]
        self.free_pages = list(range(serve.num_pages - 1, -1, -1))
        self.refcount = np.zeros(serve.num_pages, np.int32)
        self.slot_pages: Dict[int, List[int]] = {}
        # SLO overload-control mirror (engine.py policy, numpy arithmetic):
        # per-slot class/deadline, the host-side KV offload staging dict
        # (slot -> spilled bytes) and the ordered decision log the
        # differential harness compares event-for-event with the device.
        self.request_id = np.full(S, -1, np.int64)
        self.slo_class = np.zeros(S, np.int32)
        self.deadline = np.full(S, np.iinfo(np.int32).max, np.int64)
        self.offload: Dict[int, dict] = {}
        self.events: List[tuple] = []
        # prefix plane (identical policy to the device engine's frontend)
        self.prefix = PrefixIndex(serve.page_size) if serve.prefix_cache \
            else None
        self.slot_cached = np.zeros(S, np.int32)
        # mixed-phase chunk cursor (mirror of ring.prefill_done_len)
        self.prefill_done = np.zeros(S, np.int32)
        self.lane_slot = np.full(serve.decode_batch, -1, np.int32)
        # ring integrity mirror (seq / checksum / commit flag / validation
        # verdict / watchdog stall counter — same semantics as the
        # RingState fields, numpy arithmetic)
        self.seq = np.full(S, -1, np.int64)
        self.checksum = np.zeros(S, np.int64)
        self.committed = np.zeros(S, np.int32)
        self.validated = np.zeros(S, np.int32)
        self.stall = np.zeros(S, np.int32)
        self.seq_seen = -1
        self._step_faults: List[int] = []
        self.key = jax.random.PRNGKey(seed)
        self.step_count = 0
        # telemetry
        self.submit_time = np.zeros(S, np.float64)
        self.first_token_time = np.full(S, -1.0, np.float64)
        self.token_times: List[List[float]] = [[] for _ in range(S)]
        # CPU-free telemetry mirror (numpy twins of TelemetryState; the
        # differential harness compares these arrays element-for-element
        # with the drained device plane). Counter rows are always
        # mirrored; the event log when ``serve.telemetry`` is on.
        self.tel_on = serve.telemetry
        E = serve.telemetry_events_per_slot
        self.tel_rows: List[np.ndarray] = []
        self.tel_ev_code = np.zeros((S, E), np.int32)
        self.tel_ev_step = np.full((S, E), -1, np.int32)
        self.tel_ev_count = np.zeros(S, np.int32)
        self.tel_ev_seq = np.full(S, -1, np.int64)
        self.tel_last_state = np.zeros(S, np.int32)
        self.tel_submit_step = np.full(S, -1, np.int32)
        self._tel_info = {"wd_fired": 0, "decode_lanes": 0, "chunk_disp": 0}

        # jitted compute steps (the GPU work; CUDA-graph analogue)
        cfg = api.cfg

        def _prefill(params, prompts, lens, cached, cache, slots, active,
                     temps, key, step):
            kw = {} if cached is None else {"cached_lens": cached}
            logits, cache = api.prefill(params, prompts, lens, cache, slots,
                                        active, **kw)
            tok = sample_tokens(key, logits.astype(jnp.float32), temps,
                                top_p=serve.top_p, slot_ids=slots, step=step)
            return tok, cache

        def _chunk(params, prompts, lens, cursors, cache, slots, active,
                   temps, key, step):
            # the batched chunk step: ONE dispatch for all PREFILLING lanes
            # (same ModelApi entry point as the device engine's mixed step).
            # ``ok`` is the poison-guard verdict (finite logits per lane) —
            # the mirror of the device engine's quarantine predicate.
            logits, cache = api.prefill_batched(params, prompts, lens, cache,
                                                slots, active, cursors)
            ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
            tok = sample_tokens(key, logits.astype(jnp.float32), temps,
                                top_p=serve.top_p, slot_ids=slots, step=step)
            return tok, ok, cache

        def _decode(params, tokens, cache, slots, active, temps, key, step):
            logits, cache = api.decode(params, tokens, cache, slots, active)
            ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
            tok = sample_tokens(key, logits.astype(jnp.float32), temps,
                                top_p=serve.top_p, slot_ids=slots, step=step)
            return tok, ok, cache

        self._prefill_fn = jax.jit(_prefill, donate_argnums=(4,))
        self._chunk_fn = jax.jit(_chunk, donate_argnums=(4,)) \
            if api.prefill_batched is not None else None
        self._decode_fn = jax.jit(_decode, donate_argnums=(2,))

    def reset(self, seed: int = 0) -> None:
        """Fresh serving state, KEEPING the compiled step functions (so
        benchmark timing excludes compilation)."""
        serve = self.serve
        S = serve.num_slots
        self.cache = cache_for_serve(self.api, serve, enc_len=self._enc_len)
        self.slot_state = np.zeros(S, np.int32)
        self.arrival = np.full(S, np.iinfo(np.int32).max, np.int64)
        self.prompt = [None] * S
        self.max_new = np.zeros(S, np.int32)
        self.generated = np.zeros(S, np.int32)
        self.last_token = np.zeros(S, np.int32)
        self.temperature = np.zeros(S, np.float32)
        self.outputs = [[] for _ in range(S)]
        self.free_pages = list(range(serve.num_pages - 1, -1, -1))
        self.refcount = np.zeros(serve.num_pages, np.int32)
        self.slot_pages = {}
        self.request_id = np.full(S, -1, np.int64)
        self.slo_class = np.zeros(S, np.int32)
        self.deadline = np.full(S, np.iinfo(np.int32).max, np.int64)
        self.offload = {}
        self.events = []
        self.prefix = PrefixIndex(serve.page_size) if serve.prefix_cache \
            else None
        self.slot_cached = np.zeros(S, np.int32)
        self.prefill_done = np.zeros(S, np.int32)
        self.lane_slot = np.full(serve.decode_batch, -1, np.int32)
        self.seq = np.full(S, -1, np.int64)
        self.checksum = np.zeros(S, np.int64)
        self.committed = np.zeros(S, np.int32)
        self.validated = np.zeros(S, np.int32)
        self.stall = np.zeros(S, np.int32)
        self.seq_seen = -1
        self._step_faults = []
        self.key = jax.random.PRNGKey(seed)
        self.step_count = 0
        self.submit_time = np.zeros(S, np.float64)
        self.first_token_time = np.full(S, -1.0, np.float64)
        self.token_times = [[] for _ in range(S)]
        E = serve.telemetry_events_per_slot
        self.tel_rows = []
        self.tel_ev_code = np.zeros((S, E), np.int32)
        self.tel_ev_step = np.full((S, E), -1, np.int32)
        self.tel_ev_count = np.zeros(S, np.int32)
        self.tel_ev_seq = np.full(S, -1, np.int64)
        self.tel_last_state = np.zeros(S, np.int32)
        self.tel_submit_step = np.full(S, -1, np.int32)
        self._tel_info = {"wd_fired": 0, "decode_lanes": 0, "chunk_disp": 0}

    # -- frontend ----------------------------------------------------------
    def submit(self, tokens, max_new: int, temperature: float = 0.0,
               arrival: Optional[int] = None, slo_class: int = 0,
               deadline: Optional[int] = None,
               request_id: Optional[int] = None, seq: Optional[int] = None,
               checksum: Optional[int] = None,
               committed: bool = True) -> int:
        """``seq``/``checksum``/``committed`` mirror
        ``ring_buffer.submit_request``'s integrity-protocol overrides: by
        default a well-formed entry (next monotone seq, correct digest,
        commit flag set); fault injection passes them explicitly."""
        free = np.where(self.slot_state == rb.EMPTY)[0]
        if len(free) == 0:
            return -1
        s = int(free[0])
        self.prompt[s] = list(tokens)
        self.request_id[s] = s if request_id is None else int(request_id)
        self.slo_class[s] = int(slo_class)
        self.deadline[s] = np.iinfo(np.int32).max if deadline is None \
            else int(deadline)
        self.max_new[s] = max_new
        self.generated[s] = 0
        self.temperature[s] = temperature
        self.outputs[s] = []
        self.token_times[s] = []
        self.slot_cached[s] = 0
        self.prefill_done[s] = 0
        self.slot_pages[s] = []
        if self.prefix is not None:
            # identical policy to the device frontend: match at submit and
            # take the request's reference on the shared chain
            cached_len, shared = self.prefix.match(self.prompt[s])
            self.slot_cached[s] = cached_len
            self.slot_pages[s] = list(shared)
            for p in shared:
                self.refcount[p] += 1
        self.arrival[s] = arrival if arrival is not None else self.step_count
        # integrity protocol (mirror of rb.submit_request): monotone seq,
        # payload checksum over the post-prefix-match metadata, commit
        # flag conceptually written last
        if seq is None:
            seq = max(int(self.seq_seen), int(self.seq.max())) + 1
        if checksum is None:
            checksum = rb.entry_checksum(
                seq=int(seq), prompt_len=len(self.prompt[s]),
                max_new=int(max_new), arrival=int(self.arrival[s]),
                cached_len=int(self.slot_cached[s]),
                slo_class=int(slo_class),
                deadline_step=int(self.deadline[s]),
                temperature=float(temperature), tokens=self.prompt[s],
                shared_pages=self.slot_pages.get(s, []))
        self.seq[s] = int(seq)
        self.checksum[s] = int(checksum)
        self.validated[s] = 0
        self.stall[s] = 0
        self.committed[s] = 1 if committed else 0
        self.slot_state[s] = rb.PREFILL_PENDING
        self.submit_time[s] = time.perf_counter()
        self.first_token_time[s] = -1.0
        # device twin stamps ring.submit_step at submit_request; here the
        # DPU-plane submission happens between steps, i.e. at step_count
        self.tel_submit_step[s] = self.step_count
        return s

    def drain(self, slot: int) -> List[int]:
        toks = self.outputs[slot]
        self.slot_state[slot] = rb.EMPTY
        self.arrival[slot] = np.iinfo(np.int32).max
        self.slo_class[slot] = 0
        self.deadline[slot] = np.iinfo(np.int32).max
        # integrity-protocol resets (mirror of rb.release_slot)
        self.seq[slot] = -1
        self.checksum[slot] = 0
        self.committed[slot] = 0
        self.validated[slot] = 0
        self.stall[slot] = 0
        return toks

    def _commit_prompt_to_trie(self, slot: int) -> None:
        """Index a fully prefilled prompt's full pages into the trie (the
        trie takes one ref per newly indexed page) — at prefill complete,
        never off a partial chunk."""
        if self.prefix is None:
            return
        n_full = len(self.prompt[slot]) // self.serve.page_size
        row = self.slot_pages.get(slot, [])[:n_full]
        for p in self.prefix.insert(self.prompt[slot], row):
            self.refcount[p] += 1

    def _emit_first_token(self, slot: int, tok: int, now: float) -> bool:
        """First-token bookkeeping shared by the exclusive prefill and the
        mixed final chunk. Returns True if the request completed
        (max_new == 1)."""
        self.outputs[slot].append(tok)
        self.token_times[slot].append(now)
        self.first_token_time[slot] = now
        self.generated[slot] = 1
        self.last_token[slot] = tok
        if self.generated[slot] >= self.max_new[slot]:
            self._complete(slot)
            return True
        return False

    def _release_row(self, pages: List[int]) -> None:
        """Drop one reference per page; refcount-zero pages rejoin the pool."""
        for p in pages:
            self.refcount[p] -= 1
            if self.refcount[p] <= 0:
                self.free_pages.append(p)

    def maybe_evict(self, want_free: int) -> None:
        """LRU-evict zero-external-ref trie chains under page backpressure
        (mirror of the device frontend's valve)."""
        if self.prefix is None:
            return
        deficit = int(want_free) - len(self.free_pages)
        if deficit > 0:
            self._release_row(self.prefix.evict(deficit,
                                                refcount=self.refcount))

    # -- fault plane (mirror of the device engine's quarantine paths) -------
    def _fault(self, slot: int) -> None:
        """Quarantine one slot: free its lane, release its pages through
        the refcounted drain, park it FAULTED (terminal). Mirrors the
        device's watchdog / intake / poison fault paths — partial output
        stays in ``outputs`` until drained."""
        self.lane_slot[self.lane_slot == slot] = -1
        self.slot_state[slot] = rb.FAULTED
        self.stall[slot] = 0
        self._release_slot_pages(slot)
        self._step_faults.append(slot)

    def _validate_intake(self) -> None:
        """Python mirror of ``ring_buffer.validate_intake``: every
        committed, not-yet-validated PREFILL_PENDING entry is checked
        exactly once against the top-of-step snapshot — duplicate/stale
        seq, checksum mismatch, payload out of range -> FAULTED; otherwise
        ``validated`` = 1. Verdicts are computed from the snapshot FIRST,
        then applied (the device computes them vectorised)."""
        serve = self.serve
        vocab = self.api.cfg.vocab_size
        W = serve.max_prompt_len
        st = self.slot_state
        cand = (st == rb.PREFILL_PENDING) & (self.committed > 0) \
            & (self.validated == 0)
        live = st != rb.EMPTY
        claimant = live & ((self.validated > 0) | cand)
        val0 = self.validated.copy()
        verdicts = []
        for s in np.flatnonzero(cand):
            s = int(s)
            dup = any(claimant[j] and self.seq[j] == self.seq[s]
                      and (val0[j] > 0 or j < s)
                      for j in range(len(st)) if j != s)
            bad = dup or int(self.seq[s]) <= int(self.seq_seen)
            if serve.ring_checksum and not bad:
                want = rb.entry_checksum(
                    seq=int(self.seq[s]), prompt_len=len(self.prompt[s]),
                    max_new=int(self.max_new[s]),
                    arrival=int(self.arrival[s]),
                    cached_len=int(self.slot_cached[s]),
                    slo_class=int(self.slo_class[s]),
                    deadline_step=int(self.deadline[s]),
                    temperature=float(self.temperature[s]),
                    tokens=self.prompt[s],
                    shared_pages=self.slot_pages.get(s, []))
                bad = want != int(self.checksum[s])
            if not bad:
                p = self.prompt[s]
                bad = (not 0 < len(p) <= W) \
                    or any(t < 0 or t >= vocab for t in p) \
                    or not 0 < int(self.max_new[s]) <= serve.max_new_tokens \
                    or not np.isfinite(self.temperature[s]) \
                    or self.temperature[s] < 0 \
                    or not 0 <= int(self.slot_cached[s]) < len(p)
            verdicts.append((s, bad))
        if verdicts:
            self.seq_seen = max(self.seq_seen,
                                max(int(self.seq[s]) for s, _ in verdicts))
        for s, bad in verdicts:
            if bad:
                self._fault(s)
            else:
                self.validated[s] = 1

    def _watchdog_eligible(self) -> np.ndarray:
        # mirror of engine.watchdog_eligible: only uncommitted pending
        # entries (torn writes) and decoding lanes owe progress every
        # step; PREFILLING is exempt (the max_prefills_per_step rotation
        # legitimately starves later lanes)
        st = self.slot_state
        return ((st == rb.PREFILL_PENDING) & (self.validated == 0)) \
            | (st == rb.DECODE_PROCESSING)

    # -- telemetry mirror ---------------------------------------------------
    def _tel_prologue(self) -> None:
        """Numpy twin of ``telemetry.state.device_prologue``: boundary
        transitions (submission, offload service) diffed against the
        previous end-of-step snapshot, before any sub-phase runs."""
        mask, code, stamp, submitted = tel_lib.boundary_candidates(
            np, last_state=self.tel_last_state, cur_state=self.slot_state,
            cur_seq=self.seq, ev_seq=self.tel_ev_seq,
            submit_step=self.tel_submit_step, step=self.step_count)
        self.tel_ev_count = np.where(
            submitted, 0, self.tel_ev_count).astype(np.int32)
        tel_lib.host_scatter(self.tel_ev_code, self.tel_ev_step,
                             self.tel_ev_count, mask, code, stamp)
        self.tel_ev_seq = np.where(submitted, self.seq, self.tel_ev_seq)

    def _tel_epilogue(self, st0, pd0, gen0, val0) -> None:
        """Numpy twin of ``telemetry.state.device_epilogue``: this step's
        counter row (+ in-step events when the log is on) from the same
        top/end-of-step diff."""
        S = self.serve.num_slots
        prompt_len = np.array([0 if p is None else len(p)
                               for p in self.prompt], np.int32)
        masks, codes, counters = tel_lib.step_candidates(
            np, mixed=self.serve.prefill_chunk_tokens > 0,
            top_state=st0, top_pd=pd0, top_gen=gen0, top_val=val0,
            end_state=self.slot_state, end_pd=self.prefill_done,
            end_gen=self.generated, end_val=self.validated,
            cached=self.slot_cached, prompt_len=prompt_len)
        info = self._tel_info
        counts, self.cache = cache_lib.drain_expert_counts(self.cache)
        experts = [0, 0] if counts is None else np.asarray(counts).tolist()
        self.tel_rows.append(np.array([
            self.step_count, info["decode_lanes"], counters["tokens"],
            counters["chunk_tokens"], info["chunk_disp"],
            counters["admitted"], counters["cancelled"],
            counters["preempted"], counters["resumed"],
            counters["faulted"], info["wd_fired"], len(self.free_pages),
            counters["trie_hit_tokens"]] + experts, np.int32))
        if not self.tel_on:
            return
        tel_lib.host_scatter(
            self.tel_ev_code, self.tel_ev_step, self.tel_ev_count,
            np.stack(masks, axis=1), np.stack(codes, axis=1),
            np.full((S, len(masks)), self.step_count, np.int32))
        self.tel_last_state = self.slot_state.copy()

    # -- one host-driven scheduler iteration --------------------------------
    def step(self) -> None:
        if self.tel_on:
            self._tel_prologue()
        tel_top = (self.slot_state.copy(), self.prefill_done.copy(),
                   self.generated.copy(), self.validated.copy())
        self._tel_info = {"wd_fired": 0, "decode_lanes": 0, "chunk_disp": 0}
        if self.serve.prefill_chunk_tokens > 0:
            self._step_mixed()
        else:
            self._step_exclusive()
        self._tel_epilogue(*tel_top)
        # flush this step's quarantines as ordered events (ascending slot —
        # the order the differential harness reconstructs device faults in)
        for s in sorted(self._step_faults):
            self.events.append(("fault", self._rid(s), s))
        self._step_faults = []
        self.step_count += 1
        # DPU-plane overload service AFTER the step counter advances —
        # the device analogue (core.offload.service_overload) runs between
        # windows, i.e. with the post-window step value at window=1
        if self.serve.slo_preempt:
            self._service_overload()

    def _scan_pending(self):
        """Host-side ring scan (FCFS, or EDF when the SLO machinery is on —
        mirror of ``engine.select_pending_edf``'s two-key lexsort) + the
        prefix-eviction starvation valve. Returns (pending slots in
        admission order, free lanes)."""
        serve = self.serve
        # admission only ever sees entries the integrity protocol accepted
        pending = np.where((self.slot_state == rb.PREFILL_PENDING)
                           & (self.validated > 0))[0]
        if serve.deadline_policy != "none" or serve.slo_preempt:
            pending = pending[np.lexsort((self.arrival[pending],
                                          self.deadline[pending]))]
        else:
            pending = pending[np.argsort(self.arrival[pending],
                                         kind="stable")]
        free_lanes = np.where(self.lane_slot < 0)[0]
        self.jitter()                      # host touch 2: batch assembly
        # starvation fallback (identical policy to the device frontend):
        # the trie must never hoard the pool against pending admissions
        starved = 0
        if self.prefix is not None:
            for s in pending:
                total = -(-(len(self.prompt[s]) + int(self.max_new[s]))
                          // serve.page_size)
                starved = max(starved,
                              total - int(self.slot_cached[s])
                              // serve.page_size)
        self.maybe_evict(max(serve.prefix_evict_watermark, starved))
        return pending, free_lanes

    def _admit_scan(self, pending, free_lanes) -> List[int]:
        """FCFS admission under the 3-condition gate (pending / lane
        capacity / suffix pages, all-or-nothing). Pops the pages and wires
        block-table rows; returns the admitted slots."""
        serve = self.serve
        admit: List[int] = []
        for s in pending[: serve.admit_per_step]:
            if len(admit) >= len(free_lanes):
                break
            if self.paged:
                cached_pages = int(self.slot_cached[s]) // serve.page_size
                need = -(-(len(self.prompt[s]) + int(self.max_new[s]))
                         // serve.page_size) - cached_pages
                if need > len(self.free_pages):
                    continue                # backpressure: stay pending
                pages = [self.free_pages.pop() for _ in range(need)]
                for p in pages:
                    self.refcount[p] = 1
                # row = shared prefix chain + freshly allocated suffix
                self.slot_pages[s] = self.slot_pages.get(s, [])[:cached_pages] \
                    + pages
                bt = self.cache["kv"].block_table
                row = np.full(bt.shape[1], -1, np.int32)
                row[:len(self.slot_pages[s])] = self.slot_pages[s]
                self.cache["kv"] = dc.replace(
                    self.cache["kv"], block_table=bt.at[s].set(
                        jnp.asarray(row)))
            admit.append(int(s))
        return admit

    def _step_exclusive(self) -> None:
        """Legacy phase-exclusive iteration: a step runs prefill for the
        admitted batch OR one decode step, never both (vLLM-class)."""
        self.jitter()                      # host touch 1: scheduler wakeup
        self._validate_intake()
        pending, free_lanes = self._scan_pending()
        admit = self._admit_scan(pending, free_lanes)
        if admit:
            self._tel_info["chunk_disp"] = 1
            self._run_prefill(admit, free_lanes)
        else:
            self._tel_info["decode_lanes"] = \
                int(np.count_nonzero(self.lane_slot >= 0))
            self._run_decode()

    def _step_mixed(self) -> None:
        """Mixed-phase iteration — the exact policy of the device engine's
        ``engine_step_mixed`` (cancel -> preempt -> snapshot -> resume ->
        admit -> chunk -> decode, with the decode lane set snapshotted
        post-cancel/preempt): decode never pauses for admission, prefill
        advances one bounded chunk per step, and the SLO sub-policies run
        only when their ServeConfig flags are on (identical step to the
        pre-SLO engine otherwise)."""
        serve = self.serve
        self.jitter()                      # host touch 1: scheduler wakeup
        # top-of-step snapshot for the watchdog's progress accounting
        st0 = self.slot_state.copy()
        pd0 = self.prefill_done.copy()
        gen0 = self.generated.copy()
        val0 = self.validated.copy()
        stall0 = self.stall.copy()
        # 0w. watchdog: slots whose stall counter reached the threshold
        # leave the scheduler before anything else looks at them
        if serve.watchdog_steps > 0:
            wd = self._watchdog_eligible() & (self.stall
                                              >= serve.watchdog_steps)
            for s in np.flatnonzero(wd):
                self._fault(int(s))
            self._tel_info["wd_fired"] = int(np.count_nonzero(wd))
        # 0v. intake validation (the integrity protocol's device side)
        self._validate_intake()
        # 0a. deadline cancellation over the top-of-step snapshot
        if serve.deadline_policy != "none":
            self._cancel_expired()
        pending, free_lanes = self._scan_pending()
        # 0b. preemption decision (frees the victim's lane pre-snapshot)
        if serve.slo_preempt:
            self._preempt_decide(pending)
        # decode snapshot (post cancel/preempt — a cancelled or preempted
        # slot must not emit): lanes generating at the top of the step
        # decode this step no matter what admission/chunking does
        slots = np.maximum(self.lane_slot, 0)
        decode_active = (self.lane_slot >= 0) & \
            (self.slot_state[slots] == rb.DECODE_PROCESSING)
        self._tel_info["decode_lanes"] = int(np.count_nonzero(decode_active))
        # 0c. restored victims re-acquire lanes ahead of fresh admission
        if serve.slo_preempt:
            self._resume_grant()
            free_lanes = np.where(self.lane_slot < 0)[0]

        # 1. admit: reserve a lane, wire pages, cursor at the cached prefix
        for k, s in enumerate(self._admit_scan(pending, free_lanes)):
            self.slot_state[s] = rb.PREFILLING
            self.prefill_done[s] = int(self.slot_cached[s])
            self.lane_slot[int(free_lanes[k])] = s
        # 2. chunk (freshly admitted slots run their first chunk this step).
        # Adaptive mode: the per-lane budget is the SAME pure function of
        # the top-of-step decode snapshot the device engine evaluates —
        # plain python ints here, jnp int32 there, identical result.
        budget = serve.prefill_chunk_tokens
        if serve.prefill_chunk_tokens_max > 0:
            from repro.core.engine import adaptive_chunk_budget
            budget = int(adaptive_chunk_budget(
                int(decode_active.sum()), serve.decode_batch,
                serve.prefill_block_q, serve.prefill_chunk_tokens_max))
        # same predicate the device's hoisted chunk cond evaluates
        self._tel_info["chunk_disp"] = \
            int((self.slot_state == rb.PREFILLING).any())
        if serve.attn_unified:
            # 2+3 unified (attn_unified): chunk rows and decode lanes share
            # ONE dispatch — two host touches per iteration instead of four
            self._run_unified(budget, decode_active)
        else:
            self._run_chunk(budget)
            # 3. decode all snapshot lanes
            self._run_decode(decode_active)
        # 4. watchdog progress accounting against the top-of-step snapshot
        if serve.watchdog_steps > 0:
            moved = (self.slot_state != st0) | (self.prefill_done != pd0) \
                | (self.generated != gen0) | (self.validated != val0)
            self.stall = np.where(self._watchdog_eligible() & ~moved,
                                  stall0 + 1, 0).astype(np.int32)

    def _dispatch_prefill(self, slot_list, width: int, bucket: int,
                          tokens_of, chunked: bool) -> np.ndarray:
        """Assemble a left-padded ``[width, bucket]`` prefill batch and
        dispatch ONE jitted step — shared by the exclusive prefill (whole
        suffix per slot, ``api.prefill``) and the mixed batched chunk step
        (one chunk per slot with heterogeneous cursors,
        ``api.prefill_batched``). ``tokens_of(slot) -> (tokens,
        cached_len)`` selects each slot's piece. Returns the sampled
        tokens on host."""
        prompts = np.zeros((width, bucket), np.int32)
        lens = np.zeros(width, np.int32)
        cached = np.zeros(width, np.int32)
        slots = np.zeros(width, np.int32)
        active = np.zeros(width, bool)
        temps = np.zeros(width, np.float32)
        for j, s in enumerate(slot_list):
            toks, c = tokens_of(int(s))
            prompts[j, bucket - len(toks):] = toks   # left pad
            lens[j] = len(toks)
            cached[j] = c
            slots[j] = s
            active[j] = True
            temps[j] = self.temperature[s]           # per-request temp
        self.jitter()                      # host touch 3: kernel dispatch

        if chunked:
            tok, ok, self.cache = self._chunk_fn(
                self.params, jnp.asarray(prompts), jnp.asarray(lens),
                jnp.asarray(cached), self.cache, jnp.asarray(slots),
                jnp.asarray(active), jnp.asarray(temps), self.key,
                jnp.asarray(self.step_count, jnp.int32))
            ok_host = np.asarray(jax.device_get(ok))
        else:
            cached_arg = jnp.asarray(cached) \
                if self.prefix is not None else None
            tok, self.cache = self._prefill_fn(
                self.params, jnp.asarray(prompts), jnp.asarray(lens),
                cached_arg, self.cache, jnp.asarray(slots),
                jnp.asarray(active), jnp.asarray(temps), self.key,
                jnp.asarray(self.step_count, jnp.int32))
            ok_host = np.ones(width, bool)
        tok_host = np.asarray(jax.device_get(tok))   # PCIe round-trip
        self.jitter()                      # host touch 4: copy-back handling
        return tok_host, ok_host

    def _run_prefill(self, admit: List[int], free_lanes) -> None:
        serve = self.serve
        for s in admit:
            self.slot_state[s] = rb.PREFILL_PROCESSING
        tok_host, _ = self._dispatch_prefill(
            admit, serve.admit_per_step, serve.max_prompt_len,
            # suffix only beyond the cached prefix
            lambda s: (self.prompt[s][int(self.slot_cached[s]):],
                       int(self.slot_cached[s])),
            chunked=False)

        for s in admit:   # commit freshly prefilled pages (trie ref)
            self._commit_prompt_to_trie(s)

        now = time.perf_counter()
        for j, s in enumerate(admit):
            if not self._emit_first_token(s, int(tok_host[j]), now):
                self.slot_state[s] = rb.DECODE_PROCESSING
                self.lane_slot[int(free_lanes[j])] = s

    def _run_chunk(self, budget: int) -> None:
        """Advance up to ``max_prefills_per_step`` PREFILLING slots (FCFS)
        by one ``budget``-token chunk, all sharing ONE batched dispatch
        (``api.prefill_batched`` via ``_chunk_fn``; the compiled bucket is
        ``serve.chunk_bucket`` — the adaptive budget only shortens the
        live columns). The final chunk samples the first token and commits
        the prompt's pages into the prefix trie (chunk-complete, not
        admission — partial pages must never be indexed)."""
        serve = self.serve
        bucket = serve.chunk_bucket
        filling = np.where(self.slot_state == rb.PREFILLING)[0]
        if len(filling) == 0:
            return
        filling = filling[np.argsort(self.arrival[filling], kind="stable")
                          ][:serve.max_prefills_per_step]
        tok_host, ok_host = self._dispatch_prefill(
            filling, serve.max_prefills_per_step, bucket,
            # one chunk, resuming from the cursor
            lambda s: (self.prompt[s][int(self.prefill_done[s]):
                                      int(self.prefill_done[s]) + budget],
                       int(self.prefill_done[s])),
            chunked=True)

        now = time.perf_counter()
        for j, s in enumerate(filling):
            s = int(s)
            self.prefill_done[s] += min(
                budget, len(self.prompt[s]) - int(self.prefill_done[s]))
            if self.prefill_done[s] < len(self.prompt[s]):
                continue                   # partial: no token surfaces
            if not ok_host[j]:
                # poison guard (device chunk_branch mirror): a completing
                # lane with non-finite first-token logits faults instead
                # of publishing its first token or indexing the trie
                self._fault(s)
                continue
            self._commit_prompt_to_trie(s)
            # final chunk: the first token
            if self._emit_first_token(s, int(tok_host[j]), now):
                self.lane_slot[self.lane_slot == s] = -1
            else:
                self.slot_state[s] = rb.DECODE_PROCESSING

    def _run_decode(self, active: Optional[np.ndarray] = None) -> None:
        """One decode step. ``active`` (mixed-phase) restricts to the
        top-of-step snapshot of DECODE_PROCESSING lanes — a slot still
        PREFILLING holds its reserved lane but must not decode."""
        serve = self.serve
        if active is None:
            active = self.lane_slot >= 0
        if not active.any():
            return
        slots = np.maximum(self.lane_slot, 0)
        tokens = self.last_token[slots]
        temps = self.temperature[slots]
        self.jitter()                      # host touch 3: kernel dispatch

        tok, ok, self.cache = self._decode_fn(
            self.params, jnp.asarray(tokens), self.cache, jnp.asarray(slots),
            jnp.asarray(active), jnp.asarray(temps), self.key,
            jnp.asarray(self.step_count, jnp.int32))
        tok_host = np.asarray(jax.device_get(tok))   # PCIe round-trip
        ok_host = np.asarray(jax.device_get(ok))
        self.jitter()                      # host touch 4: batch reassembly

        now = time.perf_counter()
        for lane in range(serve.decode_batch):
            if not active[lane]:
                continue
            s = int(self.lane_slot[lane])
            if not ok_host[lane]:
                # poison guard (device decode_branch mirror): quarantine
                # instead of streaming garbage
                self._fault(s)
                continue
            t = int(tok_host[lane])
            self.outputs[s].append(t)
            self.token_times[s].append(now)
            if self.first_token_time[s] < 0:
                self.first_token_time[s] = now
            self.generated[s] += 1
            self.last_token[s] = t
            if t == serve.eos_token or self.generated[s] >= self.max_new[s]:
                self._complete(s)
                self.lane_slot[lane] = -1

    def _run_unified(self, budget: int, decode_active: np.ndarray) -> None:
        """Mixed-phase chunk + decode through ONE ragged dispatch (mirror
        of the device engine's ``unified_branch``): chunk rows occupy the
        first ``max_prefills_per_step`` rows, decode lanes ride along as
        q_len=1 rows with their token in the last column and their cursor
        at the slot's current KV length. Two host touches per iteration
        instead of four — the host-involvement delta the unified kernel
        buys is visible in the mirror's jitter accounting."""
        serve = self.serve
        bucket = serve.chunk_bucket
        mp = serve.max_prefills_per_step
        bd = serve.decode_batch
        filling = np.where(self.slot_state == rb.PREFILLING)[0]
        filling = filling[np.argsort(self.arrival[filling], kind="stable")
                          ][:mp]
        if len(filling) == 0 and not decode_active.any():
            return
        width = mp + bd
        prompts = np.zeros((width, bucket), np.int32)
        lens = np.zeros(width, np.int32)
        cached = np.zeros(width, np.int32)
        slots = np.zeros(width, np.int32)
        active = np.zeros(width, bool)
        temps = np.zeros(width, np.float32)
        for j, s in enumerate(filling):
            s = int(s)
            cur = int(self.prefill_done[s])
            toks = self.prompt[s][cur:cur + budget]
            prompts[j, bucket - len(toks):] = toks   # left pad
            lens[j] = len(toks)
            cached[j] = cur
            slots[j] = s
            active[j] = True
            temps[j] = self.temperature[s]
        lane_slots = np.maximum(self.lane_slot, 0)
        for lane in range(bd):
            row = mp + lane
            s = int(lane_slots[lane])
            slots[row] = s
            if not decode_active[lane]:
                continue                     # q_len=0 filler: dead tile
            prompts[row, bucket - 1] = int(self.last_token[s])
            lens[row] = 1
            # the slot's current KV length — same value the device branch
            # reads from cache seq_lens (prompt fully resident + all but
            # the newest generated token written back)
            cached[row] = len(self.prompt[s]) + int(self.generated[s]) - 1
            active[row] = True
            temps[row] = self.temperature[s]
        self.jitter()                      # host touch 3: the ONE dispatch

        tok, ok, self.cache = self._chunk_fn(
            self.params, jnp.asarray(prompts), jnp.asarray(lens),
            jnp.asarray(cached), self.cache, jnp.asarray(slots),
            jnp.asarray(active), jnp.asarray(temps), self.key,
            jnp.asarray(self.step_count, jnp.int32))
        tok_host = np.asarray(jax.device_get(tok))   # PCIe round-trip
        ok_host = np.asarray(jax.device_get(ok))
        self.jitter()                      # host touch 4: copy-back handling

        # chunk commit tail (rows [:mp]) — identical to _run_chunk
        now = time.perf_counter()
        for j, s in enumerate(filling):
            s = int(s)
            self.prefill_done[s] += min(
                budget, len(self.prompt[s]) - int(self.prefill_done[s]))
            if self.prefill_done[s] < len(self.prompt[s]):
                continue                   # partial: no token surfaces
            if not ok_host[j]:
                self._fault(s)
                continue
            self._commit_prompt_to_trie(s)
            if self._emit_first_token(s, int(tok_host[j]), now):
                self.lane_slot[self.lane_slot == s] = -1
            else:
                self.slot_state[s] = rb.DECODE_PROCESSING
        # decode commit tail (rows [mp:]) — identical to _run_decode
        for lane in range(bd):
            if not decode_active[lane]:
                continue
            s = int(self.lane_slot[lane])
            if not ok_host[mp + lane]:
                self._fault(s)
                continue
            t = int(tok_host[mp + lane])
            self.outputs[s].append(t)
            self.token_times[s].append(now)
            if self.first_token_time[s] < 0:
                self.first_token_time[s] = now
            self.generated[s] += 1
            self.last_token[s] = t
            if t == serve.eos_token or self.generated[s] >= self.max_new[s]:
                self._complete(s)
                self.lane_slot[lane] = -1

    def _complete(self, slot: int) -> None:
        self.slot_state[slot] = rb.DECODE_COMPLETED
        self._release_slot_pages(slot)

    def _release_slot_pages(self, slot: int) -> None:
        """Drop the slot's page references and clear its block-table row —
        shared by completion and cancellation (the refcounted drain)."""
        if self.paged and self.slot_pages.get(slot):
            pages = self.slot_pages.pop(slot)
            if self.prefix is not None:
                self._release_row(pages)  # shared pages survive via refs
            else:
                self.free_pages.extend(reversed(pages))
                for p in pages:
                    self.refcount[p] = 0
            bt = self.cache["kv"].block_table
            self.cache["kv"] = dc.replace(
                self.cache["kv"],
                block_table=bt.at[slot].set(-1))

    # -- SLO overload-control mirror (engine.py policy, numpy arithmetic) ---
    def _rid(self, slot: int) -> int:
        return int(self.request_id[slot])

    def _cancel(self, slot: int) -> None:
        """Mirror of the device cancel branch for one slot: free its lane,
        release its pages through the refcounted drain (a queued slot owns
        no row — nothing to free; a mid-PREFILLING or mid-decode slot's
        full row comes back), mark CANCELLED. Partial output stays in
        ``outputs`` until drained."""
        self.lane_slot[self.lane_slot == slot] = -1
        self.slot_state[slot] = rb.CANCELLED
        self._release_slot_pages(slot)
        self.events.append(("cancel", self._rid(slot), slot))

    def _cancel_expired(self) -> None:
        """Deadline cancellation over the top-of-step snapshot (mirror of
        ``engine.expired_mask``): "ttft" cancels only slots still waiting
        for their first token; "e2e" additionally cancels mid-decode,
        restored-awaiting-lane and preempted-awaiting-offload slots
        (OFFLOADED expiry is the offload manager's, step-count parity with
        the device's between-window service point)."""
        st = self.slot_state
        scope = (st == rb.PREFILL_PENDING) | (st == rb.PREFILLING)
        if self.serve.deadline_policy == "e2e":
            scope = scope | (st == rb.DECODE_PROCESSING) | \
                (st == rb.DECODE_PAUSED) | (st == rb.PREEMPTED)
        for s in np.flatnonzero(scope & (self.deadline <= self.step_count)):
            self._cancel(int(s))

    def _preempt_decide(self, pending) -> None:
        """Mirror of ``engine.preempt_branch``: at most one victim per
        step, chosen only when the EDF-head pending candidate is page- or
        lane-blocked, no earlier victim still awaits offload, and a
        strictly-lower-class DECODE_PROCESSING slot exists. Victim = worst
        slack (staged lexicographic max: class, deadline, arrival)."""
        serve = self.serve
        if len(pending) == 0 or (self.slot_state == rb.PREEMPTED).any():
            return
        top = int(pending[0])
        blocked = not (self.lane_slot < 0).any()
        if self.paged and not blocked:
            need = -(-(len(self.prompt[top]) + int(self.max_new[top]))
                     // serve.page_size)
            need = max(need - int(self.slot_cached[top]) // serve.page_size,
                       0)
            blocked = need > len(self.free_pages)
        if not blocked:
            return
        elig = (self.slot_state == rb.DECODE_PROCESSING) & \
            (self.slo_class > int(self.slo_class[top]))
        if not elig.any():
            return
        e2 = elig & (self.slo_class == np.where(elig, self.slo_class,
                                                -1).max())
        e3 = e2 & (self.deadline == np.where(e2, self.deadline, -1).max())
        victim = int(np.argmax(np.where(e3, self.arrival, -1)))
        self.slot_state[victim] = rb.PREEMPTED
        self.lane_slot[self.lane_slot == victim] = -1
        self.events.append(("preempt", self._rid(victim), victim))

    def _resume_grant(self) -> None:
        """Mirror of ``engine.resume_branch``: up to ``admit_per_step``
        restored (DECODE_PAUSED) slots re-enter DECODE_PROCESSING in EDF
        order, taking free lanes ascending — ahead of fresh admission."""
        paused = np.flatnonzero(self.slot_state == rb.DECODE_PAUSED)
        if paused.size == 0:
            return
        order = paused[np.lexsort((self.arrival[paused],
                                   self.deadline[paused]))]
        free = np.where(self.lane_slot < 0)[0]
        for k, s in enumerate(order[:self.serve.admit_per_step]):
            if k >= len(free):
                break
            self.lane_slot[int(free[k])] = int(s)
            self.slot_state[int(s)] = rb.DECODE_PROCESSING

    def _service_overload(self) -> None:
        """Mirror of ``core.offload.service_overload`` against the host
        cache: spill PREEMPTED rows to ``self.offload`` (byte-exact numpy
        copies) and release their pages, drop e2e-expired spilled slots,
        then restore earliest-deadline-first from surplus (never below the
        EDF-head pending admission's page need, never more restores than
        free lanes minus already-waiting restored slots)."""
        serve = self.serve
        kvc = self.cache["kv"]
        # 1. spill every PREEMPTED slot (ascending slot order)
        for s in np.flatnonzero(self.slot_state == rb.PREEMPTED):
            s = int(s)
            pages = list(self.slot_pages.get(s, []))
            idx = jnp.asarray(np.asarray(pages, np.int32))
            self.offload[s] = {
                "seq_len": int(kvc.seq_lens[s]), "n_pages": len(pages),
                "k": np.asarray(kvc.k_pages[:, idx]),
                "v": np.asarray(kvc.v_pages[:, idx]),
                "k_scale": (np.asarray(kvc.k_scale[:, idx])
                            if kvc.quantized else None),
                "v_scale": (np.asarray(kvc.v_scale[:, idx])
                            if kvc.quantized else None),
                "restore_pages": None, "restored": 0,
            }
            self._release_slot_pages(s)
            kvc = self.cache["kv"]
            self.slot_state[s] = rb.OFFLOADED
            self.events.append(("offload", self._rid(s), s))
        # 2. drop spilled slots whose e2e deadline passed
        if serve.deadline_policy == "e2e":
            for s in sorted(self.offload):
                if int(self.deadline[s]) <= self.step_count:
                    entry = self.offload.pop(s)
                    if entry["restore_pages"] is not None:
                        # mid-restore drop: return the pre-allocated pages
                        self.free_pages.extend(
                            reversed(entry["restore_pages"]))
                        for p in entry["restore_pages"]:
                            self.refcount[p] = 0
                    self.slot_state[s] = rb.CANCELLED
                    self.events.append(("drop", self._rid(s), s))
        # 3. restore earliest-deadline-first, from surplus only (chunked:
        # pages taken all-or-nothing at start, bytes copied back at most
        # one chunk's worth of pages per pass — mirror of the device's
        # ``_restore_page_budget`` bound)
        from repro.core.offload import _restore_page_budget
        budget = _restore_page_budget(serve)
        in_progress = sum(1 for e in self.offload.values()
                          if e["restore_pages"] is not None)
        lanes_free = int((self.lane_slot < 0).sum()) \
            - int((self.slot_state == rb.DECODE_PAUSED).sum()) - in_progress
        reserve = 0
        pend = np.flatnonzero(self.slot_state == rb.PREFILL_PENDING)
        if pend.size:
            head = int(pend[np.lexsort((self.arrival[pend],
                                        self.deadline[pend]))][0])
            reserve = -(-(len(self.prompt[head]) + int(self.max_new[head]))
                        // serve.page_size)
            if serve.prefix_cache:
                reserve = max(
                    reserve - int(self.slot_cached[head]) // serve.page_size,
                    0)
        order = sorted(self.offload,
                       key=lambda s: (int(self.deadline[s]),
                                      int(self.arrival[s])))
        for s in order:
            entry = self.offload[s]
            if entry["restore_pages"] is None:
                # not started: lane reservation + all pages up front
                if lanes_free <= 0 or (budget is not None and budget <= 0):
                    continue
                if len(self.free_pages) - entry["n_pages"] < reserve:
                    continue   # smaller spill later in EDF order may fit
                pages = [self.free_pages.pop()
                         for _ in range(entry["n_pages"])]
                for p in pages:
                    self.refcount[p] = 1
                entry["restore_pages"] = pages
                lanes_free -= 1
            # copy the next chunk of pages (all of them when unbounded)
            done = entry["restored"]
            n_copy = entry["n_pages"] - done
            if budget is not None:
                n_copy = min(n_copy, budget)
                budget -= n_copy
            if n_copy > 0:
                ids = jnp.asarray(np.asarray(
                    entry["restore_pages"][done:done + n_copy], np.int32))
                kvc = dc.replace(
                    kvc,
                    k_pages=kvc.k_pages.at[:, ids].set(
                        jnp.asarray(entry["k"][:, done:done + n_copy],
                                    kvc.k_pages.dtype)),
                    v_pages=kvc.v_pages.at[:, ids].set(
                        jnp.asarray(entry["v"][:, done:done + n_copy],
                                    kvc.v_pages.dtype)))
                if kvc.quantized:
                    kvc = dc.replace(
                        kvc,
                        k_scale=kvc.k_scale.at[:, ids].set(jnp.asarray(
                            entry["k_scale"][:, done:done + n_copy],
                            kvc.k_scale.dtype)),
                        v_scale=kvc.v_scale.at[:, ids].set(jnp.asarray(
                            entry["v_scale"][:, done:done + n_copy],
                            kvc.v_scale.dtype)))
                entry["restored"] = done + n_copy
            if entry["restored"] < entry["n_pages"]:
                continue       # partial: keep OFFLOADED, resume next pass
            # final chunk landed: wire the row, park DECODE_PAUSED, emit
            pages = entry["restore_pages"]
            self.slot_pages[s] = list(pages)
            row = np.full(kvc.block_table.shape[1], -1, np.int32)
            row[:len(pages)] = pages
            kvc = dc.replace(
                kvc,
                block_table=kvc.block_table.at[s].set(jnp.asarray(row)),
                seq_lens=kvc.seq_lens.at[s].set(entry["seq_len"]))
            self.cache["kv"] = kvc
            # restored slot owns its whole row afresh (no shared prefix)
            self.slot_cached[s] = 0
            self.prefill_done[s] = len(self.prompt[s])
            self.slot_state[s] = rb.DECODE_PAUSED
            del self.offload[s]
            self.events.append(("restore", self._rid(s), s))
        self.cache["kv"] = kvc

    # -- convenience ---------------------------------------------------------
    def run_until_idle(self, max_steps: int = 10_000) -> int:
        steps = 0
        inflight = (rb.PREFILL_PENDING, rb.DECODE_PAUSED, rb.PREEMPTED,
                    rb.OFFLOADED)
        while steps < max_steps:
            busy = np.isin(self.slot_state, inflight).any() or \
                   (self.lane_slot >= 0).any()
            if not busy:
                break
            self.step()
            steps += 1
        return steps
