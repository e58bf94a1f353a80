"""Blink serving stack driver: DPU-plane frontend + device-plane engine.

``BlinkFrontend`` simulates the BlueField plane of Fig. 2: request intake ①,
tokenization ②, slot acquisition ③, prompt submission (the one-sided RDMA
write ⑤ becomes a functional ring update between window launches), token
retrieval ⑩/⑪ (TokenReader), detokenization ⑫ and streaming ⑬ (callback).

``BlinkServer`` is the end-to-end loop: the host's ONLY steady-state job is
re-launching the persistent window with donated state (the tail launch);
frontend work happens strictly between windows and never blocks the device
program — mirroring the paper's decoupling of the two planes.

``frontend_jitter``: optional callable applied per frontend operation. In
the paper the frontend lives on the DPU and is immune to host interference;
benchmarks use this to show Blink's *engine* is jitter-free even when the
(simulated) frontend is slowed.

Prefix plane (``ServeConfig.prefix_cache``): the radix prefix index lives
here, on the DPU plane with the tokenizer. Submission matches each prompt
against the trie (stamping ``cached_len`` + the shared page chain into the
ring and taking one allocator reference per matched page); the poll path
commits freshly prefilled prompts' full pages back into the trie (taking
the trie's reference) and, on drain, releases the slot's references —
refcounted pages return to the pool only when the last co-owner lets go.
LRU eviction of zero-ref chains runs under page backpressure, between
windows, like every other frontend touch.

Mixed-phase scheduling (``ServeConfig.prefill_chunk_tokens > 0``) changes
nothing structurally on this plane, but two invariants matter: the poll
path must not surface a request's first token until its chunk cursor
completes (guaranteed — ``ring.generated`` stays 0 through PREFILLING),
and the prefix-trie commit happens at chunk-complete, not admission (a
PREFILLING slot's pages are partially written; see ``poll``).
"""
from __future__ import annotations

import copy
import dataclasses
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ServeConfig
from repro.core import engine as eng
from repro.core import offload as offload_lib
from repro.core import recovery as recovery_lib
from repro.core import ring_buffer as rb
from repro.frontend.prefix_index import PrefixIndex
from repro.frontend.slot_tracker import SlotTracker
from repro.frontend.token_reader import TokenReader
from repro.frontend.tokenizer import BPETokenizer
from repro.models import cache as cache_lib
from repro.models.api import ModelApi
from repro.telemetry import export as tel_export
from repro.telemetry import host as host_tel
from repro.telemetry import state as tel_lib


@dataclass
class Request:
    request_id: int
    tokens: List[int]
    max_new: int
    temperature: float = 0.0
    submit_wall: float = 0.0
    ring_wall: float = -1.0          # written into the ring
    first_token_step: int = -1       # engine step that emitted token 0
    first_token_wall: float = -1.0   # token 0 read by the poll
    finish_wall: float = -1.0
    slot: int = -1
    output: List[int] = field(default_factory=list)
    text: Optional[str] = None
    cached_len: int = 0          # prefix tokens served from the radix trie
    committed: bool = False      # prompt pages indexed into the trie
    # SLO metadata + terminal status. status is "pending" until the
    # request reaches one of: "completed" (full stream), "timed_out"
    # (deadline expired — partial output in ``output``), "preempted"
    # (evicted to the offload buffer, then expired before restore),
    # "rejected" (bounced at intake — queue overload or a malformed
    # payload caught by submit validation, before a ring slot is
    # consumed), "faulted" (quarantined by the ring integrity protocol,
    # watchdog or poison guard — partial output stays in ``output``).
    slo_class: int = 0
    status: str = "pending"
    shared_pages: List[int] = field(default_factory=list)


class BlinkFrontend:
    def __init__(self, serve: ServeConfig,
                 tokenizer: Optional[BPETokenizer] = None,
                 jitter: Optional[Callable[[], None]] = None,
                 on_token: Optional[Callable[[int, int, int], None]] = None,
                 vocab: Optional[int] = None,
                 recorder: Optional[host_tel.Recorder] = None):
        self.serve = serve
        self.tokenizer = tokenizer
        self.recorder = recorder or host_tel.recorder()
        self.vocab = vocab               # token-id range for submit validation
        self.jitter = jitter or (lambda: None)
        self.tracker = SlotTracker(serve.num_slots)
        self.reader = TokenReader(serve.num_slots, on_token=on_token)
        self.prefix = PrefixIndex(serve.page_size) if serve.prefix_cache \
            else None
        self.queue: List[Request] = []           # not yet in the ring
        self.in_flight: Dict[int, Request] = {}  # slot -> request
        self.done: Dict[int, Request] = {}       # request_id -> request
        self._arrival = 0
        self._next_id = 0

    # -- intake (HTTP/SSE layer stand-in) ------------------------------------
    def enqueue(self, prompt, max_new: int, temperature: float = 0.0,
                slo_class: int = 0) -> int:
        self.jitter()                              # request parse/validate
        if isinstance(prompt, str):
            assert self.tokenizer is not None, "text prompt needs a tokenizer"
            tokens = self.tokenizer.encode(prompt)  # DPU tokenization
        else:
            tokens = list(prompt)
        tokens = tokens[: self.serve.max_prompt_len]
        req = Request(self._next_id, tokens, max_new, temperature,
                      submit_wall=time.perf_counter(), slo_class=slo_class)
        self._next_id += 1
        # frontend-side submit validation: a malformed request bounces at
        # the DPU edge BEFORE a ring slot is consumed. The ring integrity
        # protocol downstream is the backstop for corruption IN FLIGHT
        # (RDMA bit-rot, torn writes), not a substitute for validating
        # what the client actually sent.
        malformed = (
            not tokens
            or max_new <= 0 or max_new > self.serve.max_new_tokens
            or (self.vocab is not None
                and any(t < 0 or t >= self.vocab for t in tokens))
            or not np.isfinite(temperature) or temperature < 0)
        if malformed:
            req.status = "rejected"
            req.finish_wall = req.submit_wall
            self.done[req.request_id] = req
            return req.request_id
        limit = self.serve.intake_queue_limit
        if limit and len(self.queue) >= limit:
            # overload rejection at the DPU edge: the request never touches
            # the ring — terminal immediately, no tokens
            req.status = "rejected"
            req.finish_wall = req.submit_wall
            self.done[req.request_id] = req
            return req.request_id
        self.queue.append(req)
        return req.request_id

    # -- submission plane (the RDMA writes, between windows) -----------------
    def flush_submissions(self, ring: rb.RingState, step: int, alloc=None):
        """Move queued requests into EMPTY ring slots. With the prefix
        plane enabled, each prompt is first matched against the radix trie:
        the cached length + shared page chain ride into the ring slot and
        the request takes one allocator reference per matched page (so the
        chain cannot be freed or evicted while the request is pending).
        Returns (ring, alloc)."""
        rec = self.recorder
        with rec.span("blink.flush") as span:
            written = 0
            if self.queue:
                self.tracker.refresh(np.asarray(ring.slot_state))  # bulk read
            still: List[Request] = []
            for req in self.queue:
                slot = self.tracker.acquire()
                if slot is None:
                    still.append(req)              # ring full: queue on DPU
                    continue
                cached_len, shared = 0, None
                if self.prefix is not None:
                    cached_len, shared = self.prefix.match(req.tokens)
                    if shared:
                        alloc = cache_lib.share_pages(
                            alloc, jnp.asarray(shared, jnp.int32))
                req.cached_len = cached_len
                req.shared_pages = list(shared or [])
                rel = self.serve.deadline_steps(req.slo_class, req.max_new)
                self.jitter()                      # staging + RDMA write
                with rec.span("blink.ring_write", prompt_len=len(req.tokens),
                              request_id=req.request_id):
                    ring = rb.submit_request(
                        ring, slot, tokens=req.tokens,
                        request_id=req.request_id, max_new=req.max_new,
                        arrival=self._arrival, temperature=req.temperature,
                        step=step, cached_len=cached_len,
                        shared_pages=shared, slo_class=req.slo_class,
                        deadline=None if rel is None else step + rel)
                req.ring_wall = host_tel.clock()
                written += 1
                self._arrival += 1
                req.slot = slot
                self.in_flight[slot] = req
                self.reader.mark_urgent(slot)
            self.queue = still
            span.attrs["written"] = written
        return ring, alloc

    # -- retrieval plane (token reader poll, between windows) ----------------
    def poll(self, ring: rb.RingState, alloc=None, kvc=None):
        """Drain new tokens / completions. With the prefix plane enabled
        this is also where page lifetime is arbitrated: freshly prefilled
        prompts' full pages are committed into the trie (trie takes its
        reference) BEFORE any drained slot's references are released, and
        drained rows return to the pool only at refcount zero.
        Returns (ring, alloc, kvc)."""
        rec = self.recorder
        with rec.span("blink.poll") as span:
            self.jitter()                          # poll cycle
            with rec.span("blink.poll_read"):
                slot_states = np.asarray(ring.slot_state)
                generated = np.asarray(ring.generated)
                arena = np.asarray(ring.output_arena)
                prefill_step = np.asarray(ring.prefill_step)
            new_tokens, completed = self.reader.poll(slot_states, generated,
                                                     arena)
            now = host_tel.clock()
            first = []
            for slot, toks in new_tokens.items():
                req = self.in_flight.get(slot)
                if req is None:
                    continue
                if req.first_token_wall < 0:
                    req.first_token_wall = now
                    req.first_token_step = int(prefill_step[slot])
                    first.append((req.request_id, req.ring_wall,
                                  req.first_token_step, now))
                req.output.extend(int(t) for t in toks)
            if self.prefix is not None:
                # commit pass: runs over completing slots too — their pages
                # are still live (release is deferred to the drain below). A
                # slot still PREFILLING (mixed-phase chunk cursor
                # mid-prompt) is deliberately NOT in this set: its pages are
                # partially written, so the trie commit happens at
                # chunk-complete — the step its state reaches DECODE_* —
                # never at admission.
                prefilled = (rb.DECODE_PROCESSING, rb.DECODE_PAUSED,
                             rb.DECODE_COMPLETED)
                for slot, req in self.in_flight.items():
                    if not req.committed and slot_states[slot] in prefilled:
                        alloc = self._commit_prefix(slot, req, alloc, kvc)
                alloc = self._cap_trie_bytes(alloc, kvc)
            with rec.span("blink.release"):
                for slot in completed:
                    ring, alloc, kvc = self._release(slot, slot_states[slot],
                                                     now, ring, alloc, kvc)
            span.attrs.update(tokens=sum(map(len, new_tokens.values())),
                              completed=len(completed), first_tokens=first)
        return ring, alloc, kvc

    def _release(self, slot: int, state: int, now: float, ring, alloc,
                 kvc):
        """Retire a drained terminal slot: stamp its request, release its
        page references (prefix plane) and return the slot to EMPTY."""
        req = self.in_flight.pop(slot, None)
        if req is None:
            return ring, alloc, kvc
        req.finish_wall = now
        if state == rb.CANCELLED:
            if req.status != "preempted":          # offload drop wins
                req.status = "timed_out"
        elif state == rb.FAULTED:
            req.status = "faulted"                 # quarantined, not served
        else:
            req.status = "completed"
        if self.tokenizer is not None:
            req.text = self.tokenizer.decode(req.output)  # detokenize
        self.done[req.request_id] = req
        if self.prefix is not None:
            # release the slot's page references (shared prefix pages
            # survive via the trie's / other slots' refs). Three drain
            # shapes, disambiguated by what the slot still owns:
            #   - a wired row (admitted; completion or mid-PREFILLING/
            #     mid-decode cancel): free the row — it already carries
            #     the shared prefix chain plus the suffix pages;
            #   - no row, never produced a token (cancelled while
            #     queued): the only refs held are the matched prefix
            #     chain taken at submit — free exactly those;
            #   - no row, tokens produced (cancelled while spilled):
            #     every ref was already released at offload — nothing.
            row = np.asarray(kvc.block_table[slot])
            if (row >= 0).any():
                alloc = cache_lib.free_pages(alloc, kvc.block_table[slot])
                kvc = dataclasses.replace(
                    kvc, block_table=kvc.block_table.at[slot].set(-1))
            elif not len(req.output) and req.shared_pages:
                alloc = cache_lib.free_pages(
                    alloc, jnp.asarray(req.shared_pages, jnp.int32))
        ring = rb.release_slot(ring, slot)         # slot -> EMPTY
        self.tracker.mark_free(slot)
        return ring, alloc, kvc

    def _commit_prefix(self, slot: int, req: Request, alloc, kvc):
        """Index the prompt's full pages into the trie; the trie takes one
        allocator reference per newly indexed page. Duplicate chains (two
        identical prompts prefilled concurrently) keep the first request's
        pages — insert returns only the extension."""
        ps = self.serve.page_size
        n_full = len(req.tokens) // ps
        if n_full:
            row = np.asarray(kvc.block_table[slot])[:n_full]
            if (row >= 0).all():
                new = self.prefix.insert(req.tokens, row.tolist())
                if new:
                    alloc = cache_lib.share_pages(
                        alloc, jnp.asarray(new, jnp.int32))
        req.committed = True
        return alloc

    def _cap_trie_bytes(self, alloc, kvc):
        """PROACTIVE trie bound (``ServeConfig.prefix_trie_max_bytes``):
        whenever the trie's retained pages exceed the byte budget, evict
        LRU zero-external-ref chains down to it — on every poll, not only
        under admission backpressure, so an overloaded frontend's memory
        stays bounded even while admission is starved of candidates."""
        cap = self.serve.prefix_trie_max_bytes
        if not cap or self.prefix is None or kvc is None:
            return alloc
        max_pages = cap // cache_lib.page_nbytes(kvc)
        excess = self.prefix.num_pages - max_pages
        if excess > 0:
            pages = self.prefix.evict(excess,
                                      refcount=np.asarray(alloc.refcount))
            if pages:
                alloc = cache_lib.free_pages(
                    alloc, jnp.asarray(pages, jnp.int32))
        return alloc

    def starved_pages_needed(self, ring: rb.RingState) -> int:
        """Largest suffix-page demand among ring-pending requests. The
        engine's admission gate is all-or-nothing per candidate, so
        freeing this many pages guarantees the FCFS head can make
        progress — the trie must never wedge admission by hoarding the
        pool (a starved request's own matched chain is co-owned by the
        request, so eviction cannot take it out from under it)."""
        if self.prefix is None or not self.in_flight:
            return 0
        states = np.asarray(ring.slot_state)
        ps = self.serve.page_size
        need = 0
        for slot, req in self.in_flight.items():
            if states[slot] == rb.PREFILL_PENDING:
                total = -(-(len(req.tokens) + req.max_new) // ps)
                need = max(need, total - req.cached_len // ps)
        return need

    def maybe_evict(self, alloc, want_free: int):
        """Page backpressure valve: when fewer than ``want_free`` pages are
        free, drop LRU zero-external-ref trie chains until the deficit is
        covered (or the trie runs out of cold chains)."""
        if self.prefix is None:
            return alloc
        deficit = int(want_free) - int(alloc.top)
        if deficit > 0:
            pages = self.prefix.evict(deficit,
                                      refcount=np.asarray(alloc.refcount))
            if pages:
                alloc = cache_lib.free_pages(
                    alloc, jnp.asarray(pages, jnp.int32))
        return alloc

    @property
    def idle(self) -> bool:
        return not self.queue and not self.in_flight


def _prefetch_boundary_reads(state: eng.EngineState) -> None:
    """Start the device-to-host copies of everything the next boundary
    reads back — the step, the counter rows, the ring fields the poll
    reads — as soon as the window is enqueued: they then transfer together
    when it ends, instead of one round trip after another."""
    ring = state.ring
    for x in (state.step, state.telemetry.rows, ring.slot_state,
              ring.generated, ring.output_arena, ring.prefill_step):
        x.copy_to_host_async()


class BlinkServer:
    """End-to-end Blink stack: frontend + persistent-window engine."""

    def __init__(self, api: ModelApi, serve: ServeConfig, params, *,
                 tokenizer: Optional[BPETokenizer] = None,
                 frontend_jitter: Optional[Callable[[], None]] = None,
                 host_jitter: Optional[Callable[[], None]] = None,
                 on_token=None, seed: int = 0, enc_len: int = 0,
                 prompt_buckets: Optional[tuple] = None,
                 recorder: Optional[host_tel.Recorder] = None):
        self.api = api
        self.serve = serve
        self.params = params
        # host spans and the per-window step→wall map (telemetry/host.py);
        # the owner token marks this server's entries in a shared recorder
        self.recorder = recorder or host_tel.recorder()
        self.owner = host_tel.new_owner()
        self.frontend = BlinkFrontend(serve, tokenizer,
                                      jitter=frontend_jitter,
                                      on_token=on_token,
                                      vocab=api.cfg.vocab_size,
                                      recorder=self.recorder)
        self.host_jitter = host_jitter or (lambda: None)
        self._enc_len = enc_len
        self.state = eng.init_engine_state(api, serve, seed=seed,
                                           enc_len=enc_len)
        # the paper's CUDA graph cache: window executables per prompt bucket
        # (tightest fit selected per window; max shape is the fallback)
        self.windows = eng.WindowCache(api, serve, prompt_buckets)
        self.window_wall: List[float] = []
        # host-DRAM staging for preempted requests' spilled KV (DPU plane)
        self.offload_buf = offload_lib.KVOffloadBuffer()
        # crash-recovery snapshot (serve.snapshot_every_steps > 0): the
        # latest window-boundary image of the full engine + spill buffer +
        # frontend (trie, reader counts, in-flight map)
        self.snapshot: Optional[recovery_lib.EngineSnapshot] = None
        self._snapshot_frontend: Optional[BlinkFrontend] = None
        # telemetry drain, at window boundaries like the token reader (the
        # device plane never pushes): every window's counter rows go to the
        # recorder and into running totals; with serve.telemetry on,
        # per-request event timelines are keyed by request_id
        self.counter_totals = tel_lib.CounterTotals()
        self._request_events: Dict[int, list] = {}
        self._tel_snapshot = None

    def submit(self, prompt, max_new: int, temperature: float = 0.0,
               slo_class: int = 0) -> int:
        return self.frontend.enqueue(prompt, max_new, temperature,
                                     slo_class=slo_class)

    def reset(self, seed: int = 0) -> None:
        """Fresh engine + frontend state, KEEPING the compiled window."""
        fe = self.frontend
        self.frontend = BlinkFrontend(self.serve, fe.tokenizer,
                                      jitter=fe.jitter,
                                      on_token=fe.reader.on_token,
                                      vocab=fe.vocab,
                                      recorder=self.recorder)
        self.state = eng.init_engine_state(self.api, self.serve, seed=seed,
                                           enc_len=self._enc_len)
        self.owner = host_tel.new_owner()   # steps restart from 0
        self.window_wall = []
        self.offload_buf = offload_lib.KVOffloadBuffer()
        self.snapshot = None
        self._snapshot_frontend = None
        self.counter_totals = tel_lib.CounterTotals()
        self._request_events = {}
        self._tel_snapshot = None

    def run_window(self) -> None:
        """One boundary and one window: flush submissions, launch the
        window, wait for it, drain the counter rows, poll tokens, then the
        overload service and the snapshot. Each part is a ``blink.*`` span
        of ``self.recorder``; ``window_wall`` is the launch plus the
        wait."""
        rec = self.recorder
        fe = self.frontend
        with rec.span("blink.run_window", owner=self.owner) as top:
            step = int(self.state.step)
            alloc = self.state.alloc
            if fe.prefix is not None:
                with rec.span("blink.evict"):
                    alloc = fe.maybe_evict(
                        alloc, max(self.serve.prefix_evict_watermark,
                                   fe.starved_pages_needed(self.state.ring)))
            ring, alloc = fe.flush_submissions(self.state.ring, step, alloc)
            if ring is not self.state.ring or alloc is not self.state.alloc:
                self.state = dataclasses.replace(self.state, ring=ring,
                                                 alloc=alloc)
            self.host_jitter()             # the ONE host touch per window
            with rec.span("blink.select"):
                window_fn = self.windows.select(
                    self.windows.max_pending_len(self.state.ring))
            with rec.span("blink.launch") as launch:
                self.state = window_fn(self.params, self.state)
                _prefetch_boundary_reads(self.state)
            with rec.span("blink.wait") as wait:
                jax.block_until_ready(self.state.step)
            self.window_wall.append(wait.t1 - launch.t0)
            win = rec.window(step, self.serve.window, launch.t0, wait.t1,
                             owner=self.owner)
            top.attrs["window"] = win.index
            with rec.span("blink.drain"):
                # drain BEFORE poll: completing slots still map to requests
                self._drain_telemetry(win)
            kvc = self.state.cache.get("kv")
            ring, alloc, kvc = fe.poll(self.state.ring, self.state.alloc,
                                       kvc)
            st = self.state
            if ring is not st.ring or alloc is not st.alloc \
                    or kvc is not st.cache.get("kv"):
                cache = st.cache if kvc is st.cache.get("kv") \
                    else dict(st.cache, kv=kvc)
                self.state = dataclasses.replace(st, ring=ring, alloc=alloc,
                                                 cache=cache)
            if self.serve.slo_preempt:
                # DPU-plane overload service: spill freshly preempted
                # slots' KV to the host buffer, cancel spilled slots past
                # their e2e deadline, restore earliest-deadline-first from
                # surplus. A dropped request surfaces as "preempted" when
                # the NEXT poll drains its CANCELLED slot.
                with rec.span("blink.overload"):
                    self.state, events = offload_lib.service_overload(
                        self.state, self.offload_buf, self.serve)
                for kind, _rid, slot in events:
                    if kind == "drop" and slot in fe.in_flight:
                        fe.in_flight[slot].status = "preempted"
            if self.serve.snapshot_every_steps:
                # crash-recovery snapshot: taken AFTER every DPU-plane
                # touch of this boundary, so the image is exactly what the
                # next window would have consumed — restoring replays from
                # here losing zero committed tokens
                if (step + self.serve.window) \
                        % self.serve.snapshot_every_steps == 0:
                    with rec.span("blink.snapshot"):
                        self.take_snapshot()

    # -- crash recovery (window-boundary snapshot / restore) -----------------
    def take_snapshot(self) -> None:
        """Byte-exact image of engine + spill buffer + frontend (trie,
        reader counts, in-flight map) at the current window boundary."""
        self.snapshot = recovery_lib.snapshot_engine(self.state,
                                                     self.offload_buf)
        self._snapshot_frontend = self._copy_frontend(self.frontend)
        self._tel_snapshot = (copy.deepcopy(self.counter_totals),
                              copy.deepcopy(self._request_events))

    def restore_snapshot(self) -> None:
        """Rewind the whole serving stack to the latest snapshot — the
        recovery path after a window kill. Compiled windows are KEPT (they
        are pure functions); only state rewinds. Token streams after the
        restore are identical to the unkilled run."""
        assert self.snapshot is not None, "no snapshot taken yet"
        self.state, buf = recovery_lib.restore_engine(self.snapshot)
        self.offload_buf = buf if buf is not None \
            else offload_lib.KVOffloadBuffer()
        self.frontend = self._copy_frontend(self._snapshot_frontend)
        if self._tel_snapshot is not None:
            totals, events = self._tel_snapshot
            self.counter_totals = copy.deepcopy(totals)
            self._request_events = copy.deepcopy(events)

    def _copy_frontend(self, fe: BlinkFrontend) -> BlinkFrontend:
        """Deep copy of the frontend that shares the recorder: the span
        log records what ran, so a restore does not rewind it."""
        return copy.deepcopy(fe, {id(self.recorder): self.recorder})

    def run_until_idle(self, max_windows: int = 1000) -> int:
        n = 0
        while n < max_windows:
            if self.frontend.idle:
                break
            self.run_window()
            n += 1
        return n

    # -- telemetry -------------------------------------------------------------
    def _drain_telemetry(self, win: host_tel.WindowRecord) -> None:
        """Read the device telemetry at a window boundary.

        The window's counter rows (window-deep, and a window starts at a
        multiple of its length, so exactly this window's, in step order)
        go to its recorder entry and into the running totals. With the event log on, each
        in-flight slot's log is re-read whole and keyed by request id —
        timelines grow monotonically until terminal, so overwriting is
        idempotent."""
        tel = self.state.telemetry
        win.rows = np.asarray(tel.rows)
        self.counter_totals.add(win.rows)
        if tel.ev_code is None:
            return
        ev_code = np.asarray(tel.ev_code)
        ev_step = np.asarray(tel.ev_step)
        ev_count = np.asarray(tel.ev_count)
        for slot, req in self.frontend.in_flight.items():
            self._request_events[req.request_id] = tel_lib.events_of_slot(
                ev_code, ev_step, ev_count, slot)

    def telemetry_records(self) -> List[dict]:
        """Per-request records built from the drained event timelines.

        Shaped like ``metrics.request_records`` output (minus ring-stamp
        ITL, which needs live token stamps) so the exporters accept them
        directly. ``terminal`` is the frontend status — it distinguishes
        ``timed_out`` from ``preempted`` drops, which the ring's CANCELLED
        state alone cannot."""
        recs = []
        fe = self.frontend
        reqs = list(fe.done.values()) + list(fe.in_flight.values())
        for req in reqs:
            ev = self._request_events.get(req.request_id, [])
            stamps: Dict[str, int] = {}
            for name, step in ev:
                stamps.setdefault(name, step)
            ttft = None
            if "first_token" in stamps and "submitted" in stamps:
                ttft = stamps["first_token"] - stamps["submitted"]
            recs.append({
                "slot": req.slot, "request_id": req.request_id,
                "terminal": req.status, "n_tokens": len(req.output),
                "submit_step": stamps.get("submitted", -1),
                "events": ev, "ttft_steps": ttft, "tpot_steps": None,
                "itl_steps": [],
            })
        return recs

    def metrics_text(self) -> str:
        """Prometheus text exposition of everything drained so far."""
        return tel_export.prometheus_text(
            self.counter_totals, records=self.telemetry_records(),
            recorder=self.recorder, owner=self.owner)

    def trace_json(self) -> dict:
        """Chrome-trace / Perfetto JSON object: the recorder's host spans
        and the request spans, on one timeline."""
        return tel_export.perfetto_trace(self.telemetry_records(),
                                         self.recorder, self.owner)

    def request_metrics(self) -> List[dict]:
        out = []
        for req in self.frontend.done.values():
            ttft = (req.first_token_wall - req.submit_wall
                    if req.first_token_wall > 0 else float("nan"))
            ntok = len(req.output)
            tpot = ((req.finish_wall - req.first_token_wall) / max(ntok - 1, 1)
                    if req.finish_wall > 0 else float("nan"))
            rec = {"request_id": req.request_id, "ttft": ttft,
                   "tpot": tpot, "tokens": ntok,
                   "latency": req.finish_wall - req.submit_wall,
                   "cached_len": req.cached_len,
                   "prompt_len": len(req.tokens),
                   "slo_class": req.slo_class, "status": req.status}
            if self.serve.telemetry:
                rec["events"] = self._request_events.get(req.request_id, [])
            out.append(rec)
        return out
