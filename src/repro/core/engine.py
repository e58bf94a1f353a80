"""Blink persistent-window serving engine (the paper's core, TPU-adapted).

Paper §4.2, mechanism -> JAX mapping:

  * persistent scheduler kernel        -> ``engine_step`` fused into a
    (infinite control loop)               ``lax.fori_loop`` window program;
                                          all control flow is device-side
  * fire-and-forget graph launches,    -> window of ``serve.window`` steps per
    120-launch limit, tail-launch         jitted invocation; the host's only
    recovery                              steady-state job is re-invoking with
                                          DONATED state buffers (the tail
                                          launch; state survives, zero copy)
  * parallel slot scanning + CAS claim -> vectorized FCFS selection over the
                                          slot-state array (ring_scan Pallas
                                          kernel is the TPU hot-path form)
  * pause-and-resume continuous        -> two policies, selected by
    batching with inline prefill          ``ServeConfig.prefill_chunk_tokens``:
                                          (0) phase-exclusive: a step either
                                          runs a (max-shape) prefill for <= A
                                          new requests while decode lanes are
                                          DECODE_PAUSED, or one decode step
                                          for all active lanes; (>0) MIXED-
                                          PHASE: every step decodes all
                                          generating lanes AND advances at
                                          most ``prefill_chunk_tokens`` of
                                          pending prefill (see below), so
                                          admission never stalls decode
  * admission gating (3 conditions)    -> (i) pending prefills, (ii) free
                                          decode-lane capacity, (iii) KV page
                                          availability (all-or-nothing alloc
                                          = backpressure)
  * on-device sampling inside graph    -> sampling fused into the same step
  * paged KV management on device      -> PageAllocator free-list updated
                                          inside the window program

The engine treats the model as opaque via ``repro.models.api.ModelApi``.
Attention inside that opaque step is pluggable for BOTH phases: build the
api with ``make_model(cfg, attn_backend=serve.attn_backend)`` to route the
per-token decode KV read through either the jnp gather path ("gather", HBM
traffic scales with the provisioned ``max_kv``) or the Pallas
paged-attention kernel ("pallas", traffic scales with the live KV length),
and the prefill bucket through either dense ``gqa_attend`` ("gather",
O(T^2) logits in HBM) or the flash prefill kernel ("pallas", tiled online
softmax, no T x T logits; K/V pages populated inside the layer scan either
way). The ``REPRO_ATTN_BACKEND`` env var overrides both.
``ServeConfig.kv_cache_dtype = "int8"`` serves a quantised KV pool; the
pallas decode backend dequantises fused in-kernel and prefill writes
quantise inside the scan via ``cache.write_kv_layer``.

Mixed-phase step (``ServeConfig.prefill_chunk_tokens > 0``), mapped onto
the paper's persistent-kernel scheduling loop (Fig. 2 / §4): the paper's
GPU-resident scheduler never leaves its control loop — each iteration
scans the ring, admits work and runs whatever compute is due, so a newly
arrived prompt costs running requests at most one bounded iteration, never
a full prefill. The phase-exclusive policy above approximates that loop
but re-introduces the head-of-line blocking the paper's P99 TPOT
comparison (Table 6) penalises in vLLM-class schedulers: one admitted
long prompt suspends every decode lane for its whole prefill. The mixed
step restores the bounded-iteration property with three sub-phases per
iteration, all inside the same fused program:

  1. admit: up to A PREFILL_PENDING slots pass the 3-condition gate
     (pending / lane capacity / suffix pages), get their pages wired and
     enter ``PREFILLING`` with chunk cursor ``ring.prefill_done_len`` =
     ``cached_len`` — no model compute yet;
  2. chunk: up to ``max_prefills_per_step`` PREFILLING slots (FCFS)
     advance one chunk of suffix prefill in ONE batched dispatch
     (``api.prefill_batched`` — heterogeneous chunk cursors, ragged chunk
     lengths and per-lane cached prefixes ride a single fused call, so
     per-iteration launch cost does not scale with the lane count),
     resuming from the cursor via the same ``cached_lens`` machinery as
     radix prefix reuse (bitwise-equal to single shot); the final chunk
     samples the first token;
  3. decode: ALL lanes that were DECODE_PROCESSING at the top of the step
     run one decode step — a prefill in flight never pauses them, so the
     per-lane inter-token gap is bounded by one (decode + chunk) step.

Greedy token streams are identical under both policies (chunking is
bitwise-equal and each request's KV/positions don't depend on the
interleave); ``tests/test_scheduler_diff.py`` holds both engines to that.
The chunk size trades TTFT against TPOT jitter — ``benchmarks/
tpot_under_load.py`` sweeps it. ``ServeConfig.prefill_chunk_tokens_max``
makes that tradeoff load-adaptive: each iteration picks its per-lane
chunk budget from the top-of-step decode-lane occupancy snapshot
(``adaptive_chunk_budget`` — a pure integer policy the host engine
mirrors bit-for-bit), shrinking toward the ``prefill_block_q`` tile floor
when the decode batch is near-full and growing toward the ceiling when
lanes sit idle. The compiled chunk shape stays fixed at the ceiling
(``ServeConfig.chunk_bucket``); the budget only clamps how many columns
of it are live, so adaptivity costs zero extra executables.

Prefix plane (``ServeConfig.prefix_cache``), mapped onto the paper's
Fig. 2 DPU/GPU split: the radix prefix index
(``frontend.prefix_index.PrefixIndex``) is request-metadata-only state, so
it lives on the DPU plane next to the tokenizer (②) — matching happens at
submission (③), before the one-sided ring write (⑤), and stamps
``cached_len`` + the shared page chain into the slot's ring metadata. The
GPU plane stays CPU-free: at admission the engine wires the shared pages
into the block table, allocates SUFFIX pages only (the admission gate
likewise charges only the suffix), and runs a suffix-only prefill whose
attention folds the cached prefix in from the paged pool (the prefix-aware
flash kernel / gather reference). Page lifetime is arbitrated by per-page
refcounts inside ``PageAllocator``: slots and the trie co-own shared
pages, and release moves from the decode branch to the frontend's
slot-drain path (⑪→⑬) so freshly prefilled prefixes are indexed before
they can be freed; LRU eviction of zero-ref chains under page
backpressure happens on the same DPU plane, between windows.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.core import ring_buffer as rb
from repro.core.sampling import sample_tokens
from repro.models import cache as cache_lib
from repro.models.api import ModelApi, cache_for_serve
from repro.telemetry import state as tel_lib

INT_MAX = jnp.iinfo(jnp.int32).max


@jax.tree_util.register_dataclass
@dataclass
class EngineState:
    ring: rb.RingState
    cache: Dict[str, Any]
    alloc: cache_lib.PageAllocator
    lane_slot: jax.Array        # [Bd] int32, -1 = free lane
    key: jax.Array              # PRNG key
    step: jax.Array             # [] int32 global device step counter
    windows_done: jax.Array     # [] int32
    # CPU-free telemetry plane: counter rows always, the event log when
    # ServeConfig.telemetry is on
    telemetry: tel_lib.TelemetryState


def _check_attn_backend(api: ModelApi, serve: ServeConfig) -> None:
    """ServeConfig.attn_backend is consumed where the model api is built
    (make_model), not here — catch the silent no-op where the config asks
    for an accelerated backend but the api was built with the default.
    ``api.attn_backend`` names the backend bound into BOTH the decode and
    prefill callables, so this check covers prefill too."""
    want = os.environ.get("REPRO_ATTN_BACKEND") or serve.attn_backend
    if want != api.attn_backend and api.attn_backend == "gather":
        raise ValueError(
            f"ServeConfig.attn_backend={serve.attn_backend!r} but the model "
            f"api was built with {api.attn_backend!r}; pass "
            f"make_model(cfg, attn_backend=serve.attn_backend, "
            f"attn_pages_per_block=serve.attn_pages_per_block, "
            f"prefill_block_q=serve.prefill_block_q, "
            f"prefill_block_k=serve.prefill_block_k)")


def _check_prefix_cache(api: ModelApi, serve: ServeConfig) -> None:
    """Prefix reuse restores context from paged KV alone; recurrent state
    (SSM/hybrid) and per-slot dense cross-attention K/V (enc-dec) cannot be
    rebuilt from shared pages — refuse at init instead of serving garbage."""
    if not serve.prefix_cache:
        return
    cfg = api.cfg
    if (cfg.arch_type not in ("dense", "moe", "vlm")
            or cfg.is_encoder_decoder or not cfg.uses_paged_kv):
        raise ValueError(
            f"ServeConfig.prefix_cache requires a paged-KV decoder-only "
            f"attention arch; {cfg.name!r} is {cfg.arch_type!r}")


def _check_mixed_phase(api: ModelApi, serve: ServeConfig) -> None:
    """The mixed-phase scheduler resumes a prompt from its already-written
    KV pages chunk by chunk (the ``cached_lens`` machinery); recurrent
    state (SSM/hybrid) and enc-dec cross-attention cannot be suspended
    mid-prompt that way — refuse at init instead of serving garbage."""
    if serve.prefill_chunk_tokens <= 0:
        return
    cfg = api.cfg
    if (cfg.arch_type not in ("dense", "moe", "vlm")
            or cfg.is_encoder_decoder or not cfg.uses_paged_kv):
        raise ValueError(
            f"ServeConfig.prefill_chunk_tokens (mixed-phase scheduling) "
            f"requires a paged-KV decoder-only attention arch; "
            f"{cfg.name!r} is {cfg.arch_type!r}")
    if api.prefill_batched is None:
        raise ValueError(
            f"ServeConfig.prefill_chunk_tokens (mixed-phase scheduling) "
            f"requires ModelApi.prefill_batched — the one-dispatch batched "
            f"chunk step — but the {cfg.name!r} api does not provide it")


def _check_unified(api: ModelApi, serve: ServeConfig) -> None:
    """``attn_unified`` changes the traced shape of the mixed step (one
    attention dispatch instead of two) — a config/api mismatch would
    silently serve the wrong dispatch count, so refuse at init, same as
    ``_check_attn_backend``."""
    if bool(serve.attn_unified) != bool(api.attn_unified):
        raise ValueError(
            f"ServeConfig.attn_unified={serve.attn_unified!r} but the model "
            f"api was built with attn_unified={api.attn_unified!r}; pass "
            f"make_model(cfg, ..., attn_unified=serve.attn_unified, "
            f"kv_fused_layout=serve.kv_fused_layout)")


def _check_mesh(api: ModelApi, serve: ServeConfig) -> None:
    """``mesh_model_size`` selects the SPMD layout of the whole window —
    a config/api mismatch would silently serve unsharded (or on the wrong
    mesh), so refuse at init, same as ``_check_attn_backend``."""
    from repro.distribution import sharding as shard_lib
    have = shard_lib.mesh_model_size(api.mesh)
    if serve.mesh_model_size != have:
        raise ValueError(
            f"ServeConfig.mesh_model_size={serve.mesh_model_size} but the "
            f"model api was built over a model axis of size {have}; pass "
            f"make_model(cfg, ..., mesh=sharding.make_serve_mesh("
            f"serve.mesh_model_size))")


def engine_state_shardings(api: ModelApi, state: "EngineState"):
    """NamedSharding tree matching ``state`` on the api's serving mesh:
    the paged KV pool sharded over KV heads on "model", every other leaf
    (ring, allocator, lanes, RNG, counters, telemetry) replicated — the
    scheduler decides identically on all shards, which is what keeps the
    donation loop, snapshot/restore and EDF/preemption policies unchanged.
    Used for initial placement AND re-asserted at the end of every step so
    the donated window buffers keep one deterministic layout."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.distribution import sharding as shard_lib
    mesh = api.mesh
    rep = NamedSharding(mesh, PartitionSpec())
    shardings = jax.tree.map(lambda _: rep, state)
    kv_named = shard_lib.to_named(mesh, shard_lib.cache_pspecs(
        api.cfg, state.cache, shard_lib.mesh_model_size(mesh),
        data_axis=None)["kv"])
    return dataclasses.replace(
        shardings, cache=dict(shardings.cache, kv=kv_named))


def _place_state(api: ModelApi, state: "EngineState") -> "EngineState":
    """Commit every state leaf to the serving mesh (initial placement)."""
    if api.mesh is None:
        return state
    return jax.tree.map(jax.device_put, state,
                        engine_state_shardings(api, state))


def _constrain_state(api: ModelApi, state: "EngineState") -> "EngineState":
    """End-of-step sharding re-assert (no-op copies when already placed)."""
    if api.mesh is None:
        return state
    return jax.tree.map(jax.lax.with_sharding_constraint, state,
                        engine_state_shardings(api, state))


def adaptive_chunk_budget(busy_lanes, decode_batch: int, floor: int,
                          ceiling: int):
    """Per-lane chunk budget for one mixed-step iteration (pure policy).

    ``busy_lanes`` is the top-of-step count of decode lanes that will run
    this iteration (the same snapshot the decode sub-phase uses); the
    budget interpolates linearly on the idle-lane fraction from ``floor``
    (= ``ServeConfig.prefill_block_q``, one kernel query tile — the
    smallest chunk that doesn't waste tile compute) at a full decode batch
    up to ``ceiling`` (= ``ServeConfig.prefill_chunk_tokens_max``) when
    every lane is idle, then aligns down to whole ``floor`` tiles.

    Properties the adaptive-chunk tests pin: result always lies in
    [floor, ceiling]; monotone non-decreasing in the idle-lane count;
    floor-aligned; and — being integer arithmetic over the occupancy
    snapshot alone — bit-identical between the device engine (jnp int32)
    and the host mirror (python ints), so the differential harness keeps
    working in adaptive mode. Requires ``ceiling`` to be a multiple of
    ``floor`` (validated by ``ServeConfig.__post_init__``).
    """
    idle = decode_batch - busy_lanes
    budget = floor + ((ceiling - floor) * idle) // decode_batch
    return (budget // floor) * floor


def init_engine_state(api: ModelApi, serve: ServeConfig, *, seed: int = 0,
                      enc_len: int = 0) -> EngineState:
    _check_attn_backend(api, serve)
    _check_prefix_cache(api, serve)
    _check_mixed_phase(api, serve)
    _check_unified(api, serve)
    _check_mesh(api, serve)
    cache = cache_for_serve(api, serve, enc_len=enc_len)
    state = EngineState(
        ring=rb.make_ring(serve),
        cache=cache,
        alloc=cache_lib.make_page_allocator(serve.num_pages),
        lane_slot=jnp.full((serve.decode_batch,), -1, jnp.int32),
        key=jax.random.PRNGKey(seed),
        step=jnp.asarray(0, jnp.int32),
        windows_done=jnp.asarray(0, jnp.int32),
        telemetry=tel_lib.make_telemetry_state(serve),
    )
    return _place_state(api, state)


def free_done_rows(alloc, block_table, slots, done):
    """Release the block-table rows of ``done`` slots (one allocator ref
    per page) and clear them — shared by the prefill/chunk branches
    (max_new==1 completions), the decode branch, and ``drain_completed``."""
    S = block_table.shape[0]

    def free_one(carry, xs):
        alloc, block_table = carry
        slot, is_done = xs
        row = block_table[jnp.clip(slot, 0, S - 1)]
        alloc2 = cache_lib.free_pages(alloc, row)
        alloc = jax.tree.map(
            lambda a, b: jnp.where(is_done, b, a), alloc, alloc2)
        block_table = block_table.at[
            jnp.where(is_done, slot, S)].set(-1, mode="drop")
        return (alloc, block_table), None

    (alloc, block_table), _ = jax.lax.scan(
        free_one, (alloc, block_table), (slots, done))
    return alloc, block_table


def drain_completed(state: EngineState) -> EngineState:
    """Engine-side slot drain for FRONTEND-LESS serving: release every
    DECODE_COMPLETED slot — free its block-table row (one allocator ref per
    page) and return the slot to EMPTY — after the caller has read its
    output tokens from ``ring.output_arena``.

    This closes the ROADMAP-noted leak: under ``ServeConfig.prefix_cache``
    page release is frontend-owned by design (the trie must index freshly
    prefilled pages before the slot's references drop), so engine-only
    serving used to strand completed slots' pages forever. Without a
    ``BlinkFrontend`` nothing ever populates the prefix trie or
    ``ring.shared_pages`` — every page has exactly one owner — so this
    plain release is conservation-exact. With a frontend attached, use
    ``BlinkFrontend.poll`` instead: draining here would bypass the trie
    commit and evict reusable prefixes."""
    ring = state.ring
    S = ring.num_slots
    done = (ring.slot_state == rb.DECODE_COMPLETED) | \
        (ring.slot_state == rb.CANCELLED) | \
        (ring.slot_state == rb.FAULTED)
    alloc, cache = state.alloc, state.cache
    kvc = cache.get("kv")
    if kvc is not None:
        alloc, bt = free_done_rows(alloc, kvc.block_table,
                                   jnp.arange(S, dtype=jnp.int32), done)
        cache = dict(cache, kv=dataclasses.replace(kvc, block_table=bt))
    ring = dataclasses.replace(
        ring,
        slot_state=jnp.where(done, rb.EMPTY, ring.slot_state),
        arrival=jnp.where(done, INT_MAX, ring.arrival),
        cached_len=jnp.where(done, 0, ring.cached_len),
        prefill_done_len=jnp.where(done, 0, ring.prefill_done_len),
        shared_pages=jnp.where(done[:, None], -1, ring.shared_pages),
        seq=jnp.where(done, -1, ring.seq),
        checksum=jnp.where(done, 0, ring.checksum),
        committed=jnp.where(done, 0, ring.committed),
        validated=jnp.where(done, 0, ring.validated),
        stall_steps=jnp.where(done, 0, ring.stall_steps),
    )
    return dataclasses.replace(state, ring=ring, alloc=alloc, cache=cache)


# ---------------------------------------------------------------------------
# FCFS admission selection (the "parallel slot scan")
# ---------------------------------------------------------------------------


def admissible_pending(ring: rb.RingState) -> jax.Array:
    """[S] bool — PREFILL_PENDING entries admission may look at: validated
    by the intake sub-phase (``ring_buffer.validate_intake``). Uncommitted
    (torn) and not-yet-validated entries are invisible; validation runs at
    the top of every step, so a clean submission is admissible the same
    step it is first seen — zero added latency on the healthy path."""
    return (ring.slot_state == rb.PREFILL_PENDING) & (ring.validated > 0)


def select_pending_fcfs(ring: rb.RingState, max_admit: int):
    """Pick up to ``max_admit`` admissible PREFILL_PENDING slots,
    earliest-arrival first.

    jnp formulation — semantically identical to
    ``repro.kernels.ring_scan.ring_select_topk`` (the Pallas TPU hot path)
    over the validated pending set; tests assert equivalence."""
    keyed = jnp.where(admissible_pending(ring), ring.arrival, INT_MAX)
    order = jnp.argsort(keyed)
    cand = order[:max_admit].astype(jnp.int32)
    valid = keyed[cand] != INT_MAX
    return cand, valid


def select_pending_edf(ring: rb.RingState, max_admit: int):
    """Slack-aware admission selection: up to ``max_admit`` PREFILL_PENDING
    slots ordered earliest-deadline-first, arrival ticket as the tiebreak
    (``lexsort``'s LAST key is primary). Requests with no deadline carry
    INT_MAX and sort behind every deadlined one — so with no deadlines
    stamped at all this degrades to exactly the FCFS order of
    ``select_pending_fcfs``. Used by the mixed-phase scheduler whenever the
    SLO machinery is on; the host mirror runs the same two-key sort with
    ``np.lexsort`` (identical semantics, asserted by the differential
    harness)."""
    pend = admissible_pending(ring)
    dl = jnp.where(pend, ring.deadline_step, INT_MAX)
    ar = jnp.where(pend, ring.arrival, INT_MAX)
    cand = jnp.lexsort((ar, dl))[:max_admit].astype(jnp.int32)
    return cand, pend[cand]


# ---------------------------------------------------------------------------
# The per-step function (one iteration of the persistent scheduler loop)
# ---------------------------------------------------------------------------


def _left_pad_prompts(ring: rb.RingState, slots: jax.Array,
                      bucket: Optional[int] = None,
                      start: Optional[jax.Array] = None,
                      limit: Optional[jax.Array] = None):
    """Gather [A, bucket] prompts, left-padded (right-aligned).

    ``bucket`` < max_prompt_len realizes the paper's CUDA-graph-cache shape
    matching: the prefill branch is compiled at the bucket length, so short
    prompts don't pay max-shape compute. Prompts longer than the bucket are
    the caller's responsibility (WindowCache routes them to a bigger
    executable; the max-shape window is the paper's fallback graph).

    ``start`` [A]: skip each slot's first ``start`` prompt tokens (the
    cached prefix) — the gathered bucket then holds only the suffix.

    ``limit``: traced scalar clamp on the gathered length (the adaptive
    chunk budget) — the bucket SHAPE stays static, only fewer of its
    trailing columns are live. Must clamp before the gather so the live
    columns hold the FIRST ``limit`` pending tokens, not the last.
    """
    rows = ring.input_arena[slots]                    # [A, P] left-aligned
    A, P = rows.shape
    B = bucket or P
    st = jnp.zeros((A,), jnp.int32) if start is None else start
    cap = B if limit is None else jnp.minimum(B, limit)
    lens = jnp.clip(ring.prompt_len[slots] - st, 0, cap)
    col = jnp.arange(B)[None, :]
    src = col - (B - lens)[:, None] + st[:, None]       # [A, B]
    valid = col >= (B - lens)[:, None]
    gathered = jnp.take_along_axis(rows, jnp.clip(src, 0, P - 1), axis=1)
    return jnp.where(valid, gathered, 0), lens


def make_engine_step(api: ModelApi, serve: ServeConfig,
                     prompt_bucket: Optional[int] = None
                     ) -> Callable[[Any, EngineState], EngineState]:
    cfg = api.cfg
    A = serve.admit_per_step
    Bd = serve.decode_batch
    ps = serve.page_size
    ppr = serve.pages_per_req
    paged = cfg.uses_paged_kv
    use_prefix = serve.prefix_cache
    C = serve.prefill_chunk_tokens
    Cmax = serve.prefill_chunk_tokens_max
    chunk_bucket = serve.chunk_bucket
    Mp = serve.max_prefills_per_step
    mixed = C > 0
    adaptive = Cmax > 0
    # SLO-aware overload control (mixed-phase only; validated in
    # ServeConfig.__post_init__). All three sub-policies are pure
    # functions over the top-of-step snapshot, mirrored bit-for-bit by
    # HostEngine — when both flags are off they compile to nothing and
    # the step is the exact pre-SLO program.
    policy = serve.deadline_policy
    slo_on = policy != "none"
    preempt_on = serve.slo_preempt
    select_pending = (select_pending_edf if (slo_on or preempt_on)
                      else select_pending_fcfs)

    def suffix_pages_needed(ring, cand):
        """Pages a candidate still needs: lifetime total minus its cached
        prefix pages (0 cached = the full formula — one code path)."""
        total = cache_lib.pages_needed(ring.prompt_len[cand],
                                       ring.max_new[cand], ps)
        if not use_prefix:
            return total
        return jnp.maximum(total - ring.cached_len[cand] // ps, 0)

    def assign_lanes(state, cand, cand_valid):
        """Reserve one free decode lane per valid candidate (FCFS order).
        Lanes are assigned by rank AMONG THE VALID candidates (cumsum
        compaction), not by candidate position — when the page gate drops a
        mid-list candidate, later candidates still land on genuinely free
        lanes (the host baseline compacts the same way; positional
        assignment would defer an admission the gate already passed).
        Returns (lanes [A], admit [A] — valid & lane available)."""
        free_lane_order = jnp.argsort(
            jnp.where(state.lane_slot < 0, 0, 1), stable=True)
        pos = jnp.cumsum(cand_valid.astype(jnp.int32)) - 1   # rank if valid
        lanes = free_lane_order[jnp.clip(pos, 0, Bd - 1)].astype(jnp.int32)
        lane_free = state.lane_slot[lanes] < 0
        return lanes, cand_valid & lane_free

    def wire_pages(ring, cache, alloc, cand, admit):
        """Page allocation: all-or-nothing per request (backpressure),
        charging only the SUFFIX beyond a cached prefix; wires the
        block-table row (shared prefix chain + fresh suffix pages).
        Returns (cache, alloc, admit) with admit &= allocation ok."""
        if not paged:
            return cache, alloc, admit
        need = suffix_pages_needed(ring, cand)

        def alloc_one(carry, xs):
            alloc, = carry
            n, want = xs
            pages, alloc2, ok = cache_lib.alloc_pages(alloc, n, ppr)
            ok = ok & want
            alloc = jax.tree.map(
                lambda a, b: jnp.where(ok, b, a), alloc, alloc2)
            return (alloc,), (jnp.where(ok, pages, -1), ok)

        (alloc,), (page_rows, alloc_ok) = jax.lax.scan(
            alloc_one, (alloc,), (need, admit))
        admit = admit & alloc_ok
        if use_prefix:
            # block-table row = shared prefix chain (frontend-owned
            # refs, read-only) followed by the freshly allocated
            # suffix pages shifted past it
            cached_pages = ring.cached_len[cand] // ps      # [A]
            blk = jnp.arange(ppr)[None, :]
            shift = blk - cached_pages[:, None]
            suffix_rows = jnp.where(
                shift >= 0,
                jnp.take_along_axis(page_rows,
                                    jnp.clip(shift, 0, ppr - 1), axis=1),
                -1)
            page_rows = jnp.where(blk < cached_pages[:, None],
                                  ring.shared_pages[cand], suffix_rows)
        kvc = cache["kv"]
        sel = jnp.where(admit, cand, kvc.block_table.shape[0])
        block_table = kvc.block_table.at[sel].set(page_rows, mode="drop")
        cache = dict(cache, kv=dataclasses.replace(
            kvc, block_table=block_table))
        return cache, alloc, admit

    def gate_candidates(state, cand, cand_valid):
        """Admission gating (paper §4.2's three conditions): (i) pending
        prefills [cand_valid], (ii) KV page availability — candidates whose
        pages can't be allocated stay PENDING and must NOT block the step,
        (iii) free decode-lane capacity. Page arithmetic only exists for
        paged configs — SSM archs admit on lane capacity alone."""
        n_free = jnp.sum(state.lane_slot < 0)
        if paged:
            need = suffix_pages_needed(state.ring, cand)
            running = state.alloc.top
        count = jnp.int32(0)
        gated = []
        for j in range(A):         # A is small & static: unrolled
            fits = cand_valid[j] & (count < n_free)
            if paged:
                fits &= need[j] <= running
                running = jnp.where(fits, running - need[j], running)
            count = count + fits.astype(jnp.int32)
            gated.append(fits)
        return jnp.stack(gated)

    def prefill_branch(params, state: EngineState, cand, cand_valid):
        ring, cache, alloc = state.ring, state.cache, state.alloc

        # (pause running decode lanes for this step — paper's pause-and-resume)
        running = state.lane_slot >= 0
        safe_lane_slots = jnp.maximum(state.lane_slot, 0)
        ring_states = ring.slot_state.at[safe_lane_slots].set(
            jnp.where(running, rb.DECODE_PAUSED,
                      ring.slot_state[safe_lane_slots]), mode="drop")

        lanes, admit = assign_lanes(state, cand, cand_valid)
        cache, alloc, admit = wire_pages(ring, cache, alloc, cand, admit)

        # run the (max-shape) prefill for admitted requests — suffix-only
        # when a cached prefix is present
        cached = ring.cached_len[cand] if use_prefix else None
        prompts, lens = _left_pad_prompts(ring, cand, prompt_bucket,
                                          start=cached)
        mark = jnp.where(admit, cand, ring.num_slots)
        ring_states = ring_states.at[mark].set(rb.PREFILL_PROCESSING,
                                               mode="drop")
        if use_prefix:
            logits, cache = api.prefill(params, prompts, lens, cache, cand,
                                        admit, cached_lens=cached)
        else:
            logits, cache = api.prefill(params, prompts, lens, cache, cand,
                                        admit)

        # first-token sampling (on-device, per-slot temperature)
        tok = sample_tokens(state.key, logits.astype(jnp.float32),
                            ring.temperature[cand], top_p=serve.top_p,
                            slot_ids=cand, step=state.step)

        out_arena = ring.output_arena.at[mark, 0].set(tok, mode="drop")
        tok_step = ring.token_step.at[mark, 0].set(state.step, mode="drop")
        generated = ring.generated.at[mark].set(1, mode="drop")
        last_token = ring.last_token.at[mark].set(tok, mode="drop")
        prefill_step = ring.prefill_step.at[mark].set(state.step, mode="drop")

        # single-token completions (max_new == 1)
        done = admit & (generated[jnp.clip(cand, 0, ring.num_slots - 1)]
                        >= ring.max_new[cand])
        new_state_code = jnp.where(done, rb.DECODE_COMPLETED,
                                   rb.DECODE_PROCESSING)
        ring_states = ring_states.at[mark].set(new_state_code, mode="drop")

        # free prefill-completed requests' pages right here — they never
        # occupy a decode lane, so the decode branch's free pass would
        # never see them (under prefix_cache release is the frontend's)
        if paged and not use_prefix:
            alloc, block_table = free_done_rows(
                alloc, cache["kv"].block_table, cand, done)
            cache = dict(cache, kv=dataclasses.replace(
                cache["kv"], block_table=block_table))

        # resume paused decode lanes
        ring_states = ring_states.at[safe_lane_slots].set(
            jnp.where(running, rb.DECODE_PROCESSING,
                      ring_states[safe_lane_slots]), mode="drop")

        # merge admitted into lanes (not-done only)
        lane_slot = state.lane_slot.at[jnp.where(admit & ~done, lanes, Bd)
                                       ].set(cand, mode="drop")

        ring = dataclasses.replace(
            ring, slot_state=ring_states, output_arena=out_arena,
            token_step=tok_step, generated=generated, last_token=last_token,
            prefill_step=prefill_step)
        return dataclasses.replace(
            state, ring=ring, cache=cache, alloc=alloc, lane_slot=lane_slot)

    def decode_branch(params, state: EngineState, active):
        """One decode step over ``active`` lanes ([Bd] bool). Phase-exclusive
        passes every occupied lane; the mixed step passes its top-of-step
        snapshot of DECODE_PROCESSING lanes (a slot still PREFILLING holds
        its reserved lane but must not decode)."""
        ring, cache = state.ring, state.cache
        slots = jnp.maximum(state.lane_slot, 0)
        tokens = ring.last_token[slots]

        logits, cache = api.decode(params, tokens, cache, slots, active)
        tok = sample_tokens(state.key, logits.astype(jnp.float32),
                            ring.temperature[slots], top_p=serve.top_p,
                            slot_ids=slots, step=state.step)
        state = dataclasses.replace(state, cache=cache)
        return decode_commit(state, active, logits, tok)

    def decode_commit(state: EngineState, active, logits, tok):
        """Post-dispatch bookkeeping of one decode step: poison guard,
        token emission, completion transitions, page frees, lane release.
        Split out so the unified (single-dispatch) step commits its decode
        rows through EXACTLY the code the split step runs — bitwise parity
        between the two dispatch shapes reduces to the attention math."""
        ring, cache, alloc = state.ring, state.cache, state.alloc
        slots = jnp.maximum(state.lane_slot, 0)
        # poison guard: a lane whose logits are non-finite (bit-rotted KV
        # page, numerically wedged model) must not stream garbage — it is
        # quarantined in FAULTED instead of emitting. Healthy logits leave
        # this a no-op, so bitwise parity with the host mirror holds.
        row_ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
        poisoned = active & ~row_ok
        emit = active & row_ok

        out_idx = ring.generated[slots]                       # [Bd]
        mark = jnp.where(emit, slots, ring.num_slots)
        out_arena = ring.output_arena.at[
            mark, jnp.clip(out_idx, 0, serve.max_new_tokens - 1)
        ].set(tok, mode="drop")
        tok_step = ring.token_step.at[
            mark, jnp.clip(out_idx, 0, serve.max_new_tokens - 1)
        ].set(state.step, mode="drop")
        new_gen = out_idx + 1
        generated = ring.generated.at[mark].set(new_gen, mode="drop")
        last_token = ring.last_token.at[mark].set(tok, mode="drop")

        done = emit & ((tok == serve.eos_token)
                       | (new_gen >= ring.max_new[slots]))
        ring_states = ring.slot_state.at[jnp.where(done, slots, ring.num_slots)
                                         ].set(rb.DECODE_COMPLETED,
                                               mode="drop")
        ring_states = ring_states.at[
            jnp.where(poisoned, slots, ring.num_slots)
        ].set(rb.FAULTED, mode="drop")

        # free KV pages of finished requests (device-side page management).
        # Under prefix_cache release is DEFERRED to the frontend's slot
        # drain: the trie must index freshly prefilled prefix pages (taking
        # its reference) before the slot's references are dropped.
        # Poison-faulted lanes release through the same path — zero leaks.
        if paged and not use_prefix:
            alloc, block_table = free_done_rows(
                alloc, cache["kv"].block_table, slots, done | poisoned)
            cache = dict(cache, kv=dataclasses.replace(
                cache["kv"], block_table=block_table))

        lane_slot = jnp.where(done | poisoned, -1, state.lane_slot)
        ring = dataclasses.replace(
            ring, slot_state=ring_states, output_arena=out_arena,
            token_step=tok_step, generated=generated, last_token=last_token)
        return dataclasses.replace(
            state, ring=ring, cache=cache, alloc=alloc, lane_slot=lane_slot)

    # -- mixed-phase sub-branches (ServeConfig.prefill_chunk_tokens > 0) ----

    def admit_branch(state: EngineState, cand, cand_valid):
        """Admission WITHOUT model compute: reserve a lane, wire pages,
        enter PREFILLING with the chunk cursor at the cached prefix."""
        ring, cache, alloc = state.ring, state.cache, state.alloc
        lanes, admit = assign_lanes(state, cand, cand_valid)
        cache, alloc, admit = wire_pages(ring, cache, alloc, cand, admit)
        mark = jnp.where(admit, cand, ring.num_slots)
        ring = dataclasses.replace(
            ring,
            slot_state=ring.slot_state.at[mark].set(rb.PREFILLING,
                                                    mode="drop"),
            prefill_done_len=ring.prefill_done_len.at[mark].set(
                ring.cached_len[cand] if use_prefix
                else jnp.zeros_like(cand), mode="drop"))
        lane_slot = state.lane_slot.at[jnp.where(admit, lanes, Bd)
                                       ].set(cand, mode="drop")
        return dataclasses.replace(
            state, ring=ring, cache=cache, alloc=alloc, lane_slot=lane_slot)

    def chunk_branch(params, state: EngineState, budget):
        """Advance up to ``max_prefills_per_step`` PREFILLING slots (FCFS)
        by one chunk — all lanes share ONE ``api.prefill_batched``
        dispatch (heterogeneous cursors, ragged lengths, per-lane cached
        prefixes), resuming from the cursor via the cached_lens machinery
        (chunk i's cached prefix = everything already written). ``budget``
        (adaptive mode) clamps this iteration's per-lane chunk length; the
        final chunk samples the first token."""
        ring = state.ring
        pslots, pvalid, cursor, prompts, lens = chunk_select(ring, budget)
        logits, cache = api.prefill_batched(params, prompts, lens,
                                            state.cache, pslots, pvalid,
                                            cursor)
        tok = sample_tokens(state.key, logits.astype(jnp.float32),
                            ring.temperature[pslots], top_p=serve.top_p,
                            slot_ids=pslots, step=state.step)
        state = dataclasses.replace(state, cache=cache)
        return chunk_commit(state, pslots, pvalid, cursor, lens, logits, tok)

    def chunk_select(ring, budget):
        """FCFS pick of this iteration's PREFILLING lanes + their chunk
        windows. Shared by the split chunk branch and the unified
        (single-dispatch) step so both select identical work."""
        keyed = jnp.where(ring.slot_state == rb.PREFILLING, ring.arrival,
                          INT_MAX)
        pslots = jnp.argsort(keyed)[:Mp].astype(jnp.int32)
        pvalid = keyed[pslots] != INT_MAX
        cursor = ring.prefill_done_len[pslots]                  # [Mp]
        prompts, lens = _left_pad_prompts(ring, pslots, chunk_bucket,
                                          start=cursor, limit=budget)
        lens = jnp.where(pvalid, lens, 0)
        return pslots, pvalid, cursor, prompts, lens

    def chunk_commit(state: EngineState, pslots, pvalid, cursor, lens,
                     logits, tok):
        """Post-dispatch bookkeeping of one batched chunk step (cursor
        advance, first-token emission, completions, faults, lane release)
        — the counterpart of ``decode_commit`` for the prefill rows."""
        ring, cache, alloc = state.ring, state.cache, state.alloc
        new_done = cursor + lens
        completing = pvalid & (new_done >= ring.prompt_len[pslots])
        # poison guard (same quarantine as the decode sub-phase): a
        # completing lane whose first-token logits are non-finite faults
        # instead of publishing its first token.
        row_ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
        poisoned = completing & ~row_ok
        completing = completing & row_ok
        adv = jnp.where(pvalid, pslots, ring.num_slots)
        done_len = ring.prefill_done_len.at[adv].set(new_done, mode="drop")

        # first-token bookkeeping for completing slots only — partial
        # chunks emit nothing (the poll plane sees generated == 0)
        mark = jnp.where(completing, pslots, ring.num_slots)
        out_arena = ring.output_arena.at[mark, 0].set(tok, mode="drop")
        tok_step = ring.token_step.at[mark, 0].set(state.step, mode="drop")
        generated = ring.generated.at[mark].set(1, mode="drop")
        last_token = ring.last_token.at[mark].set(tok, mode="drop")
        prefill_step = ring.prefill_step.at[mark].set(state.step, mode="drop")

        # single-token completions (max_new == 1) finish at the final chunk
        done = completing & (ring.max_new[pslots] <= 1)
        new_state_code = jnp.where(done, rb.DECODE_COMPLETED,
                                   rb.DECODE_PROCESSING)
        ring_states = ring.slot_state.at[mark].set(new_state_code,
                                                   mode="drop")
        ring_states = ring_states.at[
            jnp.where(poisoned, pslots, ring.num_slots)
        ].set(rb.FAULTED, mode="drop")
        if paged and not use_prefix:
            alloc, block_table = free_done_rows(
                alloc, cache["kv"].block_table, pslots, done | poisoned)
            cache = dict(cache, kv=dataclasses.replace(
                cache["kv"], block_table=block_table))

        # release the reserved lane of max_new==1 completions and of
        # poison-faulted lanes
        lane_done = jnp.any(
            (state.lane_slot[:, None] == pslots[None, :])
            & (done | poisoned)[None, :], axis=1)
        lane_slot = jnp.where(lane_done, -1, state.lane_slot)

        ring = dataclasses.replace(
            ring, slot_state=ring_states, prefill_done_len=done_len,
            output_arena=out_arena, token_step=tok_step, generated=generated,
            last_token=last_token, prefill_step=prefill_step)
        return dataclasses.replace(
            state, ring=ring, cache=cache, alloc=alloc, lane_slot=lane_slot)

    def unified_branch(params, state: EngineState, budget, decode_active):
        """ONE attention dispatch per iteration (ServeConfig.attn_unified):
        the chunk-prefill rows and the decode lanes ride the SAME
        ``api.prefill_batched`` call — decode lanes become q_len=1 rows
        whose token sits at the bucket's last column with the chunk cursor
        at the lane's current KV length (the cornerstone identity: a
        decode step IS a one-token chunk). Selection and commit reuse the
        split branches' code verbatim, in the split order (chunk rows
        first), so token streams match the two-dispatch path bitwise on
        the gather leg and greedy-token-exactly on the pallas leg."""
        ring = state.ring
        pslots, pvalid, cursor, prompts, lens = chunk_select(ring, budget)

        slots_d = jnp.maximum(state.lane_slot, 0)               # [Bd]
        dtokens = ring.last_token[slots_d]
        dprompts = jnp.zeros((Bd, chunk_bucket), prompts.dtype)
        dprompts = dprompts.at[:, -1].set(dtokens)
        dlens = jnp.where(decode_active, 1, 0).astype(lens.dtype)
        dcursor = state.cache["kv"].seq_lens[slots_d]

        all_prompts = jnp.concatenate([prompts, dprompts], axis=0)
        all_lens = jnp.concatenate([lens, dlens])
        all_slots = jnp.concatenate([pslots, slots_d])
        all_active = jnp.concatenate([pvalid, decode_active])
        all_cursor = jnp.concatenate([cursor, dcursor])
        logits, cache = api.prefill_batched(
            params, all_prompts, all_lens, state.cache, all_slots,
            all_active, all_cursor)
        tok = sample_tokens(state.key, logits.astype(jnp.float32),
                            ring.temperature[all_slots], top_p=serve.top_p,
                            slot_ids=all_slots, step=state.step)
        # per-row sampling keys fold in (slot, step) only, so the combined
        # batch samples exactly what the two split batches would
        state = dataclasses.replace(state, cache=cache)
        state = chunk_commit(state, pslots, pvalid, cursor, lens,
                             logits[:Mp], tok[:Mp])
        return decode_commit(state, decode_active, logits[Mp:], tok[Mp:])

    # -- SLO overload-control sub-branches (mixed-phase only) ---------------

    def expired_mask(state):
        """Slots whose deadline has passed, restricted to the states the
        policy may cancel. "ttft": only slots still waiting for their
        first token (queued or mid-PREFILLING) — once streaming, immune.
        "e2e": additionally mid-decode, restored-awaiting-lane, and
        preempted-awaiting-offload slots (OFFLOADED slots hold no device
        pages; the DPU-plane offload manager cancels those)."""
        ring = state.ring
        st = ring.slot_state
        scope = (st == rb.PREFILL_PENDING) | (st == rb.PREFILLING)
        if policy == "e2e":
            scope = scope | (st == rb.DECODE_PROCESSING) | \
                (st == rb.DECODE_PAUSED) | (st == rb.PREEMPTED)
        return scope & (ring.deadline_step <= state.step)

    def cancel_branch(state: EngineState, expired) -> EngineState:
        """Move expired slots to the CANCELLED terminal state: free their
        decode lanes and (non-prefix configs) their block-table rows
        through the same refcounted release as completion. Queued slots
        have empty rows, so the row free is a no-op for them; under
        prefix_cache release stays frontend-owned (the drain path
        disambiguates shared-prefix refs). Partial output stays readable
        in the arena until the frontend drains the slot."""
        ring = state.ring
        safe = jnp.maximum(state.lane_slot, 0)
        lane_dead = (state.lane_slot >= 0) & expired[safe]
        lane_slot = jnp.where(lane_dead, -1, state.lane_slot)
        alloc, cache = state.alloc, state.cache
        if paged and not use_prefix:
            alloc, bt = free_done_rows(
                alloc, cache["kv"].block_table,
                jnp.arange(ring.num_slots, dtype=jnp.int32), expired)
            cache = dict(cache, kv=dataclasses.replace(
                cache["kv"], block_table=bt))
        ring = dataclasses.replace(
            ring,
            slot_state=jnp.where(expired, rb.CANCELLED, ring.slot_state))
        return dataclasses.replace(state, ring=ring, alloc=alloc,
                                   cache=cache, lane_slot=lane_slot)

    def preempt_branch(state: EngineState, cand, cand_valid) -> EngineState:
        """Decode-lane preemption decision (pure, at most one victim per
        step): if the EDF-head pending candidate cannot admit for lack of
        pages or lanes, mark the worst-slack strictly-lower-class
        DECODE_PROCESSING victim PREEMPTED and free its lane immediately.
        Its KV stays resident until the DPU plane spills it at the next
        window boundary (``core.offload.service_overload``) — so a
        page-blocked candidate admits only after the spill, while a
        lane-blocked one admits this very step. A new victim is never
        chosen while one still awaits offload (no preemption cascade)."""
        ring = state.ring
        have = jnp.any(cand_valid)
        top = cand[jnp.argmax(cand_valid)]       # EDF head (first valid)
        blocked = jnp.sum(state.lane_slot < 0) == 0
        if paged:
            blocked = blocked | \
                (suffix_pages_needed(ring, top) > state.alloc.top)
        elig = (ring.slot_state == rb.DECODE_PROCESSING) & \
            (ring.slo_class > ring.slo_class[top])
        # worst slack, staged lexicographic max: lowest class first, then
        # latest deadline (INT_MAX = infinite slack, preferred victim),
        # then latest arrival (unique ticket -> deterministic)
        e2 = elig & (ring.slo_class == jnp.max(
            jnp.where(elig, ring.slo_class, -1)))
        e3 = e2 & (ring.deadline_step == jnp.max(
            jnp.where(e2, ring.deadline_step, -1)))
        victim = jnp.argmax(jnp.where(e3, ring.arrival, -1)).astype(jnp.int32)
        clear = ~jnp.any(ring.slot_state == rb.PREEMPTED)
        do = have & blocked & jnp.any(elig) & clear
        slot_state = ring.slot_state.at[
            jnp.where(do, victim, ring.num_slots)
        ].set(rb.PREEMPTED, mode="drop")
        lane_slot = jnp.where(do & (state.lane_slot == victim), -1,
                              state.lane_slot)
        return dataclasses.replace(
            state, ring=dataclasses.replace(ring, slot_state=slot_state),
            lane_slot=lane_slot)

    def resume_branch(state: EngineState) -> EngineState:
        """Grant lanes back to restored victims: the offload manager parks
        a restored slot in DECODE_PAUSED (its KV is resident again, its
        cursor says fully prefilled); here up to ``admit_per_step`` of
        them re-enter DECODE_PROCESSING in EDF order, AHEAD of fresh
        admission — a restored victim already paid its prefill, so a lane
        spent on it emits a token next step. Granted slots join the decode
        snapshot from the NEXT step, exactly like a freshly admitted slot
        finishing its last chunk."""
        ring = state.ring
        paused = ring.slot_state == rb.DECODE_PAUSED
        dl = jnp.where(paused, ring.deadline_step, INT_MAX)
        ar = jnp.where(paused, ring.arrival, INT_MAX)
        rcand = jnp.lexsort((ar, dl))[:A].astype(jnp.int32)
        lanes, grant = assign_lanes(state, rcand, paused[rcand])
        slot_state = ring.slot_state.at[
            jnp.where(grant, rcand, ring.num_slots)
        ].set(rb.DECODE_PROCESSING, mode="drop")
        lane_slot = state.lane_slot.at[jnp.where(grant, lanes, Bd)
                                       ].set(rcand, mode="drop")
        return dataclasses.replace(
            state, ring=dataclasses.replace(ring, slot_state=slot_state),
            lane_slot=lane_slot)

    # -- fault plane (watchdog + intake validation) -------------------------

    def watchdog_eligible(ring):
        """States that OWE progress every step: an uncommitted
        PREFILL_PENDING entry (a torn write whose commit flag should land)
        and a DECODE_PROCESSING lane (it decodes every step by
        construction). Everything that can legitimately wait is exempt:
        validated-pending (admission backpressure), PREFILLING (the
        ``max_prefills_per_step`` rotation starves later lanes for
        arbitrarily many steps), DECODE_PAUSED, PREEMPTED, OFFLOADED."""
        st = ring.slot_state
        return ((st == rb.PREFILL_PENDING) & (ring.validated == 0)) \
            | (st == rb.DECODE_PROCESSING)

    def watchdog_branch(state: EngineState) -> EngineState:
        """Quarantine slots whose stall counter (accumulated at the end of
        every step against the top-of-step snapshot) reached
        ``watchdog_steps``: FAULTED, lane freed, block-table row released
        through the same refcounted path as completion (frontend-owned
        under prefix_cache). A pure function of the snapshot counters."""
        ring = state.ring
        wd = watchdog_eligible(ring) & \
            (ring.stall_steps >= serve.watchdog_steps)
        safe = jnp.maximum(state.lane_slot, 0)
        lane_dead = (state.lane_slot >= 0) & wd[safe]
        lane_slot = jnp.where(lane_dead, -1, state.lane_slot)
        alloc, cache = state.alloc, state.cache
        if paged and not use_prefix:
            alloc, bt = free_done_rows(
                alloc, cache["kv"].block_table,
                jnp.arange(ring.num_slots, dtype=jnp.int32), wd)
            cache = dict(cache, kv=dataclasses.replace(
                cache["kv"], block_table=bt))
        ring = dataclasses.replace(
            ring,
            slot_state=jnp.where(wd, rb.FAULTED, ring.slot_state),
            stall_steps=jnp.where(wd, 0, ring.stall_steps))
        return dataclasses.replace(state, ring=ring, alloc=alloc,
                                   cache=cache, lane_slot=lane_slot)

    def intake_branch(state: EngineState) -> EngineState:
        """Ring intake validation (``ring_buffer.validate_intake``) — the
        device side of the integrity protocol, run before any policy looks
        at the pending set."""
        return dataclasses.replace(
            state, ring=rb.validate_intake(
                state.ring, vocab=cfg.vocab_size,
                check_checksum=serve.ring_checksum))

    # -- the per-iteration scheduler functions ------------------------------

    def engine_step_exclusive(params, state: EngineState) -> EngineState:
        if serve.telemetry:
            # event log: boundary transitions (submission) observed before any
            # sub-phase; top-of-step snapshot taken after, like the host
            state = dataclasses.replace(
                state, telemetry=tel_lib.device_prologue(
                    state.telemetry, state.ring, state.step))
        ring_top = state.ring
        lane_top = state.lane_slot
        # intake validation first: admission below only ever sees entries
        # the integrity protocol accepted
        state = intake_branch(state)
        # overlapped ring scan (paper: scan happens while decode executes;
        # here: same fused program, no host involvement either way)
        cand, cand_valid = select_pending_fcfs(state.ring, A)
        cand_valid = gate_candidates(state, cand, cand_valid)
        do_prefill = jnp.any(cand_valid)
        any_active = jnp.any(state.lane_slot >= 0)

        def decode_or_idle(s):
            # idle scheduler iterations (no batch, nothing pending) cost only
            # the slot scan — like the persistent kernel spinning on the ring
            return jax.lax.cond(
                any_active,
                lambda st: decode_branch(params, st, st.lane_slot >= 0),
                lambda st: st,
                s)

        state = jax.lax.cond(
            do_prefill,
            lambda s: prefill_branch(params, s, cand, cand_valid),
            decode_or_idle,
            state)
        # a prefill step pauses every lane; otherwise all top-of-step
        # lanes decode (the decode_or_idle predicate)
        lanes = jnp.where(
            do_prefill, 0, jnp.sum((lane_top >= 0).astype(jnp.int32)))
        experts, cache = cache_lib.drain_expert_counts(state.cache)
        state = dataclasses.replace(
            state, cache=cache, telemetry=tel_lib.device_epilogue(
                state.telemetry, ring_top, state.ring, state.step,
                mixed=False, wd_fired=jnp.asarray(0, jnp.int32),
                decode_lanes=lanes,
                chunk_dispatch=do_prefill.astype(jnp.int32),
                free_pages=state.alloc.top, experts=experts))
        state = dataclasses.replace(
            state,
            step=state.step + 1,
            key=state.key,  # key reuse is safe: folded with (slot, step)
        )
        return _constrain_state(api, state)

    def engine_step_mixed(params, state: EngineState) -> EngineState:
        if serve.telemetry:
            # event log: boundary transitions (submission, offload,
            # restore, drop) observed before any sub-phase touches the ring
            state = dataclasses.replace(
                state, telemetry=tel_lib.device_prologue(
                    state.telemetry, state.ring, state.step))
        # top-of-step snapshot for the watchdog's progress accounting
        ring_top = state.ring

        # 0w. watchdog: slots whose stall counter reached the threshold
        # leave the scheduler before anything else looks at them.
        # Compiled out entirely when the watchdog is off.
        if serve.watchdog_steps > 0:
            wd_any = jnp.any(
                watchdog_eligible(state.ring)
                & (state.ring.stall_steps >= serve.watchdog_steps))
            state = jax.lax.cond(wd_any, watchdog_branch,
                                 lambda s: s, state)
        # faults so far are the watchdog's alone (intake runs next)
        wd_fired = jnp.sum(((state.ring.slot_state == rb.FAULTED)
                            & (ring_top.slot_state != rb.FAULTED))
                           .astype(jnp.int32))

        # 0v. intake validation: admission below only ever sees entries
        # the integrity protocol accepted
        state = intake_branch(state)

        # 0a. deadline cancellation: expired slots leave the scheduler
        # before anything else looks at them (they neither decode nor
        # chunk this step). Compiled out entirely when the policy is off.
        if slo_on:
            expired = expired_mask(state)
            state = jax.lax.cond(
                jnp.any(expired),
                lambda s: cancel_branch(s, expired),
                lambda s: s,
                state)

        # candidate selection — EDF when the SLO machinery is on (pending
        # set is untouched by preemption/resume, so one selection serves
        # the preemption decision AND admission)
        cand, cand_valid = select_pending(state.ring, A)

        # 0b. preemption decision over the same snapshot (frees the
        # victim's lane before it is snapshotted below)
        if preempt_on:
            state = preempt_branch(state, cand, cand_valid)

        # decode-lane snapshot: lanes generating at the top of the step
        # (post cancel/preempt — a cancelled or preempted slot must not
        # emit) decode this step no matter what admission/chunking does —
        # the no-lane-ever-skips-a-step guarantee the differential harness
        # asserts (a slot completing its prefill this step starts decoding
        # next step, exactly like the phase-exclusive policy).
        slots0 = jnp.maximum(state.lane_slot, 0)
        decode_active = (state.lane_slot >= 0) & \
            (state.ring.slot_state[slots0] == rb.DECODE_PROCESSING)

        # 0c. restored victims re-acquire lanes ahead of fresh admission
        if preempt_on:
            state = jax.lax.cond(
                jnp.any(state.ring.slot_state == rb.DECODE_PAUSED),
                resume_branch,
                lambda s: s,
                state)

        # 1. admit (no model compute — PREFILLING + cursor at cached_len)
        cand_valid = gate_candidates(state, cand, cand_valid)
        state = jax.lax.cond(
            jnp.any(cand_valid),
            lambda s: admit_branch(s, cand, cand_valid),
            lambda s: s,
            state)

        # 2. chunk: freshly admitted slots run their first chunk this very
        # step (TTFT parity with phase-exclusive for single-chunk prompts).
        # Adaptive mode sizes the per-lane budget off the SAME decode-lane
        # snapshot the decode sub-phase uses — a pure function of ring
        # state, so the host mirror lands on the identical budget.
        budget = None
        if adaptive:
            n_busy = jnp.sum(decode_active.astype(jnp.int32))
            budget = adaptive_chunk_budget(n_busy, Bd,
                                           serve.prefill_block_q, Cmax)
        do_chunk = jnp.any(state.ring.slot_state == rb.PREFILLING)
        if serve.attn_unified:
            # 2+3 unified: chunk rows and decode lanes share ONE attention
            # dispatch (the whole point of attn_unified — the traced step
            # contains exactly one attention pallas_call; jaxpr-asserted
            # in tier-1)
            state = jax.lax.cond(
                do_chunk | jnp.any(decode_active),
                lambda s: unified_branch(params, s, budget, decode_active),
                lambda s: s,
                state)
        else:
            state = jax.lax.cond(
                do_chunk,
                lambda s: chunk_branch(params, s, budget),
                lambda s: s,
                state)

            # 3. decode all snapshot lanes
            state = jax.lax.cond(
                jnp.any(decode_active),
                lambda s: decode_branch(params, s, decode_active),
                lambda s: s,
                state)

        # 4. watchdog progress accounting against the top-of-step
        # snapshot: a lifecycle transition, chunk-cursor advance, token
        # emission or validation verdict all count as progress; eligible
        # slots that showed none age their stall counter by one.
        if serve.watchdog_steps > 0:
            r1 = state.ring
            moved = (r1.slot_state != ring_top.slot_state) \
                | (r1.prefill_done_len != ring_top.prefill_done_len) \
                | (r1.generated != ring_top.generated) \
                | (r1.validated != ring_top.validated)
            stall = jnp.where(watchdog_eligible(r1) & ~moved,
                              ring_top.stall_steps + 1, 0)
            state = dataclasses.replace(
                state, ring=dataclasses.replace(
                    r1, stall_steps=stall.astype(jnp.int32)))
        # 5. telemetry epilogue: counter row (+ in-step events when the
        # log is on), all derived from the same top/end-of-step diff the
        # watchdog's progress accounting uses (no branch internals touched)
        experts, cache = cache_lib.drain_expert_counts(state.cache)
        state = dataclasses.replace(
            state, cache=cache, telemetry=tel_lib.device_epilogue(
                state.telemetry, ring_top, state.ring, state.step,
                mixed=True, wd_fired=wd_fired,
                decode_lanes=jnp.sum(decode_active.astype(jnp.int32)),
                chunk_dispatch=do_chunk.astype(jnp.int32),
                free_pages=state.alloc.top, experts=experts))
        state = dataclasses.replace(
            state,
            step=state.step + 1,
            key=state.key,  # key reuse is safe: folded with (slot, step)
        )
        return _constrain_state(api, state)

    return engine_step_mixed if mixed else engine_step_exclusive


# ---------------------------------------------------------------------------
# The window program (fire-and-forget window + tail-launch recovery)
# ---------------------------------------------------------------------------


def make_serve_window(api: ModelApi, serve: ServeConfig, *,
                      donate: bool = True, prompt_bucket: Optional[int] = None):
    """Returns jitted ``window_fn(params, state) -> state`` running
    ``serve.window`` scheduler iterations per invocation.

    The host re-invocation IS the tail launch: all engine state lives in
    donated device buffers and survives re-instantiation (paper §4.2
    "window-based tail-launch recovery"); steady-state host work is one
    dispatch per ``serve.window`` tokens instead of per token.
    """
    engine_step = make_engine_step(api, serve, prompt_bucket)

    def window_fn(params, state: EngineState) -> EngineState:
        if tel_lib.stacks_rows(serve):
            # the loop stacks each step's counter row (telemetry/state.py)
            def body(st, _):
                st = engine_step(params, st)
                return st, st.telemetry.row

            state, rows = jax.lax.scan(body, state, None,
                                       length=serve.window)
            return dataclasses.replace(
                state, windows_done=state.windows_done + 1,
                telemetry=dataclasses.replace(state.telemetry, rows=rows))

        def body(_, st):
            return engine_step(params, st)

        state = jax.lax.fori_loop(0, serve.window, body, state)
        return dataclasses.replace(state,
                                   windows_done=state.windows_done + 1)

    if donate:
        return jax.jit(window_fn, donate_argnums=(1,))
    return jax.jit(window_fn)


# ---------------------------------------------------------------------------
# Window executable cache (the paper's CUDA graph cache, §4.2)
# ---------------------------------------------------------------------------


class WindowCache:
    """Pre-compiled window executables keyed by prefill shape bucket.

    Paper §4.2: "the host captures inference computation as CUDA graphs for
    a dense grid of (batch size, sequence length) pairs ... At runtime, the
    scheduler selects the tightest-fitting prefill graph via a precomputed
    lookup table ... a maximum-shape fallback graph handles any combination
    not in the cache."

    TPU adaptation: one jitted window program per prompt-length bucket (the
    decode batch is fixed by the lane table, so the grid is 1-D here);
    selection happens at the window boundary — the same granularity as every
    other host interaction in this design, preserving the CPU-free
    steady state. All buckets share one EngineState (identical shapes), so
    donated state flows freely between executables — the paper's shared
    device buffers ("all graphs reuse a single set of device buffers").
    """

    def __init__(self, api: ModelApi, serve: ServeConfig,
                 buckets: Optional[tuple] = None):
        self.serve = serve
        bs = sorted(set(list(buckets or ()) + [serve.max_prompt_len]))
        assert all(1 <= b <= serve.max_prompt_len for b in bs)
        if serve.prefill_chunk_tokens > 0:
            # mixed-phase scheduling prefills at the FIXED chunk shape —
            # prompt-length buckets would compile identical programs, so
            # the cache degenerates to the single fallback executable
            bs = [serve.max_prompt_len]
        self.buckets = bs
        self._fns = {b: make_serve_window(api, serve, prompt_bucket=b)
                     for b in bs}
        self.selections = {b: 0 for b in bs}

    def select(self, max_pending_len: int):
        """Tightest-fitting executable (max-shape fallback included)."""
        for b in self.buckets:
            if max_pending_len <= b:
                self.selections[b] += 1
                return self._fns[b]
        self.selections[self.buckets[-1]] += 1
        return self._fns[self.buckets[-1]]

    def max_pending_len(self, ring: rb.RingState) -> int:
        """Longest pending prefill SUFFIX (prompt minus its cached prefix) —
        with prefix reuse a long shared-prompt request still fits the small
        bucket, which is where the TTFT win materialises."""
        states = np.asarray(ring.slot_state)
        lens = np.asarray(ring.prompt_len) - np.asarray(ring.cached_len)
        pend = lens[states == rb.PREFILL_PENDING]
        return int(pend.max()) if pend.size else 0
