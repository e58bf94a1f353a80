"""Property-based tests (hypothesis) on system invariants: page allocator
hygiene, ring lifecycle protocol, tokenizer roundtrip, FCFS selection
equivalence (engine jnp path == Pallas ring-scan kernel), sampling."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.configs.base import ServeConfig
from repro.core import ring_buffer as rb
from repro.core.engine import select_pending_fcfs
from repro.core.sampling import sample_tokens, top_p_filter
from repro.frontend.tokenizer import BPETokenizer, NaiveBPETokenizer
from repro.kernels import ops
from repro.models import cache as cache_lib

HSET = settings(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# Page allocator: never double-allocates, never leaks
# ---------------------------------------------------------------------------


@HSET
@given(st.lists(st.tuples(st.booleans(), st.integers(1, 6)),
                min_size=1, max_size=30))
def test_allocator_no_double_alloc_no_leak(script):
    P, MAXN = 24, 6
    alloc = cache_lib.make_page_allocator(P)
    held = []          # list of np arrays of held pages
    for is_alloc, n in script:
        if is_alloc:
            pages, alloc2, ok = cache_lib.alloc_pages(
                alloc, jnp.asarray(n), MAXN)
            if bool(ok):
                alloc = alloc2
                got = np.asarray(pages)
                got = got[got >= 0]
                assert len(got) == n
                held.append(got)
        elif held:
            pages = held.pop(0)
            row = np.full(MAXN, -1, np.int32)
            row[: len(pages)] = pages
            alloc = cache_lib.free_pages(alloc, jnp.asarray(row))
        # invariant: free + held partition the pool, no duplicates
        free_now = np.asarray(alloc.free_stack)[: int(alloc.top)]
        held_now = np.concatenate(held) if held else np.array([], np.int64)
        combined = np.concatenate([free_now, held_now])
        assert len(combined) == P
        assert len(np.unique(combined)) == P


@HSET
@given(st.integers(0, 24))
def test_allocator_all_or_nothing(n):
    alloc = cache_lib.make_page_allocator(8)
    pages, alloc2, ok = cache_lib.alloc_pages(alloc, jnp.asarray(n), 24)
    if n <= 8:
        assert bool(ok)
        assert int(alloc2.top) == 8 - n
    else:
        assert not bool(ok)
        assert int(alloc2.top) == 8          # unchanged: backpressure


@HSET
@given(st.lists(st.tuples(st.integers(0, 2), st.integers(1, 5)),
                min_size=1, max_size=40))
def test_refcount_conservation_alloc_share_free(script):
    """Refcounted sharing never leaks or double-frees: across random
    alloc / share (co-own) / free (release) scripts, (i) free pages +
    pages with refcount > 0 partition the pool, (ii) every page's refcount
    equals the number of owners the model says it has, (iii) a page returns
    to the free stack exactly when its last owner releases it."""
    P, MAXN = 16, 5
    alloc = cache_lib.make_page_allocator(P)
    owners = {}                 # page -> model refcount
    held = []                   # allocations that still hold their pages
    for op, n in script:
        if op == 0:             # alloc n pages (one owner each)
            pages, alloc2, ok = cache_lib.alloc_pages(
                alloc, jnp.asarray(n), MAXN)
            if bool(ok):
                alloc = alloc2
                got = [int(p) for p in np.asarray(pages) if p >= 0]
                assert len(got) == n
                for p in got:
                    assert owners.get(p, 0) == 0, "double-allocated page"
                    owners[p] = 1
                held.append(got)
        elif op == 1 and held:  # share: a second owner joins the oldest row
            row = held[0]
            alloc = cache_lib.share_pages(
                alloc, jnp.asarray(row, jnp.int32))
            for p in row:
                owners[p] += 1
            held.append(list(row))
        elif op == 2 and held:  # free: one owner releases its row
            row = held.pop(0)
            alloc = cache_lib.free_pages(alloc, jnp.asarray(row, jnp.int32))
            for p in row:
                owners[p] -= 1
        rc = np.asarray(alloc.refcount)
        expect = np.zeros(P, np.int64)
        for p, c in owners.items():
            expect[p] = c
        np.testing.assert_array_equal(rc, expect)
        free_now = np.asarray(alloc.free_stack)[: int(alloc.top)]
        assert len(np.unique(free_now)) == len(free_now)
        assert set(free_now.tolist()) == {p for p in range(P)
                                          if expect[p] == 0}


# ---------------------------------------------------------------------------
# FCFS selection: engine jnp formulation == Pallas ring-scan kernel
# ---------------------------------------------------------------------------


@HSET
@given(st.integers(0, 2**31 - 2), st.integers(1, 4))
def test_fcfs_engine_equals_kernel(seed, k):
    rng = np.random.default_rng(seed)
    S = 64
    serve = ServeConfig(num_slots=S)
    ring = rb.make_ring(serve)
    states = rng.integers(0, 4, S).astype(np.int32)
    arrivals = rng.permutation(S).astype(np.int32)
    # admission only looks at validated entries (ring integrity protocol);
    # the Pallas ring-scan kernel is handed the already-validated view, so
    # the equivalence is over rings where every entry passed validation
    ring = dataclasses.replace(ring, slot_state=jnp.asarray(states),
                               arrival=jnp.asarray(arrivals),
                               validated=jnp.ones(S, jnp.int32))
    cand, valid = select_pending_fcfs(ring, k)
    ids_k, found_k = ops.ring_select_topk(
        jnp.asarray(states), jnp.asarray(arrivals),
        want_state=rb.PREFILL_PENDING, k=k, block_size=16)
    cand = np.asarray(cand)
    valid = np.asarray(valid)
    np.testing.assert_array_equal(np.where(valid, cand, -1),
                                  np.asarray(ids_k))
    np.testing.assert_array_equal(valid, np.asarray(found_k))


# ---------------------------------------------------------------------------
# Ring lifecycle protocol
# ---------------------------------------------------------------------------

# legal lifecycle edges (paper §4.2) as observable at WINDOW boundaries: a
# window may advance a slot several states at once, so the observable
# relation is the transitive closure of the per-step machine (plus self
# loops; EMPTY is only re-entered through the frontend's release).
# PREFILLING is the mixed-phase chunk-cursor state: entered from
# PREFILL_PENDING at admission, held across steps while chunks advance,
# left for DECODE_PROCESSING (or straight to DECODE_COMPLETED on a
# max_new==1 early finish at the final chunk); it never pauses.
_LIFECYCLE_CLOSURE = {
    rb.EMPTY: {rb.EMPTY},
    # FAULTED joins from every state the integrity protocol scopes:
    # PREFILL_PENDING (validation failure / watchdog on a torn entry),
    # PREFILLING and DECODE_PROCESSING (poison guard, stall watchdog)
    rb.PREFILL_PENDING: {rb.PREFILL_PENDING, rb.PREFILL_PROCESSING,
                         rb.PREFILLING, rb.DECODE_PROCESSING,
                         rb.DECODE_PAUSED, rb.DECODE_COMPLETED,
                         rb.FAULTED},
    rb.PREFILL_PROCESSING: {rb.PREFILL_PROCESSING, rb.DECODE_PROCESSING,
                            rb.DECODE_PAUSED, rb.DECODE_COMPLETED},
    rb.PREFILLING: {rb.PREFILLING, rb.DECODE_PROCESSING,
                    rb.DECODE_COMPLETED, rb.FAULTED},
    rb.DECODE_PROCESSING: {rb.DECODE_PROCESSING, rb.DECODE_PAUSED,
                           rb.DECODE_COMPLETED, rb.FAULTED},
    rb.DECODE_PAUSED: {rb.DECODE_PROCESSING, rb.DECODE_PAUSED,
                       rb.DECODE_COMPLETED},
    rb.DECODE_COMPLETED: {rb.DECODE_COMPLETED},
    rb.FAULTED: {rb.FAULTED},
}


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**31 - 2))
def test_ring_lifecycle_under_admission_backpressure(seed, tiny_apis):
    """Random shared-prefix-free workloads against a page pool too small
    for the whole batch: every observed slot transition stays inside the
    lifecycle state machine, the allocator conserves pages at every window
    boundary, and everything eventually completes (backpressure never
    wedges admission)."""
    from repro.core import engine as eng
    api, params = tiny_apis("qwen2-1.5b")
    rng = np.random.default_rng(seed)
    serve = ServeConfig(num_slots=8, max_prompt_len=16, max_new_tokens=8,
                        decode_batch=4, window=4, admit_per_step=4,
                        page_size=4, num_pages=14, eos_token=-1)
    n_req = int(rng.integers(3, 7))
    state = eng.init_engine_state(api, serve)
    ring = state.ring
    for i in range(n_req):
        toks = rng.integers(3, api.cfg.vocab_size,
                            int(rng.integers(2, 15))).tolist()
        ring = rb.submit_request(ring, i, tokens=toks, request_id=i,
                                 max_new=int(rng.integers(1, 8)), arrival=i,
                                 step=0)
    state = dataclasses.replace(state, ring=ring)
    fn = eng.make_serve_window(api, serve)
    prev = np.asarray(state.ring.slot_state)
    saw_backpressure = False
    for _ in range(40):
        state = fn(params, state)
        cur = np.asarray(state.ring.slot_state)
        for s in range(serve.num_slots):
            assert cur[s] in _LIFECYCLE_CLOSURE[prev[s]], \
                f"illegal transition {rb.STATE_NAMES[prev[s]]} -> " \
                f"{rb.STATE_NAMES[cur[s]]} (slot {s})"
        saw_backpressure |= bool((cur[:n_req] == rb.PREFILL_PENDING).any())
        # page conservation at every window boundary: free + referenced
        # partition the pool (all refcounts 1: no sharing in this workload)
        rc = np.asarray(state.alloc.refcount)
        assert int(state.alloc.top) + int((rc > 0).sum()) == serve.num_pages
        free_now = np.asarray(state.alloc.free_stack)[:int(state.alloc.top)]
        assert len(np.unique(free_now)) == len(free_now)
        prev = cur
        if (cur[:n_req] == rb.DECODE_COMPLETED).all():
            break
    assert (prev[:n_req] == rb.DECODE_COMPLETED).all(), \
        "backpressure wedged admission"
    assert int(state.alloc.top) == serve.num_pages


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**31 - 2))
def test_ring_lifecycle_mixed_phase_chunk_cursor(seed, tiny_apis):
    """Mixed-phase scheduler under page backpressure: every observed slot
    transition stays inside the extended (PREFILLING) state machine, the
    chunk cursor ``prefill_done_len`` is monotone non-decreasing and
    bounded by prompt_len, admission never overshoots lane capacity
    mid-chunk (PREFILLING + DECODE_PROCESSING slots <= decode_batch), the
    allocator conserves pages at every window boundary — including
    max_new==1 requests that finish DURING a partial prefill's final chunk
    and must free their suffix pages — and everything completes."""
    from repro.core import engine as eng

    api, params = tiny_apis("qwen2-1.5b")
    rng = np.random.default_rng(seed)
    serve = ServeConfig(num_slots=8, max_prompt_len=16, max_new_tokens=8,
                        decode_batch=4, window=2, admit_per_step=4,
                        page_size=4, num_pages=14, eos_token=-1,
                        prefill_chunk_tokens=4, max_prefills_per_step=2)
    fn = _mixed_window_fn(tiny_apis, serve)
    n_req = int(rng.integers(3, 7))
    state = eng.init_engine_state(api, serve)
    ring = state.ring
    for i in range(n_req):
        toks = rng.integers(3, api.cfg.vocab_size,
                            int(rng.integers(2, 16))).tolist()
        # max_new==1 long prompts: early finish at the final chunk
        ring = rb.submit_request(ring, i, tokens=toks, request_id=i,
                                 max_new=int(rng.integers(1, 8)), arrival=i,
                                 step=0)
    state = dataclasses.replace(state, ring=ring)
    prev = np.asarray(state.ring.slot_state)
    prev_done = np.asarray(state.ring.prefill_done_len)
    for _ in range(80):
        state = fn(params, state)
        cur = np.asarray(state.ring.slot_state)
        cur_done = np.asarray(state.ring.prefill_done_len)
        plen = np.asarray(state.ring.prompt_len)
        for s in range(serve.num_slots):
            assert cur[s] in _LIFECYCLE_CLOSURE[prev[s]], \
                f"illegal transition {rb.STATE_NAMES[prev[s]]} -> " \
                f"{rb.STATE_NAMES[cur[s]]} (slot {s})"
        # chunk cursor: monotone, bounded; == prompt_len once generating
        assert (cur_done >= prev_done).all()
        assert (cur_done <= plen).all()
        gen_states = (cur == rb.DECODE_PROCESSING) | \
                     (cur == rb.DECODE_COMPLETED)
        assert (cur_done[gen_states & (plen > 0)]
                == plen[gen_states & (plen > 0)]).all()
        # lane capacity is never overshot mid-chunk
        in_lanes = ((cur == rb.PREFILLING) | (cur == rb.DECODE_PROCESSING))
        assert int(in_lanes.sum()) <= serve.decode_batch
        # page conservation at every window boundary
        rc = np.asarray(state.alloc.refcount)
        assert int(state.alloc.top) + int((rc > 0).sum()) == serve.num_pages
        free_now = np.asarray(state.alloc.free_stack)[:int(state.alloc.top)]
        assert len(np.unique(free_now)) == len(free_now)
        prev, prev_done = cur, cur_done
        if (cur[:n_req] == rb.DECODE_COMPLETED).all():
            break
    assert (prev[:n_req] == rb.DECODE_COMPLETED).all(), \
        "mixed-phase scheduling wedged"
    # drain (engine-side fallback): the pool must come back whole
    state = eng.drain_completed(state)
    assert int(state.alloc.top) == serve.num_pages


_MIXED_FN_CACHE = {}


def _mixed_window_fn(tiny_apis, serve):
    """One compiled window per config, shared across hypothesis examples."""
    if serve not in _MIXED_FN_CACHE:
        from repro.core import engine as eng
        api, _ = tiny_apis("qwen2-1.5b")
        _MIXED_FN_CACHE[serve] = eng.make_serve_window(api, serve)
    return _MIXED_FN_CACHE[serve]


# extended machine under SLO overload control, as observed at (window +
# overload-service) boundaries: CANCELLED is reachable from every
# non-terminal admission/decode state (deadline expiry — including
# mid-chunk PREFILLING), DECODE_PROCESSING can be preempted (and spilled
# to OFFLOADED within the same boundary), OFFLOADED either restores to
# DECODE_PAUSED or is dropped to CANCELLED. PREEMPTED is transient: the
# overload service spills it at the very next boundary.
_SLO_CLOSURE = {
    **_LIFECYCLE_CLOSURE,
    rb.PREFILL_PENDING:
        _LIFECYCLE_CLOSURE[rb.PREFILL_PENDING] | {rb.CANCELLED},
    rb.PREFILLING: _LIFECYCLE_CLOSURE[rb.PREFILLING] | {rb.CANCELLED},
    rb.DECODE_PROCESSING: _LIFECYCLE_CLOSURE[rb.DECODE_PROCESSING]
        | {rb.CANCELLED, rb.PREEMPTED, rb.OFFLOADED},
    rb.DECODE_PAUSED: _LIFECYCLE_CLOSURE[rb.DECODE_PAUSED] | {rb.CANCELLED},
    rb.PREEMPTED: {rb.PREEMPTED, rb.OFFLOADED, rb.CANCELLED},
    rb.OFFLOADED: {rb.OFFLOADED, rb.DECODE_PAUSED, rb.CANCELLED},
    rb.CANCELLED: {rb.CANCELLED},
    rb.FAULTED: {rb.FAULTED},
}

# states a deadline fault may legally be injected into (anything the
# cancellation machinery is supposed to reach)
_INJECTABLE = (rb.PREFILL_PENDING, rb.PREFILLING, rb.DECODE_PROCESSING,
               rb.DECODE_PAUSED, rb.OFFLOADED)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**31 - 2))
def test_fault_injection_slo_overload(seed, tiny_apis):
    """Random preempt/cancel/timeout scripts against the SLO-enabled
    mixed-phase engine + between-window overload service: random SLO
    traces run under scarce lanes/pages, and on top of the organic policy
    traffic the script INJECTS deadline faults (stamping ``deadline_step``
    to 'now') into arbitrary live slots — including mid-chunk PREFILLING
    and already-spilled OFFLOADED ones. At every boundary: (i) every slot
    transition stays inside the extended lifecycle machine, (ii) pages are
    conserved with the offload buffer in play (free + refcounted partition
    the pool; spilled pages were RELEASED, the buffer holds byte copies),
    (iii) buffer entries are in bijection with OFFLOADED slots, (iv) lanes
    never leak: they only point at live PREFILLING/DECODE_PROCESSING
    slots, no slot holds two lanes, occupancy never exceeds decode_batch.
    Everything must still drain — faults never wedge the scheduler."""
    from repro.core import engine as eng
    from repro.core import offload as offload_lib

    api, params = tiny_apis("qwen2-1.5b")
    rng = np.random.default_rng(seed)
    serve = ServeConfig(num_slots=8, max_prompt_len=16, max_new_tokens=8,
                        decode_batch=2, window=1, admit_per_step=2,
                        page_size=4, num_pages=14, eos_token=-1,
                        prefill_chunk_tokens=4, slo_classes=2,
                        slo_preempt=True, deadline_policy="e2e",
                        slo_ttft_steps=(8, 40), slo_tpot_steps=(3, 10))
    fn = _mixed_window_fn(tiny_apis, serve)
    buf = offload_lib.KVOffloadBuffer()
    state = eng.init_engine_state(api, serve)
    n_req = int(rng.integers(4, 8))
    reqs = [(int(rng.integers(0, 11)),                 # arrival step
             rng.integers(3, api.cfg.vocab_size,
                          int(rng.integers(2, 16))).tolist(),
             int(rng.integers(1, 8)),                  # max_new
             int(rng.integers(0, 2)))                  # slo class
            for _ in range(n_req)]
    submitted = set()
    prev = np.asarray(state.ring.slot_state)
    for it in range(150):
        step = int(state.step)
        ring = state.ring
        states_np = np.asarray(ring.slot_state)
        for i, (arr, toks, max_new, slo) in enumerate(reqs):
            if arr > step or i in submitted:
                continue
            empties = np.where(states_np == rb.EMPTY)[0]
            if not len(empties):
                continue
            rel = serve.deadline_steps(slo, max_new)
            ring = rb.submit_request(ring, int(empties[0]), tokens=toks,
                                     request_id=i, max_new=max_new,
                                     arrival=i, step=step, slo_class=slo,
                                     deadline=step + rel)
            states_np = np.asarray(ring.slot_state)
            submitted.add(i)
        # fault injection: expire a random live slot RIGHT NOW
        if rng.random() < 0.3:
            live = np.where(np.isin(states_np, _INJECTABLE))[0]
            if len(live):
                victim = int(rng.choice(live))
                ring = dataclasses.replace(
                    ring,
                    deadline_step=ring.deadline_step.at[victim].set(step))
        prev = np.asarray(ring.slot_state)
        state = dataclasses.replace(state, ring=ring)
        state = fn(params, state)
        state, _events = offload_lib.service_overload(state, buf, serve)
        cur = np.asarray(state.ring.slot_state)
        for s in range(serve.num_slots):
            assert cur[s] in _SLO_CLOSURE[prev[s]], \
                f"illegal transition {rb.STATE_NAMES[prev[s]]} -> " \
                f"{rb.STATE_NAMES[cur[s]]} (slot {s})"
        # page conservation with the offload buffer in play
        rc = np.asarray(state.alloc.refcount)
        assert int(state.alloc.top) + int((rc > 0).sum()) == serve.num_pages
        free_now = np.asarray(state.alloc.free_stack)[:int(state.alloc.top)]
        assert len(np.unique(free_now)) == len(free_now)
        # buffer <-> OFFLOADED bijection
        assert set(buf.entries) == set(
            np.flatnonzero(cur == rb.OFFLOADED).tolist())
        # lane hygiene
        lanes = np.asarray(state.lane_slot)
        held = lanes[lanes >= 0]
        assert len(held) <= serve.decode_batch
        assert len(np.unique(held)) == len(held), "slot holds two lanes"
        assert all(cur[s] in (rb.PREFILLING, rb.DECODE_PROCESSING)
                   for s in held), "lane points at a non-running slot"
        nonterminal = _INJECTABLE + (rb.PREEMPTED, rb.PREFILL_PROCESSING)
        if len(submitted) == n_req and not buf.entries \
                and not np.isin(cur, nonterminal).any():
            break
    else:
        raise AssertionError("fault script wedged the scheduler")
    state = eng.drain_completed(state)
    assert int(state.alloc.top) == serve.num_pages
    assert not buf.entries and buf.restores + buf.drops == buf.offloads


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**31 - 2))
def test_fault_injection_page_conservation(seed, tiny_apis):
    """Scripted ingress faults (``recovery.FaultInjector``: torn writes,
    duplicate/stale sequences, corrupted checksums, post-submit bit-flips,
    malformed payloads) against the mixed-phase engine with the stall
    watchdog armed: every quarantine lands in FAULTED through a legal
    lifecycle edge, pages are conserved at every window boundary, lanes
    never leak, and the trace drains — every fault-free request completes,
    every FAULTED slot releases its pages through the refcounted drain."""
    from repro.core import engine as eng
    from repro.core import recovery as rec

    api, params = tiny_apis("qwen2-1.5b")
    rng = np.random.default_rng(seed)
    serve = ServeConfig(num_slots=8, max_prompt_len=16, max_new_tokens=8,
                        decode_batch=2, window=1, admit_per_step=2,
                        page_size=4, num_pages=14, eos_token=-1,
                        prefill_chunk_tokens=4, watchdog_steps=4)
    fn = _mixed_window_fn(tiny_apis, serve)
    state = eng.init_engine_state(api, serve)
    inj = rec.FaultInjector(seed=seed, vocab=api.cfg.vocab_size)
    n_req = int(rng.integers(3, 7))
    reqs = [(int(rng.integers(0, 8)),                  # arrival step
             rng.integers(3, api.cfg.vocab_size,
                          int(rng.integers(2, 16))).tolist(),
             int(rng.integers(1, 8)))                  # max_new
            for _ in range(n_req)]
    plan = inj.plan(n_req)
    submitted = {}
    issued = []
    prev = np.asarray(state.ring.slot_state)
    for it in range(150):
        step = int(state.step)
        ring = state.ring
        states_np = np.asarray(ring.slot_state)
        for i, (arr, toks, max_new) in enumerate(reqs):
            if arr > step or i in submitted:
                continue
            empties = np.where(states_np == rb.EMPTY)[0]
            if not len(empties):
                continue
            fault = inj.resolve(i, plan[i], tokens=toks, max_new=max_new,
                                temperature=0.0, issued_seqs=issued)
            slot = int(empties[0])
            ring = rec.faulty_submit_device(ring, slot, fault,
                                            request_id=i, arrival=i,
                                            step=step)
            issued.append(int(ring.seq[slot]))
            states_np = np.asarray(ring.slot_state)
            submitted[i] = slot
        prev = np.asarray(ring.slot_state)
        state = dataclasses.replace(state, ring=ring)
        state = fn(params, state)
        cur = np.asarray(state.ring.slot_state)
        for s in range(serve.num_slots):
            assert cur[s] in _LIFECYCLE_CLOSURE[prev[s]], \
                f"illegal transition {rb.STATE_NAMES[prev[s]]} -> " \
                f"{rb.STATE_NAMES[cur[s]]} (slot {s})"
        # page conservation at every boundary, faults in flight or not
        rc = np.asarray(state.alloc.refcount)
        assert int(state.alloc.top) + int((rc > 0).sum()) == serve.num_pages
        free_now = np.asarray(state.alloc.free_stack)[:int(state.alloc.top)]
        assert len(np.unique(free_now)) == len(free_now)
        # lane hygiene: a quarantined slot frees its lane the same step
        lanes = np.asarray(state.lane_slot)
        held = lanes[lanes >= 0]
        assert len(np.unique(held)) == len(held)
        assert all(cur[s] in (rb.PREFILLING, rb.DECODE_PROCESSING)
                   for s in held), "lane points at a non-running slot"
        if len(submitted) == n_req and all(
                cur[s] in (rb.DECODE_COMPLETED, rb.FAULTED)
                for s in submitted.values()):
            break
    else:
        raise AssertionError("fault script wedged the scheduler")
    # clean requests complete; scripted faults quarantine (a dup/stale
    # script with nothing issued yet still faults: seq -1 is stale)
    for i, s in submitted.items():
        if plan[i] is None:
            assert cur[s] == rb.DECODE_COMPLETED, \
                f"clean request {i} did not complete: " \
                f"{rb.STATE_NAMES[cur[s]]}"
        else:
            assert cur[s] == rb.FAULTED, \
                f"{plan[i]} request {i} not quarantined: " \
                f"{rb.STATE_NAMES[cur[s]]}"
    state = eng.drain_completed(state)
    assert int(state.alloc.top) == serve.num_pages


def test_ring_submit_release_protocol():
    serve = ServeConfig(num_slots=4, max_prompt_len=8, max_new_tokens=4)
    ring = rb.make_ring(serve)
    assert int(ring.slot_state[2]) == rb.EMPTY
    ring = rb.submit_request(ring, 2, tokens=[5, 6, 7], request_id=11,
                             max_new=4, arrival=3, step=0)
    assert int(ring.slot_state[2]) == rb.PREFILL_PENDING
    assert int(ring.prompt_len[2]) == 3
    assert ring.input_arena[2, :3].tolist() == [5, 6, 7]
    ring = rb.release_slot(ring, 2)
    assert int(ring.slot_state[2]) == rb.EMPTY
    assert int(ring.arrival[2]) == np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# Tokenizer properties
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained_tok():
    corpus = ["the quick brown fox jumps over the lazy dog",
              "blink serves tokens with persistent kernels",
              "ring buffers and paged caches on device 123"] * 3
    return BPETokenizer.train(corpus, num_merges=150)


@HSET
@given(st.text(min_size=0, max_size=200))
def test_tokenizer_roundtrip(trained_tok, s):
    assert trained_tok.decode(trained_tok.encode(s)) == s


@HSET
@given(st.text(min_size=1, max_size=80))
def test_fast_equals_naive_bpe(trained_tok, s):
    naive = NaiveBPETokenizer(list(trained_tok.merges.keys()))
    assert trained_tok.encode(s) == naive.encode(s)


def test_tokenizer_ids_in_vocab(trained_tok):
    ids = trained_tok.encode("hello brown fox 123 !!!")
    assert all(0 <= i < trained_tok.vocab_size for i in ids)


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------


def test_greedy_at_zero_temperature():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (5, 33))
    tok = sample_tokens(key, logits, jnp.zeros(5),
                        slot_ids=jnp.arange(5), step=jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(tok),
                                  np.argmax(np.asarray(logits), -1))


def test_sampling_is_slot_step_deterministic():
    key = jax.random.PRNGKey(1)
    logits = jax.random.normal(key, (4, 64))
    t = jnp.ones(4)
    a = sample_tokens(key, logits, t, slot_ids=jnp.arange(4),
                      step=jnp.int32(3))
    b = sample_tokens(key, logits, t, slot_ids=jnp.arange(4),
                      step=jnp.int32(3))
    c = sample_tokens(key, logits, t, slot_ids=jnp.arange(4),
                      step=jnp.int32(4))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) != np.asarray(c)).any()


@HSET
@given(st.floats(0.1, 0.99))
def test_top_p_keeps_nucleus_only(p):
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(3, 50)),
                         jnp.float32)
    filtered = top_p_filter(logits, jnp.full((3,), p))
    probs = np.asarray(jax.nn.softmax(logits, -1))
    kept = np.asarray(jnp.isfinite(filtered))
    for b in range(3):
        order = np.argsort(-probs[b])
        csum = np.cumsum(probs[b][order])
        k = int(np.searchsorted(csum, p) + 1)
        expect = np.zeros(50, bool)
        expect[order[:k]] = True
        np.testing.assert_array_equal(kept[b], expect)


# ---------------------------------------------------------------------------
# Tensor-parallel head partition: exact cover, GQA alignment, rejection
# ---------------------------------------------------------------------------


@HSET
@given(st.integers(1, 16), st.integers(1, 8), st.integers(1, 8))
def test_head_partition_exact_cover(kv_heads, group, model_size):
    """Over random (kv_heads, q_heads = kv_heads * group, mesh_model_size)
    tuples: when the split divides, ``head_partition`` is an EXACT cover —
    contiguous equal ranges, every head in exactly one shard, and each
    shard's q range maps onto its kv range in whole GQA groups (``h // G``
    is the same local->kv map on every shard). When it does not divide,
    partitioning and model validation both reject with a clear error."""
    from repro.distribution import sharding as shard_lib
    q_heads = kv_heads * group
    if kv_heads % model_size == 0:
        for num in (kv_heads, q_heads):
            parts = shard_lib.head_partition(num, model_size)
            assert len(parts) == model_size
            per = num // model_size
            covered = []
            for i, (lo, hi) in enumerate(parts):
                assert (lo, hi) == (i * per, (i + 1) * per)
                covered.extend(range(lo, hi))
            assert covered == list(range(num))       # exact cover, ordered
        # GQA alignment: shard i's q heads use exactly shard i's kv heads
        qparts = shard_lib.head_partition(q_heads, model_size)
        kparts = shard_lib.head_partition(kv_heads, model_size)
        for (qlo, qhi), (klo, khi) in zip(qparts, kparts):
            assert {h // group for h in range(qlo, qhi)} == \
                set(range(klo, khi))
    else:
        with pytest.raises(ValueError, match="no ragged shards"):
            shard_lib.head_partition(kv_heads, model_size)


@HSET
@given(st.integers(1, 16), st.integers(1, 8), st.integers(2, 8))
def test_head_sharding_validation_matches_partition(kv_heads, group,
                                                    model_size):
    """``validate_head_sharding`` (the make_model gate) accepts exactly
    the tuples ``head_partition`` can cover: divisibility of BOTH head
    counts, rejected with an error naming the offending count."""
    from repro.configs.registry import TINY_ARCHS
    from repro.distribution import sharding as shard_lib
    cfg = TINY_ARCHS["qwen2-1.5b"].replace(
        num_heads=kv_heads * group, num_kv_heads=kv_heads)
    divides = kv_heads % model_size == 0 and \
        (kv_heads * group) % model_size == 0
    if divides:
        shard_lib.validate_head_sharding(cfg, model_size)
    else:
        with pytest.raises(ValueError, match="does not divide"):
            shard_lib.validate_head_sharding(cfg, model_size)


def test_mesh_model_size_config_validation():
    """ServeConfig rejects a non-positive mesh and the fused-layout
    combination at construction (the pool has no per-shard layout);
    make_model-level rejection covers SSM archs and bad head counts."""
    from repro.configs.registry import TINY_ARCHS
    from repro.distribution import sharding as shard_lib
    with pytest.raises(ValueError, match="mesh_model_size must be >= 1"):
        ServeConfig(mesh_model_size=0)
    with pytest.raises(ValueError, match="kv_fused_layout"):
        ServeConfig(prefill_chunk_tokens=8, attn_unified=True,
                    kv_fused_layout=True, mesh_model_size=2)
    with pytest.raises(ValueError, match="decoder-only"):
        shard_lib.validate_head_sharding(TINY_ARCHS["rwkv6-7b"], 2)
    with pytest.raises(ValueError, match=">= 1"):
        shard_lib.head_partition(4, 0)


# ---------------------------------------------------------------------------
# Ragged attention metadata: cu-lens construction (unified kernel input)
# ---------------------------------------------------------------------------


@HSET
@given(st.lists(st.tuples(st.integers(0, 16), st.integers(0, 64)),
                min_size=1, max_size=32))
def test_build_cu_lens_monotone_bounds(rows):
    """``build_cu_lens`` feeds the unified kernel's scalar prefetch: both
    prefix-sum vectors must be int32, start at 0, be monotone
    non-decreasing, and reproduce exactly the per-row (q_len, q_len +
    cached) spans — any slack or overlap would make the kernel read a
    neighbour row's tokens."""
    from repro.kernels.ragged_attention import build_cu_lens
    q_lens = np.asarray([q for q, _ in rows], np.int32)
    cached = np.asarray([c for _, c in rows], np.int32)
    cu_q, cu_kv = build_cu_lens(jnp.asarray(q_lens), jnp.asarray(cached))
    cu_q, cu_kv = np.asarray(cu_q), np.asarray(cu_kv)
    assert cu_q.dtype == np.int32 and cu_kv.dtype == np.int32
    assert cu_q.shape == cu_kv.shape == (len(rows) + 1,)
    assert cu_q[0] == 0 and cu_kv[0] == 0
    np.testing.assert_array_equal(np.diff(cu_q), q_lens)
    np.testing.assert_array_equal(np.diff(cu_kv), q_lens + cached)
    assert (np.diff(cu_q) <= np.diff(cu_kv)).all()
    assert cu_q[-1] == q_lens.sum() and cu_kv[-1] == (q_lens + cached).sum()
