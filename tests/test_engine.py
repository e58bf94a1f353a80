"""Blink engine integration: engine-vs-host-baseline token equivalence,
ring lifecycle, backpressure, page hygiene, pause/resume batching."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.configs.registry import TINY_ARCHS
from repro.core import engine as eng
from repro.core import ring_buffer as rb
from repro.core.host_engine import HostEngine
from repro.models import attn_backend
from repro.models.api import make_model


def _submit_all(state, reqs, max_new=6):
    ring = state.ring
    for i, toks in enumerate(reqs):
        ring = rb.submit_request(ring, i, tokens=toks, request_id=i,
                                 max_new=max_new, arrival=i, step=0)
    return dataclasses.replace(state, ring=ring)


def _mk_reqs(cfg, n=5, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab_size,
                         size=int(rng.integers(4, 12))).tolist()
            for _ in range(n)]


@pytest.mark.parametrize("name", ["qwen2-1.5b", "rwkv6-7b", "zamba2-2.7b",
                                  "mixtral-8x7b"])
def test_engine_matches_host_baseline(name, tiny_apis, small_serve):
    """Greedy decoding through the persistent-window engine produces
    token-for-token identical output to the host-driven baseline."""
    api, params = tiny_apis(name)
    serve = small_serve
    reqs = _mk_reqs(api.cfg)

    state = _submit_all(eng.init_engine_state(api, serve), reqs)
    window_fn = eng.make_serve_window(api, serve)
    for _ in range(6):
        state = window_fn(params, state)
        if int(jnp.sum(state.ring.slot_state[:5] == rb.DECODE_COMPLETED)) == 5:
            break
    out = np.asarray(state.ring.output_arena)
    gen = np.asarray(state.ring.generated)
    blink = [out[i, :gen[i]].tolist() for i in range(5)]

    host = HostEngine(api, serve, params)
    for i, toks in enumerate(reqs):
        host.submit(toks, max_new=6, arrival=i)
    host.run_until_idle()
    expected = [host.outputs[i] for i in range(5)]
    assert blink == expected


def test_all_pages_freed_after_completion(tiny_apis, small_serve):
    api, params = tiny_apis("qwen2-1.5b")
    state = _submit_all(eng.init_engine_state(api, small_serve),
                        _mk_reqs(api.cfg))
    window_fn = eng.make_serve_window(api, small_serve)
    for _ in range(6):
        state = window_fn(params, state)
    assert int(state.alloc.top) == small_serve.num_pages
    bt = np.asarray(state.cache["kv"].block_table)
    assert (bt == -1).all()
    # free stack holds a permutation of all pages (no dup / loss)
    stack = np.asarray(state.alloc.free_stack)
    assert sorted(stack.tolist()) == list(range(small_serve.num_pages))


def test_backpressure_when_pages_exhausted(tiny_apis):
    """With a page pool too small for all requests at once, admission must
    stall (slots stay PREFILL_PENDING) and later complete everything."""
    api, params = tiny_apis("qwen2-1.5b")
    serve = ServeConfig(num_slots=8, max_prompt_len=16, max_new_tokens=8,
                        decode_batch=4, window=10, admit_per_step=4,
                        page_size=4, num_pages=12, eos_token=-1)
    # each request needs ceil((len+8)/4) pages ~ 4-6 -> only ~2 fit at once
    state = _submit_all(eng.init_engine_state(api, serve),
                        _mk_reqs(api.cfg, n=5), max_new=8)
    window_fn = eng.make_serve_window(api, serve)
    state = window_fn(params, state)
    states_now = np.asarray(state.ring.slot_state[:5])
    assert (states_now == rb.PREFILL_PENDING).any(), \
        "some requests should be backpressured"
    for _ in range(12):
        state = window_fn(params, state)
    assert (np.asarray(state.ring.slot_state[:5])
            == rb.DECODE_COMPLETED).all()
    assert int(state.alloc.top) == serve.num_pages


def test_fcfs_admission_order(tiny_apis, small_serve):
    """Arrival tickets, not slot indices, determine admission order."""
    api, params = tiny_apis("qwen2-1.5b")
    serve = dataclasses.replace(small_serve, decode_batch=2,
                                admit_per_step=1, window=2)
    state = eng.init_engine_state(api, serve)
    ring = state.ring
    # slot 0 arrives LAST, slot 3 arrives first
    arrivals = {0: 10, 1: 5, 2: 3, 3: 1}
    rng = np.random.default_rng(0)
    for s, arr in arrivals.items():
        ring = rb.submit_request(ring, s, tokens=rng.integers(3, 100, 5)
                                 .tolist(), request_id=s, max_new=4,
                                 arrival=arr, step=0)
    state = dataclasses.replace(state, ring=ring)
    window_fn = eng.make_serve_window(api, serve)
    state = window_fn(params, state)  # 2 steps: admits exactly 2 requests
    st = np.asarray(state.ring.slot_state)
    admitted = {s for s in arrivals if st[s] != rb.PREFILL_PENDING}
    assert admitted == {3, 2}, f"FCFS violated: {admitted}"


def test_state_survives_window_reinstantiation(tiny_apis, small_serve):
    """Splitting the same workload across many small windows must produce
    the same tokens as one big window (tail-launch state continuity)."""
    api, params = tiny_apis("qwen2-1.5b")
    reqs = _mk_reqs(api.cfg, n=3)

    def run(window):
        serve = dataclasses.replace(small_serve, window=window)
        state = _submit_all(eng.init_engine_state(api, serve), reqs)
        fn = eng.make_serve_window(api, serve)
        for _ in range(60 // window):
            state = fn(params, state)
        out = np.asarray(state.ring.output_arena)
        gen = np.asarray(state.ring.generated)
        return [out[i, :gen[i]].tolist() for i in range(3)]

    assert run(60) == run(5)


def test_single_token_requests_complete_at_prefill(tiny_apis, small_serve):
    api, params = tiny_apis("qwen2-1.5b")
    state = _submit_all(eng.init_engine_state(api, small_serve),
                        _mk_reqs(api.cfg, n=2), max_new=1)
    fn = eng.make_serve_window(api, small_serve)
    state = fn(params, state)
    st = np.asarray(state.ring.slot_state[:2])
    assert (st == rb.DECODE_COMPLETED).all()
    assert (np.asarray(state.ring.generated[:2]) == 1).all()
    # prefill-completed requests free their pages in the prefill branch
    # (they never reach a decode lane, so the decode free pass can't)
    assert int(state.alloc.top) == small_serve.num_pages
    assert (np.asarray(state.cache["kv"].block_table)[:2] == -1).all()


def test_continuous_batching_joins_running_batch(tiny_apis, small_serve):
    """A request submitted while others are decoding must merge into the
    running batch (pause-and-resume) and complete."""
    api, params = tiny_apis("qwen2-1.5b")
    serve = dataclasses.replace(small_serve, window=4)
    state = _submit_all(eng.init_engine_state(api, serve),
                        _mk_reqs(api.cfg, n=2), max_new=8)
    fn = eng.make_serve_window(api, serve)
    state = fn(params, state)   # now 2 requests mid-decode
    assert (np.asarray(state.ring.slot_state[:2])
            == rb.DECODE_PROCESSING).all()
    ring = rb.submit_request(state.ring, 5,
                             tokens=[4, 5, 6, 7], request_id=99, max_new=4,
                             arrival=100, step=int(state.step))
    state = dataclasses.replace(state, ring=ring)
    for _ in range(8):
        state = fn(params, state)
    st = np.asarray(state.ring.slot_state)
    assert st[5] == rb.DECODE_COMPLETED
    assert (st[:2] == rb.DECODE_COMPLETED).all()


def test_window_cache_tightest_fit_and_equivalence(tiny_apis, small_serve):
    """The graph-cache analogue (paper §4.2): bucketed window executables
    produce identical tokens and the tightest-fitting bucket is selected,
    with the max-shape window as fallback."""
    api, params = tiny_apis("qwen2-1.5b")
    serve = dataclasses.replace(small_serve, max_prompt_len=16, window=8)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(3, api.cfg.vocab_size, int(n)).tolist()
               for n in (3, 4, 14)]

    def run(buckets):
        cache = eng.WindowCache(api, serve, buckets)
        state = eng.init_engine_state(api, serve)
        ring = state.ring
        for i, p in enumerate(prompts):
            ring = rb.submit_request(ring, i, tokens=p, request_id=i,
                                     max_new=4, arrival=i, step=0)
        state = dataclasses.replace(state, ring=ring)
        for _ in range(8):
            fn = cache.select(cache.max_pending_len(state.ring))
            state = fn(params, state)
        out = np.asarray(state.ring.output_arena)
        gen = np.asarray(state.ring.generated)
        return [out[i, :gen[i]].tolist() for i in range(3)], cache.selections

    base, _ = run(None)
    bucketed, sel = run((4, 8))
    assert base == bucketed
    assert sel[4] > 0            # tightest bucket used for the short prompts
    assert sel[16] > 0           # fallback used for the length-14 prompt


def test_engine_only_prefix_cache_fallback_free(tiny_apis, small_serve):
    """ROADMAP-noted leak: with ``prefix_cache`` on, page release is
    frontend-owned, so engine-only serving used to strand completed slots'
    pages forever. ``eng.drain_completed`` is the engine-side fallback:
    serve to idle WITHOUT a BlinkFrontend, drain, and the PageAllocator
    must be whole again (and the slots reusable)."""
    api, params = tiny_apis("qwen2-1.5b")
    serve = dataclasses.replace(small_serve, prefix_cache=True)
    state = _submit_all(eng.init_engine_state(api, serve),
                        _mk_reqs(api.cfg))
    window_fn = eng.make_serve_window(api, serve)
    for _ in range(8):
        state = window_fn(params, state)
    assert (np.asarray(state.ring.slot_state[:5])
            == rb.DECODE_COMPLETED).all()
    # the leak: completed slots still hold their pages (release deferred
    # to a frontend that does not exist)
    assert int(state.alloc.top) < serve.num_pages
    state = eng.drain_completed(state)
    assert int(state.alloc.top) == serve.num_pages
    stack = np.asarray(state.alloc.free_stack)
    assert sorted(stack.tolist()) == list(range(serve.num_pages))
    assert (np.asarray(state.cache["kv"].block_table) == -1).all()
    assert (np.asarray(state.ring.slot_state) == rb.EMPTY).all()
    # drained slots are genuinely reusable: serve a second batch through
    state = _submit_all(state, _mk_reqs(api.cfg, seed=11))
    for _ in range(8):
        state = window_fn(params, state)
    assert (np.asarray(state.ring.slot_state[:5])
            == rb.DECODE_COMPLETED).all()


def test_mixed_phase_prefilling_visible_and_decode_uninterrupted(
        tiny_apis, small_serve):
    """White-box mixed-phase check: with a multi-chunk prompt admitted
    while lanes decode, the PREFILLING state and its advancing cursor are
    visible at window boundaries, and the decoding lanes publish a token
    EVERY step throughout (the no-stall guarantee)."""
    api, params = tiny_apis("qwen2-1.5b")
    serve = dataclasses.replace(small_serve, window=1,
                                prefill_chunk_tokens=4,
                                max_prefills_per_step=1)
    state = _submit_all(eng.init_engine_state(api, serve),
                        _mk_reqs(api.cfg, n=2), max_new=8)
    fn = eng.make_serve_window(api, serve)
    for _ in range(8):              # enough chunk steps (Mp=1) for both
        state = fn(params, state)   # short prompts to reach decode
    assert (np.asarray(state.ring.slot_state[:2])
            == rb.DECODE_PROCESSING).all()
    long_prompt = np.random.default_rng(0).integers(
        3, api.cfg.vocab_size, 16).tolist()      # 4 chunks of 4
    ring = rb.submit_request(state.ring, 5, tokens=long_prompt,
                             request_id=9, max_new=2, arrival=100,
                             step=int(state.step))
    state = dataclasses.replace(state, ring=ring)
    cursors = []
    for _ in range(6):
        state = fn(params, state)
        st = np.asarray(state.ring.slot_state)
        if st[5] == rb.PREFILLING:
            cursors.append(int(state.ring.prefill_done_len[5]))
    # chunk cursor observed mid-flight, strictly advancing by the chunk
    assert cursors and cursors == sorted(cursors)
    assert all(c % 4 == 0 for c in cursors)
    for _ in range(8):
        state = fn(params, state)
    assert np.asarray(state.ring.slot_state)[5] == rb.DECODE_COMPLETED
    # decode lanes never skipped a step while the prefill was in flight
    ts = np.asarray(state.ring.token_step)
    for s in range(2):
        stamps = ts[s][ts[s] >= 0]
        assert (np.diff(stamps) == 1).all(), stamps


def _spy_on_decode_attention(monkeypatch, name, seen):
    """Rebind decode backend ``name`` to itself plus a host callback that
    records the attended length (``pos + 1``) it is handed per lane."""
    make_inner = attn_backend._REGISTRY[name]

    def factory(*, pages_per_block=1):
        inner = make_inner(pages_per_block=pages_per_block)

        def attend(cfg, q, kvc, layer, slot_ids, pos, window):
            jax.debug.callback(lambda p: seen.append(np.asarray(p) + 1), pos)
            return inner(cfg, q, kvc, layer, slot_ids, pos, window)
        return attend

    monkeypatch.setitem(attn_backend._REGISTRY, name, factory)


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_decode_attention_skips_lanes_not_decoding(backend, small_serve,
                                                   monkeypatch):
    """In a mixed step with a PREFILLING lane and an idle lane beside two
    decoding lanes, decode attention is handed an attended length of 0 for
    the two that do not decode (the pallas kernel then reads no page for
    them), and the decoding lanes' tokens are the gather backend's, bitwise
    (the pallas leg in interpret mode)."""
    cfg = TINY_ARCHS["qwen2-1.5b"]
    serve = dataclasses.replace(small_serve, window=1,
                                prefill_chunk_tokens=4,
                                max_prefills_per_step=1)
    reqs = _mk_reqs(cfg, n=2)
    long_prompt = np.random.default_rng(0).integers(
        3, cfg.vocab_size, 16).tolist()          # 4 chunks of 4

    def serve_all(name):
        seen = []
        _spy_on_decode_attention(monkeypatch, name, seen)
        api = make_model(cfg, attn_backend=name)
        params = api.init_params(jax.random.PRNGKey(0))
        state = _submit_all(eng.init_engine_state(api, serve), reqs,
                            max_new=8)
        fn = eng.make_serve_window(api, serve)
        for _ in range(8):                       # both reach decode
            state = fn(params, state)
        ring = rb.submit_request(state.ring, 5, tokens=long_prompt,
                                 request_id=9, max_new=2, arrival=100,
                                 step=int(state.step))
        state = dataclasses.replace(state, ring=ring)
        checked = 0
        for _ in range(6):
            lanes = np.asarray(state.lane_slot)
            slots = np.maximum(lanes, 0)
            st = np.asarray(state.ring.slot_state)[slots]
            decoding = (lanes >= 0) & (st == rb.DECODE_PROCESSING)
            want = np.where(decoding,
                            np.asarray(state.cache["kv"].seq_lens)[slots]
                            + 1, 0)
            seen.clear()
            state = fn(params, state)
            jax.effects_barrier()
            prefilling = (lanes >= 0) & (st == rb.PREFILLING)
            if seen and prefilling.any() and (lanes < 0).any():
                checked += 1
            for got in seen:                      # one record per layer
                np.testing.assert_array_equal(got, want)
        assert checked, "no step held a PREFILLING and an idle lane"
        for _ in range(10):
            state = fn(params, state)
        out = np.asarray(state.ring.output_arena)
        gen = np.asarray(state.ring.generated)
        return [out[i, :gen[i]].tolist() for i in (0, 1, 5)]

    toks = serve_all(backend)
    assert all(len(t) for t in toks)
    if backend != "gather":
        assert toks == serve_all("gather")


@pytest.mark.parametrize("name", ["qwen2-moe-a2.7b", "internvl2-2b",
                                  "seamless-m4t-medium", "gemma2-9b",
                                  "olmo-1b", "qwen1.5-32b"])
def test_engine_serves_every_arch(name, tiny_apis):
    """The persistent engine treats the model as opaque (paper §4.3):
    every assigned architecture family serves through it."""
    api, params = tiny_apis(name)
    serve = ServeConfig(num_slots=4, max_prompt_len=12, max_new_tokens=4,
                        decode_batch=2, window=8, admit_per_step=2,
                        page_size=4, num_pages=32, eos_token=-1)
    state = eng.init_engine_state(api, serve,
                                  enc_len=8 if api.cfg.is_encoder_decoder
                                  else 0)
    rng = np.random.default_rng(0)
    ring = state.ring
    for i in range(2):
        ring = rb.submit_request(ring, i,
                                 tokens=rng.integers(3, api.cfg.vocab_size,
                                                     6).tolist(),
                                 request_id=i, max_new=3, arrival=i, step=0)
    state = dataclasses.replace(state, ring=ring)
    fn = eng.make_serve_window(api, serve)
    for _ in range(4):
        state = fn(params, state)
    st = np.asarray(state.ring.slot_state[:2])
    gen = np.asarray(state.ring.generated[:2])
    assert (st == rb.DECODE_COMPLETED).all(), f"{name}: {st}"
    assert (gen == 3).all()
    out = np.asarray(state.ring.output_arena[:2, :3])
    assert (out >= 0).all() and (out < api.cfg.vocab_size).all()
