"""Device-resident prefix KV cache: radix trie, refcounted page sharing,
suffix-only prefill, chunked prefill, engine/host-engine equivalence.

The tentpole acceptance criteria live here: a shared-prefix batch decodes
identically to no-cache serving while the page accounting shows suffix-only
allocation and shared-page refcounts > 1; chunked prefill of a long prompt
matches single-shot prefill bitwise on the gather reference backend."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.configs.registry import TINY_ARCHS
from repro.core.host_engine import HostEngine
from repro.frontend.prefix_index import PrefixIndex
from repro.frontend.server import BlinkServer
from repro.kernels import ops, ref
from repro.models import attn_backend, cache as cache_lib
from repro.models.api import cache_for_serve, make_model

SERVE_KW = dict(num_slots=8, max_prompt_len=16, max_new_tokens=8,
                decode_batch=4, window=12, admit_per_step=2,
                page_size=4, num_pages=64, eos_token=-1)


def _serve(**kw):
    base = dict(SERVE_KW)
    base.update(kw)
    return ServeConfig(**base)


def _shared_prefix_requests(cfg, n=5, prefix_tokens=9, seed=3):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(3, cfg.vocab_size, prefix_tokens).tolist()
    return [prefix + rng.integers(3, cfg.vocab_size,
                                  int(rng.integers(2, 7))).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# PrefixIndex (the DPU-plane radix trie)
# ---------------------------------------------------------------------------


class TestPrefixIndex:
    def test_match_is_page_granular(self):
        idx = PrefixIndex(page_size=4)
        toks = list(range(100, 112))                    # 3 full pages
        assert idx.insert(toks, [7, 8, 9]) == [7, 8, 9]
        # full 3-page prefix + longer suffix
        cached, pages = idx.match(toks + [1, 2])
        assert (cached, pages) == (12, [7, 8, 9])
        # prompt diverging inside page 2 matches only pages 0-1
        cached, pages = idx.match(toks[:9] + [55, 56, 57])
        assert (cached, pages) == (8, [7, 8])
        # sub-page prefix matches nothing
        assert idx.match(toks[:3]) == (0, [])

    def test_match_leaves_one_suffix_token(self):
        idx = PrefixIndex(page_size=4)
        toks = list(range(8))
        idx.insert(toks, [1, 2])
        # exact-multiple prompt: last page is dropped so >= 1 token prefills
        assert idx.match(toks) == (4, [1])
        assert idx.match(toks + [99]) == (8, [1, 2])

    def test_insert_dedupes_and_extends(self):
        idx = PrefixIndex(page_size=4)
        toks = list(range(12))
        assert idx.insert(toks, [1, 2, 3]) == [1, 2, 3]
        # identical chain from a concurrent request: nothing new adopted
        assert idx.insert(toks, [7, 8, 9]) == []
        assert idx.match(toks + [0])[1] == [1, 2, 3]
        # extension adopts only the new tail
        assert idx.insert(toks + [50, 51, 52, 53], [1, 2, 3, 6]) == [6]
        assert idx.num_pages == 4

    def test_lru_eviction_of_zero_ref_leaves(self):
        idx = PrefixIndex(page_size=2)
        idx.insert([1, 2, 3, 4], [10, 11])       # chain A
        idx.insert([5, 6], [12])                 # chain B
        idx.match([1, 2, 3, 4, 9])               # A is now most recent
        assert idx.evict(1) == [12]              # LRU leaf = B
        # chains evict bottom-up: leaf 11 before its parent 10
        assert idx.evict(4) == [11, 10]
        assert idx.num_pages == 0

    def test_evict_skips_externally_referenced(self):
        idx = PrefixIndex(page_size=2)
        idx.insert([1, 2], [5])
        idx.insert([3, 4], [6])
        rc = np.zeros(8, np.int32)
        rc[5] = 3                                # page 5 co-owned by slots
        rc[6] = 1                                # page 6 trie-only
        assert idx.evict(2, refcount=rc) == [6]
        assert idx.num_pages == 1


# ---------------------------------------------------------------------------
# Refcounted PageAllocator
# ---------------------------------------------------------------------------


def test_share_then_free_keeps_page_resident():
    alloc = cache_lib.make_page_allocator(8)
    pages, alloc, ok = cache_lib.alloc_pages(alloc, jnp.asarray(2), 4)
    assert bool(ok)
    alloc = cache_lib.share_pages(alloc, pages)      # second owner
    alloc = cache_lib.free_pages(alloc, pages)       # first owner releases
    assert int(alloc.top) == 6                       # still resident
    assert (np.asarray(alloc.refcount)[np.asarray(pages)[:2]] == 1).all()
    alloc = cache_lib.free_pages(alloc, pages)       # last owner releases
    assert int(alloc.top) == 8
    assert (np.asarray(alloc.refcount) == 0).all()
    stack = np.asarray(alloc.free_stack)[:8]
    assert sorted(stack.tolist()) == list(range(8))


# ---------------------------------------------------------------------------
# Prefix-aware flash prefill kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (6, 0.0), (0, 30.0)])
def test_flash_prefill_prefix_matches_ref(window, softcap):
    rng = np.random.default_rng(0)
    B, T, KV, G, hd = 3, 16, 2, 2, 8
    P, ps, mb = 32, 4, 8
    q = jnp.asarray(rng.normal(size=(B, T, KV * G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KV, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KV, ps, hd)), jnp.float32)
    rows = jnp.asarray(rng.permutation(P)[:B * mb].reshape(B, mb), jnp.int32)
    cached = jnp.asarray([8, 0, 5], jnp.int32)       # mixed hit/miss lanes
    offs = jnp.asarray([10, 3, 9], jnp.int32)
    args = dict(window=jnp.int32(window), softcap=softcap,
                k_pages=kp, v_pages=vp, block_rows=rows, cached_lens=cached)
    out_k = ops.flash_prefill_attention(q, k, v, offs, block_q=8, block_k=8,
                                        **args)
    out_r = ref.flash_prefill_ref(q, k, v, offs, window=window,
                                  softcap=softcap, k_pages=kp, v_pages=vp,
                                  block_rows=rows, cached_lens=cached)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-5)


def test_flash_prefill_zero_cache_equals_plain():
    """cached_lens = 0 lanes must reproduce the non-prefix kernel exactly —
    one compiled program serves mixed hit/miss batches."""
    rng = np.random.default_rng(1)
    B, T, KV, G, hd = 2, 16, 2, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, KV * G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(16, KV, 4, hd)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, 16, (B, 6)), jnp.int32)
    offs = jnp.asarray([5, 0], jnp.int32)
    plain = ops.flash_prefill_attention(q, k, v, offs, block_q=8, block_k=8)
    prefixed = ops.flash_prefill_attention(
        q, k, v, offs, block_q=8, block_k=8, k_pages=kp, v_pages=kp,
        block_rows=rows, cached_lens=jnp.zeros(B, jnp.int32))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(prefixed))


# ---------------------------------------------------------------------------
# Model-level: suffix-only prefill over shared pages; chunked prefill
# ---------------------------------------------------------------------------


def _wired_cache(api, serve, B):
    cache = cache_for_serve(api, serve)
    ppr = serve.pages_per_req
    bt = np.full((serve.num_slots, ppr), -1, np.int32)
    for b in range(B):
        bt[b] = np.arange(b * ppr, (b + 1) * ppr)
    cache["kv"] = dataclasses.replace(cache["kv"],
                                      block_table=jnp.asarray(bt))
    return cache


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_suffix_prefill_over_shared_pages_matches_full(backend, monkeypatch):
    """Prefill only a suffix against another slot's prefix pages ==
    prefilling the whole prompt, for logits AND subsequent decodes."""
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)  # pin `backend`
    cfg = TINY_ARCHS["qwen2-1.5b"].replace(dtype="float32")
    serve = _serve()
    api = make_model(cfg, attn_backend=backend)
    params = api.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    T = serve.max_prompt_len
    prefix = rng.integers(3, cfg.vocab_size, 8).tolist()
    full = prefix + rng.integers(3, cfg.vocab_size, 3).tolist()
    slots = jnp.arange(3)
    cache = _wired_cache(api, serve, 3)

    # slot 0: donor prompt sharing the 8-token prefix, prefilled fully
    donor = prefix + rng.integers(3, cfg.vocab_size, 5).tolist()
    p = np.zeros((3, T), np.int32)
    p[0, T - len(donor):] = donor
    _, cache = api.prefill(params, jnp.asarray(p),
                           jnp.asarray([len(donor), 0, 0], jnp.int32),
                           cache, slots, jnp.asarray([True, False, False]))

    # slot 1: wire slot 0's first 2 pages as the shared prefix, prefill the
    # 3-token suffix only
    bt = np.asarray(cache["kv"].block_table).copy()
    bt[1, :2] = bt[0, :2]
    cache["kv"] = dataclasses.replace(cache["kv"],
                                      block_table=jnp.asarray(bt))
    sp = np.zeros((3, T), np.int32)
    sp[1, T - 3:] = full[8:]
    lg1, cache = api.prefill(
        params, jnp.asarray(sp), jnp.asarray([0, 3, 0], jnp.int32), cache,
        slots, jnp.asarray([False, True, False]),
        cached_lens=jnp.asarray([0, 8, 0], jnp.int32))

    # slot 2: the same full prompt, no cache — the oracle
    p2 = np.zeros((3, T), np.int32)
    p2[2, T - len(full):] = full
    lg2, cache = api.prefill(params, jnp.asarray(p2),
                             jnp.asarray([0, 0, len(full)], jnp.int32),
                             cache, slots, jnp.asarray([False, False, True]))
    np.testing.assert_allclose(np.asarray(lg1[1]), np.asarray(lg2[2]),
                               atol=2e-4)
    # 3 decode steps with identical token streams must stay identical
    act = jnp.asarray([False, True, True])
    for t in rng.integers(3, cfg.vocab_size, 3):
        toks = jnp.full((3,), int(t), jnp.int32)
        d, cache = api.decode(params, toks, cache, slots, act)
        np.testing.assert_allclose(np.asarray(d[1]), np.asarray(d[2]),
                                   atol=2e-4)


def test_chunked_prefill_matches_single_shot_bitwise_on_gather(monkeypatch):
    """Acceptance criterion: chunked prefill of a long prompt is BITWISE
    identical to single-shot prefill on the gather reference backend —
    logits and the KV pages it leaves behind."""
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)  # gather-only
    cfg = TINY_ARCHS["qwen2-1.5b"].replace(dtype="float32")
    serve = _serve()
    api = make_model(cfg, attn_backend="gather")
    params = api.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    T = serve.max_prompt_len
    prompts = np.zeros((3, T), np.int32)
    lens = np.asarray([13, 6, 16], np.int32)
    for b, n in enumerate(lens):
        prompts[b, T - n:] = rng.integers(3, cfg.vocab_size, n)
    slots, act = jnp.arange(3), jnp.ones(3, bool)

    lg_s, cache_s = api.prefill(params, jnp.asarray(prompts),
                                jnp.asarray(lens), _wired_cache(api, serve, 3),
                                slots, act)
    lg_c, cache_c = api.prefill_chunked(
        params, jnp.asarray(prompts), jnp.asarray(lens),
        _wired_cache(api, serve, 3), slots, act, chunk=5)
    np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_c))
    np.testing.assert_array_equal(np.asarray(cache_s["kv"].k_pages),
                                  np.asarray(cache_c["kv"].k_pages))
    np.testing.assert_array_equal(np.asarray(cache_s["kv"].v_pages),
                                  np.asarray(cache_c["kv"].v_pages))
    np.testing.assert_array_equal(np.asarray(cache_s["kv"].seq_lens),
                                  np.asarray(cache_c["kv"].seq_lens))


def test_chunked_prefill_close_on_pallas(monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)  # pallas-only
    cfg = TINY_ARCHS["qwen2-1.5b"].replace(dtype="float32")
    serve = _serve()
    api = make_model(cfg, attn_backend="pallas")
    params = api.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    T = serve.max_prompt_len
    prompts = np.zeros((2, T), np.int32)
    lens = np.asarray([16, 11], np.int32)
    for b, n in enumerate(lens):
        prompts[b, T - n:] = rng.integers(3, cfg.vocab_size, n)
    slots, act = jnp.arange(2), jnp.ones(2, bool)
    lg_s, _ = api.prefill(params, jnp.asarray(prompts), jnp.asarray(lens),
                          _wired_cache(api, serve, 2), slots, act)
    lg_c, _ = api.prefill_chunked(params, jnp.asarray(prompts),
                                  jnp.asarray(lens),
                                  _wired_cache(api, serve, 2), slots, act,
                                  chunk=6)
    np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_c), atol=1e-4)


def test_prefix_reuse_rejected_for_recurrent_archs():
    cfg = TINY_ARCHS["zamba2-2.7b"].replace(dtype="float32")
    api = make_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    serve = _serve()
    cache = cache_for_serve(api, serve)
    with pytest.raises(ValueError, match="prefix"):
        api.prefill(params, jnp.zeros((1, 8), jnp.int32),
                    jnp.asarray([4], jnp.int32), cache, jnp.asarray([0]),
                    jnp.asarray([True]),
                    cached_lens=jnp.asarray([4], jnp.int32))


# ---------------------------------------------------------------------------
# Engine-level equivalence (the tentpole acceptance test)
# ---------------------------------------------------------------------------


def _run_server(api, params, reqs, prefix_cache, max_new=5, **extra):
    serve = _serve(prefix_cache=prefix_cache, **extra)
    srv = BlinkServer(api, serve, params, prompt_buckets=(8, 16))
    ids = [srv.submit(reqs[0], max_new=max_new)]
    srv.run_window()                 # request 0 prefills + commits its chain
    ids += [srv.submit(r, max_new=max_new) for r in reqs[1:]]
    max_rc, min_top = 0, serve.num_pages
    for _ in range(40):
        if srv.frontend.idle:
            break
        srv.run_window()
        max_rc = max(max_rc, int(jnp.max(srv.state.alloc.refcount)))
        min_top = min(min_top, int(srv.state.alloc.top))
    assert srv.frontend.idle
    outs = [srv.frontend.done[i].output for i in ids]
    cached = [srv.frontend.done[i].cached_len for i in ids]
    return outs, cached, max_rc, min_top, srv


def test_shared_prefix_batch_reuses_pages_and_decodes_identically(tiny_apis):
    """Same system prompt, distinct suffixes: with prefix_cache on, decode
    output is identical to no-cache serving while (i) later requests carry
    a nonzero cached_len (suffix-only prefill: the small WindowCache bucket
    is selected), (ii) shared-page refcounts exceed 1 in flight, and
    (iii) fewer pages are consumed from the pool."""
    api, params = tiny_apis("qwen2-1.5b")
    reqs = _shared_prefix_requests(api.cfg)

    outs_off, cached_off, _, _, srv_off = _run_server(
        api, params, reqs, prefix_cache=False)
    outs_on, cached_on, _, _, srv_on = _run_server(
        api, params, reqs, prefix_cache=True)

    assert outs_on == outs_off                     # token-for-token identical
    assert cached_off == [0] * len(reqs)
    assert cached_on[0] == 0 and all(c == 8 for c in cached_on[1:])
    # suffix-only prefill FLOPs: the reused requests' 3-8 token suffixes fit
    # the 8-token bucket; without reuse every 11+-token prompt needs the
    # max-shape window (idle windows also pick the smallest bucket, so
    # compare only the runs' PREFILL-bearing windows: off admitted all five
    # prompts through the 16 bucket, on pushed four through the 8 bucket)
    assert srv_on.windows.selections[8] > srv_off.windows.selections[8]
    assert srv_off.windows.selections[16] > srv_on.windows.selections[16]
    # the trie retains the committed chains after the batch drains
    assert srv_on.frontend.prefix.num_pages > 0
    assert int(jnp.sum(srv_on.state.alloc.refcount)) == \
        srv_on.frontend.prefix.num_pages

    # page accounting with window=2 (mid-flight sampling; a 12-step window
    # admits, decodes and frees whole requests between observations):
    # suffix-only allocation keeps more of the pool free at peak
    *_, top_off2, _ = _run_server(api, params, reqs, prefix_cache=False,
                                  window=2)
    *_, top_on2, _ = _run_server(api, params, reqs, prefix_cache=True,
                                 window=2)
    assert top_on2 > top_off2


def test_shared_page_refcounts_exceed_one_in_flight(tiny_apis):
    """While shared-prefix requests are pending/decoding, the prefix pages
    are co-owned: allocator refcount > 1 (trie + requests)."""
    api, params = tiny_apis("qwen2-1.5b")
    reqs = _shared_prefix_requests(api.cfg, n=4)
    serve = _serve(prefix_cache=True, window=6, max_new_tokens=16)
    srv = BlinkServer(api, serve, params)
    srv.submit(reqs[0], max_new=2)
    srv.run_window()                               # commit the chain
    for r in reqs[1:]:
        srv.submit(r, max_new=16)                  # long decodes stay live
    srv.run_window()
    rc = np.asarray(srv.state.alloc.refcount)
    assert rc.max() > 1, f"no shared page co-ownership observed: {rc.max()}"
    # conservation: pages with refs + free pages partition the pool
    assert int(srv.state.alloc.top) + int((rc > 0).sum()) == serve.num_pages
    for _ in range(40):
        if srv.frontend.idle:
            break
        srv.run_window()
    assert srv.frontend.idle


def test_trie_eviction_under_backpressure_returns_pages(tiny_apis):
    """Filling the trie then raising the watermark drops LRU chains and
    returns their (unshared) pages to the pool."""
    api, params = tiny_apis("qwen2-1.5b")
    rng = np.random.default_rng(7)
    # distinct prompts -> distinct chains, all committed
    reqs = [rng.integers(3, api.cfg.vocab_size, 9).tolist() for _ in range(4)]
    serve = _serve(prefix_cache=True)
    srv = BlinkServer(api, serve, params)
    for r in reqs:
        srv.submit(r, max_new=2)
    for _ in range(20):
        if srv.frontend.idle:
            break
        srv.run_window()
    held = srv.frontend.prefix.num_pages
    assert held > 0
    free_before = int(srv.state.alloc.top)
    alloc = srv.frontend.maybe_evict(srv.state.alloc, serve.num_pages)
    assert srv.frontend.prefix.num_pages == 0
    assert int(alloc.top) == free_before + held
    stack = np.asarray(alloc.free_stack)[:int(alloc.top)]
    assert sorted(stack.tolist()) == list(range(serve.num_pages))
    assert (np.asarray(alloc.refcount) == 0).all()


def test_trie_never_starves_admission(tiny_apis):
    """Regression: with the default watermark (0) a stream of DISTINCT
    prompts must not wedge — the trie's references are evicted on demand
    when a pending admission cannot get pages (the starvation fallback),
    on both engines."""
    api, params = tiny_apis("qwen2-1.5b")
    rng = np.random.default_rng(5)
    reqs = [rng.integers(3, api.cfg.vocab_size, 9).tolist() for _ in range(6)]
    serve = _serve(prefix_cache=True, num_pages=12, admit_per_step=2,
                   decode_batch=2)

    srv = BlinkServer(api, serve, params)
    ids = [srv.submit(r, max_new=2) for r in reqs]
    for _ in range(60):
        if srv.frontend.idle:
            break
        srv.run_window()
    assert srv.frontend.idle, "trie-held pages wedged admission"
    assert all(len(srv.frontend.done[i].output) == 2 for i in ids)

    host = HostEngine(api, serve, params)
    for i, r in enumerate(reqs):
        host.submit(r, max_new=2, arrival=i)
    host.run_until_idle()
    assert (host.slot_state[:6] == 5).all(), \
        "host trie-held pages wedged admission"


def test_host_engine_identical_policy(tiny_apis):
    """HostEngine with prefix_cache matches both its own no-cache run and
    the device engine (controlled-comparison requirement)."""
    api, params = tiny_apis("qwen2-1.5b")
    reqs = _shared_prefix_requests(api.cfg)

    def run_host(prefix_on):
        host = HostEngine(api, _serve(prefix_cache=prefix_on), params)
        host.submit(reqs[0], max_new=5, arrival=0)
        host.run_until_idle()
        for i, r in enumerate(reqs[1:]):
            host.submit(r, max_new=5, arrival=i + 1)
        host.run_until_idle()
        return ([host.outputs[s] for s in range(len(reqs))],
                [int(c) for c in host.slot_cached[:len(reqs)]])

    outs_off, _ = run_host(False)
    outs_on, cached_on = run_host(True)
    assert outs_on == outs_off
    assert cached_on[0] == 0 and all(c == 8 for c in cached_on[1:])

    dev_outs, _, _, _, _ = _run_server(api, params, reqs, prefix_cache=True)
    assert dev_outs == outs_off


# ---------------------------------------------------------------------------
# Satellites
# ---------------------------------------------------------------------------


def test_host_prefill_respects_temperature(tiny_apis):
    """Regression: host-engine prefill used to hardcode temperature 0 (its
    first sampled token was always greedy). With per-request temperatures
    the host baseline must match the device engine token-for-token under
    sampling (same PRNG key, slot and step fold)."""
    api, params = tiny_apis("qwen2-1.5b")
    rng = np.random.default_rng(11)
    reqs = [rng.integers(3, api.cfg.vocab_size, 6).tolist() for _ in range(2)]
    serve = _serve()
    import repro.core.engine as eng
    import repro.core.ring_buffer as rb

    state = eng.init_engine_state(api, serve)
    ring = state.ring
    for i, toks in enumerate(reqs):
        ring = rb.submit_request(ring, i, tokens=toks, request_id=i,
                                 max_new=4, arrival=i, temperature=1.3,
                                 step=0)
    state = dataclasses.replace(state, ring=ring)
    fn = eng.make_serve_window(api, serve)
    for _ in range(6):
        state = fn(params, state)
    gen = np.asarray(state.ring.generated)
    out = np.asarray(state.ring.output_arena)
    dev = [out[i, :gen[i]].tolist() for i in range(2)]

    host = HostEngine(api, serve, params)
    for i, toks in enumerate(reqs):
        host.submit(toks, max_new=4, temperature=1.3, arrival=i)
    host.run_until_idle()
    assert [host.outputs[i] for i in range(2)] == dev
    # and sampling actually happened: greedy run differs somewhere
    host0 = HostEngine(api, serve, params)
    for i, toks in enumerate(reqs):
        host0.submit(toks, max_new=4, temperature=0.0, arrival=i)
    host0.run_until_idle()
    assert [host0.outputs[i] for i in range(2)] != dev


def test_serve_config_prefill_tiles_validated_at_build(monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)  # default=gather
    cfg = TINY_ARCHS["qwen2-1.5b"]
    api = make_model(cfg, prefill_block_q=64, prefill_block_k=32)
    assert api.attn_backend == "gather"
    with pytest.raises(ValueError, match="prefill_block_q"):
        make_model(cfg, prefill_block_q=0)
    with pytest.raises(ValueError, match="multiple of 8"):
        make_model(cfg, prefill_block_k=12)
    with pytest.raises(ValueError, match="positive int"):
        attn_backend.get_prefill_backend("pallas", block_q=-8, block_k=128)


def test_engine_rejects_prefix_cache_for_unsupported_archs(tiny_apis):
    import repro.core.engine as eng
    serve = _serve(prefix_cache=True)
    for name in ("rwkv6-7b", "zamba2-2.7b", "seamless-m4t-medium"):
        api, _ = tiny_apis(name)
        with pytest.raises(ValueError, match="prefix_cache"):
            eng.init_engine_state(api, serve,
                                  enc_len=8 if api.cfg.is_encoder_decoder
                                  else 0)


def test_prefix_trie_byte_cap(tiny_apis):
    """``prefix_trie_max_bytes`` proactively bounds trie-retained KV:
    after bursts with DISTINCT shared prefixes (each committing fresh
    chains), the trie holds at most cap // page_nbytes pages — eviction
    runs on every poll, not only under admission backpressure — and the
    pool still partitions into free + referenced pages."""
    api, params = tiny_apis("qwen2-1.5b")
    base = _serve(max_prompt_len=24, max_new_tokens=4, window=1,
                  prefill_chunk_tokens=8, prefix_cache=True)
    probe = BlinkServer(api, base, params)
    pnb = cache_lib.page_nbytes(probe.state.cache["kv"])
    cap_pages = 4
    serve = dataclasses.replace(base, prefix_trie_max_bytes=cap_pages * pnb)
    srv = BlinkServer(api, serve, params)
    rng = np.random.default_rng(0)
    for _burst in range(4):     # 4 bursts x 3 committed pages > cap
        prefix = rng.integers(3, 500, 8).tolist()
        for _ in range(2):
            srv.submit(prefix + rng.integers(3, 500, 4).tolist(), max_new=2)
        for _ in range(200):
            if srv.frontend.idle:
                break
            srv.run_window()
        assert srv.frontend.idle, "burst did not drain"
        assert srv.frontend.prefix.num_pages <= cap_pages
    rc = np.asarray(srv.state.alloc.refcount)
    assert int(srv.state.alloc.top) + int((rc > 0).sum()) == serve.num_pages
    # with every slot drained, only the trie still holds references
    assert srv.frontend.prefix.num_pages == int((rc > 0).sum())
