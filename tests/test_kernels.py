"""Per-kernel validation: sweep shapes/dtypes, assert allclose against the
pure-jnp oracles in repro.kernels.ref (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.cache import scale_lanes


def _lanes(sc):
    """[P, KV, ps] scales -> the pool's [P, KV, lanes] layout."""
    pad = scale_lanes(sc.shape[-1]) - sc.shape[-1]
    return jnp.pad(sc, ((0, 0), (0, 0), (0, pad)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,KV,G,hd,P,ps,mb", [
    (2, 2, 4, 32, 16, 8, 4),
    (1, 1, 8, 64, 8, 16, 2),
    (4, 4, 1, 16, 32, 4, 6),
])
def test_paged_attention_shapes(B, KV, G, hd, P, ps, mb, dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (B, KV, G, hd), dtype)
    kp = jax.random.normal(keys[1], (P, KV, ps, hd), dtype)
    vp = jax.random.normal(keys[2], (P, KV, ps, hd), dtype)
    bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
    kv_lens = jnp.asarray(
        np.random.default_rng(0).integers(1, mb * ps + 1, B), jnp.int32)
    out = ops.paged_attention(q, kp, vp, bt, kv_lens)
    expect = ref.paged_attention_ref(q, kp, vp, bt, kv_lens)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (5, 0.0), (0, 20.0),
                                            (9, 20.0)])
def test_paged_attention_window_softcap(window, softcap):
    B, KV, G, hd, P, ps, mb = 3, 2, 2, 32, 16, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, KV, ps, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, KV, ps, hd), jnp.float32)
    bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
    kv_lens = jnp.array([3, 15, 32])
    out = ops.paged_attention(q, kp, vp, bt, kv_lens, window=window,
                              softcap=softcap)
    expect = ref.paged_attention_ref(q, kp, vp, bt, kv_lens, window=window,
                                     softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


@pytest.mark.parametrize("ppb", [1, 2, 4])
def test_paged_attention_ignores_unassigned_pages(ppb):
    """-1 entries in the block table beyond kv_len must not contribute."""
    B, KV, G, hd, P, ps = 1, 1, 2, 16, 8, 4
    q = jnp.ones((B, KV, G, hd))
    kp = jnp.full((P, KV, ps, hd), 1e9, jnp.float32)   # poison
    vp = jnp.full((P, KV, ps, hd), 1e9, jnp.float32)
    kp = kp.at[3].set(1.0)
    vp = vp.at[3].set(2.0)
    bt = jnp.array([[3, -1, -1]])
    out = ops.paged_attention(q, kp, vp, bt, jnp.array([4]),
                              pages_per_block=ppb)
    np.testing.assert_allclose(np.asarray(out), 2.0, atol=1e-5)


def test_paged_attention_zero_len_lane():
    """kv_len == 0 lanes (fresh slot, nothing cached) must produce zeros,
    not NaN, and must not perturb sibling lanes."""
    B, KV, G, hd, P, ps, mb = 2, 2, 2, 16, 8, 4, 2
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, KV, ps, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, KV, ps, hd), jnp.float32)
    bt = jnp.array([[-1, -1], [0, 1]])
    kv_lens = jnp.array([0, 7])
    out = ops.paged_attention(q, kp, vp, bt, kv_lens)
    expect = ref.paged_attention_ref(q, kp, vp, bt, kv_lens)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out[0]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


@pytest.mark.parametrize("ppb", [1, 2, 3, 4])
def test_paged_attention_window_softcap_combined(ppb):
    """Sliding window + softcap together (gemma2 local layers), including
    the page-skip fast path, across pages_per_block settings."""
    B, KV, G, hd, P, ps, mb = 3, 2, 2, 32, 16, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, KV, ps, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, KV, ps, hd), jnp.float32)
    bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
    kv_lens = jnp.array([1, 19, 32])
    out = ops.paged_attention(q, kp, vp, bt, kv_lens, window=9, softcap=20.0,
                              pages_per_block=ppb)
    expect = ref.paged_attention_ref(q, kp, vp, bt, kv_lens, window=9,
                                     softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


def test_paged_attention_dynamic_window():
    """window passed as a traced scalar (the per-layer scan path) matches
    the static reference."""
    B, KV, G, hd, P, ps, mb = 2, 1, 2, 16, 8, 4, 3
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, KV, ps, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, KV, ps, hd), jnp.float32)
    bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
    kv_lens = jnp.array([5, 12])
    for w in (0, 6):
        out = ops.paged_attention(q, kp, vp, bt, kv_lens, window=jnp.int32(w))
        expect = ref.paged_attention_ref(q, kp, vp, bt, kv_lens, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=1e-5)


@pytest.mark.parametrize("ppb", [1, 2, 4])
def test_paged_attention_int8_fused_dequant(ppb):
    """int8 pages + per-(token, head) scales: the fused in-kernel dequant
    matches the dequantising reference, and tracks the float answer within
    quantisation tolerance."""
    B, KV, G, hd, P, ps, mb = 2, 2, 2, 32, 8, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kf = jax.random.normal(keys[1], (P, KV, ps, hd), jnp.float32)
    vf = jax.random.normal(keys[2], (P, KV, ps, hd), jnp.float32)
    bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
    kv_lens = jnp.array([6, 20])

    def quant(x):
        amax = jnp.max(jnp.abs(x), axis=-1)
        scale = jnp.maximum(amax / 127.0, 1e-8)
        qx = jnp.clip(jnp.round(x / scale[..., None]), -127, 127)
        return qx.astype(jnp.int8), scale

    kq, ks = quant(kf)
    vq, vs = quant(vf)
    ks, vs = _lanes(ks), _lanes(vs)
    out = ops.paged_attention(q, kq, vq, bt, kv_lens, k_scale=ks, v_scale=vs,
                              pages_per_block=ppb)
    expect = ref.paged_attention_ref(q, kq, vq, bt, kv_lens, k_scale=ks,
                                     v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)
    # fused int8 path stays within quantisation error of the float answer
    float_ref = ref.paged_attention_ref(q, kf, vf, bt, kv_lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(float_ref),
                               atol=5e-2)


def test_paged_attention_pages_per_block_parity():
    """All pages_per_block settings produce the same output, including a
    tail group when max_blocks % pages_per_block != 0."""
    B, KV, G, hd, P, ps, mb = 2, 2, 3, 16, 16, 4, 5
    keys = jax.random.split(jax.random.PRNGKey(19), 4)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, KV, ps, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, KV, ps, hd), jnp.float32)
    bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
    kv_lens = jnp.array([3, 18])
    base = ops.paged_attention(q, kp, vp, bt, kv_lens)
    for ppb in (2, 3, 5):
        out = ops.paged_attention(q, kp, vp, bt, kv_lens, pages_per_block=ppb)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-6)


def _quant_pool(x):
    """Per-(token, head) absmax int8 of a ``[P, KV, ps, hd]`` pool, with
    its scales in the pool's ``[P, KV, lanes]`` layout."""
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    qx = jnp.clip(jnp.round(x / scale[..., None]), -127, 127)
    return qx.astype(jnp.int8), _lanes(scale)


# The page loop's edges, ps = 4: each case is (kv_lens, table width mb,
# window, softcap, int8, pages per buffer slot).
LIVE_PAGE_CASES = {
    # the chat cell's ratio: the table ~6x wider than the longest lane
    "wide_table": ([16, 9, 1], 24, 0, 0.0, False, 1),
    "wide_table_ppb4": ([16, 9, 1], 24, 0, 0.0, False, 4),
    # lanes ending exactly on a page boundary and one token past it
    "page_edges": ([8, 9, 12, 13], 6, 0, 0.0, False, 1),
    "page_edges_ppb4": ([8, 9, 12, 13], 6, 0, 0.0, False, 4),
    # kv_len == 0 beside lanes that fill the whole table
    "zero_beside_full": ([0, 24, 0, 24], 6, 0, 0.0, False, 4),
    # the window's floor lo mid-table and mid-page
    "window_floor_mid_table": ([23, 17, 3], 8, 7, 0.0, False, 1),
    "window_floor_mid_table_ppb4": ([23, 17, 3], 8, 7, 0.0, False, 4),
    "int8": ([19, 0, 7], 8, 0, 0.0, True, 4),
    "int8_window": ([19, 5, 30], 8, 6, 0.0, True, 1),
    "softcap": ([19, 2, 30], 8, 0, 20.0, False, 4),
    # live page counts 5, 3 and 7: no multiple of 4
    "ppb4_ragged_tail": ([20, 11, 26], 8, 0, 0.0, False, 4),
}


@pytest.mark.parametrize("case", sorted(LIVE_PAGE_CASES))
def test_paged_attention_live_page_loop(case):
    """The kernel loops over each lane's live pages only, whatever the
    table width: at every edge of that loop it matches the reference."""
    lens, mb, window, softcap, int8, ppb = LIVE_PAGE_CASES[case]
    B, KV, G, hd, ps = len(lens), 2, 3, 32, 4
    P = B * mb
    keys = jax.random.split(jax.random.PRNGKey(23), 4)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, KV, ps, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, KV, ps, hd), jnp.float32)
    bt = jax.random.permutation(keys[3], P).reshape(B, mb)
    kv_lens = jnp.asarray(lens, jnp.int32)
    # pages past each lane's length are unassigned, as in the engine
    bt = jnp.where(jnp.arange(mb)[None, :] * ps < kv_lens[:, None], bt, -1)
    scales = {}
    if int8:
        kp, ks = _quant_pool(kp)
        vp, vs = _quant_pool(vp)
        scales = dict(k_scale=ks, v_scale=vs)
    out = ops.paged_attention(q, kp, vp, bt, kv_lens, window=window,
                              softcap=softcap, pages_per_block=ppb, **scales)
    expect = ref.paged_attention_ref(q, kp, vp, bt, kv_lens, window=window,
                                     softcap=softcap, **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)
    dead = np.asarray(kv_lens) == 0
    np.testing.assert_array_equal(np.asarray(out)[dead], 0.0)


def test_paged_attention_grid_is_not_table_wide():
    """At the chat cell's shapes (32 lanes, 288 blocks of 16 tokens) no
    grid axis of the decode kernel is as wide as the block table: its work
    follows each lane's live pages, not the table's width."""
    from repro.jaxpr_inspect import iter_jaxprs
    B, KV, G, hd, ps, P, mb = 32, 2, 6, 128, 16, 9216, 288
    S = jax.ShapeDtypeStruct
    args = [S((B, KV, G, hd), jnp.bfloat16),
            S((P, KV, ps, hd), jnp.bfloat16), S((P, KV, ps, hd), jnp.bfloat16),
            S((B, mb), jnp.int32), S((B,), jnp.int32)]
    jaxpr = jax.make_jaxpr(
        lambda *a: ops.paged_attention(*a, interpret=True))(*args)
    grids = [eqn.params["grid_mapping"].grid
             for j in iter_jaxprs(jaxpr.jaxpr) for eqn in j.eqns
             if eqn.primitive.name == "pallas_call"
             and eqn.params["name"] == "paged_attention"]
    assert len(grids) == 1
    assert mb not in grids[0], grids[0]
    assert B in grids[0], grids[0]


@pytest.mark.parametrize("S,block", [(64, 16), (128, 64), (256, 32)])
def test_ring_scan_blocks(S, block):
    rng = np.random.default_rng(S)
    states = jnp.asarray(rng.integers(0, 4, S), jnp.int32)
    arrivals = jnp.asarray(rng.permutation(S), jnp.int32)
    got = ops.ring_scan_blocks(states, arrivals, want_state=1,
                               block_size=block)
    expect = ref.ring_scan_blocks_ref(states, arrivals, want_state=1,
                                      block_size=block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def test_ring_select_topk_fcfs_order():
    rng = np.random.default_rng(7)
    S = 128
    states = jnp.asarray(rng.integers(0, 3, S), jnp.int32)
    arrivals = jnp.asarray(rng.permutation(S), jnp.int32)
    ids, found = ops.ring_select_topk(states, arrivals, want_state=1, k=5,
                                      block_size=32)
    pend = np.where(np.asarray(states) == 1)[0]
    order = pend[np.argsort(np.asarray(arrivals)[pend])][:5]
    expect = np.full(5, -1)
    expect[: len(order)] = order
    np.testing.assert_array_equal(np.asarray(ids), expect)
    np.testing.assert_array_equal(np.asarray(found), expect >= 0)


@pytest.mark.parametrize("Bz,T,H,Pd,N,chunk", [
    (2, 32, 3, 16, 8, 8),
    (1, 64, 2, 32, 16, 16),
    (3, 16, 4, 8, 4, 16),   # chunk > T -> single chunk
])
def test_ssd_chunk_scan(Bz, T, H, Pd, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(Bz), 6)
    x = jax.random.normal(ks[0], (Bz, T, H, Pd)) * 0.5
    B_in = jax.random.normal(ks[1], (Bz, T, N)) * 0.5
    C_in = jax.random.normal(ks[2], (Bz, T, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (Bz, T, H)))
    A = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
    h0 = jax.random.normal(ks[5], (Bz, H, Pd, N)) * 0.1
    y_k, h_k = ops.ssd_chunk_scan(x, B_in, C_in, dt, A, h0, chunk=chunk)
    y_r, h_r = ref.ssd_sequential_ref(x, B_in, C_in, dt, A, h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-4)


def test_ssd_kernel_matches_model_chunked_form():
    """The Pallas kernel and the model's jnp chunked form agree."""
    Bz, T, H, Pd, N = 2, 32, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    x = jax.random.normal(ks[0], (Bz, T, H, Pd)) * 0.5
    B_in = jax.random.normal(ks[1], (Bz, T, N)) * 0.5
    C_in = jax.random.normal(ks[2], (Bz, T, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (Bz, T, H)))
    A = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
    h0 = jnp.zeros((Bz, H, Pd, N))
    y_k, h_k = ops.ssd_chunk_scan(x, B_in, C_in, dt, A, h0, chunk=8)
    y_j, h_j = ref.ssd_chunk_scan_ref(x, B_in, C_in, dt, A, h0, chunk=8)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_j), atol=1e-4)


# ---------------------------------------------------------------------------
# flash prefill attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,KV,G,hd,bq,bk", [
    (2, 16, 2, 2, 16, 128, 128),    # blocks clamp to T
    (3, 24, 2, 4, 32, 8, 8),        # multi-block
    (2, 20, 1, 2, 16, 8, 16),       # T not a block multiple -> left pad
    (1, 7, 1, 1, 8, 4, 4),          # odd everything
])
def test_flash_prefill_matches_reference(B, T, KV, G, hd, bq, bk):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, T, KV * G, hd), jnp.float32)
    k = jax.random.normal(keys[1], (B, T, KV, hd), jnp.float32)
    v = jax.random.normal(keys[2], (B, T, KV, hd), jnp.float32)
    offs = jnp.asarray(np.random.default_rng(1).integers(0, T, B), jnp.int32)
    out = ops.flash_prefill_attention(q, k, v, offs, block_q=bq, block_k=bk)
    expect = ref.flash_prefill_ref(q, k, v, offs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (5, 0.0), (0, 20.0),
                                            (9, 20.0)])
def test_flash_prefill_window_softcap(window, softcap):
    B, T, KV, G, hd = 2, 32, 2, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (B, T, KV * G, hd), jnp.float32)
    k = jax.random.normal(keys[1], (B, T, KV, hd), jnp.float32)
    v = jax.random.normal(keys[2], (B, T, KV, hd), jnp.float32)
    offs = jnp.array([0, 13], jnp.int32)
    out = ops.flash_prefill_attention(q, k, v, offs, window=window,
                                      softcap=softcap, block_q=8, block_k=8)
    expect = ref.flash_prefill_ref(q, k, v, offs, window=window,
                                   softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


def test_flash_prefill_dynamic_window_scans_over_layers():
    """The window is a traced scalar: a lax.scan over per-layer widths must
    produce per-layer results matching per-layer references (the gemma2
    local/global pattern through one compiled kernel)."""
    B, T, KV, G, hd = 2, 16, 1, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (B, T, KV * G, hd), jnp.float32)
    k = jax.random.normal(keys[1], (B, T, KV, hd), jnp.float32)
    v = jax.random.normal(keys[2], (B, T, KV, hd), jnp.float32)
    offs = jnp.array([0, 5], jnp.int32)
    windows = jnp.array([0, 4, 7], jnp.int32)

    def body(_, w):
        return None, ops.flash_prefill_attention(q, k, v, offs, window=w,
                                                 block_q=8, block_k=8)

    _, outs = jax.lax.scan(body, None, windows)
    for i, w in enumerate([0, 4, 7]):
        expect = ref.flash_prefill_ref(q, k, v, offs, window=w)
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(expect),
                                   atol=1e-5)


def test_flash_prefill_fully_padded_lane_is_finite():
    """offset == T (no valid tokens, e.g. an inactive engine lane) must
    yield zeros, not NaN, and not perturb sibling lanes."""
    B, T, KV, G, hd = 2, 8, 1, 1, 8
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (B, T, KV * G, hd), jnp.float32)
    k = jax.random.normal(keys[1], (B, T, KV, hd), jnp.float32)
    v = jax.random.normal(keys[2], (B, T, KV, hd), jnp.float32)
    offs = jnp.array([T, 0], jnp.int32)
    out = ops.flash_prefill_attention(q, k, v, offs, block_q=4, block_k=4)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out[0]), 0.0, atol=1e-6)
    expect = ref.flash_prefill_ref(q, k, v, offs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


# --- tensor-parallel head slicing: per-shard kernels == full kernel ---------
#
# The SPMD engine (ServeConfig.mesh_model_size > 1) runs these kernels
# inside shard_map bodies on contiguous head slices from
# distribution.sharding.head_partition. Heads are batch dimensions of
# every contraction, so each shard's math IS the single-device kernel on
# its slice — concatenating shard outputs over the head axis must be
# BITWISE equal to the full-width kernel. No mesh needed: the per-shard
# body is plain slicing, so this pins the engine's correctness argument
# on one device.

from repro.distribution.sharding import head_partition  # noqa: E402


@pytest.mark.parametrize("model_size", [2, 4])
@pytest.mark.parametrize("window,quant", [(0, False), (9, False), (0, True)])
def test_paged_attention_head_shards_concat_bitwise(model_size, window,
                                                    quant):
    B, KV, G, hd, P, ps, mb = 3, 4, 2, 32, 16, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, KV, ps, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, KV, ps, hd), jnp.float32)
    bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
    kv_lens = jnp.array([3, 17, 32])
    scales = {}
    if quant:
        rng = np.random.default_rng(3)
        scales = dict(
            k_scale=_lanes(jnp.asarray(
                np.abs(rng.standard_normal((P, KV, ps))) / 30 + 1e-3,
                jnp.bfloat16)),
            v_scale=_lanes(jnp.asarray(
                np.abs(rng.standard_normal((P, KV, ps))) / 30 + 1e-3,
                jnp.bfloat16)))
        kp = jnp.asarray(np.clip(np.round(np.asarray(kp) * 30),
                                 -127, 127), jnp.int8)
        vp = jnp.asarray(np.clip(np.round(np.asarray(vp) * 30),
                                 -127, 127), jnp.int8)
    full = ops.paged_attention(q, kp, vp, bt, kv_lens, window=window,
                               **scales)
    parts = []
    for lo, hi in head_partition(KV, model_size):
        sub = {k2: v2[:, lo:hi] for k2, v2 in scales.items()}
        parts.append(ops.paged_attention(
            q[:, lo:hi], kp[:, lo:hi], vp[:, lo:hi], bt, kv_lens,
            window=window, **sub))
    got = jnp.concatenate(parts, axis=1)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(got))


@pytest.mark.parametrize("model_size", [2])
@pytest.mark.parametrize("window", [0, 9])
def test_flash_prefill_head_shards_concat_bitwise(model_size, window):
    B, T, KV, G, hd = 3, 24, 2, 3, 32
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(keys[0], (B, T, KV * G, hd), jnp.float32)
    k = jax.random.normal(keys[1], (B, T, KV, hd), jnp.float32)
    v = jax.random.normal(keys[2], (B, T, KV, hd), jnp.float32)
    offs = jnp.asarray(np.random.default_rng(1).integers(0, T, B), jnp.int32)
    full = ops.flash_prefill_attention(q, k, v, offs, window=window,
                                       block_q=8, block_k=8)
    qparts = head_partition(KV * G, model_size)
    kparts = head_partition(KV, model_size)
    parts = [ops.flash_prefill_attention(
        q[:, :, qlo:qhi], k[:, :, klo:khi], v[:, :, klo:khi], offs,
        window=window, block_q=8, block_k=8)
        for (qlo, qhi), (klo, khi) in zip(qparts, kparts)]
    got = jnp.concatenate(parts, axis=2)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(got))
