"""The dropless, share-aware expert layer on the serving path: served
logits against the plain float32 reference (prefill, chunked prefill and
decode over the paged cache, pallas kernels interpreted), the shares of an
expert-parallel layer adding up to the whole layer, batch invariance
(idle lanes and padded chunk rows take no expert rows), the expert
counters, and the grouped matmul against a dense product."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import weights as weights_lib
from bench.harness.moe_ref import MoeDecoder
from repro.configs.base import ModelConfig, ServeConfig
from repro.frontend.server import BlinkServer
from repro.kernels import ops
from repro.models import moe as moe_lib
from repro.models.api import cache_for_serve, make_model
from repro.telemetry import state as tel_lib

# Qwen3-MoE's form at test size: QK-norm, GQA 4/2, untied, 8 experts
# top-3, of which this chip holds experts 4-7
CFG = ModelConfig(
    name="qwen3-moe-test", arch_type="moe", num_layers=2, d_model=64,
    num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32, vocab_size=512,
    qk_norm=True, rope_theta=1e6, tie_embeddings=False, dtype="float32",
    num_experts=8, top_k=3, moe_d_ff=32, experts_held=4, first_expert=4)
SERVE = ServeConfig(num_slots=4, max_prompt_len=32, max_new_tokens=8,
                    decode_batch=4, page_size=4, num_pages=64,
                    attn_backend="pallas", prefill_block_q=8,
                    prefill_block_k=8)


def _ref(cfg):
    return MoeDecoder(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                      head_dim=cfg.resolved_head_dim,
                      rope_theta=cfg.rope_theta, norm_eps=1e-6,
                      top_k=cfg.top_k, first_expert=cfg.first_expert)


@pytest.fixture(scope="module")
def served():
    api = make_model(CFG, attn_backend="pallas", prefill_block_q=8,
                     prefill_block_k=8)
    params = weights_lib.make_params(api.param_specs(), 2 ** 31 + 5)
    return api, params


def _cache(api, slots):
    cache = cache_for_serve(api, SERVE)
    ppr = SERVE.pages_per_req
    bt = np.full((SERVE.num_slots, ppr), -1, np.int32)
    for i, s in enumerate(slots):
        bt[s] = np.arange(i * ppr, (i + 1) * ppr)
    cache["kv"] = dataclasses.replace(cache["kv"],
                                      block_table=jnp.asarray(bt))
    return cache


def _ref_logits(params, seq):
    ref = _ref(CFG)
    return np.asarray(ref.logits(params, ref.hidden(params,
                                                    jnp.asarray(seq))))


def _left_pad(seq, T):
    out = np.zeros((1, T), np.int32)
    out[0, T - len(seq):] = seq
    return jnp.asarray(out)


def test_served_logits_match_the_reference(served):
    """Prefill, then decode over the cache; chunked prefill; and the
    mixed step's batched chunks, each against the reference's logits."""
    api, params = served
    prefill, decode = jax.jit(api.prefill), jax.jit(api.decode)
    batched = jax.jit(api.prefill_batched)
    seq = np.random.default_rng(0).integers(3, CFG.vocab_size, 24)
    n = 13
    want = _ref_logits(params, seq)
    tol = dict(atol=2e-3, rtol=2e-3)

    cache = _cache(api, [0])
    slot, active = jnp.array([0]), jnp.array([True])
    lg, cache = prefill(params, _left_pad(seq[:n], 16), jnp.array([n]),
                        cache, slot, active)
    np.testing.assert_allclose(np.asarray(lg[0]), want[n - 1], **tol)
    for i in range(3):
        lg, cache = decode(params, jnp.asarray(seq[n + i: n + i + 1]),
                           cache, slot, active)
        np.testing.assert_allclose(np.asarray(lg[0]), want[n + i], **tol)

    cache = _cache(api, [1])
    lg, _ = jax.jit(functools.partial(api.prefill_chunked, chunk=8))(
        params, _left_pad(seq[:21], 24), jnp.array([21]), cache,
        jnp.array([1]), active)
    np.testing.assert_allclose(np.asarray(lg[0]), want[20], **tol)

    # two lanes' chunks in one dispatch: lane 0 resumes at its cursor,
    # lane 1 is an empty (padded) row
    cache = _cache(api, [2])
    slots2, act2 = jnp.array([2, 3]), jnp.array([True, False])
    for lo, hi in ((0, 8), (8, n)):
        lg, cache = batched(
            params, jnp.concatenate([_left_pad(seq[lo:hi], 8),
                                     jnp.zeros((1, 8), jnp.int32)]),
            jnp.array([hi - lo, 0]), cache, slots2, act2,
            jnp.array([lo, 0]))
    np.testing.assert_allclose(np.asarray(lg[0]), want[n - 1], **tol)


def _layer_params(cfg, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    D, F, G, E = cfg.d_model, cfg.moe_d_ff, cfg.held_experts, cfg.num_experts
    return {
        "router": jax.random.normal(k[0], (D, E)) / np.sqrt(D),
        "w_gate": jax.random.normal(k[1], (G, D, F)) / np.sqrt(D),
        "w_up": jax.random.normal(k[2], (G, D, F)) / np.sqrt(D),
        "w_down": jax.random.normal(k[3], (G, F, D)) / np.sqrt(F),
    }


def test_expert_shares_add_up_to_the_whole_layer():
    """8 experts top-4 over 4 ranks of 2: the ranks' partial outputs sum
    to the uncut layer, and to the reference's dense layer."""
    whole = CFG.replace(num_experts=8, top_k=4, experts_held=0,
                        first_expert=0)
    p = _layer_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, whole.d_model))
    valid = jnp.ones((3, 5), bool)
    full, counts = moe_lib.expert_layer(p, whole, x, valid)
    parts, rows = 0.0, 0
    for r in range(4):
        share = whole.replace(experts_held=2, first_expert=2 * r)
        pr = dict(p, **{n: p[n][2 * r: 2 * r + 2]
                        for n in ("w_gate", "w_up", "w_down")})
        out, c = moe_lib.expert_layer(pr, share, x, valid)
        parts, rows = parts + out, rows + int(c[0])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(full),
                               atol=1e-5, rtol=1e-5)
    assert rows == int(counts[0]) == 3 * 5 * 4        # dropless
    dense = jax.vmap(lambda xb: _ref(whole).experts_out(xb, p))(x)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dense),
                               atol=1e-4, rtol=1e-4)


def _capacity_dispatch(p, cfg, x, factor=1.25):
    """The fixed-capacity dispatch the serving path used before the
    dropless layer: each expert keeps its first ``C`` (row, choice) pairs
    in row order and drops the rest."""
    B, T, D = x.shape
    N, k, E = B * T, cfg.top_k, cfg.num_experts
    xf = x.reshape(N, D)
    w, e = moe_lib.router_topk(xf @ p["router"], k)
    C = max(8, -(-int(N * k / E * factor) // 8) * 8)
    onehot = jax.nn.one_hot(e.reshape(-1), E, dtype=jnp.int32)
    keep = jnp.sum((jnp.cumsum(onehot, 0) - 1) * onehot, 1).reshape(N, k) < C
    ys = jax.vmap(lambda g, u, d: (jax.nn.silu(xf @ g) * (xf @ u)) @ d)(
        p["w_gate"], p["w_up"], p["w_down"])                  # [E, N, D]
    y = ys[e, jnp.arange(N)[:, None]]                         # [N, k, D]
    return jnp.sum(jnp.where(keep[..., None], y, 0.0) * w[..., None],
                   1).reshape(B, T, D)


def test_idle_lanes_and_padding_take_no_expert_rows():
    """One real row among 63 padded ones gives the output it gives alone,
    and the padding is counted nowhere. A fixed-capacity dispatch does
    not: identical padded rows all choose the same experts and fill their
    capacity before the real row, which sorts last."""
    cfg = CFG.replace(num_experts=4, top_k=2, experts_held=0,
                      first_expert=0)
    p = _layer_params(cfg, seed=3)
    real = jax.random.normal(jax.random.PRNGKey(4), (1, 1, cfg.d_model))
    p["router"] = p["router"].at[:, 0].add(
        1e3 * real[0, 0] / jnp.sum(real[0, 0] ** 2))     # it picks expert 0
    alone, c1 = moe_lib.expert_layer(p, cfg, real, jnp.ones((1, 1), bool))
    batch = jnp.zeros((1, 64, cfg.d_model)).at[0, 63].set(real[0, 0])
    valid = jnp.zeros((1, 64), bool).at[0, 63].set(True)
    beside, c64 = moe_lib.expert_layer(p, cfg, batch, valid)
    np.testing.assert_allclose(np.asarray(beside[0, 63]),
                               np.asarray(alone[0, 0]), atol=1e-6)
    assert np.asarray(c64).tolist() == np.asarray(c1).tolist() == [2, 2]
    old_alone = _capacity_dispatch(p, cfg, real)
    old_beside = _capacity_dispatch(p, cfg, batch)
    np.testing.assert_allclose(np.asarray(old_alone[0, 0]),
                               np.asarray(alone[0, 0]), atol=1e-5)
    assert float(jnp.max(jnp.abs(old_beside[0, 63] - old_alone[0, 0]))) \
        > 1e-3


def test_a_request_decodes_alike_alone_and_beside_idle_lanes(served):
    """Through the model: a lane's logits are the same whether its chunk
    and its decode step run alone, or beside other requests' chunks, a
    padded chunk row and idle decode lanes holding junk tokens."""
    api, params = served
    decode, batched = jax.jit(api.decode), jax.jit(api.prefill_batched)
    seq = np.random.default_rng(1).integers(3, CFG.vocab_size, 12)
    prompt = np.pad(seq[:10], (6, 0)).astype(np.int32)

    cache = _cache(api, [0])
    one, on = jnp.array([0]), jnp.array([True])
    lg, cache = batched(params, jnp.asarray(prompt[None]), jnp.array([10]),
                        cache, one, on, jnp.array([0]))
    alone = [np.asarray(lg[0])]
    lg, _ = decode(params, jnp.asarray(seq[10:11]), cache, one, on)
    alone.append(np.asarray(lg[0]))

    cache = _cache(api, [0, 1, 2, 3])
    slots = jnp.arange(4)
    prompts = jnp.asarray(np.stack([prompt, np.full(16, 7), np.full(16, 9),
                                    np.full(16, 11)]).astype(np.int32))
    lg, cache = batched(
        params, prompts, jnp.array([10, 16, 16, 0]), cache, slots,
        jnp.array([True, True, True, False]), jnp.zeros(4, jnp.int32))
    beside = [np.asarray(lg[0])]
    lg, _ = decode(params, jnp.array([seq[10], 5, 6, 8]), cache, slots,
                   jnp.array([True, False, False, False]))
    beside.append(np.asarray(lg[0]))
    for a, b in zip(alone, beside):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_expert_counters_match_the_references_routing():
    cfg = CFG
    p = _layer_params(cfg, seed=7)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 9, cfg.d_model))
    valid = jnp.arange(9)[None, :] >= jnp.array([[2], [6]])   # left pads
    _, counts = moe_lib.expert_layer(p, cfg, x, valid)
    _, idx = _ref(cfg).expert_weights(x.reshape(-1, cfg.d_model),
                                      p["router"])
    idx = np.asarray(idx)[np.asarray(valid).reshape(-1)]
    held = idx[(idx >= cfg.first_expert)
               & (idx < cfg.first_expert + cfg.held_experts)]
    assert np.asarray(counts).tolist() == [held.size, np.unique(held).size]


def test_counter_rows_carry_the_expert_columns(served):
    api, params = served
    serve = dataclasses.replace(SERVE, num_slots=4, window=4,
                                prefill_chunk_tokens=8,
                                max_prefills_per_step=2, eos_token=-1)
    srv = BlinkServer(api, serve, params)
    for n in (5, 11):
        srv.submit(list(range(3, 3 + n)), max_new=4)
    srv.run_until_idle(max_windows=20)
    rows = srv.counter_totals.sums
    col = tel_lib.COL
    assert rows[col["expert_rows"]] > 0
    assert 0 < rows[col["experts_hit"]] <= rows[col["expert_rows"]]
    # every token of every layer routes top_k choices; some land here
    tokens = 5 + 11 + 2 * 3
    assert rows[col["expert_rows"]] <= tokens * CFG.num_layers * CFG.top_k


@pytest.mark.parametrize("sizes", [[3, 0, 140, 20], [0, 0, 0, 0],
                                   [128, 0, 0, 1]])
def test_grouped_matmul_matches_a_dense_product(sizes):
    rng = np.random.default_rng(0)
    G, K, N, M = 4, 32, 48, 256
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((G, K, N)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((G, K, N)), jnp.float32)
    s = jnp.asarray(sizes, jnp.int32)
    out = np.asarray(ops.expert_gmm(x, w, s))
    glu = np.asarray(ops.expert_gmm(x, w, s, w2=w2))
    off = np.concatenate([[0], np.cumsum(sizes)])
    xn, wn, w2n = np.asarray(x), np.asarray(w), np.asarray(w2)
    for g in range(G):
        xs = xn[off[g]:off[g + 1]]
        a, b = xs @ wn[g], xs @ w2n[g]
        np.testing.assert_allclose(out[off[g]:off[g + 1]], a,
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(glu[off[g]:off[g + 1]],
                                   a / (1 + np.exp(-a)) * b,
                                   atol=1e-3, rtol=1e-3)
