"""Unified ragged attention kernel: equivalence + epilogue + validation.

The ragged kernel (``kernels.ragged_attention``) replaces the
paged-decode/flash-prefill split with ONE dispatch per engine iteration:
decode lanes are q_len=1 rows, prefill chunks are ragged rows, dead lanes
are q_len=0 rows, all in the same grid. Four invariant families:

  * attention equivalence — random mixed batches (decode-only,
    prefill-only, mixed, all-dead, single-token prompts; sliding-window x
    int8 x fused-layout combos) against the gather oracle
    (``ref.ragged_attention_ref`` -> ``flash_prefill_ref``), across
    ``block_q`` / ``pages_per_block`` tilings (the autotune sweep axes);
  * KV-write epilogue — the kernel's fused pool merge (satellite of the
    unification: int8 quantise happens in the epilogue, not a jnp
    round-trip) is BITWISE equal to ``cache.write_kv_layer`` for every
    pool layout x dtype combo, scales compared by bit pattern;
  * ragged metadata — ``build_cu_lens`` is monotone and bounds-respecting
    (seeded floor here; the hypothesis-driven variant lives in
    ``test_properties.py``);
  * the compiled-mode tiling validation layer and the engine's
    config/api ``attn_unified`` handshake reject bad configs with
    actionable errors (exercised with ``interpret=False`` — the
    validation must work on CPU, before any TPU is near), and interpret
    mode follows the platform.

Whole-engine invariants (one attention dispatch per traced mixed step, no
jnp quantise staging, unified==split token streams) live in
``test_scheduler_diff.py``.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.ragged_attention import build_cu_lens
from repro.models import cache as cache_lib

KV, G, HD = 2, 2, 16
PS, MB, P = 4, 8, 32


def _pages_for(q_lens, cached):
    """Sequential block table covering each row's kv_len, -1 elsewhere."""
    B = len(q_lens)
    bt = -np.ones((B, MB), np.int32)
    nxt = 0
    for b in range(B):
        kv = int(q_lens[b]) + int(cached[b])
        for j in range(-(-kv // PS)):
            bt[b, j] = nxt
            nxt += 1
    assert nxt <= P
    return bt


def _make_batch(seed, q_lens, cached, *, dtype=np.float32, quant=False,
                fused=False):
    """Left-padded q/k/v + pools + ragged metadata for one test case."""
    rng = np.random.default_rng(seed)
    q_lens = np.asarray(q_lens, np.int32)
    cached = np.asarray(cached, np.int32)
    B = len(q_lens)
    T = max(8, int(q_lens.max(initial=0)))
    bt = _pages_for(q_lens, cached)
    q = rng.standard_normal((B, T, KV * G, HD)).astype(dtype)
    k = np.zeros((B, T, KV, HD), dtype)
    v = np.zeros((B, T, KV, HD), dtype)
    for b in range(B):
        L = int(q_lens[b])
        off = T - L
        q[b, :off] = 0
        k[b, off:] = rng.standard_normal((L, KV, HD))
        v[b, off:] = rng.standard_normal((L, KV, HD))
    kp = rng.standard_normal((P, KV, PS, HD))
    vp = rng.standard_normal((P, KV, PS, HD))
    pools = {}
    if quant:
        # [P, KV, lanes] with token t in lane t; the padding lanes hold
        # junk on purpose — nothing may read them
        lanes = cache_lib.scale_lanes(PS)
        pools["k_scale"] = jnp.asarray(
            (np.abs(rng.standard_normal((P, KV, lanes))) / 30 + 1e-3),
            jnp.bfloat16)
        pools["v_scale"] = jnp.asarray(
            (np.abs(rng.standard_normal((P, KV, lanes))) / 30 + 1e-3),
            jnp.bfloat16)
        kp = np.clip(np.round(kp * 30), -127, 127).astype(np.int8)
        vp = np.clip(np.round(vp * 30), -127, 127).astype(np.int8)
    else:
        kp = kp.astype(dtype)
        vp = vp.astype(dtype)
    if fused:
        pools["kv_fused"] = jnp.asarray(np.stack([kp, vp], axis=2))
    else:
        pools["k_pages"] = jnp.asarray(kp)
        pools["v_pages"] = jnp.asarray(vp)
    cu_q, cu_kv = build_cu_lens(jnp.asarray(q_lens), jnp.asarray(cached))
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), cu_q, cu_kv,
            jnp.asarray(bt)), pools


# decode lanes, chunk resuming mid-page, dead lane, fresh prefill — one
# batch exercising every row species the unified engine step emits
SCENARIOS = {
    "mixed": ([1, 5, 0, 8], [9, 6, 3, 0]),
    "decode_only": ([1, 1, 1, 1], [9, 5, 13, 1]),
    "prefill_only": ([6, 8, 3, 1], [0, 0, 0, 0]),
    "all_dead": ([0, 0, 0, 0], [4, 0, 9, 2]),
    "single_token_prompts": ([1, 1], [0, 3]),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
@pytest.mark.parametrize("window,quant,fused", [
    (0, False, False), (6, False, False), (0, True, False),
    (0, False, True), (6, True, True),
])
def test_ragged_matches_gather_oracle(name, window, quant, fused):
    q_lens, cached = SCENARIOS[name]
    args, pools = _make_batch(hash(name) % 997, q_lens, cached,
                              quant=quant, fused=fused)
    out = ops.ragged_attention(*args, window=window, block_q=4,
                               pages_per_block=2, **pools)
    expect = ref.ragged_attention_ref(*args, window=window, **pools)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=2e-5)
    # dead rows (q_len == 0) and left-pad rows contribute exact zeros
    q_lens = np.asarray(q_lens)
    T = args[0].shape[1]
    for b in range(len(q_lens)):
        np.testing.assert_array_equal(
            np.asarray(out)[b, :T - q_lens[b]], 0.0)


@pytest.mark.parametrize("block_q,ppb", [(2, 1), (4, 2), (8, 3), (16, 8)])
def test_ragged_tiling_sweep(block_q, ppb):
    """Output is tiling-invariant — the autotune sweep axes
    (``block_q`` x ``pages_per_block``) must never change results, only
    speed (oversized tiles included: 16 > T, 8 pages > any row)."""
    args, pools = _make_batch(3, *SCENARIOS["mixed"])
    expect = ref.ragged_attention_ref(*args, **pools)
    out = ops.ragged_attention(*args, block_q=block_q,
                               pages_per_block=ppb, **pools)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)


def test_ragged_softcap_bfloat16():
    args, pools = _make_batch(11, *SCENARIOS["mixed"], dtype=jnp.bfloat16)
    out = ops.ragged_attention(*args, softcap=20.0, block_q=4,
                               pages_per_block=2, **pools)
    expect = ref.ragged_attention_ref(*args, softcap=20.0, **pools)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=2e-2)


# --- KV-write epilogue: bitwise vs cache.write_kv_layer ---------------------


@pytest.mark.parametrize("dtype,pool_dtype,fused", [
    (np.float32, "fp", False),
    (np.float32, "int8", False),
    (np.float32, "fp", True),
    (np.float32, "int8", True),
    ("bfloat16", "fp", False),
    ("bfloat16", "int8", True),
])
def test_epilogue_bitwise_equals_write_kv_layer(dtype, pool_dtype, fused):
    """The kernel's KV-merge epilogue (fused int8 quantise included) lands
    the SAME bytes as the jnp scatter path it replaces — pools bitwise,
    bf16 scales compared via their bit patterns. Rows cover a decode
    token, a chunk resuming mid-page, a dead lane and a fresh prefill."""
    dtype = jnp.bfloat16 if dtype == "bfloat16" else dtype
    quant = pool_dtype == "int8"
    q_lens = np.array([1, 5, 0, 8], np.int32)
    cached = np.array([9, 6, 3, 0], np.int32)
    args, pools = _make_batch(5, q_lens, cached, dtype=dtype, quant=quant,
                              fused=fused)
    _, k, v, cu_q, cu_kv, bt = args

    # reference: the jnp scatter path on a split-pool cache
    if fused:
        kvf = np.asarray(pools["kv_fused"])
        kp, vp = kvf[:, :, 0], kvf[:, :, 1]
    else:
        kp, vp = np.asarray(pools["k_pages"]), np.asarray(pools["v_pages"])
    c = cache_lib.PagedKVCache(
        k_pages=jnp.asarray(kp)[None], v_pages=jnp.asarray(vp)[None],
        block_table=bt, seq_lens=jnp.asarray(cached),
        k_scale=pools["k_scale"][None] if quant else None,
        v_scale=pools["v_scale"][None] if quant else None)
    T = k.shape[1]
    c2 = cache_lib.write_kv_layer(
        c, 0, jnp.arange(len(q_lens)), k, v,
        start_pos=jnp.asarray(cached) - (T - jnp.asarray(q_lens)),
        lengths=jnp.asarray(cached + q_lens),
        active=jnp.asarray(q_lens) > 0, min_pos=jnp.asarray(cached))

    res = ops.ragged_attention(*args, block_q=4, pages_per_block=2,
                               writes_kv=True, **pools)
    got = list(res[1:])
    if fused:
        fzd = np.asarray(got.pop(0))
        gk, gv = fzd[:, :, 0], fzd[:, :, 1]
    else:
        gk, gv = np.asarray(got.pop(0)), np.asarray(got.pop(0))
    np.testing.assert_array_equal(gk, np.asarray(c2.k_pages[0]))
    np.testing.assert_array_equal(gv, np.asarray(c2.v_pages[0]))
    if quant:
        for got_s, ref_s in zip(got, (c2.k_scale[0], c2.v_scale[0])):
            np.testing.assert_array_equal(
                np.asarray(got_s).view(np.uint16),
                np.asarray(ref_s).view(np.uint16))


# --- ragged metadata: build_cu_lens (seeded floor) --------------------------


@pytest.mark.parametrize("seed", range(8))
def test_build_cu_lens_monotone_and_bounded(seed):
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 12))
    q_lens = rng.integers(0, 9, B).astype(np.int32)
    cached = rng.integers(0, 33, B).astype(np.int32)
    cu_q, cu_kv = build_cu_lens(jnp.asarray(q_lens), jnp.asarray(cached))
    cu_q, cu_kv = np.asarray(cu_q), np.asarray(cu_kv)
    assert cu_q.dtype == np.int32 and cu_kv.dtype == np.int32
    assert cu_q.shape == cu_kv.shape == (B + 1,)
    assert cu_q[0] == 0 and cu_kv[0] == 0
    assert (np.diff(cu_q) >= 0).all() and (np.diff(cu_kv) >= 0).all()
    np.testing.assert_array_equal(np.diff(cu_q), q_lens)
    np.testing.assert_array_equal(np.diff(cu_kv), q_lens + cached)
    # per-row bounds: every row's span fits inside the totals
    assert cu_q[-1] == q_lens.sum() and cu_kv[-1] == (q_lens + cached).sum()
    # q span never exceeds kv span (causal: cached prefix only grows kv)
    assert (np.diff(cu_q) <= np.diff(cu_kv)).all()


# --- compiled-mode tiling validation (interpret=False on CPU) ---------------


def test_validate_compiled_tiling_accepts_aligned():
    ops.validate_compiled_tiling(head_dim=128, block_q=128, block_k=128,
                                 pages_per_block=2, attn_unified=True,
                                 interpret=False)


@pytest.mark.parametrize("kw,needle", [
    (dict(head_dim=20), "head_dim=20"),
    (dict(head_dim=64), "head_dim=64"),
    (dict(block_q=0), "prefill_block_q=0"),
    (dict(block_k=12), "prefill_block_k=12"),
    (dict(block_k=0), "prefill_block_k=0"),
    (dict(pages_per_block=0), "attn_pages_per_block=0"),
])
def test_validate_compiled_tiling_rejects(kw, needle):
    """Each setting the TPU compiler refuses is named in the error with
    its value and a concrete fix — actionable on CPU, before any TPU
    lowering runs."""
    base = dict(head_dim=128, block_q=128, block_k=128, pages_per_block=1,
                attn_unified=True, interpret=False)
    base.update(kw)
    with pytest.raises(ValueError, match="interpret=False") as err:
        ops.validate_compiled_tiling(**base)
    assert needle in str(err.value)


def test_validate_compiled_tiling_noop_in_interpret():
    """On the CPU kernels run interpreted, where any positive tile works,
    so the compiled-mode rules are not applied."""
    assert jax.default_backend() == "cpu" and ops.interpret_mode()
    ops.validate_compiled_tiling(head_dim=20, block_q=3, block_k=5,
                                 pages_per_block=0,
                                 attn_unified=True)  # no raise


def test_make_model_validates_tiling_compiled(monkeypatch):
    """make_model runs the validation — a bad tile dies at model build on
    the TPU (steered here by reporting the platform as "tpu"), not at
    first dispatch."""
    from repro.configs.registry import TINY_ARCHS
    from repro.models.api import make_model
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="prefill_block_k=12"):
        make_model(TINY_ARCHS["qwen2-1.5b"], prefill_block_k=12)


@pytest.mark.parametrize("platform,interpret,expect", [
    ("cpu", None, True), ("cpu", False, False), ("tpu", None, False),
    ("tpu", True, ValueError), ("gpu", None, RuntimeError),
])
def test_interpret_mode_follows_platform(monkeypatch, platform, interpret,
                                         expect):
    """Interpreted only on the CPU; the TPU always compiles (asking it to
    interpret raises); any other platform has no kernel path."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    if isinstance(expect, bool):
        assert ops.interpret_mode(interpret) is expect
    else:
        with pytest.raises(expect):
            ops.interpret_mode(interpret)


# --- engine config/api handshake -------------------------------------------


def test_engine_rejects_attn_unified_mismatch():
    from repro.configs.base import ServeConfig
    from repro.configs.registry import TINY_ARCHS
    from repro.core import engine as eng
    from repro.models.api import make_model
    api = make_model(TINY_ARCHS["qwen2-1.5b"])  # split api
    serve = ServeConfig(num_slots=8, max_prompt_len=16, max_new_tokens=4,
                        decode_batch=2, window=1, admit_per_step=1,
                        page_size=4, num_pages=16, eos_token=-1,
                        prefill_chunk_tokens=8, attn_unified=True)
    with pytest.raises(ValueError, match="attn_unified"):
        eng.init_engine_state(api, serve, seed=0)


def test_serve_config_rejects_unified_without_chunking():
    from repro.configs.base import ServeConfig
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ServeConfig(num_slots=8, max_prompt_len=16, max_new_tokens=4,
                    decode_batch=2, window=1, admit_per_step=1,
                    page_size=4, num_pages=16, eos_token=-1,
                    attn_unified=True)


def test_make_model_rejects_fused_without_unified():
    from repro.configs.registry import TINY_ARCHS
    from repro.models.api import make_model
    with pytest.raises(ValueError, match="kv_fused_layout"):
        make_model(TINY_ARCHS["qwen2-1.5b"], kv_fused_layout=True)


# --- tensor-parallel head slicing: per-shard ragged == full ragged ----------
#
# The SPMD unified step (ServeConfig.mesh_model_size > 1) runs THIS kernel
# inside a shard_map body on contiguous head slices. Heads are batch dims
# of every contraction, so concatenating per-shard outputs (and, with
# writes_kv, per-shard updated pools) over the head axis must be BITWISE
# equal to the full-width kernel — the single-device proof of the sharded
# engine's correctness argument, int8 and sliding-window included.


@pytest.mark.parametrize("name", ["mixed", "decode_only"])
@pytest.mark.parametrize("window,quant", [(0, False), (6, False), (0, True)])
def test_ragged_head_shards_concat_bitwise(name, window, quant):
    from repro.distribution.sharding import head_partition
    model_size = 2                     # KV = 2 here: one kv head per shard
    q_lens, cached = SCENARIOS[name]
    args, pools = _make_batch(hash(name) % 997, q_lens, cached, quant=quant)
    q, k, v, cu_q, cu_kv, bt = args
    full = ops.ragged_attention(*args, window=window, block_q=4,
                                pages_per_block=2, writes_kv=True, **pools)
    att_parts, pool_parts = [], []
    qparts = head_partition(KV * G, model_size)
    kparts = head_partition(KV, model_size)
    for (qlo, qhi), (klo, khi) in zip(qparts, kparts):
        sub = {n: p[:, klo:khi] for n, p in pools.items()}
        res = ops.ragged_attention(
            q[:, :, qlo:qhi], k[:, :, klo:khi], v[:, :, klo:khi],
            cu_q, cu_kv, bt, window=window, block_q=4, pages_per_block=2,
            writes_kv=True, **sub)
        att_parts.append(res[0])
        pool_parts.append(res[1:])
    np.testing.assert_array_equal(
        np.asarray(full[0]), np.asarray(jnp.concatenate(att_parts, axis=2)))
    # updated pools (and int8 scales) reassemble bitwise over the kv axis
    for i, full_pool in enumerate(full[1:]):
        got = jnp.concatenate([p[i] for p in pool_parts], axis=1)
        if str(np.asarray(full_pool).dtype) == "bfloat16":  # int8 scales
            np.testing.assert_array_equal(
                np.asarray(got).view(np.uint16),
                np.asarray(full_pool).view(np.uint16))
        else:
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(full_pool))
