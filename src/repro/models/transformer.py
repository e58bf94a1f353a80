"""Decoder-only model family: dense / MoE / VLM / SSM (RWKV-6) / hybrid (Zamba2).

Design rules:
  * per-layer params are stacked on a leading [L] axis and consumed through
    ``jax.lax.scan`` — compact HLO even for 64-layer configs;
  * three entry points per family: ``train_loss`` (full causal),
    ``prefill`` (left-padded prompt -> cache + first logits), ``decode``
    (one token against the persistent cache). Prefill and decode are pure
    functions over an explicit cache pytree so the Blink engine can run them
    inside its persistent window program;
  * prompts are LEFT-padded so every lane's last token sits at index T-1 —
    this makes SSM state handoff exact and last-logit extraction uniform.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attn_backend as attn_backend_lib
from repro.models import cache as cache_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    apply_rope, attn_out, embed, gqa_attend, mlp, norm, qkv_project, unembed,
)

def layer_scan(f, init, xs, length=None):
    """jax.lax.scan that fully unrolls when REPRO_SCAN_UNROLL=1.

    XLA's cost_analysis counts while-loop bodies ONCE (trip counts are not
    folded in); the dry-run sets this env var so the roofline FLOP/byte
    terms are exact. Runtime paths keep the rolled loop (compact HLO)."""
    unroll = os.environ.get("REPRO_SCAN_UNROLL") == "1"
    return jax.lax.scan(f, init, xs, length=length, unroll=True if unroll
                        else 1)


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

_INIT = {
    "normal": lambda key, shape, dt, fan: (
        jax.random.normal(key, shape, jnp.float32) / np.sqrt(max(fan, 1))
    ).astype(dt),
    "zeros": lambda key, shape, dt, fan: jnp.zeros(shape, dt),
    "half": lambda key, shape, dt, fan: jnp.full(shape, 0.5, dt),
    "decay": lambda key, shape, dt, fan: jnp.full(shape, -0.6, dt),
    "alog": lambda key, shape, dt, fan: jnp.zeros(shape, dt),
    "ones": lambda key, shape, dt, fan: jnp.ones(shape, dt),
}


def _leaf(shape, init="normal", dtype=None):
    return {"shape": tuple(int(s) for s in shape), "init": init, "dtype": dtype}


def _attn_leaves(cfg: ModelConfig, L: int, prefix_dims=()) -> Dict[str, Any]:
    D, H, KV, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                    cfg.resolved_head_dim)
    lead = (L,) if L else ()
    out = {
        "wq": _leaf(lead + (D, H * hd)),
        "wk": _leaf(lead + (D, KV * hd)),
        "wv": _leaf(lead + (D, KV * hd)),
        "wo": _leaf(lead + (H * hd, D)),
    }
    if cfg.qkv_bias:
        out["bq"] = _leaf(lead + (H * hd,), "zeros")
        out["bk"] = _leaf(lead + (KV * hd,), "zeros")
        out["bv"] = _leaf(lead + (KV * hd,), "zeros")
    return out


def _mlp_leaves(cfg: ModelConfig, L: int) -> Dict[str, Any]:
    D, F = cfg.d_model, cfg.d_ff
    lead = (L,) if L else ()
    return {
        "w_gate": _leaf(lead + (D, F)),
        "w_up": _leaf(lead + (D, F)),
        "w_down": _leaf(lead + (F, D)),
    }


def _moe_leaves(cfg: ModelConfig, L: int) -> Dict[str, Any]:
    D, E, Fe = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    lead = (L,) if L else ()
    out = {
        "router": _leaf(lead + (D, E)),
        "w_gate": _leaf(lead + (E, D, Fe)),
        "w_up": _leaf(lead + (E, D, Fe)),
        "w_down": _leaf(lead + (E, Fe, D)),
    }
    if cfg.shared_expert_d_ff:
        Fs = cfg.shared_expert_d_ff
        out.update({
            "ws_gate": _leaf(lead + (D, Fs)),
            "ws_up": _leaf(lead + (D, Fs)),
            "ws_down": _leaf(lead + (Fs, D)),
            "shared_gate": _leaf(lead + (D,), "zeros"),
        })
    return out


def _rwkv_leaves(cfg: ModelConfig, L: int) -> Dict[str, Any]:
    D, F = cfg.d_model, cfg.d_ff
    H, hd = ssm_lib.rwkv_heads(cfg)
    R = 64  # decay LoRA rank
    lead = (L,)
    return {
        "ln1": _leaf(lead + (D,), "zeros"),
        "ln2": _leaf(lead + (D,), "zeros"),
        **{f"mu_{n}": _leaf(lead + (D,), "half") for n in "rkvgw"},
        "wr": _leaf(lead + (D, D)),
        "wk": _leaf(lead + (D, D)),
        "wv": _leaf(lead + (D, D)),
        "wg": _leaf(lead + (D, D)),
        "wo": _leaf(lead + (D, D)),
        "w_lora_a": _leaf(lead + (D, R)),
        "w_lora_b": _leaf(lead + (R, D)),
        "w_decay": _leaf(lead + (H, hd), "decay"),
        "u_bonus": _leaf(lead + (H, hd), "zeros"),
        "cm_mu_k": _leaf(lead + (D,), "half"),
        "cm_mu_r": _leaf(lead + (D,), "half"),
        "cm_wk": _leaf(lead + (D, F)),
        "cm_wv": _leaf(lead + (F, D)),
        "cm_wr": _leaf(lead + (D, D)),
    }


def _mamba_leaves(cfg: ModelConfig, L: int) -> Dict[str, Any]:
    D = cfg.d_model
    di, H, N = ssm_lib.mamba2_dims(cfg)
    lead = (L,)
    return {
        "ln": _leaf(lead + (D,), "zeros"),
        "z_proj": _leaf(lead + (D, di)),
        "x_proj": _leaf(lead + (D, di)),
        "b_proj": _leaf(lead + (D, N)),
        "c_proj": _leaf(lead + (D, N)),
        "dt_proj": _leaf(lead + (D, H)),
        "conv_w": _leaf(lead + (cfg.ssm_conv, di)),
        "conv_b": _leaf(lead + (di,), "zeros"),
        "A_log": _leaf(lead + (H,), "alog"),
        "D_skip": _leaf(lead + (H,), "ones"),
        "dt_bias": _leaf(lead + (H,), "zeros"),
        "out_ln": _leaf(lead + (di,), "zeros"),
        "out_proj": _leaf(lead + (di, D)),
    }


def _dense_block_leaves(cfg: ModelConfig, L: int) -> Dict[str, Any]:
    out = {}
    if cfg.norm_type != "nonparametric_ln":
        out["ln1"] = _leaf((L, cfg.d_model), "zeros")
        out["ln2"] = _leaf((L, cfg.d_model), "zeros")
    out.update(_attn_leaves(cfg, L))
    if cfg.num_experts:
        out.update(_moe_leaves(cfg, L))
    else:
        out.update(_mlp_leaves(cfg, L))
    return out


def param_template(cfg: ModelConfig) -> Dict[str, Any]:
    """Nested dict of leaf descriptors for the whole model."""
    V, D, L = cfg.vocab_size, cfg.d_model, cfg.num_layers
    t: Dict[str, Any] = {"embed": _leaf((V, D))}
    if not cfg.tie_embeddings:
        t["unembed"] = _leaf((D, V))
    if cfg.norm_type != "nonparametric_ln":
        t["final_norm"] = _leaf((D,), "zeros")

    if cfg.is_encoder_decoder:
        from repro.models import encdec
        t.update(encdec.encdec_template(cfg))
        return t

    if cfg.arch_type == "ssm":
        t["blocks"] = _rwkv_leaves(cfg, L)
    elif cfg.arch_type == "hybrid":
        t["blocks"] = _mamba_leaves(cfg, L)
        shared = {}
        if cfg.norm_type != "nonparametric_ln":
            shared["ln1"] = _leaf((D,), "zeros")
            shared["ln2"] = _leaf((D,), "zeros")
        shared.update(_attn_leaves(cfg, 0))
        shared.update(_mlp_leaves(cfg, 0))
        t["shared_attn"] = shared
    else:  # dense / moe / vlm
        t["blocks"] = _dense_block_leaves(cfg, L)
    return t


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct tree — used by the dry-run (no allocation)."""
    dt = cfg.jnp_dtype
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf["shape"], leaf["dtype"] or dt),
        param_template(cfg),
        is_leaf=lambda x: isinstance(x, dict) and "shape" in x,
    )


def init_params(key: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    template = param_template(cfg)
    leaves, treedef = jax.tree.flatten(
        template, is_leaf=lambda x: isinstance(x, dict) and "shape" in x)
    keys = jax.random.split(key, len(leaves))
    dt = cfg.jnp_dtype
    out = []
    for k, leaf in zip(keys, leaves):
        shape = leaf["shape"]
        fan = shape[-2] if len(shape) >= 2 else shape[-1]
        out.append(_INIT[leaf["init"]](k, shape, leaf["dtype"] or dt, fan))
    return jax.tree.unflatten(treedef, out)


def count_params(cfg: ModelConfig) -> int:
    specs = jax.tree.leaves(param_specs(cfg))
    return int(sum(np.prod(s.shape) for s in specs))


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE counts top-k + shared experts only)."""
    total = count_params(cfg)
    if not cfg.num_experts:
        return total
    expert_leaf = 2 * cfg.d_model * cfg.moe_d_ff + cfg.moe_d_ff * cfg.d_model
    inactive = cfg.num_layers * (cfg.num_experts - cfg.top_k) * expert_leaf
    return total - inactive


# ---------------------------------------------------------------------------
# Per-layer window pattern (gemma2 local/global; mixtral SWA)
# ---------------------------------------------------------------------------


def window_array(cfg: ModelConfig) -> np.ndarray:
    """[L] int32, 0 = full attention, else sliding-window width."""
    L = cfg.num_attn_layers
    ws = np.zeros(L, np.int32)
    for i in range(L):
        w = cfg.layer_window(i)
        ws[i] = 0 if w is None else w
    return ws


# ---------------------------------------------------------------------------
# Dense/MoE/VLM block (training & prefill form: full self-attention)
# ---------------------------------------------------------------------------


class PrefillCtx(NamedTuple):
    """Paged-KV prefill context threaded through ``forward_hidden``.

    When present, the layer scan (a) routes attention through the pluggable
    ``attend`` prefill backend and (b) scatters each layer's K/V into the
    paged pool *inside* the scan body (``kvc`` rides the carry) — no
    ``[L, B, T, KV, hd]`` staging buffer, no second per-layer scatter pass.

    ``cached_lens`` (prefix reuse / chunked prefill): when not None, lane
    b's first ``cached_lens[b]`` tokens already live in the paged pool
    (through the slot's block table); the in-flight bucket holds only the
    suffix. Each layer's attention then folds the cached prefix in via a
    ``attn_backend.PagedPrefix`` view, and the K/V writes are clamped to
    positions >= cached so shared prefix pages stay read-only.
    """
    kvc: Any                 # PagedKVCache, threaded through the scan carry
    slot_ids: jax.Array      # [B]
    active: jax.Array        # [B] bool
    offset: jax.Array        # [B] left-pad columns (T - suffix_len)
    lengths: jax.Array       # [B] suffix lengths (in-flight tokens)
    attend: Callable         # prefill backend (attn_backend.get_prefill_backend)
    cached_lens: Optional[jax.Array] = None  # [B] cached prefix tokens


def _layer_prefix(ctx: PrefillCtx, kvc, layer):
    """PagedPrefix view of one layer's cached-prefix pages (None when the
    prefill carries no cached prefix)."""
    if ctx.cached_lens is None:
        return None
    return attn_backend_lib.PagedPrefix(
        block_rows=kvc.block_table[ctx.slot_ids],
        cached_lens=ctx.cached_lens, **cache_lib.layer_pools(kvc, layer))


def _pool_writeback(kvc, layer, pools):
    """Scatter one layer's updated pool arrays — returned by a unified
    backend whose kernel merges new K/V in its epilogue (``writes_kv``) —
    back into the cache at ``layer`` (a traced index inside the layer
    scan). Pool order matches ``kernels.ragged_attention``: values first
    (fused or split pair), then int8 scales."""
    pools = list(pools)
    new = {}
    if kvc.fused:
        new["kv_fused"] = kvc.kv_fused.at[layer].set(pools.pop(0))
    else:
        new["k_pages"] = kvc.k_pages.at[layer].set(pools.pop(0))
        new["v_pages"] = kvc.v_pages.at[layer].set(pools.pop(0))
    if kvc.quantized:
        new["k_scale"] = kvc.k_scale.at[layer].set(
            pools.pop(0).astype(kvc.k_scale.dtype))
        new["v_scale"] = kvc.v_scale.at[layer].set(
            pools.pop(0).astype(kvc.v_scale.dtype))
    return dataclasses.replace(kvc, **new)


def _dense_block(cfg: ModelConfig, bp: dict, x: jax.Array,
                 positions: jax.Array, window: jax.Array,
                 kv_mask: jax.Array, attend: Optional[Callable] = None,
                 offset: Optional[jax.Array] = None, prefix=None):
    """One transformer block over [B, T, D]. Returns (x, router_aux, (k, v)).

    ``attend``/``offset``: prefill-attention backend + left-pad widths; when
    None (training path) the inline ``gqa_attend`` reference runs.
    ``prefix``: optional ``PagedPrefix`` forwarded to the backend."""
    h = norm(cfg, x, bp.get("ln1"))
    q, k, v = qkv_project(bp, cfg, h)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    new_pools = None
    if attend is not None:
        att = attend(cfg, q, k, v, offset, window, prefix=prefix)
        if isinstance(att, tuple):
            # unified writes_kv backend: the kernel epilogue already merged
            # this layer's new K/V into the pool pages it returns
            att, new_pools = att[0], att[1:]
    else:
        # window: runtime scalar; 0 means full. Encode as huge width.
        eff_window = jnp.where(window > 0, window, jnp.int32(2**30))
        att = gqa_attend(q, k, v, q_positions=positions,
                         k_positions=positions, causal=True,
                         window=eff_window, kv_mask=kv_mask,
                         softcap=cfg.attn_softcap)
    x = x + attn_out(bp, att)
    h2 = norm(cfg, x, bp.get("ln2"))
    aux = jnp.float32(0)
    if cfg.num_experts:
        B, T, _ = h2.shape
        y, rl = moe_lib.moe_ffn(bp, cfg, h2, return_router_logits=True)
        aux = moe_lib.load_balance_loss(rl.reshape(B * T, -1), cfg.top_k,
                                        cfg.num_experts)
    else:
        y = mlp(bp, cfg, h2)
    return x + y, aux, (k, v) if new_pools is None else new_pools


def forward_hidden(params: dict, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array, kv_mask: jax.Array,
                   *, remat: bool = False,
                   prefill_ctx: Optional[PrefillCtx] = None):
    """Run the full stack over embeddings [B, T, D] (train/prefill path).

    Returns (hidden [B, T, D], aux_loss, extras).

    Without ``prefill_ctx`` (training / reference forward), extras is the
    per-layer (k, v) stacked [L, B, T, KV, hd] (pass-through of the scan's
    ys), or SSM final states for recurrent families.

    With ``prefill_ctx`` (paged-KV prefill), each layer's K/V are written
    into the paged pool inside the scan body (``write_kv_layer``, including
    int8 quantisation) and extras is the updated ``PagedKVCache`` (hybrid:
    ``(ssm_final_states, PagedKVCache)``) — the [L, B, T, KV, hd] staging
    buffer never exists.
    """
    if cfg.arch_type == "ssm":
        return _rwkv_forward(params, cfg, x, kv_mask, remat=remat)
    if cfg.arch_type == "hybrid":
        return _hybrid_forward(params, cfg, x, positions, kv_mask,
                               remat=remat, prefill_ctx=prefill_ctx)

    windows = jnp.asarray(window_array(cfg))

    if prefill_ctx is not None:
        ctx = prefill_ctx

        writes_kv = getattr(ctx.attend, "writes_kv", False)

        def body_write(carry, xs):
            h, aux, kvc = carry
            bp, layer, window = xs
            cached = ctx.cached_lens
            h, a, extras = _dense_block(cfg, bp, h, positions, window,
                                        kv_mask, attend=ctx.attend,
                                        offset=ctx.offset,
                                        prefix=_layer_prefix(ctx, kvc, layer))
            if writes_kv:
                # the unified kernel's epilogue merged this layer's new K/V
                # (int8: quantised in-kernel — no float staging tensor)
                kvc = _pool_writeback(kvc, layer, extras)
            else:
                k, v = extras
                start = -ctx.offset if cached is None else cached - ctx.offset
                total = ctx.lengths if cached is None else ctx.lengths + cached
                kvc = cache_lib.write_kv_layer(
                    kvc, layer, ctx.slot_ids, k, v, start_pos=start,
                    lengths=total, active=ctx.active, min_pos=cached)
            return (h, aux + a, kvc), None

        fn = jax.checkpoint(body_write) if remat else body_write
        (h, aux, kvc), _ = layer_scan(
            fn, (x, jnp.float32(0), ctx.kvc),
            (params["blocks"], jnp.arange(cfg.num_layers), windows))
        return h, aux, kvc

    def body_collect(carry, xs):
        h, aux = carry
        bp, window = xs
        h, a, kv = _dense_block(cfg, bp, h, positions, window, kv_mask)
        return (h, aux + a), kv

    fn = jax.checkpoint(body_collect) if remat else body_collect
    (h, aux), kvs = layer_scan(fn, (x, jnp.float32(0)),
                               (params["blocks"], windows))
    return h, aux, kvs


def _rwkv_forward(params: dict, cfg: ModelConfig, x: jax.Array,
                  kv_mask: jax.Array, *, remat: bool = False,
                  init_states: Optional[dict] = None):
    """RWKV stack over [B, T, D]. Returns (hidden, 0.0, final_states)."""
    B, T, _ = x.shape
    if init_states is None:
        st = ssm_lib.rwkv6_init_state(cfg, B)
        init_states = jax.tree.map(
            lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), st)

    def body(h, xs):
        bp, st = xs
        h, new_st = ssm_lib.rwkv6_layer_seq_chunked(bp, cfg, h, st, kv_mask)
        return h, new_st

    fn = jax.checkpoint(body) if remat else body
    h, final_states = layer_scan(fn, x, (params["blocks"], init_states))
    return h, jnp.float32(0), final_states


def _hybrid_forward(params: dict, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array, kv_mask: jax.Array,
                    *, remat: bool = False, init_states: Optional[dict] = None,
                    prefill_ctx: Optional[PrefillCtx] = None):
    """Zamba2-style stack: Mamba2 every layer, shared attention block every
    ``attn_every`` layers. Returns (hidden, 0.0, (ssm_states, attn_kvs)),
    where attn_kvs is (k, v) stacked [L, B, T, KV, hd] (zeros on non-attn
    layers) plus the [L] attn-layer flags.

    With ``prefill_ctx`` the shared-attn K/V are written straight into the
    paged pool at cache row ``layer_idx // attn_every`` inside the scan
    (the cond carries the cache) and the return is (hidden, 0.0,
    (ssm_states, PagedKVCache)) — no staging, no layer_select compression
    pass."""
    B, T, _ = x.shape
    if init_states is None:
        st = ssm_lib.mamba2_init_state(cfg, B)
        init_states = jax.tree.map(
            lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), st)
    sp = params["shared_attn"]
    every = cfg.attn_every
    ctx = prefill_ctx

    def attn_block(h):
        hh = norm(cfg, h, sp.get("ln1"))
        q, k, v = qkv_project(sp, cfg, hh)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        if ctx is not None:
            att = ctx.attend(cfg, q, k, v, ctx.offset, jnp.int32(0))
        else:
            att = gqa_attend(q, k, v, q_positions=positions,
                             k_positions=positions, causal=True,
                             kv_mask=kv_mask)
        h = h + attn_out(sp, att)
        h2 = norm(cfg, h, sp.get("ln2"))
        return h + mlp(sp, cfg, h2), (k, v)

    if ctx is not None:
        def body_write(carry, xs):
            h, kvc = carry
            bp, st, layer_idx = xs
            is_attn = (layer_idx % every) == 0

            def with_attn(operand):
                h, kvc = operand
                h, (k, v) = attn_block(h)
                kvc = cache_lib.write_kv_layer(
                    kvc, layer_idx // every, ctx.slot_ids, k, v,
                    start_pos=-ctx.offset, lengths=ctx.lengths,
                    active=ctx.active)
                return h, kvc

            h, kvc = jax.lax.cond(is_attn, with_attn, lambda o: o, (h, kvc))
            h, new_st = ssm_lib.mamba2_layer_seq_chunked(bp, cfg, h, st,
                                                         kv_mask)
            return (h, kvc), new_st

        fn = jax.checkpoint(body_write) if remat else body_write
        (h, kvc), final_states = layer_scan(
            fn, (x, ctx.kvc),
            (params["blocks"], init_states, jnp.arange(cfg.num_layers)))
        return h, jnp.float32(0), (final_states, kvc)

    def body(h, xs):
        bp, st, layer_idx = xs
        is_attn = (layer_idx % every) == 0

        def no_attn(h):
            kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            zeros = jnp.zeros((B, T, kv, hd), h.dtype)
            return h, (zeros, zeros)

        h, (k, v) = jax.lax.cond(is_attn, attn_block, no_attn, h)
        h, new_st = ssm_lib.mamba2_layer_seq_chunked(bp, cfg, h, st, kv_mask)
        return h, (new_st, (k, v), is_attn)

    fn = jax.checkpoint(body) if remat else body
    layer_idx = jnp.arange(cfg.num_layers)
    h, (final_states, kvs, attn_flags) = layer_scan(
        fn, x, (params["blocks"], init_states, layer_idx))
    return h, jnp.float32(0), (final_states, kvs, attn_flags)


# ---------------------------------------------------------------------------
# Training loss
# ---------------------------------------------------------------------------


def train_loss(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array],
               *, remat: bool = True, aux_weight: float = 0.01):
    """batch: tokens [B,T], labels [B,T], mask [B,T] (+ modal_embeds)."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = embed(params, cfg, tokens)
    if cfg.num_modal_tokens and "modal_embeds" in batch:
        M = cfg.num_modal_tokens
        x = jnp.concatenate(
            [batch["modal_embeds"].astype(x.dtype), x[:, M:]], axis=1)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    kv_mask = batch.get("mask", jnp.ones((B, T), bool)).astype(bool)
    h, aux, _ = forward_hidden(params, cfg, x, positions, kv_mask, remat=remat)
    h = norm(cfg, h, params.get("final_norm"))
    logits = unembed(params, cfg, h)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = kv_mask.astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux_weight * aux, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array,
            lengths: jax.Array, cache: Dict[str, Any], slot_ids: jax.Array,
            active: jax.Array, modal_embeds: Optional[jax.Array] = None,
            prefill_attend: Optional[Any] = None,
            cached_lens: Optional[jax.Array] = None):
    """Process left-padded prompts [B, T]; fill the cache; return last logits.

    tokens must be LEFT-padded (lane b's prompt occupies [T-len_b, T)).
    Returns (logits [B, V] at the last prompt token, cache').

    ``prefill_attend`` is a prefill-attention backend from
    ``repro.models.attn_backend`` (None -> resolve the default:
    REPRO_ATTN_BACKEND env var, else "gather"). K/V pages are populated
    inside the layer scan (see ``PrefillCtx``), so no per-layer staging
    buffer is allocated on either backend.

    ``cached_lens`` (prefix reuse / chunked prefill): when given, ``tokens``
    holds only each lane's SUFFIX (``lengths`` = suffix lengths) and lane
    b's first ``cached_lens[b]`` tokens' K/V are already resident in the
    slot's paged-pool pages — attention folds them in, RoPE positions shift
    by cached, only suffix pages are written, and seq_lens lands on
    cached + suffix. Requires a paged-KV decoder-only attention arch
    (SSM/hybrid recurrent state cannot be restored from KV pages).
    """
    B, T = tokens.shape
    if cached_lens is not None and (cfg.arch_type not in ("dense", "moe", "vlm")
                                    or cfg.is_encoder_decoder):
        raise ValueError(
            f"cached_lens (prefix reuse) requires a paged-KV decoder-only "
            f"arch; {cfg.name!r} is {cfg.arch_type!r}")
    offset = T - lengths                                    # [B]
    pos_in_seq = jnp.arange(T)[None, :] - offset[:, None]   # [-off .. len)
    kv_mask = pos_in_seq >= 0
    x = embed(params, cfg, tokens)
    if cfg.num_modal_tokens and modal_embeds is not None:
        # modal prefix occupies the first num_modal_tokens *valid* positions;
        # with left padding those are columns [offset, offset+M). For the
        # dry-run stub we scatter at those columns.
        M = modal_embeds.shape[1]
        col = offset[:, None] + jnp.arange(M)[None, :]
        bidx = jnp.arange(B)[:, None].repeat(M, 1)
        x = x.at[bidx, jnp.clip(col, 0, T - 1)].set(
            modal_embeds.astype(x.dtype))
    x = jnp.where(kv_mask[..., None], x, 0)
    positions = jnp.maximum(pos_in_seq, 0)
    if cached_lens is not None:
        positions = positions + cached_lens[:, None]

    ctx = None
    if cfg.uses_paged_kv:
        if prefill_attend is None:
            prefill_attend = attn_backend_lib.get_prefill_backend()
        ctx = PrefillCtx(kvc=cache["kv"], slot_ids=slot_ids, active=active,
                         offset=offset, lengths=lengths,
                         attend=prefill_attend, cached_lens=cached_lens)

    h, _aux, extras = forward_hidden(params, cfg, x, positions, kv_mask,
                                     prefill_ctx=ctx)
    h = norm(cfg, h, params.get("final_norm"))
    last_logits = unembed(params, cfg, h[:, -1:, :])[:, 0]

    # store cache state (K/V pages were already written inside the scan)
    if cfg.arch_type == "ssm":
        cache = _store_ssm_states(cache, extras, slot_ids, active)
    elif cfg.arch_type == "hybrid":
        if ctx is not None:
            final_states, kvc = extras
            cache = _store_ssm_states(dict(cache, kv=kvc), final_states,
                                      slot_ids, active)
        else:  # attn-free hybrid (attn_every == 0): recurrent state only
            final_states = extras[0]
            cache = _store_ssm_states(cache, final_states, slot_ids, active)
    else:
        cache = dict(cache)
        cache["kv"] = extras
    if cfg.uses_paged_kv:
        total = lengths if cached_lens is None else lengths + cached_lens
        cache["kv"] = cache_lib.set_seq_lens(
            cache["kv"], slot_ids, total, active)
    return last_logits, cache


def chunked_prefill(params: dict, cfg: ModelConfig, tokens: jax.Array,
                    lengths: jax.Array, cache: Dict[str, Any],
                    slot_ids: jax.Array, active: jax.Array, *, chunk: int,
                    prefill_attend: Optional[Any] = None):
    """Prefill left-padded prompts [B, T] in ``chunk``-token pieces.

    The ROADMAP's "bucket > VMEM budget" follow-up: instead of one prefill
    over the whole bucket, run ceil(T / chunk) prefills of ``chunk`` tokens
    each; chunk i reads chunks [0, i)'s K/V from the paged pool via the
    same ``cached_lens`` machinery as radix prefix reuse (each chunk's
    cached prefix = the tokens already written). Per-lane ragged: a lane
    whose prompt ends inside chunk i goes inactive for later chunks and its
    final-token logits are taken from its last live chunk.

    Returns (logits [B, V] at each lane's last prompt token, cache') —
    identical to single-shot ``prefill`` (the equivalence test asserts it).
    """
    B, T = tokens.shape
    n_chunks = -(-T // chunk)
    col = jnp.arange(chunk)[None, :]
    logits = None
    for i in range(n_chunks):
        clen = jnp.clip(lengths - i * chunk, 0, chunk)          # [B]
        cached = jnp.minimum(lengths, i * chunk)
        live = clen > 0
        # gather chunk i's tokens (prompt positions [i*chunk, i*chunk+clen))
        # right-aligned into a [B, chunk] bucket
        src = col - (chunk - clen)[:, None] + (T - lengths)[:, None] \
            + i * chunk
        valid = col >= (chunk - clen)[:, None]
        toks = jnp.where(valid,
                         jnp.take_along_axis(tokens,
                                             jnp.clip(src, 0, T - 1), axis=1),
                         0)
        lg, cache = prefill(params, cfg, toks, clen, cache, slot_ids,
                            active & live, prefill_attend=prefill_attend,
                            cached_lens=cached)
        logits = lg if logits is None else jnp.where(live[:, None], lg,
                                                     logits)
    return logits, cache


def prefill_batched(params: dict, cfg: ModelConfig, tokens: jax.Array,
                    lengths: jax.Array, cache: Dict[str, Any],
                    slot_ids: jax.Array, active: jax.Array,
                    cursors: jax.Array,
                    prefill_attend: Optional[Any] = None):
    """One fused dispatch advancing a batch of PREFILLING lanes by a chunk.

    The mixed-phase scheduler's hot path (``ModelApi.prefill_batched``):
    ``tokens`` [B, T] holds up to ``max_prefills_per_step`` lanes' next
    chunks, left-padded; ``cursors[b]`` counts lane b's already-resident
    prompt tokens (radix-cached prefix + previously completed chunks), so
    the batch is heterogeneous by construction — fresh admissions
    (cursor = cached_len), mid-prompt resumes, and final ragged chunks all
    share the single dispatch. Each lane's attention folds its resident
    prefix in from the paged pool (position-indexed on the gather
    reference, block-table scalar prefetch on the flash kernel), K/V
    writes land at absolute positions ``cursors[b] + i`` and never touch
    pages below the cursor, and ``lengths[b] == 0`` lanes are inert.

    Returns (logits [B, V] at each lane's last chunk token — meaningful
    only for lanes whose cursor completes this chunk — and the updated
    cache). Requires a paged-KV decoder-only arch, like every consumer of
    the ``cached_lens`` machinery.
    """
    if cursors is None:
        raise ValueError("prefill_batched requires per-lane cursors; use "
                         "prefill() for a from-scratch bucket")
    return prefill(params, cfg, tokens, lengths, cache, slot_ids, active,
                   prefill_attend=prefill_attend, cached_lens=cursors)


def _store_ssm_states(cache, final_states, slot_ids, active):
    """final_states leaves: [L, B, ...] -> scatter into cache['ssm'] [L, S, ...]."""
    def scatter(buf, new):
        # buf: [L, S, ...], new: [L, B, ...]
        moved = jnp.swapaxes(new, 0, 1)         # [B, L, ...]
        bufm = jnp.swapaxes(buf, 0, 1)          # [S, L, ...]
        sel = jnp.where(active[:, None], slot_ids[:, None],
                        bufm.shape[0])           # OOB drop for inactive
        bufm = bufm.at[sel[:, 0]].set(moved.astype(bufm.dtype), mode="drop")
        return jnp.swapaxes(bufm, 0, 1)

    cache = dict(cache)
    cache["ssm"] = jax.tree.map(scatter, cache["ssm"], final_states)
    return cache


# ---------------------------------------------------------------------------
# Decode (single token, persistent cache)
# ---------------------------------------------------------------------------


def decode(params: dict, cfg: ModelConfig, tokens: jax.Array,
           cache: Dict[str, Any], slot_ids: jax.Array, active: jax.Array,
           attend: Optional[Any] = None):
    """One decode step. tokens: [B] int32. Returns (logits [B, V], cache').

    ``attend`` is a decode-attention backend from
    ``repro.models.attn_backend`` (None -> resolve the default:
    REPRO_ATTN_BACKEND env var, else "gather")."""
    if attend is None:
        attend = attn_backend_lib.get_backend()
    if cfg.is_encoder_decoder:
        from repro.models import encdec
        return encdec.decode(params, cfg, tokens, cache, slot_ids, active,
                             attend=attend)
    if cfg.arch_type == "ssm":
        return _decode_rwkv(params, cfg, tokens, cache, slot_ids, active)
    if cfg.arch_type == "hybrid":
        return _decode_hybrid(params, cfg, tokens, cache, slot_ids, active,
                              attend)
    return _decode_dense(params, cfg, tokens, cache, slot_ids, active, attend)


def _decode_attn_layer(cfg, bp, x, kvc, layer, slot_ids, active, pos, window,
                       attend=None):
    """Shared attention-decode: write token KV, attend over pages.

    x: [B, 1, D]. Returns (attn output [B, 1, D] pre-wo, updated kvc).
    The attention itself is delegated to an ``attn_backend`` callable —
    "gather" (dense jnp reference) or "pallas" (paged-attention kernel,
    work bounded by live KV length). A lane that is not decoding (idle,
    or still PREFILLING) is handed position -1, an attended length of 0:
    its output is dropped, so it must cost the kernel nothing."""
    if attend is None:
        attend = attn_backend_lib.get_backend()
    q, k, v = qkv_project(bp, cfg, x)                  # [B,1,H,hd]/[B,1,KV,hd]
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)
    kvc = cache_lib.write_kv_layer(
        kvc, layer, slot_ids, k, v, start_pos=pos, lengths=pos + 1,
        active=active)
    att = attend(cfg, q, kvc, layer, slot_ids, jnp.where(active, pos, -1),
                 window)
    return att, kvc


def _decode_dense(params, cfg, tokens, cache, slot_ids, active, attend=None):
    B = tokens.shape[0]
    kvc = cache["kv"]
    pos = kvc.seq_lens[slot_ids]                      # new token's position
    x = embed(params, cfg, tokens[:, None])           # [B, 1, D]
    windows = jnp.asarray(window_array(cfg))

    def body(carry, xs):
        x, kvc = carry
        bp, layer, window = xs
        h = norm(cfg, x, bp.get("ln1"))
        att, kvc = _decode_attn_layer(cfg, bp, h, kvc, layer, slot_ids,
                                      active, pos, window, attend)
        x = x + attn_out(bp, att)
        h2 = norm(cfg, x, bp.get("ln2"))
        y = moe_lib.moe_ffn(bp, cfg, h2) if cfg.num_experts else mlp(bp, cfg, h2)
        return (x + y, kvc), None

    (x, kvc), _ = layer_scan(
        body, (x, kvc),
        (params["blocks"], jnp.arange(cfg.num_layers), windows))
    kvc = cache_lib.set_seq_lens(kvc, slot_ids, pos + 1, active)
    cache = dict(cache)
    cache["kv"] = kvc
    x = norm(cfg, x, params.get("final_norm"))
    logits = unembed(params, cfg, x)[:, 0]
    return logits, cache


def _decode_rwkv(params, cfg, tokens, cache, slot_ids, active):
    B = tokens.shape[0]
    x = embed(params, cfg, tokens[:, None])[:, 0]     # [B, D]
    states = jax.tree.map(lambda a: a[:, slot_ids], cache["ssm"])  # [L,B,...]

    def body(x, xs):
        bp, st = xs
        x, new_st = ssm_lib.rwkv6_layer_step(bp, cfg, x, st)
        return x, new_st

    x, new_states = layer_scan(body, x, (params["blocks"], states))
    cache = _store_ssm_states(cache, new_states, slot_ids, active)
    x = norm(cfg, x[:, None], params.get("final_norm"))
    logits = unembed(params, cfg, x)[:, 0]
    return logits, cache


def _decode_hybrid(params, cfg, tokens, cache, slot_ids, active, attend=None):
    B = tokens.shape[0]
    kvc = cache["kv"]
    pos = kvc.seq_lens[slot_ids]
    x = embed(params, cfg, tokens[:, None])[:, 0]     # [B, D]
    states = jax.tree.map(lambda a: a[:, slot_ids], cache["ssm"])
    sp = params["shared_attn"]
    every = cfg.attn_every

    def body(carry, xs):
        x, kvc = carry
        bp, st, layer_idx = xs
        is_attn = (layer_idx % every) == 0
        attn_row = layer_idx // every

        def with_attn(args):
            x, kvc = args
            h = norm(cfg, x[:, None], sp.get("ln1"))
            att, kvc = _decode_attn_layer(
                cfg, sp, h, kvc, attn_row, slot_ids, active, pos,
                jnp.int32(0), attend)
            x = x + attn_out(sp, att)[:, 0]
            h2 = norm(cfg, x[:, None], sp.get("ln2"))
            return x + mlp(sp, cfg, h2)[:, 0], kvc

        x, kvc = jax.lax.cond(is_attn, with_attn, lambda a: a, (x, kvc))
        x, new_st = ssm_lib.mamba2_layer_step(bp, cfg, x, st)
        return (x, kvc), new_st

    (x, kvc), new_states = layer_scan(
        body, (x, kvc),
        (params["blocks"], states, jnp.arange(cfg.num_layers)))
    kvc = cache_lib.set_seq_lens(kvc, slot_ids, pos + 1, active)
    cache = _store_ssm_states(dict(cache, kv=kvc), new_states, slot_ids, active)
    x = norm(cfg, x[:, None], params.get("final_norm"))
    logits = unembed(params, cfg, x)[:, 0]
    return logits, cache
