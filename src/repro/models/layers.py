"""Shared transformer layer primitives (pure JAX, pytree params).

All functions are shape-polymorphic over a leading layer axis where noted —
blocks are stacked ``[L, ...]`` and consumed through ``jax.lax.scan`` so the
lowered HLO stays compact (one layer body) even for 64-layer configs.
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: Optional[jax.Array], eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if weight is not None:
        x = x * (1.0 + weight.astype(jnp.float32))
    return x.astype(dt)


def nonparametric_ln(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """OLMo: LayerNorm without learnable scale/bias [arXiv:2402.00838]."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def norm(cfg: ModelConfig, x: jax.Array, weight: Optional[jax.Array]) -> jax.Array:
    if cfg.norm_type == "nonparametric_ln":
        return nonparametric_ln(x)
    return rms_norm(x, weight)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., T, H, hd]; positions: broadcastable to [..., T]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, hd/2]
    cos = jnp.cos(angles)[..., None, :]                # [..., T, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _softcap(logits: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def qkv_project(p: dict, cfg: ModelConfig, x: jax.Array):
    """x: [B, T, D] -> q [B,T,H,hd], k/v [B,T,KV,hd]. With ``qk_norm``
    (qwen3) each q and k head is RMS-normed with its gain, before RoPE."""
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("btd,dh->bth", x, p["wq"])
    k = jnp.einsum("btd,dh->bth", x, p["wk"])
    v = jnp.einsum("btd,dh->bth", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, T, cfg.num_heads, hd)
    k = k.reshape(B, T, cfg.num_kv_heads, hd)
    v = v.reshape(B, T, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


def gqa_attend(
    q: jax.Array,            # [B, Tq, H, hd]
    k: jax.Array,            # [B, Tk, KV, hd]
    v: jax.Array,            # [B, Tk, KV, hd]
    *,
    q_positions: jax.Array,  # [B, Tq] absolute positions of queries
    k_positions: jax.Array,  # [B, Tk] absolute positions of keys
    causal: bool = True,
    window: Optional[int] = None,
    kv_mask: Optional[jax.Array] = None,  # [B, Tk] valid-key mask
    softcap: Optional[float] = None,
) -> jax.Array:
    """Grouped-query attention with optional sliding window / softcap.

    Works for training (Tq == Tk), chunked prefill and single-token decode
    (Tq == 1, Tk == cache length).

    REPRO_FAST_ATTN=1 (§Perf hillclimb): keep K/V in their storage dtype and
    accumulate in f32 via preferred_element_type instead of materialising
    f32 upcasts of the (gathered) K/V — on the decode path those upcast
    temporaries triple the HBM traffic of the KV read.
    """
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    fast = os.environ.get("REPRO_FAST_ATTN") == "1"

    if fast:
        qq = (q.astype(jnp.float32) * scale).astype(q.dtype)
        logits = jnp.einsum("bqkgh,bskh->bkgqs",
                            qq.reshape(B, Tq, KV, G, hd), k,
                            preferred_element_type=jnp.float32)
    else:
        qf = (q.astype(jnp.float32) * scale).reshape(B, Tq, KV, G, hd)
        kf = k.astype(jnp.float32)
        logits = jnp.einsum("bqkgh,bskh->bkgqs", qf, kf)  # [B,KV,G,Tq,Tk]
    logits = _softcap(logits, softcap)

    dq = q_positions[:, None, None, :, None]           # [B,1,1,Tq,1]
    dk = k_positions[:, None, None, None, :]           # [B,1,1,1,Tk]
    mask = jnp.ones_like(logits, dtype=bool)
    if causal:
        mask &= dk <= dq
    if window is not None:
        mask &= (dq - dk) < window
    if kv_mask is not None:
        mask &= kv_mask[:, None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if fast:
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(q.dtype), v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Tq, H, hd).astype(q.dtype)


def attn_out(p: dict, out_heads: jax.Array) -> jax.Array:
    B, T, H, hd = out_heads.shape
    return jnp.einsum("bth,hd->btd", out_heads.reshape(B, T, H * hd), p["wo"])


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    gate = act(jnp.einsum("btd,df->btf", x, p["w_gate"]))
    up = jnp.einsum("btd,df->btf", x, p["w_up"])
    return jnp.einsum("btf,fd->btd", gate * up, p["w_down"])


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed(params: dict, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    e = params["embed"][tokens]
    if cfg.name.startswith("gemma"):
        e = e * jnp.asarray(math.sqrt(cfg.d_model), e.dtype)
    return e


def unembed(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    contract = "btd,vd->btv" if cfg.tie_embeddings else "btd,dv->btv"
    logits = jnp.einsum(contract, x.astype(jnp.float32), w.astype(jnp.float32))
    return _softcap(logits, cfg.logit_softcap)
