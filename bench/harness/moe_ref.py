"""Plain float32 forward pass of a Qwen3-MoE decoder that holds a share of
each layer's experts, for the correctness check. It imports nothing of the
program under test.

Equations (one layer, x the residual stream of one sequence [T, D]):

    h = rms(x) * g1
    q, k, v = h Wq, h Wk, h Wv                       no biases
    q, k = rms_hd(q) * gq, rms_hd(k) * gk            per head, before RoPE
    q, k = rope(q), rope(k)                          rotate-half, base theta
    o = softmax(q k^T / sqrt(hd) + causal mask) v    K/V heads shared by
    x = x + o Wo                                     groups of query heads
    h = rms(x) * g2
    p = softmax(h Wr)                                over all E experts
    w = top_k(p), renormalised to sum to 1
    x = x + sum_j w_j (silu(h Wg_j) * (h Wu_j)) Wd_j

then ``rms(x) * g_final`` and logits against the untied output matrix.
Gains are stored as offsets from one (``g = 1 + w``), as the served
weights hold them.

The one departure from the published model is the share: the weights
hold experts ``[first_expert, first_expert + G)`` of the router's
outputs, and a choice of any other expert adds nothing, as on
one chip of an expert-parallel deployment. The held experts run dense over
every row, each weighted by its renormalised top-k weight (zero where the
row did not choose it); nothing is sorted or dropped.

Weights are widened to float32 one layer at a time inside the layer scan.
Every contraction runs at ``HIGHEST`` precision. ``low`` ("int8" or
"fp8") is the control, as in ``decoder_ref``: every projection (Q, K, V,
O, the router, the experts' three matrices and the output head) takes
both operands in that format; norms, attention scores and the softmaxes
stay float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .decoder_ref import F32, HIGHEST, matmul


@dataclass(frozen=True)
class MoeDecoder:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    top_k: int
    first_expert: int        # the first held expert

    def _rms(self, x, gain):
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + self.norm_eps)
        return y * (1.0 + gain)

    def _rope(self, x):                       # x [T, heads, hd]
        T, hd = x.shape[0], x.shape[-1]
        inv = 1.0 / (self.rope_theta
                     ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
        ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def attention(self, x, lw, low=None):
        """The attention half of a layer: ``x + o Wo``."""
        T = x.shape[0]
        H, KV, hd = self.num_heads, self.num_kv_heads, self.head_dim
        h = self._rms(x, lw["ln1"])
        q, k, v = (matmul(h, lw[n], low) for n in ("wq", "wk", "wv"))
        q = self._rope(self._rms(q.reshape(T, H, hd), lw["q_norm"]))
        k = self._rope(self._rms(k.reshape(T, KV, hd), lw["k_norm"]))
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v.reshape(T, KV, hd), H // KV, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
        return x + matmul(o.reshape(T, H * hd), lw["wo"], low)

    def expert_weights(self, h, router, low=None):
        """Each row's top-k choices: renormalised weights and expert ids,
        both [T, top_k]."""
        p = jax.nn.softmax(matmul(h, router, low), axis=-1)
        top, idx = jax.lax.top_k(p, self.top_k)
        return top / jnp.sum(top, -1, keepdims=True), idx

    def experts_out(self, h, lw, low=None):
        """The held experts' part of the expert layer's output [T, D]."""
        w, idx = self.expert_weights(h, lw["router"], low)
        G = lw["w_gate"].shape[0]
        held = idx[..., None] == self.first_expert + jnp.arange(G)
        wg = jnp.sum(jnp.where(held, w[..., None], 0.0), axis=1)   # [T, G]

        def one(wgate, wup, wdown):
            up = jax.nn.silu(matmul(h, wgate, low)) * matmul(h, wup, low)
            return matmul(up, wdown, low)                          # [T, D]

        y = jax.vmap(one)(lw["w_gate"], lw["w_up"], lw["w_down"])
        return jnp.einsum("tg,gtd->td", wg, y, precision=HIGHEST)

    def _layer(self, x, lw, low):
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        x = self.attention(x, lw, low)
        return x + self.experts_out(self._rms(x, lw["ln2"]), lw, low)

    def hidden(self, w, tokens, low=None):
        """Final normed hidden states [T, D] of one sequence."""
        x = w["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(
            lambda x, lw: (self._layer(x, lw, low), None), x, w["blocks"])
        return self._rms(x, w["final_norm"].astype(F32))

    def logits(self, w, h, low=None):
        """Logits [n, V] of hidden rows ``h`` [n, D]."""
        return matmul(h, w["unembed"].astype(F32), low)
