"""Plain float32 forward pass of a pre-norm decoder, for the correctness
check. It imports nothing of the program under test.

Equations (one layer, x the residual stream of one sequence [T, D]):

    h = norm(x) * g1                       norm: RMSNorm or parameter-free LN
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = rope(q), rope(k)                rotate-half form, base ``theta``
    o = softmax(q k^T / sqrt(hd) + causal mask) v     K/V heads shared by
    x = x + o Wo                                      groups of query heads
    h = norm(x) * g2
    x = x + (silu(h Wg) * (h Wu)) Wd

then ``norm(x) * g_final`` and logits against the output matrix (the
embedding itself when tied). Gains are stored as offsets from one
(``g = 1 + w``), which is how the served weights hold them.

Weights arrive in the served type (bfloat16) and are widened to float32
one layer at a time inside the layer scan, so the pass needs one layer's
float32 copy at a time. Every contraction runs at ``HIGHEST`` precision.

``low="int8"`` or ``low="fp8"`` (e4m3) is the control: every projection
(Q, K, V, O, the three MLP matrices and the output head) takes both
operands in that format, weights scaled per output column and
activations per row; attention scores and the softmax stay float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# the control's formats: (stored type, largest value, accumulator type)
LOW = {"int8": (jnp.int8, 127.0, jnp.int32),
       "fp8": (jnp.float8_e4m3fn, 448.0, F32)}


def _quantize(x, axis, low):
    dtype, top, _ = LOW[low]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    y = x / scale
    if dtype == jnp.int8:
        y = jnp.round(y)
    return y.astype(dtype), scale


def matmul(x, w, low=None):
    """x [..., K] @ w [K, N] in float32, or, for the control, with both
    operands in ``low`` ("int8" or "fp8"): x scaled per row, w per
    column."""
    if low is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, sx = _quantize(x, -1, low)
    wq, sw = _quantize(w, 0, low)
    acc = jax.lax.dot_general(xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=LOW[low][2])
    return acc.astype(F32) * sx * sw


@dataclass(frozen=True)
class Decoder:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    norm: str            # "rms" or "layernorm_noparam"
    norm_eps: float
    qkv_bias: bool
    tied: bool

    def _norm(self, x, gain=None):
        if self.norm == "rms":
            y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + self.norm_eps)
        else:
            mu = jnp.mean(x, -1, keepdims=True)
            var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
            y = (x - mu) * jax.lax.rsqrt(var + self.norm_eps)
        return y if gain is None else y * (1.0 + gain)

    def _rope(self, x):                       # x [T, heads, hd]
        T, hd = x.shape[0], x.shape[-1]
        inv = 1.0 / (self.rope_theta
                     ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
        ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def _layer(self, x, lw, low):
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        T = x.shape[0]
        H, KV, hd = self.num_heads, self.num_kv_heads, self.head_dim
        h = self._norm(x, lw.get("ln1"))
        q, k, v = (matmul(h, lw[n], low) for n in ("wq", "wk", "wv"))
        if self.qkv_bias:
            q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
        q = self._rope(q.reshape(T, H, hd))
        k = jnp.repeat(self._rope(k.reshape(T, KV, hd)), H // KV, axis=1)
        v = jnp.repeat(v.reshape(T, KV, hd), H // KV, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
        x = x + matmul(o.reshape(T, H * hd), lw["wo"], low)
        h = self._norm(x, lw.get("ln2"))
        up = jax.nn.silu(matmul(h, lw["w_gate"], low)) \
            * matmul(h, lw["w_up"], low)
        return x + matmul(up, lw["w_down"], low)

    def hidden(self, w, tokens, low=None):
        """Final normed hidden states [T, D] of one sequence."""
        x = w["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(
            lambda x, lw: (self._layer(x, lw, low), None), x, w["blocks"])
        return self._norm(x, None if w.get("final_norm") is None
                          else w["final_norm"].astype(F32))

    def logits(self, w, h, low=None):
        """Logits [n, V] of hidden rows ``h`` [n, D]."""
        out = w["embed"].T if self.tied else w["unembed"]
        return matmul(h, out.astype(F32), low)

