"""Mixture-of-Experts: one dropless, share-aware expert layer, which serves
and trains.

Serving (``expert_layer``), on every prefill, chunk and decode step:

  * the router scores every row over its full width (``num_experts``,
    float32 softmax) and keeps the top ``top_k``, renormalised to sum to 1;
  * only *valid* rows are routed (a decoding lane, a real chunk token):
    idle lanes and padding take no expert rows, so a request's output does
    not depend on what else is in the batch;
  * the chip holds experts ``[first_expert, first_expert + experts_held)``
    (expert parallelism; all of them by default). Each (row, choice) pair
    whose expert is held is counting-sorted into that expert's run and the
    runs go through the grouped matmul (``kernels/expert_gmm.py``), whose
    work follows the routed rows: no capacity, nothing dropped, no
    expert's weights read unless a row chose it;
  * outputs combine with the renormalised weights; a choice that landed on
    an absent expert adds nothing here, as in expert parallelism, where
    the chip that holds it adds its part;
  * the layer returns its counters ``[expert_rows, experts_hit]``: routed
    pairs on held experts, and held experts given at least one row.

Training runs the same layer over every unmasked row, with the grouped
matmul swapped for ``ragged_gmm`` (``lax.ragged_dot``, which has a
gradient; the Pallas kernel has none), and adds ``load_balance_loss``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def router_topk(router_logits: jax.Array, top_k: int):
    """[N, E] -> (weights [N, k], experts [N, k]) with renormalised softmax."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts


#: rows per tile of the grouped expert matmul
EXPERT_TILE = 128


def no_expert_counts() -> jax.Array:
    """``[expert_rows, experts_hit]`` of a layer that routed nothing."""
    return jnp.zeros((2,), jnp.int32)


def ragged_gmm(x, w, group_sizes, *, w2=None, tm: int = EXPERT_TILE):
    """``ops.expert_gmm``'s product through ``lax.ragged_dot``, which has a
    gradient: the grouped matmul of the training path. Rows past the runs
    come out zero; ``tm`` is unused."""
    def dot(w):
        return jax.lax.ragged_dot(x, w, group_sizes,
                                  preferred_element_type=jnp.float32)
    y = dot(w)
    if w2 is not None:
        y = jax.nn.silu(y) * dot(w2)
    return y.astype(x.dtype)


def expert_layer(p: dict, cfg: ModelConfig, x: jax.Array, valid: jax.Array,
                 gmm=None):
    """The expert layer (module docstring). x: [B, T, D]; valid: [B, T]
    (or broadcastable) bool, the rows to route; ``gmm``: the grouped
    matmul, ``ops.expert_gmm`` unless given. Returns
    ``(out [B, T, D], counts int32[2])``.

    p keys: router [D, num_experts], w_gate/w_up [G, D, Fe] and w_down
    [G, Fe, D] for the G held experts, and qwen2-moe's shared expert
    (ws_gate, ws_up, ws_down, shared_gate), which every chip computes."""
    if gmm is None:
        from repro.kernels import ops
        gmm = ops.expert_gmm
    if cfg.mlp_act != "silu":
        raise ValueError(f"the grouped expert matmul fuses silu; "
                         f"{cfg.name} uses {cfg.mlp_act!r}")
    B, T, D = x.shape
    N, k, G = B * T, cfg.top_k, cfg.held_experts
    P = N * k                                     # (row, choice) pairs
    M = -(-P // EXPERT_TILE) * EXPERT_TILE
    xf = x.reshape(N, D)
    logits = jnp.einsum("nd,de->ne", xf, p["router"],
                        preferred_element_type=jnp.float32)
    weights, experts = router_topk(logits, k)                 # [N, k]
    local = experts - cfg.first_expert
    valid = jnp.broadcast_to(valid, (B, T)).reshape(N, 1)
    held = (valid & (local >= 0) & (local < G)).reshape(P)

    # counting sort of the held pairs into per-expert runs
    onehot = ((local.reshape(P, 1) == jnp.arange(G)) & held[:, None]
              ).astype(jnp.int32)                             # [P, G]
    sizes = jnp.sum(onehot, axis=0)
    starts = jnp.cumsum(sizes) - sizes
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    dest = jnp.where(held, jnp.sum(onehot * starts, axis=1) + rank, M)
    src = jnp.full((M,), P, jnp.int32).at[dest].set(
        jnp.arange(P, dtype=jnp.int32), mode="drop")          # pair per row
    xs = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)])[src // k]

    h = gmm(xs, p["w_gate"], sizes, w2=p["w_up"], tm=EXPERT_TILE)
    y = gmm(h, p["w_down"], sizes, tm=EXPERT_TILE)             # [M, D]
    # rows past the runs are unwritten: select, never multiply, them away
    yp = jnp.where(held[:, None],
                   y[jnp.minimum(dest, M - 1)].astype(jnp.float32), 0.0)
    out = jnp.sum(yp.reshape(N, k, D) * weights[..., None], axis=1)
    out = out.astype(x.dtype)
    if cfg.shared_expert_d_ff:
        out = out + _shared_expert(p, xf)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)])
    return out.reshape(B, T, D), counts.astype(jnp.int32)


def _shared_expert(p: dict, xf: jax.Array) -> jax.Array:
    """qwen2-moe's always-on expert with its sigmoid gate: [N, D]."""
    sg = jax.nn.silu(jnp.einsum("nd,df->nf", xf, p["ws_gate"]))
    su = jnp.einsum("nd,df->nf", xf, p["ws_up"])
    shared = jnp.einsum("nf,fd->nd", sg * su, p["ws_down"])
    gate_s = jax.nn.sigmoid(jnp.einsum(
        "nd,d->n", xf.astype(jnp.float32),
        p["shared_gate"].astype(jnp.float32)))
    return shared * gate_s[:, None].astype(xf.dtype)


def load_balance_loss(router_logits: jax.Array, top_k: int, num_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: E * sum(frac_tokens_e * mean_prob_e)."""
    N = router_logits.shape[0]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    _, experts = jax.lax.top_k(probs, top_k)
    counts = jnp.zeros(num_experts, jnp.float32).at[experts.reshape(-1)].add(1.0)
    frac = counts / (N * top_k)
    mean_prob = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(frac * mean_prob)
