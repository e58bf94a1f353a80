"""Qwen3-30B-A3B (arXiv:2505.09388; Hugging Face ``Qwen/Qwen3-30B-A3B``) as
one chip's share of an expert-parallel deployment: the program's
configuration for the sizes in ``qwen3-30b-a3b.json``, its plain float32
reference, and the operation and byte counts of one token.

Architecture as published: pre-norm decoder, RMSNorm (eps 1e-6), grouped
query attention (32 query heads sharing 4 K/V heads, head size 128)
without biases, with a per-head RMSNorm of q and k before rotary
positions (base 1e6); every layer a sparse expert layer: a router over
128 experts, the top 8 renormalised, SwiGLU experts of width 768, no
shared expert; untied output head.

The share (the JSON's ``deployment``): each layer's experts lie over 8
chips, 16 each, and this chip, rank 0, holds experts 0-15 of 24 of the
48 layers (the other 24 form the second pipeline stage). The router keeps
all 128 outputs; a choice of an absent expert adds nothing here, in the
program and in the reference alike (``bench/harness/moe_ref.py``).
"""
import json
from pathlib import Path

from bench.harness.moe_ref import MoeDecoder

DATA = json.loads(Path(__file__).with_suffix(".json").read_text())
M = DATA["model"]
SHARE = DATA["deployment"]
D, L, V = M["hidden_size"], M["num_hidden_layers"], M["vocab_size"]
H, KV, HD = (M["num_attention_heads"], M["num_key_value_heads"],
             M["head_dim"])
E, K = SHARE["router_experts"], M["num_experts_per_tok"]
HELD, FIRST, FE = M["num_experts"], SHARE["first_expert"], \
    M["moe_intermediate_size"]
BYTES = 2                                   # bfloat16 weights and K/V

REF = MoeDecoder(num_heads=H, num_kv_heads=KV, head_dim=HD,
                 rope_theta=M["rope_theta"], norm_eps=M["rms_norm_eps"],
                 top_k=K, first_expert=FIRST)
hidden = REF.hidden
logits = REF.logits


def model_config():
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=Path(__file__).stem, arch_type="moe", num_layers=L, d_model=D,
        num_heads=H, num_kv_heads=KV, head_dim=HD, d_ff=FE, vocab_size=V,
        qk_norm=True, rope_theta=M["rope_theta"],
        tie_embeddings=M["tie_word_embeddings"], dtype=M["torch_dtype"],
        num_experts=E, top_k=K, moe_d_ff=FE, experts_held=HELD,
        first_expert=FIRST)


def matmul_params() -> int:
    """Weights one token multiplies through the layers (no output head):
    attention and the router in full, and of the experts, in expectation,
    the share of its top-8 choices that land on held experts: 8 x 16/128
    = 1 expert per layer."""
    attn = D * H * HD + 2 * D * KV * HD + H * HD * D
    return L * (attn + D * E) + L * K * HELD * 3 * D * FE // E


def head_params() -> int:
    return D * V


def attn_flops(ctx_sum: int) -> int:
    """Attention operations for queries that see ``ctx_sum`` keys in all:
    QK^T and PV, 2 operations per multiply-add, every query head, layer."""
    return 4 * HD * H * L * ctx_sum


def attn_bytes(ctx: int, q_len: int) -> int:
    """Least HBM traffic of one attention row: its ``ctx`` tokens of K and
    V read once, ``q_len`` queries read and outputs written, and the new
    tokens' K and V written, in every layer."""
    return L * BYTES * (2 * KV * HD * ctx + 2 * H * HD * q_len
                        + 2 * KV * HD * q_len)


def expert_flops(rows: int) -> int:
    """Operations of the grouped expert matmul for ``rows`` routed rows
    (the ``expert_rows`` counter, summed over layers): gate, up and down,
    2 per multiply-add."""
    return 6 * D * FE * rows


def expert_bytes(hit: int, rows: int) -> int:
    """Least HBM traffic of the grouped expert matmul: each hit expert's
    three matrices read once per call (the ``experts_hit`` counter, summed
    over layers and calls), and each routed row read and written once."""
    return hit * 3 * D * FE * BYTES + rows * 2 * D * BYTES
