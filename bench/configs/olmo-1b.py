"""OLMo-1B (arXiv:2402.00838; Hugging Face ``allenai/OLMo-1B-hf``): the
program's configuration for the sizes in ``olmo-1b.json``, its plain
float32 reference, and the operation and byte counts of one token.

Architecture as published: pre-norm decoder, LayerNorm without any
parameters (eps 1e-5), multi-head attention (16 heads, each its own K/V)
without biases, rotary positions with base 10,000, SwiGLU MLP, input and
output embeddings tied, 2,048-token context.
"""
import json
from pathlib import Path

from bench.harness.decoder_ref import Decoder

DATA = json.loads(Path(__file__).with_suffix(".json").read_text())
M = DATA["model"]
D, L, V = M["hidden_size"], M["num_hidden_layers"], M["vocab_size"]
H, KV = M["num_attention_heads"], M["num_key_value_heads"]
HD, F = D // H, M["intermediate_size"]
BYTES = 2                                   # bfloat16 weights and K/V

REF = Decoder(d_model=D, num_heads=H, num_kv_heads=KV, head_dim=HD,
              rope_theta=M["rope_theta"], norm="layernorm_noparam",
              norm_eps=M["layer_norm_eps"], qkv_bias=False,
              tied=M["tie_word_embeddings"])
hidden = REF.hidden
logits = REF.logits


def model_config():
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name="olmo-1b", arch_type="dense", num_layers=L, d_model=D,
        num_heads=H, num_kv_heads=KV, d_ff=F, vocab_size=V,
        rope_theta=M["rope_theta"], norm_type="nonparametric_ln",
        tie_embeddings=M["tie_word_embeddings"], dtype=M["torch_dtype"])


def matmul_params() -> int:
    """Weights one token multiplies through the layers (no output head)."""
    return L * (D * H * HD + 2 * D * KV * HD + H * HD * D + 3 * D * F)


def head_params() -> int:
    return D * V


def attn_flops(ctx_sum: int) -> int:
    """Attention operations for queries that see ``ctx_sum`` keys in all:
    QK^T and PV, 2 operations per multiply-add, every head and layer."""
    return 4 * HD * H * L * ctx_sum


def attn_bytes(ctx: int, q_len: int) -> int:
    """Least HBM traffic of one attention row: its ``ctx`` tokens of K and
    V read once, ``q_len`` queries read and outputs written, and the new
    tokens' K and V written, in every layer."""
    return L * BYTES * (2 * KV * HD * ctx + 2 * H * HD * q_len
                        + 2 * KV * HD * q_len)
