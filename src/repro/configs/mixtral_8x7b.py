"""Mixtral-8x7B [arXiv:2401.04088]: 8 experts top-2, sliding-window attn."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="mixtral-8x7b", arch_type="moe",
    num_layers=32, d_model=4096, num_heads=32, num_kv_heads=8,
    d_ff=14336, vocab_size=32_000, sliding_window=4096,
    num_experts=8, top_k=2, moe_d_ff=14336, rope_theta=1e6,
)

TINY = CONFIG.replace(
    name="mixtral-tiny", num_layers=2, d_model=128, num_heads=4,
    num_kv_heads=2, d_ff=128, vocab_size=512, num_experts=4, top_k=2,
    moe_d_ff=128, sliding_window=16,
    dtype="float32",
)
