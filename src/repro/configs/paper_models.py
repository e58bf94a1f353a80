"""The paper's own evaluation models (§6.1), as bonus configs for the
serving benchmarks: Llama-3 8B (dense) and Qwen3-30B-A3B (MoE), whole.

Qwen3-30B-A3B as published (Hugging Face ``Qwen/Qwen3-30B-A3B``): 48
layers, GQA 32/4 with head size 128 and a per-head RMSNorm of q and k,
128 experts of width 768 with the top 8 renormalised in every layer, no
shared expert, rope base 1e6, untied output head. The benchmark serves one
chip's expert-parallel share of it (``bench/configs/qwen3-30b-a3b.json``:
16 experts of each of 24 layers)."""
from repro.configs.base import ModelConfig

LLAMA3_8B = ModelConfig(
    name="llama3-8b", arch_type="dense",
    num_layers=32, d_model=4096, num_heads=32, num_kv_heads=8,
    d_ff=14336, vocab_size=128_256, rope_theta=5e5,
)

QWEN3_30B_A3B = ModelConfig(
    name="qwen3-30b-a3b", arch_type="moe",
    num_layers=48, d_model=2048, num_heads=32, num_kv_heads=4,
    head_dim=128, d_ff=768, vocab_size=151_936, qk_norm=True,
    rope_theta=1e6, tie_embeddings=False,
    num_experts=128, top_k=8, moe_d_ff=768,
)
