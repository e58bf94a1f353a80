"""Qwen1.5-MoE-A2.7B [hf:Qwen/Qwen1.5-MoE-A2.7B]: 60 routed experts top-4
+ 4 shared experts (merged 5632 hidden), QKV bias."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen2-moe-a2.7b", arch_type="moe",
    num_layers=24, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=1408, vocab_size=151_936, qkv_bias=True,
    num_experts=60, top_k=4, moe_d_ff=1408, shared_expert_d_ff=5632,
)

TINY = CONFIG.replace(
    name="qwen2-moe-tiny", num_layers=2, d_model=128, num_heads=4,
    num_kv_heads=4, d_ff=96, vocab_size=512, num_experts=4, top_k=2,
    moe_d_ff=96, shared_expert_d_ff=128,
    dtype="float32",
)
