"""Operation and byte counts of each configuration, against values worked
by hand for one decode row and one chunk row."""
import pytest

from bench.harness import spec, work

# (config, weights per token, decode row at ctx 1000: operations, bytes,
#  chunk row [256, 512): operations, bytes)
HAND = [
    # qwen2-1.5b: 28 x (1536*1536*2 + 2*1536*256 + 3*1536*8960);
    # attention 4*128*12*28 = 172,032 per key; bytes 28*2*(...)
    ("qwen2-1.5b", 1_310_195_712, 172_032_000, 28_872_704,
     16_933_453_824, 66_060_288),
    # olmo-1b: 16 x (4*2048*2048 + 3*2048*8192); 4*128*16*16 = 131,072
    ("olmo-1b", 1_073_741_824, 131_072_000, 131_334_144,
     12_901_679_104, 134_217_728),
]


@pytest.mark.parametrize("name, params, d_ops, d_bytes, c_ops, c_bytes",
                         HAND, ids=[h[0] for h in HAND])
def test_counts(name, params, d_ops, d_bytes, c_ops, c_bytes):
    code = spec.load_config(name).code
    assert code.matmul_params() == params
    assert code.attn_flops(1000) == d_ops
    assert code.attn_bytes(1000, 1) == d_bytes
    # the chunk's queries at positions 256..511 see 257..512 keys
    assert code.attn_flops(sum(range(257, 513))) == c_ops
    assert code.attn_bytes(512, 256) == c_bytes


@pytest.mark.parametrize("name", [h[0] for h in HAND])
def test_work_between_boundaries(name):
    code = spec.load_config(name).code
    # one 512-token prompt: its second chunk, then the first token, then
    # two decode rows at contexts 513 and 514
    a, b, c = {7: (256, 0)}, {7: (512, 1)}, {7: (512, 3)}
    w = work.between(code, {7: 512}, a, b, chunk=256)
    assert (w.prefill_tokens, w.decode_tokens, w.emitted_tokens) == (256, 0, 1)
    assert (w.chunk_ctx, w.decode_ctx) == (sum(range(257, 513)), 0)
    assert (w.chunk_bytes, w.decode_bytes) == (code.attn_bytes(512, 256), 0)
    assert w.useful_flops == (2 * code.matmul_params() * 256
                              + 2 * code.head_params()
                              + code.attn_flops(w.attn_ctx))
    w = work.between(code, {7: 512}, b, c, chunk=256)
    assert (w.prefill_tokens, w.decode_tokens, w.emitted_tokens) == (0, 2, 2)
    assert (w.chunk_ctx, w.decode_ctx) == (0, 513 + 514)
    assert w.decode_bytes == code.attn_bytes(513, 1) + code.attn_bytes(514, 1)
