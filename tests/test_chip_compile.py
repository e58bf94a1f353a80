"""Ahead-of-time compiles of the attention kernels for a TPU v5e.

Interpret mode (the CPU test path) accepts any block shape, so these
tests hand the kernels to the TPU's own compiler, for a described
``v5e:2x2`` topology with no chip attached, at qwen2-1.5b serving widths
(KV=2, G=6, head_dim 128, bf16, 16-token pages, a 2048-page pool,
256-token chunks, block_q 128; the decode kernel also at the chat cell's
pool and block table). A block the compiler refuses (tiling,
VMEM) fails here, before any chip time is spent. Nothing runs, so no
result or time is checked: only that each program compiles and holds a
Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

KV, G, HD = 2, 6, 128            # qwen2-1.5b attention widths
PS, P, MB = 16, 2048, 128        # page size, pool pages, pages per lane
B, T, BQ = 8, 256, 128           # lanes, chunk tokens, query tile


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool(dtype):
    return [((P, KV, PS, HD), dtype)] * 2


def _scales():
    return [((P, KV, 128), jnp.bfloat16)] * 2   # token t in lane t


# (int8 pool, lanes, pool pages, blocks per lane): the serving widths
# above, and the chat cell's (32 lanes, 288 blocks of 16 tokens, 9,216
# pages), where the block table is ~6x wider than a typical lane.
DECODE_SHAPES = {
    "bf16": (False, B, P, MB),
    "int8": (True, B, P, MB),
    "chat-bf16": (False, 32, 9216, 288),
    "chat-int8": (True, 32, 9216, 288),
}


@pytest.mark.parametrize("shape", list(DECODE_SHAPES))
def test_paged_decode_compiles(one_chip, shape):
    int8, lanes, pages, blocks = DECODE_SHAPES[shape]

    def fn(q, kp, vp, bt, lens, *scales):
        quant = dict(zip(("k_scale", "v_scale"), scales))
        return ops.paged_attention(q, kp, vp, bt, lens, window=0,
                                   interpret=False, **quant)

    shapes = [((lanes, KV, G, HD), jnp.bfloat16)]
    shapes += [((pages, KV, PS, HD), jnp.int8 if int8 else jnp.bfloat16)] * 2
    shapes += [((lanes, blocks), jnp.int32), ((lanes,), jnp.int32)]
    shapes += [((pages, KV, 128), jnp.bfloat16)] * 2 if int8 else []
    _compile(fn, shapes, one_chip)


@pytest.mark.parametrize("prefix", [False, True], ids=["plain", "prefix"])
def test_flash_prefill_compiles(one_chip, prefix):
    def fn(q, k, v, offs, *pool):
        extra = {}
        if prefix:
            kp, vp, rows, cached = pool
            extra = dict(k_pages=kp, v_pages=vp, block_rows=rows,
                         cached_lens=cached)
        return ops.flash_prefill_attention(q, k, v, offs, window=0,
                                           block_q=BQ, block_k=BQ,
                                           interpret=False, **extra)

    shapes = [((B, T, KV * G, HD), jnp.bfloat16)]
    shapes += [((B, T, KV, HD), jnp.bfloat16)] * 2 + [((B,), jnp.int32)]
    if prefix:
        shapes += _pool(jnp.bfloat16) + [((B, MB), jnp.int32),
                                         ((B,), jnp.int32)]
    _compile(fn, shapes, one_chip)


@pytest.mark.parametrize("pool", ["bf16", "int8", "fused"])
def test_ragged_writes_kv_compiles(one_chip, pool):
    def fn(q, k, v, cu_q, cu_kv, bt, *pools):
        if pool == "fused":
            kw = dict(kv_fused=pools[0])
        else:
            kw = dict(zip(("k_pages", "v_pages", "k_scale", "v_scale"),
                          pools))
        return ops.ragged_attention(q, k, v, cu_q, cu_kv, bt, window=0,
                                    block_q=BQ, pages_per_block=2,
                                    writes_kv=True, interpret=False, **kw)

    shapes = [((B, T, KV * G, HD), jnp.bfloat16)]
    shapes += [((B, T, KV, HD), jnp.bfloat16)] * 2
    shapes += [((B + 1,), jnp.int32)] * 2 + [((B, MB), jnp.int32)]
    if pool == "fused":
        shapes += [((P, KV, 2, PS, HD), jnp.bfloat16)]
    else:
        shapes += _pool(jnp.int8 if pool == "int8" else jnp.bfloat16)
        shapes += _scales() if pool == "int8" else []
    _compile(fn, shapes, one_chip)
