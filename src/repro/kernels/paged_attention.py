"""Paged-attention decode kernel (Pallas TPU) — the split path's decode hot path.

One new query token per lane attends over that lane's paged KV cache. On
GPU the paper fuses this into the persistent scheduler's pre-captured
decode graph; the TPU-native formulation is a Pallas kernel whose work and
DMA traffic scale with each lane's *live* pages, not with the width of the
block table:

  * grid ``(B,)``: one grid step per lane, serving all of its KV heads.
    No grid axis is as wide as the block table. The kernel this replaced
    ran a ``(B, KV, table width)`` grid and elided dead steps' DMAs; on
    the chip each dead grid step still cost pipeline time, so every lane
    paid for the whole table, not ~ceil(live/ps) pages;
  * the pools (and the int8 scale slabs) stay in HBM
    (``memory_space=ANY``); each grid step runs its own page loop over
    the live pages ``[lo // ps, (kv_len - 1) // ps]`` only, bringing
    ``pages_per_block`` pages per buffer slot through DOUBLE-BUFFERED
    ``make_async_copy`` DMAs (block ``i+1`` is issued before block ``i``
    is consumed). ``lo`` is the sliding-window floor, so window attention
    reads ~window/ps pages whatever the context; a lane with
    ``kv_len == 0`` issues no DMA and writes zeros;
  * a flash-attention running softmax in f32 (the loop carry), GQA (query
    head ``h`` under KV head ``h // G``), attention-logit softcapping
    (gemma2) and a *dynamic* window scalar (0 = full attention), so
    per-layer window patterns pass through a ``lax.scan`` over layers
    without recompilation;
  * fused int8-KV dequantisation: per-(token, head) ``k_scale`` /
    ``v_scale`` slabs move with their page (a page's whole ``[KV, lanes]``
    slab, token t in lane t, lanes padded to 128: no smaller slab can be
    tiled or copied) and are applied in VMEM.

The pool is head-major within a page (``[P, KV, ps, hd]``), so a page's
``[KV, ps, hd]`` slab is one contiguous copy for all heads of the step.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _live_range(kv_len, window):
    """[lo, kv_len) is the live token range for one lane; window 0 = full."""
    lo = jnp.where(window > 0, jnp.maximum(kv_len - window, 0), 0)
    return lo.astype(jnp.int32)


def head_scale_column(sc, h, page_size: int):
    """Head ``h``'s scales from a page's ``[KV, lanes]`` scale slab (token
    t in lane t) as a ``[page_size, 1]`` column. Built from masked sums
    (each has exactly one nonzero term), so it is exact and needs no
    in-kernel transpose."""
    KV, lanes = sc.shape
    head = jax.lax.broadcasted_iota(jnp.int32, (KV, lanes), 0) == h
    row = jnp.sum(jnp.where(head, sc, 0.0), axis=0, keepdims=True)
    diag = (jax.lax.broadcasted_iota(jnp.int32, (page_size, lanes), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (page_size, lanes), 1))
    return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)


def _paged_attn_kernel(
    # scalar-prefetch refs
    block_table_ref,   # [B, mb] int32 (clamped >= 0)
    kv_lens_ref,       # [B] int32 — tokens to attend per lane
    window_ref,        # [1] int32 — sliding window (0 = full attention)
    q_ref,             # [1, KV, G, hd] VMEM
    *refs,
    page_size: int,
    pages_per_block: int,
    max_blocks: int,
    quantized: bool,
    softcap: float,
    scale: float,
):
    ps, ppb = page_size, pages_per_block
    n_pools = 4 if quantized else 2
    pools = refs[:n_pools]                # ANY-space [P, KV, ...] inputs
    o_ref = refs[n_pools]                 # [1, KV, G, hd]
    kb, vb = refs[n_pools + 1:n_pools + 3]    # [2, ppb, KV, ps, hd]
    if quantized:
        ksb, vsb = refs[n_pools + 3:n_pools + 5]   # [2, ppb, KV, lanes]
    sems = refs[-1]                       # DMA [2, ppb, n_pools]

    b = pl.program_id(0)
    kv_len = kv_lens_ref[b]
    lo = _live_range(kv_len, window_ref[0])
    first = lo // ps
    last = jnp.minimum(jnp.maximum(kv_len - 1, 0) // ps, max_blocks - 1)
    n_pages = jnp.where(kv_len > 0, last + 1 - first, 0)
    n_blocks = (n_pages + ppb - 1) // ppb

    def copies(i, slot):
        """The DMA descriptors of page block ``i`` into buffer ``slot``
        (rebuilt identically for start and wait). A tail block's pages
        past ``last`` repeat the last live page: valid data, masked."""
        out = []
        for j in range(ppb):
            pg = jnp.minimum(first + i * ppb + j, last)
            pid = block_table_ref[b, pg]
            out.append(pltpu.make_async_copy(
                pools[0].at[pid], kb.at[slot, j], sems.at[slot, j, 0]))
            out.append(pltpu.make_async_copy(
                pools[1].at[pid], vb.at[slot, j], sems.at[slot, j, 1]))
            if quantized:
                out.append(pltpu.make_async_copy(
                    pools[2].at[pid], ksb.at[slot, j],
                    sems.at[slot, j, 2]))
                out.append(pltpu.make_async_copy(
                    pools[3].at[pid], vsb.at[slot, j],
                    sems.at[slot, j, 3]))
        return out

    def start(i, slot):
        for cp in copies(i, slot):
            cp.start()

    def wait(i, slot):
        for cp in copies(i, slot):
            cp.wait()

    KV, G, hd = q_ref.shape[1:]
    n = ppb * ps

    @pl.when(n_blocks > 0)
    def _warm():
        start(0, 0)

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            start(i + 1, 1 - slot)

        wait(i, slot)
        kv_pos = (first + i * ppb) * ps + jax.lax.broadcasted_iota(
            jnp.int32, (1, n), 1)
        mask = (kv_pos >= lo) & (kv_pos < kv_len)              # [1, n]
        out = []
        for h in range(KV):
            ks_, vs_ = [], []
            for j in range(ppb):          # one [ps, hd] tile per page
                kj = kb[slot, j, h].astype(jnp.float32)
                vj = vb[slot, j, h].astype(jnp.float32)
                if quantized:
                    kj = kj * head_scale_column(
                        ksb[slot, j].astype(jnp.float32), h, ps)
                    vj = vj * head_scale_column(
                        vsb[slot, j].astype(jnp.float32), h, ps)
                ks_.append(kj)
                vs_.append(vj)
            k = jnp.concatenate(ks_, axis=0) if ppb > 1 else ks_[0]
            v = jnp.concatenate(vs_, axis=0) if ppb > 1 else vs_[0]
            q = q_ref[0, h].astype(jnp.float32) * scale        # [G, hd]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [G, n]
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, NEG_INF)
            m_prev, l_prev, acc_prev = carry[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            out.append((m_new,
                        l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                        acc_prev * alpha + jnp.dot(
                            p, v, preferred_element_type=jnp.float32)))
        return tuple(out)

    init = tuple((jnp.full((G, 1), NEG_INF, jnp.float32),
                  jnp.zeros((G, 1), jnp.float32),
                  jnp.zeros((G, hd), jnp.float32)) for _ in range(KV))
    res = jax.lax.fori_loop(0, n_blocks, body, init)
    for h in range(KV):
        _, l, acc = res[h]
        # a lane with no live page divides zeros by the floor: exact zeros
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,            # [B, KV, G, hd]
    k_pages: jax.Array,      # [P, KV, ps, hd]
    v_pages: jax.Array,
    block_table: jax.Array,  # [B, mb] int32 (-1 = unassigned)
    kv_lens: jax.Array,      # [B] int32
    *,
    window=0,                # int or traced scalar; 0 = full attention
    softcap: float = 0.0,
    k_scale: Optional[jax.Array] = None,   # [P, KV, lanes] int8 dequant
    v_scale: Optional[jax.Array] = None,
    pages_per_block: int = 1,
    interpret: bool,
) -> jax.Array:
    """Returns [B, KV, G, hd] attention output."""
    B, KV, G, hd = q.shape
    quantized = k_scale is not None
    ps = k_pages.shape[2]
    mb = block_table.shape[1]
    ppb = max(int(pages_per_block), 1)
    safe_table = jnp.maximum(block_table, 0).astype(jnp.int32)
    window_arr = jnp.reshape(jnp.asarray(window, jnp.int32), (1,))

    kernel = functools.partial(
        _paged_attn_kernel, page_size=ps, pages_per_block=ppb, max_blocks=mb, quantized=quantized, softcap=float(softcap),
        scale=1.0 / math.sqrt(hd))

    def q_map(b, *prefetch):
        return (b, 0, 0, 0)

    pools = [k_pages, v_pages] + ([k_scale, v_scale] if quantized else [])
    scratch = [pltpu.VMEM((2, ppb, KV, ps, hd), k_pages.dtype)] * 2
    if quantized:
        scratch += [pltpu.VMEM((2, ppb) + k_scale.shape[1:],
                               k_scale.dtype)] * 2
    scratch.append(pltpu.SemaphoreType.DMA((2, ppb, len(pools))))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, KV, G, hd), q_map)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((1, KV, G, hd), q_map),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(safe_table, kv_lens.astype(jnp.int32), window_arr, q, *pools)
