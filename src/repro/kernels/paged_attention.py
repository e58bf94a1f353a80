"""Paged-attention decode kernel (Pallas TPU) — the engine decode hot path.

The Blink hot path: one new query token per sequence attends over that
sequence's paged KV cache. On GPU the paper fuses this into the persistent
scheduler's pre-captured decode graph; the TPU-native formulation is a
Pallas kernel that

  * uses *scalar prefetch* for the block table, so the page gather is
    expressed through the BlockSpec ``index_map`` (pages stream HBM->VMEM
    block by block — the TPU analogue of PagedAttention's page-gather),
  * keeps a flash-attention running softmax (m, l, acc) in VMEM scratch,
  * supports sliding-window masking (mixtral/gemma2 local layers) and
    attention-logit softcapping (gemma2) for arch coverage.

Hot-path upgrades (vs the original test-only kernel):

  * per-lane live-page early exit — grid steps whose pages lie entirely
    past ``kv_lens[b]`` skip all compute via ``pl.when``, and their
    ``index_map`` is clamped to the last live page so the pipeline issues
    no new HBM fetch (Pallas skips the DMA when the block index repeats).
    Short lanes therefore pay ~ceil(live/ps) pages, not ``max_blocks``;
  * sliding-window page skip — pages entirely below ``kv_len - window``
    are likewise clamped+skipped instead of merely masked, so window
    attention reads only ~window/ps pages regardless of context length;
  * fused int8-KV dequantisation — optional per-(token, head) ``k_scale``
    / ``v_scale`` refs stream alongside the pages (a page's whole
    ``[KV, lanes]`` scale slab, token t in lane t, lanes padded to 128:
    no smaller slab can be tiled or copied) and are applied in-VMEM, so
    quantised caches run natively instead of falling back to a
    dequantising gather;
  * ``pages_per_block`` — processes several block-table entries per grid
    step (one BlockSpec per page, statically unrolled) to amortise grid
    overhead when the page size is small;
  * the window width is a *dynamic* scalar-prefetch operand (0 = full
    attention), so per-layer window patterns (gemma2 local/global) pass
    straight through a ``lax.scan`` over layers without recompilation.

Grid: (B, KV_heads, ceil(max_blocks / pages_per_block)); each step
processes ``pages_per_block`` KV pages of ``page_size`` tokens against the
G = H/KV query heads of one KV head. The pool is head-major within a page
(``[P, KV, ps, hd]``), so one head's page is a contiguous ``(ps, hd)``
tile — the last two block dims equal the array's, which Mosaic accepts
for any page size.
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
    block_table_ref,   # [B, nb*ppb] int32 (clamped >= 0)
    kv_lens_ref,       # [B] int32 — tokens to attend per lane
    window_ref,        # [1] int32 — sliding window (0 = full attention)
    *refs,
    page_size: int,
    num_groups: int,
    pages_per_block: int,
    quantized: bool,
    softcap: float,
    scale: float,
):
    ppb = pages_per_block
    q_ref = refs[0]                       # [1, 1, G, hd]
    k_refs = refs[1:1 + ppb]              # each [1, 1, ps, hd]
    v_refs = refs[1 + ppb:1 + 2 * ppb]
    at = 1 + 2 * ppb
    ks_refs = vs_refs = ()
    if quantized:
        ks_refs = refs[at:at + ppb]       # each [1, KV, lanes]
        vs_refs = refs[at + ppb:at + 2 * ppb]
        at += 2 * ppb
    o_ref = refs[at]                      # [1, 1, G, hd]
    m_scr, l_scr, acc_scr = refs[at + 1:at + 4]

    b = pl.program_id(0)
    h = pl.program_id(1)
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = kv_lens_ref[b]
    lo = _live_range(kv_len, window_ref[0])
    q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, hd]

    for j in range(ppb):
        start = (g * ppb + j) * page_size
        # live-page gate: pages past kv_len (early exit) or entirely below
        # the sliding window contribute nothing — skip the dots, not just
        # the mask. The index_map clamps these steps to a live page, so no
        # fresh HBM fetch happens either.
        live = (start < kv_len) & (start + page_size > lo)

        @pl.when(live)
        def _process(j=j, start=start):
            k = k_refs[j][0, 0].astype(jnp.float32)          # [ps, hd]
            v = v_refs[j][0, 0].astype(jnp.float32)
            if quantized:
                k = k * head_scale_column(
                    ks_refs[j][0].astype(jnp.float32), h, page_size)
                v = v * head_scale_column(
                    vs_refs[j][0].astype(jnp.float32), h, page_size)

            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [G, ps]
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)

            kv_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, page_size), 1)
            mask = (kv_pos >= lo) & (kv_pos < kv_len)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_scr[...]                               # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)                            # [G, ps]
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)                   # [G, 1]
            l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[...] = m_new

    @pl.when(g == num_groups - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


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
    P, _, ps, _ = k_pages.shape
    mb = block_table.shape[1]
    ppb = max(int(pages_per_block), 1)
    nb = -(-mb // ppb)
    if nb * ppb != mb:
        block_table = jnp.pad(block_table, ((0, 0), (0, nb * ppb - mb)),
                              constant_values=-1)
    scale = 1.0 / math.sqrt(hd)
    safe_table = jnp.maximum(block_table, 0).astype(jnp.int32)
    window_arr = jnp.reshape(jnp.asarray(window, jnp.int32), (1,))
    quantized = k_scale is not None

    grid = (B, KV, nb)

    def q_map(b, h, g, bt, kl, wl):
        return (b, h, 0, 0)

    def page_of(b, g, j, bt, kl, wl):
        """Pool page for the j-th page of group g, clamped to the live
        block range so dead grid steps repeat the previous block index
        (Pallas elides the HBM->VMEM copy when the index is unchanged)."""
        kv_len = kl[b]
        lo = _live_range(kv_len, wl[0])
        first = lo // ps
        last = jnp.maximum(kv_len - 1, 0) // ps
        blk = jnp.clip(g * ppb + j, first, last)
        return bt[b, blk]

    def kv_map(b, h, g, bt, kl, wl, *, j):
        return (page_of(b, g, j, bt, kl, wl), h, 0, 0)

    def scale_map(b, h, g, bt, kl, wl, *, j):
        return (page_of(b, g, j, bt, kl, wl), 0, 0)

    def o_map(b, h, g, bt, kl, wl):
        return (b, h, 0, 0)

    kernel = functools.partial(
        _paged_attn_kernel, page_size=ps, num_groups=nb,
        pages_per_block=ppb, quantized=quantized,
        softcap=float(softcap), scale=scale)

    kv_specs = [pl.BlockSpec((1, 1, ps, hd), functools.partial(kv_map, j=j))
                for j in range(ppb)]
    in_specs = [pl.BlockSpec((1, 1, G, hd), q_map)] + kv_specs + kv_specs
    inputs = [q] + [k_pages] * ppb + [v_pages] * ppb
    if quantized:
        sc_specs = [pl.BlockSpec((1, KV, k_scale.shape[2]),
                                 functools.partial(scale_map, j=j))
                    for j in range(ppb)]
        in_specs += sc_specs + sc_specs
        inputs += [k_scale] * ppb + [v_scale] * ppb

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, G, hd), o_map),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(safe_table, kv_lens.astype(jnp.int32), window_arr, *inputs)
    return out
