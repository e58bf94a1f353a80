"""Decode-time cache containers.

``PagedKVCache`` is the Blink paged KV cache: a global page pool plus a
per-slot block table, all device-resident. SSM/hybrid archs additionally (or
exclusively) carry fixed-size recurrent state. Everything is a pytree so the
whole cache lives inside the persistent window program and survives
re-instantiation via donation (paper §4.2 "seamless state continuity").
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


#: cache key of a step's expert counters ``[expert_rows, experts_hit]``,
#: summed over the model's calls in the step; the engine drains it into
#: the step's counter row
EXPERT_COUNTS = "experts"


def add_expert_counts(cache: Dict[str, Any], counts) -> Dict[str, Any]:
    """Add one model call's expert counters to the step's accumulator (a
    cache without experts is returned as it is)."""
    if EXPERT_COUNTS not in cache:
        return cache
    return dict(cache, **{EXPERT_COUNTS: cache[EXPERT_COUNTS] + counts})


def drain_expert_counts(cache: Dict[str, Any]):
    """``(counts, cache)``: the step's expert counters (None without
    experts) and the cache with the accumulator zeroed."""
    counts = cache.get(EXPERT_COUNTS)
    if counts is None:
        return None, cache
    return counts, dict(cache, **{EXPERT_COUNTS: jnp.zeros_like(counts)})


@jax.tree_util.register_dataclass
@dataclass
class PagedKVCache:
    """Paged KV pool.

    k_pages/v_pages: [L, P, KV, page_size, hd] — head-major within a
                     page, so one head's page is a contiguous
                     ``(page_size, hd)`` tile (the only block shape the
                     TPU kernels can fetch per head)
    block_table:     [S, max_blocks]  (page id per block, -1 = unassigned)
    seq_lens:        [S]              (tokens currently cached per slot)
    k_scale/v_scale: [L, P, KV, scale_lanes(page_size)] — per-(token,
                     head) dequant scales, token t in lane t, present only
                     for int8 KV (halves KV HBM traffic and footprint).
                     Lanes past page_size are padding: a page's scale slab
                     must span whole 128-lane rows for a kernel to copy it
    kv_fused:        [L, P, KV, 2, page_size, hd] — opt-in interleaved
                     K/V layout (``ServeConfig.kv_fused_layout``): K at
                     [:, :, :, 0] and V at [:, :, :, 1] share one page, so
                     the unified ragged kernel issues ONE page copy where
                     the split layout needs two. Mutually exclusive with
                     k_pages/v_pages (which are None when fused).
    """
    k_pages: Optional[jax.Array]
    v_pages: Optional[jax.Array]
    block_table: jax.Array
    seq_lens: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    kv_fused: Optional[jax.Array] = None

    @property
    def fused(self) -> bool:
        return self.kv_fused is not None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def page_size(self) -> int:
        pool = self.kv_fused if self.fused else self.k_pages
        return pool.shape[-2]

    @property
    def num_pages(self) -> int:
        pool = self.kv_fused if self.fused else self.k_pages
        return pool.shape[1]

    @property
    def max_blocks(self) -> int:
        return self.block_table.shape[1]

    @property
    def max_kv(self) -> int:
        return self.max_blocks * self.page_size


def scale_lanes(page_size: int) -> int:
    """Lanes of one page's int8 scale row: page_size rounded up to 128."""
    return -(-page_size // 128) * 128


def make_paged_kv_cache(
    cfg: ModelConfig,
    *,
    num_slots: int,
    num_pages: int,
    page_size: int,
    max_blocks: int,
    dtype=None,
    fused: bool = False,
) -> PagedKVCache:
    L = cfg.num_attn_layers
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    dtype = jnp.dtype(dtype) if dtype else cfg.jnp_dtype
    # k_scale/v_scale must be DISTINCT buffers: the engine donates the whole
    # cache pytree per window, and donating one buffer twice is an error.
    mk_scales = lambda: (jnp.zeros((L, num_pages, kv, scale_lanes(page_size)),
                                   jnp.bfloat16)
                         if dtype == jnp.int8 else None)
    return PagedKVCache(
        k_pages=None if fused else jnp.zeros(
            (L, num_pages, kv, page_size, hd), dtype),
        v_pages=None if fused else jnp.zeros(
            (L, num_pages, kv, page_size, hd), dtype),
        block_table=jnp.full((num_slots, max_blocks), -1, jnp.int32),
        seq_lens=jnp.zeros((num_slots,), jnp.int32),
        k_scale=mk_scales(),
        v_scale=mk_scales(),
        kv_fused=jnp.zeros((L, num_pages, kv, 2, page_size, hd), dtype)
        if fused else None,
    )


def page_nbytes(cache: PagedKVCache) -> int:
    """Device bytes one pool page occupies across all layers: K + V values
    plus dequant scales when the pool is quantised. Byte-denominated
    policies (the radix-trie byte cap, offload-buffer accounting) divide
    their budget by this to get a page budget."""
    def per_page(x):
        return math.prod(x.shape) // x.shape[1] * x.dtype.itemsize

    pool = cache.kv_fused if cache.fused else cache.k_pages
    n = (1 if cache.fused else 2) * per_page(pool)
    if cache.quantized:
        n += 2 * per_page(cache.k_scale)
    return n


def pages_needed(prompt_len, max_new, page_size: int):
    """KV pages a request occupies for its whole lifetime (prompt + all
    generated tokens). The engine's admission gate and the prefill-branch
    allocator both use this — one formula, so the gate can never admit a
    request the allocator would refuse (or vice versa)."""
    return (prompt_len + max_new + page_size - 1) // page_size


# ---------------------------------------------------------------------------
# KV page IO
# ---------------------------------------------------------------------------


def write_kv_layer(
    cache: PagedKVCache,
    layer: jax.Array,         # scalar layer index (traced ok)
    slot_ids: jax.Array,      # [B] slot per lane
    k_new: jax.Array,         # [B, Tq, KV, hd]
    v_new: jax.Array,
    start_pos: jax.Array,     # [B] cache position of k_new[:, 0] (may be <0
                              #     for left-padded prompts)
    lengths: jax.Array,       # [B] number of valid trailing tokens is
                              #     enforced via pos in [0, lengths)
    active: jax.Array,        # [B] bool — lane participates
    min_pos: Optional[jax.Array] = None,  # [B] writes below this cache
                              #     position are dropped (prefix reuse:
                              #     shared pages are read-only)
) -> PagedKVCache:
    """Scatter one layer's new K/V into the slots' pages.

    Unified for prefill (Tq = padded prompt len, left-aligned via start_pos)
    and decode (Tq = 1, start_pos = current seq_len). Does NOT update
    seq_lens — the engine owns that transition (once per step, not per layer).
    """
    B, Tq, KV, hd = k_new.shape
    ps = cache.page_size
    # pools index [layer, page, head, token]: the head slice sits between
    # the advanced indices, so the scattered block is [B, Tq, KV, ...]
    pos = start_pos[:, None] + jnp.arange(Tq)[None, :]    # [B, Tq]
    blk = jnp.clip(pos // ps, 0, cache.max_blocks - 1)
    off = pos % ps
    pages = cache.block_table[slot_ids]                   # [B, max_blocks]
    page_of = jnp.take_along_axis(pages, blk, axis=1)     # [B, Tq]
    valid = (pos >= 0) & (pos < lengths[:, None]) & active[:, None] \
        & (page_of >= 0) & (pos // ps < cache.max_blocks)
    if min_pos is not None:
        valid &= pos >= min_pos[:, None]
    page_idx = jnp.where(valid, page_of, cache.num_pages)  # OOB -> drop
    l_idx = jnp.broadcast_to(layer, (B, Tq))
    extra = {}
    if cache.quantized:
        k_new, k_sc = _quantize(k_new)
        v_new, v_sc = _quantize(v_new)
        extra["k_scale"] = cache.k_scale.at[l_idx, page_idx, :, off].set(
            k_sc.astype(cache.k_scale.dtype), mode="drop")
        extra["v_scale"] = cache.v_scale.at[l_idx, page_idx, :, off].set(
            v_sc.astype(cache.v_scale.dtype), mode="drop")
    if cache.fused:
        kv_new = jnp.stack([k_new, v_new], axis=3)       # [B, Tq, KV, 2, hd]
        kv_fused = cache.kv_fused.at[l_idx, page_idx, :, :, off].set(
            kv_new.astype(cache.kv_fused.dtype), mode="drop")
        return dataclasses.replace(cache, kv_fused=kv_fused, **extra)
    k_pages = cache.k_pages.at[l_idx, page_idx, :, off].set(
        k_new.astype(cache.k_pages.dtype), mode="drop")
    v_pages = cache.v_pages.at[l_idx, page_idx, :, off].set(
        v_new.astype(cache.v_pages.dtype), mode="drop")
    return dataclasses.replace(cache, k_pages=k_pages, v_pages=v_pages,
                               **extra)


def _quantize(x: jax.Array):
    """[..., hd] -> (int8 values, per-[...] scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def set_seq_lens(cache: PagedKVCache, slot_ids: jax.Array, new_lens: jax.Array,
                 active: jax.Array) -> PagedKVCache:
    # inactive lanes scatter to an OOB index (dropped) — slot_ids are often
    # zero-padded, and a duplicate-index scatter would leave the winner to
    # XLA (an inactive lane's stale read could overwrite an active write)
    sel = jnp.where(active, slot_ids, cache.seq_lens.shape[0])
    seq_lens = cache.seq_lens.at[sel].set(new_lens, mode="drop")
    return dataclasses.replace(cache, seq_lens=seq_lens)


def _read_pages(k_pages, v_pages, pages, k_scale=None, v_scale=None,
                kv_fused=None):
    """K/V of pool rows ``pages`` (any index shape ``[...]``) as
    token-major ``[..., ps, KV, hd]``, dequantised when scales are given.
    Takes one layer's pools: split ``[P, KV, ps, hd]`` or fused
    ``[P, KV, 2, ps, hd]``; scales ``[P, KV, lanes]``."""
    if kv_fused is not None:
        k_pages, v_pages = kv_fused[:, :, 0], kv_fused[:, :, 1]
    ps = k_pages.shape[2]
    k = jnp.swapaxes(k_pages[pages], -3, -2)              # [..., ps, KV, hd]
    v = jnp.swapaxes(v_pages[pages], -3, -2)
    if k_scale is not None:
        k = _dequant(k, jnp.swapaxes(k_scale[pages][..., :ps], -2, -1))
        v = _dequant(v, jnp.swapaxes(v_scale[pages][..., :ps], -2, -1))
    return k, v


def layer_pools(cache: PagedKVCache, layer):
    """One layer's pool arrays, keyed like ``attn_backend.PagedPrefix``."""
    return dict(
        k_pages=None if cache.fused else cache.k_pages[layer],
        v_pages=None if cache.fused else cache.v_pages[layer],
        kv_fused=cache.kv_fused[layer] if cache.fused else None,
        k_scale=cache.k_scale[layer] if cache.quantized else None,
        v_scale=cache.v_scale[layer] if cache.quantized else None)


def gather_kv_window(cache: PagedKVCache, layer: jax.Array,
                     slot_ids: jax.Array, pos: jax.Array, window: int):
    """Gather only the blocks covering [pos-window, pos] (§Perf hillclimb:
    REPRO_WINDOW_GATHER). For sliding-window archs the decode step only
    needs the last ``window`` tokens; gathering the full 500k-token block
    table reads ~128x more HBM than the live window.

    Returns (k [B, W*ps, KV, hd], v, kv_pos [B, W*ps] absolute positions).
    """
    ps = cache.page_size
    W = window // ps + 2                       # static block count
    first_blk = jnp.maximum(pos - window, 0) // ps          # [B]
    blk = first_blk[:, None] + jnp.arange(W)[None, :]       # [B, W]
    blk_c = jnp.clip(blk, 0, cache.max_blocks - 1)
    pages = jnp.take_along_axis(cache.block_table[slot_ids], blk_c, axis=1)
    safe = jnp.clip(pages, 0, cache.num_pages - 1)
    k, v = _read_pages(pages=safe, **layer_pools(cache, layer))
    B_, W_, ps_, KV, hd = k.shape
    kv_pos = (blk_c * ps)[:, :, None] + jnp.arange(ps)[None, None, :]
    # positions beyond the table or unassigned pages are masked by callers
    # via kv_pos > pos; mark invalid pages with pos = huge
    bad = (pages < 0)[:, :, None]
    kv_pos = jnp.where(bad, jnp.int32(2**30), kv_pos)
    return (k.reshape(B_, W_ * ps_, KV, hd), v.reshape(B_, W_ * ps_, KV, hd),
            kv_pos.reshape(B_, W_ * ps_))


def gather_pages(k_pages: Optional[jax.Array], v_pages: Optional[jax.Array],
                 block_rows: jax.Array, k_scale=None, v_scale=None,
                 kv_fused: Optional[jax.Array] = None):
    """Materialise [B, mb*ps, KV, hd] K/V from raw page arrays through
    per-lane block-table rows (jnp reference path for the prefix-aware
    prefill; the Pallas flash-prefill kernel fuses this gather). Rows may
    contain -1 (unassigned) — callers mask by cached length. A fused
    interleaved pool (``kv_fused`` [P, KV, 2, ps, hd]) is accepted in
    place of the split pair."""
    P = (kv_fused if kv_fused is not None else k_pages).shape[0]
    safe = jnp.clip(block_rows, 0, P - 1)
    k, v = _read_pages(k_pages, v_pages, safe, k_scale, v_scale, kv_fused)
    B, mb, ps, KV, hd = k.shape
    return k.reshape(B, mb * ps, KV, hd), v.reshape(B, mb * ps, KV, hd)


def gather_kv(cache: PagedKVCache, layer: jax.Array, slot_ids: jax.Array):
    """Materialise [B, max_kv, KV, hd] K/V for one layer (jnp reference path;
    the Pallas `paged_attention` kernel fuses this gather)."""
    pages = cache.block_table[slot_ids]                   # [B, max_blocks]
    safe = jnp.clip(pages, 0, cache.num_pages - 1)
    k, v = _read_pages(pages=safe, **layer_pools(cache, layer))
    B, mb, ps, KV, hd = k.shape
    return k.reshape(B, mb * ps, KV, hd), v.reshape(B, mb * ps, KV, hd)


# ---------------------------------------------------------------------------
# Page allocator (free-list as device arrays — managed inside the window
# program, no host involvement; paper §4.2 "KV-cache management")
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclass
class PageAllocator:
    """LIFO free list + per-page reference counts.

    ``refcount[p]`` is the number of owners of page ``p`` (0 = free). A page
    can be co-owned — by several slots sharing a cached prefix and by the
    frontend's prefix trie — and returns to the free stack only when the
    last owner releases it (``free_pages``). Everything is device-resident
    so sharing decisions made on the DPU plane (the radix prefix index)
    materialise as pure array updates between windows."""
    free_stack: jax.Array    # [P] int32
    top: jax.Array           # [] int32 — number of free pages
    refcount: jax.Array      # [P] int32 — owners per page (0 = free)


def make_page_allocator(num_pages: int) -> PageAllocator:
    return PageAllocator(
        free_stack=jnp.arange(num_pages - 1, -1, -1, dtype=jnp.int32),
        top=jnp.asarray(num_pages, jnp.int32),
        refcount=jnp.zeros((num_pages,), jnp.int32),
    )


def alloc_pages(alloc: PageAllocator, n: jax.Array, max_n: int):
    """Pop up to ``max_n`` pages; only the first ``n`` are meaningful.

    Returns (pages [max_n] int32 (-1 beyond n), new_alloc, ok bool).
    Allocation is all-or-nothing: if fewer than n pages are free, ok=False
    and the allocator is unchanged (backpressure — the request stays
    PREFILL_PENDING in the ring, the paper's admission gating). Allocated
    pages start with refcount 1 (sole owner: the allocating slot).
    """
    P = alloc.free_stack.shape[0]
    ok = alloc.top >= n
    idx = alloc.top - 1 - jnp.arange(max_n)
    take = (jnp.arange(max_n) < n) & ok
    pages = jnp.where(take, alloc.free_stack[jnp.clip(idx, 0, None)], -1)
    new_top = jnp.where(ok, alloc.top - n, alloc.top)
    refcount = alloc.refcount.at[jnp.where(pages >= 0, pages, P)].set(
        1, mode="drop")
    return (pages,
            dataclasses.replace(alloc, top=new_top, refcount=refcount), ok)


def share_pages(alloc: PageAllocator, pages: jax.Array):
    """Add one reference to each valid (>=0) entry of ``pages`` — a new
    co-owner (a slot reusing a cached prefix, or the prefix trie indexing
    freshly prefilled pages) of already-resident pages."""
    P = alloc.free_stack.shape[0]
    refcount = alloc.refcount.at[jnp.where(pages >= 0, pages, P)].add(
        1, mode="drop")
    return dataclasses.replace(alloc, refcount=refcount)


def free_pages(alloc: PageAllocator, pages: jax.Array):
    """Release one reference on each valid (>=0) entry of ``pages`` [max_n];
    pages whose refcount reaches zero return to the free stack. With all
    refcounts at 1 (no sharing) this is the plain free of the original
    allocator."""
    P = alloc.free_stack.shape[0]
    valid = pages >= 0
    safe = jnp.where(valid, pages, P)
    refcount = alloc.refcount.at[safe].add(-1, mode="drop")
    freeable = valid & (refcount[jnp.where(valid, pages, 0)] <= 0)
    n = jnp.sum(freeable.astype(jnp.int32))
    # compact freeable pages to the front
    order = jnp.argsort(~freeable, stable=True)
    compacted = pages[order]
    idx = alloc.top + jnp.arange(pages.shape[0])
    write = jnp.arange(pages.shape[0]) < n
    stack = alloc.free_stack.at[jnp.where(write, idx, P)].set(
        compacted, mode="drop")
    return dataclasses.replace(alloc, free_stack=stack, top=alloc.top + n,
                               refcount=refcount)


# ---------------------------------------------------------------------------
# SSM / hybrid / enc-dec cache bundles
# ---------------------------------------------------------------------------


def make_cache(cfg: ModelConfig, *, num_slots: int, num_pages: int,
               page_size: int, max_blocks: int, enc_len: int = 0,
               dtype=None, kv_fused_layout: bool = False) -> Dict[str, Any]:
    """Family-appropriate cache bundle, keyed by component."""
    from repro.models import ssm as ssm_mod  # local import to avoid cycle

    cache: Dict[str, Any] = {}
    if cfg.uses_paged_kv:
        cache["kv"] = make_paged_kv_cache(
            cfg, num_slots=num_slots, num_pages=num_pages,
            page_size=page_size, max_blocks=max_blocks, dtype=dtype,
            fused=kv_fused_layout)
    if cfg.num_experts:
        cache[EXPERT_COUNTS] = jnp.zeros((2,), jnp.int32)
    if cfg.arch_type == "ssm":  # rwkv6
        st = ssm_mod.rwkv6_init_state(cfg, num_slots)
        cache["ssm"] = jax.tree.map(
            lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), st)
    if cfg.arch_type == "hybrid":  # zamba2: mamba2 state every layer
        st = ssm_mod.mamba2_init_state(cfg, num_slots)
        cache["ssm"] = jax.tree.map(
            lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), st)
    if cfg.is_encoder_decoder and enc_len:
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        # quantised cache dtypes apply to the paged KV pool only (it carries
        # k_scale/v_scale); the dense cross-attention K/V have no scale
        # storage, so int8 here would truncate values to {-2..2} silently.
        enc_dtype = dtype or cfg.jnp_dtype
        if jnp.dtype(enc_dtype) == jnp.int8:
            enc_dtype = cfg.jnp_dtype
        cache["enc_k"] = jnp.zeros(
            (cfg.num_layers, num_slots, enc_len, kv, hd), enc_dtype)
        cache["enc_v"] = jnp.zeros_like(cache["enc_k"])
        cache["enc_len"] = jnp.zeros((num_slots,), jnp.int32)
    return cache
