"""Unified ragged paged-attention kernel (Pallas TPU) — one dispatch per step.

Blink's central loop is ONE bounded GPU iteration that batches, schedules
and attends without host involvement (PAPER.md Fig. 2, §4). The split
kernels (``flash_prefill`` for the chunk bucket + ``paged_attention`` for
decode lanes) forced the mixed engine step to issue TWO attention
dispatches per iteration. This kernel serves both phases in one grid, the
sglang-jax ``ragged_paged_attention`` idiom: rows are *ragged* — a decode
lane is simply a row with ``q_len == 1`` and a prefill chunk a row with
``q_len == chunk`` — described by cumulative length metadata derived from
ring state:

  * ``cu_q_lens[b+1] - cu_q_lens[b]``  = live in-flight queries of row b
    (0 = inactive row, 1 = decode lane, >1 = prefill chunk);
  * ``cu_kv_lens[b+1] - cu_kv_lens[b]`` = row b's total context; the
    difference ``kv_len - q_len`` is the *cached* prefix already resident
    in the paged KV pool, reachable through ``block_tables[b]``.

Rows are LEFT-padded into the ``[B, T]`` bucket (row b's live tokens
occupy columns ``[T - q_len, T)``) so the mask logic is identical to
``flash_prefill``; no separate offsets operand is needed.

Grid ``(B, KV, num_q_blocks)`` with the whole key loop INTERNAL to each
grid step (unlike ``flash_prefill``'s grid key axis):

  * cached-prefix pages stream HBM->VMEM through explicit DOUBLE-BUFFERED
    ``make_async_copy`` DMAs (``pages_per_block`` pages per buffer slot,
    block ``i+1`` issued before block ``i`` is consumed) — the pools ride
    in ``memory_space=ANY`` and only live pages move;
  * live-page early exit: the page loop runs ``ceil(cached/ps)`` pages,
    not the block-table width; sliding windows additionally raise the
    loop's lower bound so out-of-window pages are never fetched;
  * dead query tiles (entirely left-pad, including ``q_len == 0`` rows)
    run zero page-loop trips and their suffix masks collapse to empty —
    compute scales with live tokens, not the bucket ceiling;
  * the in-flight suffix (the ``[B, T]`` K/V of this step's new tokens)
    attends from VMEM with causal + left-pad + sliding-window masks in
    column space, exactly like ``flash_prefill``'s suffix phase;
  * GQA, softcap and fused int8-dequant of pooled K/V (per-row scales)
    are preserved from both parent kernels;
  * ``writes_kv=True`` adds a KV-WRITE EPILOGUE: after the last query
    block of each (row, kv-head), the row's new K/V tokens are merged
    into their suffix pages via read-modify-write DMAs against the
    ALIASED pool outputs — including fused int8 quantisation (bitwise
    twin of ``models.cache._quantize``), so int8 serving never
    materialises a float K/V staging tensor in HBM;
  * opt-in fused-KV layout (``kv_fused``: ``[P, KV, 2, ps, hd]``,
    K at index 0 / V at index 1 of the packed axis) halves the page
    fetch count — one DMA brings both halves of a page.

Layout: pools are head-major within a page (``[P, KV, ps, hd]``), so a
head's page is one contiguous ``(ps, hd)`` copy; int8 scales move as the
page's whole ``[KV, lanes]`` slab (token t in lane t, lanes padded to 128
— the smallest slab a DMA can move). The in-flight K/V reach the kernel
head-major (``[B, KV, Tp, hd]``): Mosaic refuses a block that slices one
head out of a second-minor KV axis.

The write epilogue is safe under the sequential grid order (b outer, h
middle, q-block inner): a row's suffix pages are exclusively owned by its
slot, prefix reads of head h all precede head h's epilogue, different
heads write disjoint ``[pid, h]`` K/V tiles, and the shared ``[KV, lanes]``
scale slab is read-modified-written by one head at a time. A parallel
grid (a megacore schedule) would need per-head scale pages.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import head_scale_column

NEG_INF = -1e30


def build_cu_lens(q_lens: jax.Array, cached_lens: jax.Array):
    """Ragged metadata from ring-derived per-row lengths.

    q_lens[b]      = live in-flight tokens of row b this step (0 = dead
                     row, 1 = decode lane, >1 = prefill chunk);
    cached_lens[b] = tokens already resident in the paged pool.

    Returns ``(cu_q_lens, cu_kv_lens)``, both ``[B+1]`` int32, monotone
    non-decreasing with ``cu[0] == 0`` — the contract the hypothesis
    property in tests/test_ragged_attention.py pins.
    """
    q_lens = jnp.asarray(q_lens, jnp.int32)
    kv_lens = jnp.asarray(cached_lens, jnp.int32) + q_lens
    zero = jnp.zeros((1,), jnp.int32)
    cu_q = jnp.concatenate([zero, jnp.cumsum(q_lens, dtype=jnp.int32)])
    cu_kv = jnp.concatenate([zero, jnp.cumsum(kv_lens, dtype=jnp.int32)])
    return cu_q, cu_kv


def _quantize_rows(x: jax.Array):
    """Bitwise twin of ``models.cache._quantize`` for one ``[ps, hd]``
    slab: per-row absmax int8 with a floor so zero rows stay finite.
    Elementwise over rows => batch-shape invariant => bitwise-equal to
    the old jnp path whatever the staging shape was. The scale comes back
    as a ``[ps, 1]`` column."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _page_rows(x: jax.Array, rel: jax.Array):
    """Rows ``rel`` (a ``[ps, 1]`` column, clipped into range) of the
    ``[Tp, hd]`` in-flight tile, as a one-hot matmul: Mosaic has no row
    gather, and each output is one exact product plus zeros."""
    rel = jnp.clip(rel, 0, x.shape[0] - 1)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (rel.shape[0], x.shape[0]),
                                       1) == rel).astype(x.dtype)
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    return jnp.dot(onehot, x, preferred_element_type=jnp.float32,
                   precision=exact).astype(x.dtype)


def _column_to_row(col: jax.Array, lanes: int):
    """``[n, 1]`` -> ``[1, lanes]`` (entry i in lane i, zeros past n) by a
    masked sum over the diagonal (one nonzero term per entry, so exact) —
    no in-kernel transpose."""
    n = col.shape[0]
    diag = (jax.lax.broadcasted_iota(jnp.int32, (n, lanes), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, lanes), 1))
    return jnp.sum(jnp.where(diag, col, 0.0), axis=0, keepdims=True)


def _ragged_kernel(
    # scalar prefetch
    cu_q_ref,      # [B+1]
    cu_kv_ref,     # [B+1]
    window_ref,    # [1]
    bt_ref,        # [B, mb] RAW block table (-1 = unassigned)
    *refs,
    block_q: int,
    pages_per_block: int,
    page_size: int,
    max_blocks: int,
    num_q_blocks: int,
    num_suffix_pages: int,
    bucket: int,
    q_per_kv: int,
    quantized: bool,
    fused: bool,
    writes_kv: bool,
    softcap: float,
    scale: float,
):
    at = 0
    q_ref = refs[at]                     # [1, bq, 1, G, hd] VMEM
    k_ref, v_ref = refs[at + 1], refs[at + 2]   # [1, 1, Tp, hd] VMEM
    at += 3
    n_pools = (1 if fused else 2) + (2 if quantized else 0)
    pools_in = refs[at:at + n_pools]     # ANY-space pool (+scale) inputs
    at += n_pools
    o_ref = refs[at]                     # [1, bq, 1, G, hd] VMEM
    at += 1
    pools_out = ()
    if writes_kv:
        pools_out = refs[at:at + n_pools]
        at += n_pools
    scratch = refs[at:]
    si = 0
    if fused:
        kvb = scratch[si]; si += 1       # [2, ppb, 2, ps, hd] pool dtype
    else:
        kb, vb = scratch[si], scratch[si + 1]; si += 2   # [2, ppb, ps, hd]
    if quantized:
        ksb, vsb = scratch[si], scratch[si + 1]; si += 2   # [2,ppb,KV,lanes]
    sems = scratch[si]; si += 1
    if writes_kv:
        if fused:
            wkv = scratch[si]; si += 1   # [2, ps, hd]
        else:
            wk, wv = scratch[si], scratch[si + 1]; si += 2
        if quantized:
            wks, wvs = scratch[si], scratch[si + 1]; si += 2   # [KV, lanes]
        wsem = scratch[si]; si += 1

    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    G = q_per_kv
    ps = page_size
    ppb = pages_per_block

    q_len = cu_q_ref[b + 1] - cu_q_ref[b]
    kv_len = cu_kv_ref[b + 1] - cu_kv_ref[b]
    cached = kv_len - q_len
    off = bucket - q_len                 # left-pad width of this row
    w = window_ref[0]
    eff_w = jnp.where(w > 0, w, jnp.int32(2**30))
    qs = qi * block_q
    q_live = qs + block_q > off

    # ---- prefix page-loop bounds (live-page early exit + window skip) ----
    p_hi = (cached + ps - 1) // ps
    qa_lo = cached + jnp.maximum(qs, off) - off   # lowest live q abs pos
    p_lo = jnp.maximum(qa_lo - eff_w + 1, 0) // ps
    n_pages = jnp.maximum(p_hi - p_lo, 0)
    # dead tile => zero trips: the whole DMA+compute loop is skipped
    n_blocks = jnp.where(q_live, (n_pages + ppb - 1) // ppb, 0)

    def prefix_copies(i, slot):
        """The DMA descriptors of page block ``i`` into buffer ``slot``
        (reconstructed identically for start and wait)."""
        base = p_lo + i * ppb
        out = []
        for j in range(ppb):
            pg = jnp.clip(base + j, 0, max_blocks - 1)
            pid = jnp.maximum(bt_ref[b, pg], 0)   # clamp: masked anyway
            c = 0
            if fused:
                out.append(pltpu.make_async_copy(
                    pools_in[0].at[pid, h], kvb.at[slot, j],
                    sems.at[slot, j, c])); c += 1
            else:
                out.append(pltpu.make_async_copy(
                    pools_in[0].at[pid, h], kb.at[slot, j],
                    sems.at[slot, j, c])); c += 1
                out.append(pltpu.make_async_copy(
                    pools_in[1].at[pid, h], vb.at[slot, j],
                    sems.at[slot, j, c])); c += 1
            if quantized:
                ksrc, vsrc = pools_in[-2], pools_in[-1]
                out.append(pltpu.make_async_copy(
                    ksrc.at[pid], ksb.at[slot, j],
                    sems.at[slot, j, c])); c += 1
                out.append(pltpu.make_async_copy(
                    vsrc.at[pid], vsb.at[slot, j],
                    sems.at[slot, j, c])); c += 1
        return out

    def issue(i, slot):
        for cp in prefix_copies(i, slot):
            cp.start()

    def wait(i, slot):
        for cp in prefix_copies(i, slot):
            cp.wait()

    hd = q_ref.shape[-1]
    q = q_ref[0, :, 0].astype(jnp.float32).reshape(block_q * G, hd) * scale

    def accumulate(carry, s, mask, v):
        """Online-softmax update with one key block's masked logits ``s``
        [bq*G, n] and values ``v`` [n, hd]; carry = (m, l, acc) values."""
        m_prev, l_prev, acc_prev = carry
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc_prev * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # ---- prefix phase: double-buffered paged K/V ------------------------
    @pl.when(n_blocks > 0)
    def _warm():
        issue(0, 0)

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _issue_next():
            issue(i + 1, 1 - slot)

        wait(i, slot)
        ks_, vs_ = [], []
        for j in range(ppb):                # one [ps, hd] tile per page
            if fused:
                kj = kvb[slot, j, 0].astype(jnp.float32)
                vj = kvb[slot, j, 1].astype(jnp.float32)
            else:
                kj = kb[slot, j].astype(jnp.float32)
                vj = vb[slot, j].astype(jnp.float32)
            if quantized:
                kj = kj * head_scale_column(
                    ksb[slot, j].astype(jnp.float32), h, ps)
                vj = vj * head_scale_column(
                    vsb[slot, j].astype(jnp.float32), h, ps)
            ks_.append(kj)
            vs_.append(vj)
        kk = jnp.concatenate(ks_, axis=0) if ppb > 1 else ks_[0]
        vv = jnp.concatenate(vs_, axis=0) if ppb > 1 else vs_[0]
        s = jnp.dot(q, kk.T, preferred_element_type=jnp.float32)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        n = ppb * ps
        q_col = qs + jax.lax.broadcasted_iota(
            jnp.int32, (block_q * G, n), 0) // G
        k_abs = (p_lo + i * ppb) * ps + jax.lax.broadcasted_iota(
            jnp.int32, (block_q * G, n), 1)
        qa = cached + q_col - off
        # causal over the prefix is automatic: k_abs < cached <= qa
        mask = (k_abs < cached) & (q_col >= off) & ((qa - k_abs) < eff_w)
        return accumulate(carry, s, mask, vv)

    init = (jnp.full((block_q * G, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q * G, 1), jnp.float32),
            jnp.zeros((block_q * G, hd), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)

    # ---- suffix phase: in-flight keys from VMEM, column-space masks ------
    kk = k_ref[0, 0].astype(jnp.float32)            # [Tp, hd]
    vv = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, kk.T, preferred_element_type=jnp.float32)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    q_col = qs + jax.lax.broadcasted_iota(
        jnp.int32, (block_q * G, bucket), 0) // G
    k_col = jax.lax.broadcasted_iota(
        jnp.int32, (block_q * G, bucket), 1)
    mask = (k_col <= q_col) & (k_col >= off) & ((q_col - k_col) < eff_w)
    m, l, acc = accumulate((m, l, acc), s, mask, vv)

    l = jnp.maximum(l, 1e-20)           # dead rows divide to exact zero
    o_ref[0, :, 0] = (acc / l).reshape(block_q, G, hd).astype(o_ref.dtype)

    if not writes_kv:
        return

    # ---- KV-write epilogue: merge this row's new tokens into the pool ----
    # Runs once per (row, head) after its last query block. Suffix pages
    # are read-modified-written so a partially-filled boundary page keeps
    # its prefix rows; int8 pools quantise HERE (no float staging in HBM).
    @pl.when((qi == num_q_blocks - 1) & (q_len > 0))
    def _epilogue():
        k_row = k_ref[0, 0]              # [Tp, hd] model dtype
        v_row = v_ref[0, 0]

        def copy(src, dst):
            cp = pltpu.make_async_copy(src, dst, wsem)
            cp.start()
            cp.wait()

        for j in range(num_suffix_pages):
            pg = cached // ps + j
            pid = bt_ref[b, jnp.clip(pg, 0, max_blocks - 1)]
            live = (pg * ps < kv_len) & (pg < max_blocks) & (pid >= 0)

            @pl.when(live)
            def _write_page(pg=pg, pid=pid):
                col = pg * ps + jax.lax.broadcasted_iota(
                    jnp.int32, (ps, 1), 0)
                valid = (col >= cached) & (col < kv_len)          # [ps, 1]
                new_k = _page_rows(k_row, col - cached + off)     # [ps, hd]
                new_v = _page_rows(v_row, col - cached + off)
                if fused:
                    copy(pools_out[0].at[pid, h], wkv)
                else:
                    copy(pools_out[0].at[pid, h], wk)
                    copy(pools_out[1].at[pid, h], wv)
                if quantized:
                    copy(pools_out[-2].at[pid], wks)
                    copy(pools_out[-1].at[pid], wvs)
                    qk, sck = _quantize_rows(new_k)
                    qv, scv = _quantize_rows(new_v)
                    KV, lanes = wks.shape
                    lane = jax.lax.broadcasted_iota(jnp.int32, (KV, lanes), 1)
                    mine = ((jax.lax.broadcasted_iota(
                        jnp.int32, (KV, lanes), 0) == h) & (lane < ps)
                        & (pg * ps + lane >= cached)
                        & (pg * ps + lane < kv_len))
                    wks[...] = jnp.where(
                        mine, _column_to_row(sck, lanes).astype(wks.dtype),
                        wks[...])
                    wvs[...] = jnp.where(
                        mine, _column_to_row(scv, lanes).astype(wvs.dtype),
                        wvs[...])
                    new_k, new_v = qk, qv
                if fused:
                    new = jnp.stack([new_k, new_v], axis=0)  # [2, ps, hd]
                    wkv[...] = jnp.where(
                        valid[None], new.astype(wkv.dtype), wkv[...])
                    copy(wkv, pools_out[0].at[pid, h])
                else:
                    wk[...] = jnp.where(valid, new_k.astype(wk.dtype), wk[...])
                    wv[...] = jnp.where(valid, new_v.astype(wv.dtype), wv[...])
                    copy(wk, pools_out[0].at[pid, h])
                    copy(wv, pools_out[1].at[pid, h])
                if quantized:
                    copy(wks, pools_out[-2].at[pid])
                    copy(wvs, pools_out[-1].at[pid])


def ragged_attention(
    q: jax.Array,            # [B, T, H, hd] LEFT-padded ragged queries
    k: jax.Array,            # [B, T, KV, hd] in-flight new K (model dtype)
    v: jax.Array,
    cu_q_lens: jax.Array,    # [B+1] int32 cumulative live query lengths
    cu_kv_lens: jax.Array,   # [B+1] int32 cumulative total context lengths
    block_tables: jax.Array,  # [B, mb] int32 pool rows (-1 = unassigned)
    k_pages: Optional[jax.Array] = None,   # [P, KV, ps, hd] split-pool K
    v_pages: Optional[jax.Array] = None,
    kv_fused: Optional[jax.Array] = None,  # [P, KV, 2, ps, hd] fused pool
    k_scale: Optional[jax.Array] = None,   # [P, KV, lanes] int8 dequant
    v_scale: Optional[jax.Array] = None,
    *,
    window=0,                # int or traced scalar; 0 = full attention
    softcap: float = 0.0,
    block_q: int = 128,
    pages_per_block: int = 4,
    writes_kv: bool = False,
    interpret: bool,
):
    """One ragged dispatch serving decode lanes and prefill chunks.

    Row b attends its ``kv_len - q_len`` cached pool tokens plus its own
    in-flight suffix causally (windowed). Returns ``[B, T, H, hd]``; with
    ``writes_kv=True`` additionally merges the new tokens' K/V into their
    suffix pages (fused int8 quantise for int8 pools) and returns
    ``(out, *updated_pools)`` where the pool tuple matches the non-None
    pool/scale operands in order.
    """
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    fused = kv_fused is not None
    if fused and (k_pages is not None or v_pages is not None):
        raise ValueError("pass either split pools or kv_fused, not both")
    if not fused and (k_pages is None or v_pages is None):
        raise ValueError("ragged_attention needs a paged KV pool "
                         "(k_pages/v_pages or kv_fused)")
    quantized = k_scale is not None
    ps = int(kv_fused.shape[3] if fused else k_pages.shape[2])
    mb = int(block_tables.shape[1])
    ppb = max(1, min(int(pages_per_block), mb))

    bq = min(int(block_q), T)
    Tp = -(-T // bq) * bq
    pad = Tp - T
    if pad:
        # pad on the LEFT: the in-kernel offset (Tp - q_len) grows by
        # `pad` automatically and every mask stays exact
        q = jnp.pad(q, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    qg = q.reshape(B, Tp, KV, G, hd)
    k = k.transpose(0, 2, 1, 3)          # [B, KV, Tp, hd]
    v = v.transpose(0, 2, 1, 3)
    nq = Tp // bq
    nsp = Tp // ps + 1                   # max pages a row's suffix spans
    window_arr = jnp.reshape(jnp.asarray(window, jnp.int32), (1,))

    pools = [kv_fused] if fused else [k_pages, v_pages]
    if quantized:
        pools += [k_scale, v_scale]
    pool_dtype = pools[0].dtype
    scale_dtype = k_scale.dtype if quantized else None

    kernel = functools.partial(
        _ragged_kernel, block_q=bq, pages_per_block=ppb, page_size=ps,
        max_blocks=mb, num_q_blocks=nq, num_suffix_pages=nsp, bucket=Tp,
        q_per_kv=G, quantized=quantized, fused=fused, writes_kv=writes_kv,
        softcap=float(softcap), scale=1.0 / math.sqrt(hd))

    def q_map(b, h, qi, *pref):
        return (b, qi, h, 0, 0)

    def kv_map(b, h, qi, *pref):
        return (b, h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, bq, 1, G, hd), q_map),
        pl.BlockSpec((1, 1, Tp, hd), kv_map),
        pl.BlockSpec((1, 1, Tp, hd), kv_map),
    ] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)

    out_specs = [pl.BlockSpec((1, bq, 1, G, hd), q_map)]
    out_shape = [jax.ShapeDtypeStruct((B, Tp, KV, G, hd), q.dtype)]
    aliases = {}
    if writes_kv:
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)
        out_shape += [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools]
        # alias indices COUNT the scalar-prefetch operands: cu_q=0,
        # cu_kv=1, window=2, block_tables=3, q=4, k=5, v=6, pools start
        # at 7; output 0 is the attention result, pools start at 1.
        aliases = {7 + i: 1 + i for i in range(len(pools))}

    n_copies = (1 if fused else 2) + (2 if quantized else 0)
    scratch = []
    if fused:
        scratch.append(pltpu.VMEM((2, ppb, 2, ps, hd), pool_dtype))
    else:
        scratch.append(pltpu.VMEM((2, ppb, ps, hd), pool_dtype))
        scratch.append(pltpu.VMEM((2, ppb, ps, hd), pool_dtype))
    if quantized:
        sl = k_scale.shape[2]
        scratch.append(pltpu.VMEM((2, ppb, KV, sl), scale_dtype))
        scratch.append(pltpu.VMEM((2, ppb, KV, sl), scale_dtype))
    scratch.append(pltpu.SemaphoreType.DMA((2, ppb, n_copies)))
    if writes_kv:
        if fused:
            scratch.append(pltpu.VMEM((2, ps, hd), pool_dtype))
        else:
            scratch.append(pltpu.VMEM((ps, hd), pool_dtype))
            scratch.append(pltpu.VMEM((ps, hd), pool_dtype))
        if quantized:
            scratch.append(pltpu.VMEM((KV, sl), scale_dtype))
            scratch.append(pltpu.VMEM((KV, sl), scale_dtype))
        scratch.append(pltpu.SemaphoreType.DMA(()))

    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, KV, nq),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        # stable dispatch identity: the engine's ONE-attention-dispatch-
        # per-iteration guarantee counts eqns with this name in the traced
        # mixed step (jaxpr_inspect.count_attention_dispatches)
        name="ragged_attention",
    )(jnp.asarray(cu_q_lens, jnp.int32), jnp.asarray(cu_kv_lens, jnp.int32),
      window_arr, jnp.asarray(block_tables, jnp.int32), qg, k, v, *pools)

    out = res[0].reshape(B, Tp, H, hd)
    out = out[:, pad:] if pad else out
    if writes_kv:
        return (out,) + tuple(res[1:])
    return out
