"""Fused prefill-attention kernel (Pallas TPU) — the engine prefill hot path.

Prefill is the TTFT-critical phase the paper attacks with its CUDA-graph
shape cache (§4.2): every admitted prompt runs the whole stack once over a
``[B, T]`` bucket. The naive path (``layers.gqa_attend``) materialises a
full ``[B, KV, G, Tq, Tk]`` f32 logits tensor per layer — O(T^2) HBM
traffic and peak memory that rivals the KV pool for bucket-2048 prefills.
This kernel is the flash-attention formulation of the same computation:

  * tiled online softmax — queries and keys stream through VMEM in
    ``(block_q, block_k)`` tiles with running (m, l, acc) scratch, so the
    T x T logits never exist in HBM;
  * left-padding aware — prompts are LEFT-padded (lane b's tokens occupy
    columns ``[offset_b, T)``); per-lane offsets ride in as scalar
    prefetch, masking both the padded key columns and (together with the
    causal test) the padded query rows;
  * causal + sliding-window *block skip* — key blocks entirely outside
    ``(q_block_start - window, q_block_end)`` skip all compute via
    ``pl.when``, and the BlockSpec ``index_map`` clamps their block index
    into the live range so the pipeline issues no fresh HBM fetch (Pallas
    elides the copy when the index repeats). A window-w layer therefore
    reads O(T * w) keys, not O(T^2);
  * the window width is a *dynamic* scalar-prefetch operand (0 = full
    attention) so per-layer window patterns (gemma2 local/global) pass
    straight through the transformer's ``lax.scan`` over layers without
    recompilation — same contract as ``paged_attention``;
  * attention-logit softcapping (gemma2) and GQA (G = H/KV query heads
    share one KV head) for arch coverage.

Prefix-aware mode (prefix KV reuse + chunked prefill): when
``k_pages``/``v_pages``/``block_rows``/``cached_lens`` are given, each lane
additionally owns a *cached prefix* of ``cached_lens[b]`` tokens whose K/V
already live in the paged pool (written by an earlier request sharing the
prefix, or by a previous chunk of the same long prompt). The grid grows a
leading run of ``max_blocks`` key steps that stream prefix pages HBM->VMEM
through the scalar-prefetched block-table rows — the same page-gather-via-
``index_map`` technique as ``kernels.paged_attention`` — so every query
tile folds the cached prefix into its online softmax before the in-flight
suffix keys. Suffix token columns sit at absolute positions
``cached_lens[b] + col - offset_b``; prefix pages past ``cached_lens`` (or
entirely below the sliding window) are clamped+skipped like dead suffix
blocks. Optional ``k_scale``/``v_scale`` fuse int8-KV dequantisation of
the pooled prefix in-VMEM. ``cached_lens = 0`` lanes skip the whole prefix
phase — one compiled program serves mixed hit/miss batches and every chunk
of a chunked prefill. This is also what makes the engine's BATCHED chunk
step a single dispatch: up to ``max_prefills_per_step`` PREFILLING lanes
with heterogeneous chunk cursors (each lane's ``cached_lens`` = its own
resume point) and ragged chunk lengths ride one kernel launch. Query
tiles that are entirely left-pad (a lane whose ragged/adaptive-budget
chunk fills only the bucket's tail) skip all compute via the shared
``q_live`` guard — the cost of a lane's chunk scales with its live
tokens, not the bucket ceiling.

Grid: ``(B, KV, Tp/block_q, max_blocks + Tp/block_k)`` with the key
dimension innermost so the online softmax accumulates prefix pages first,
then suffix key blocks, for a fixed query block. ``Tp`` is T left-padded up
to a block multiple — padding on the LEFT keeps the mask logic identical
(offsets just grow), so the wrapper never right-pads into the causal
region.

Layout: every K/V block is one head's ``(rows, hd)`` tile. The wrapper
hands the kernel the in-flight K/V head-major (``[B, KV, Tp, hd]``) and
the pool is head-major within a page (``[P, KV, ps, hd]``); int8 scales
stream as the page's whole ``[KV, lanes]`` slab. Mosaic refuses a block that
slices one head out of a second-minor KV axis.
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


def _flash_prefill_kernel(
    # scalar-prefetch refs: offsets [B], window [1],
    #                       (+ cached [B], block_rows [B, mb] in prefix mode)
    *refs,
    block_q: int,
    block_k: int,
    num_prefix_blocks: int,
    num_k_blocks: int,
    page_size: int,
    q_per_kv: int,
    quantized: bool,
    softcap: float,
    scale: float,
):
    offsets_ref, window_ref = refs[0], refs[1]
    at = 2
    if num_prefix_blocks:
        cached_ref = refs[at]
        at += 2                               # rows_ref only used by index maps
    q_ref = refs[at]                          # [1, bq, 1, G, hd]
    k_ref, v_ref = refs[at + 1], refs[at + 2]  # [1, 1, bk, hd]
    at += 3
    kp_ref = vp_ref = ksc_ref = vsc_ref = None
    if num_prefix_blocks:
        kp_ref, vp_ref = refs[at], refs[at + 1]  # [1, 1, ps, hd]
        at += 2
        if quantized:
            ksc_ref, vsc_ref = refs[at], refs[at + 1]  # [1, KV, lanes]
            at += 2
    o_ref = refs[at]                          # [1, bq, 1, G, hd]
    m_scr, l_scr, acc_scr = refs[at + 1:at + 4]

    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    G = q_per_kv

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    off = offsets_ref[b]
    w = window_ref[0]
    eff_w = jnp.where(w > 0, w, jnp.int32(2**30))
    qs = qi * block_q

    def accumulate(s, mask, v):
        """Online-softmax update of the (m, l, acc) scratch with one key
        block's masked logits ``s`` [bq*G, bk'] and values ``v`` [bk', hd]."""
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                   # [bq*G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                       # [bq*G, 1]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    def load_q():
        hd = q_ref.shape[-1]
        q = q_ref[0, :, 0].astype(jnp.float32).reshape(block_q * G, hd)
        return q * scale

    # dead query tile: every column of this q block sits in the left-pad
    # region (no live queries). Ragged batched chunks make these common —
    # a lane whose adaptive budget (or short suffix) fills only the tail
    # of the chunk bucket skips the leading tiles' compute entirely; the
    # finalize write still runs, emitting the zero rows callers never read.
    # (HBM side: a dead tile's kv_map range is empty, so its clip collapses
    # every key step to one repeated block index — the pipeline fetches
    # O(1) blocks per dead tile, not the live range.)
    q_live = qs + block_q > off

    if num_prefix_blocks:
        cached = cached_ref[b]
        ks_abs = ki * page_size
        # smallest valid query abs position in this q block bounds the
        # sliding-window reach into the prefix
        qa_lo = cached + jnp.maximum(qs, off) - off
        live_prefix = q_live & (ki < num_prefix_blocks) & (ks_abs < cached) \
            & (ks_abs + page_size > qa_lo - eff_w + 1)

        @pl.when(live_prefix)
        def _process_prefix():
            q = load_q()
            k = kp_ref[0, 0].astype(jnp.float32)             # [ps, hd]
            v = vp_ref[0, 0].astype(jnp.float32)
            if quantized:
                k = k * head_scale_column(
                    ksc_ref[0].astype(jnp.float32), h, page_size)
                v = v * head_scale_column(
                    vsc_ref[0].astype(jnp.float32), h, page_size)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            q_col = qs + jax.lax.broadcasted_iota(
                jnp.int32, (block_q * G, page_size), 0) // G
            k_abs = ks_abs + jax.lax.broadcasted_iota(
                jnp.int32, (block_q * G, page_size), 1)
            qa = cached + q_col - off
            # causal is automatic: k_abs < cached <= qa for valid queries
            mask = (k_abs < cached) & (q_col >= off) & ((qa - k_abs) < eff_w)
            accumulate(s, mask, v)

    # --- suffix phase (in-flight keys, column-space masks) ------------------
    kis = ki - num_prefix_blocks
    ks = kis * block_k
    # live key-column range for this query block: causal upper bound is the
    # block's last query column; lower bound is the left-pad edge, tightened
    # by the sliding window. Blocks outside skip compute AND (via the
    # clamped index_map) the HBM fetch.
    lo = jnp.maximum(off, jnp.where(w > 0, qs - w + 1, 0))
    live = q_live & (kis >= 0) & (ks < qs + block_q) & (ks + block_k > lo)

    @pl.when(live)
    def _process():
        q = load_q()
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq*G, bk]
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)

        # masks in column space: padding-invariant because query and key
        # positions shift by the same per-lane offset (and, in prefix mode,
        # the same per-lane cached length).
        q_col = qs + jax.lax.broadcasted_iota(
            jnp.int32, (block_q * G, block_k), 0) // G
        k_col = ks + jax.lax.broadcasted_iota(
            jnp.int32, (block_q * G, block_k), 1)
        mask = (k_col <= q_col) & (k_col >= off) & ((q_col - k_col) < eff_w)
        accumulate(s, mask, v)

    @pl.when(ki == num_prefix_blocks + num_k_blocks - 1)
    def _finalize():
        hd = o_ref.shape[-1]
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, :, 0] = (acc_scr[...] / l).reshape(
            block_q, G, hd).astype(o_ref.dtype)


def flash_prefill(
    q: jax.Array,            # [B, T, H, hd]
    k: jax.Array,            # [B, T, KV, hd]
    v: jax.Array,            # [B, T, KV, hd]
    offsets: jax.Array,      # [B] int32 — left-pad columns (T - prompt_len)
    *,
    window=0,                # int or traced scalar; 0 = full attention
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    k_pages: Optional[jax.Array] = None,    # [P, KV, ps, hd] paged prefix K
    v_pages: Optional[jax.Array] = None,
    block_rows: Optional[jax.Array] = None,  # [B, mb] int32 (-1 = unassigned)
    cached_lens: Optional[jax.Array] = None,  # [B] int32 cached prefix tokens
    k_scale: Optional[jax.Array] = None,    # [P, KV, lanes] int8 dequant
    v_scale: Optional[jax.Array] = None,
    interpret: bool,
) -> jax.Array:
    """Returns [B, T, H, hd] causal (windowed) self-attention output.

    Without prefix arguments this is plain flash prefill over the in-flight
    bucket. With them, lane b's queries additionally attend the
    ``cached_lens[b]`` prefix tokens resident in ``k_pages``/``v_pages``
    through ``block_rows[b]`` — the machinery for both radix prefix reuse
    and chunked prefill (each chunk's cached_lens = tokens already written).

    Rows in the left-pad region (column < offsets[b]) are zero — they have
    no live keys; callers never read them (left padding puts every real
    token at the tail).
    """
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    bq = min(int(block_q), T)
    bk = min(int(block_k), T)
    Tp = -(-T // math.lcm(bq, bk)) * math.lcm(bq, bk)
    pad = Tp - T
    if pad:
        # pad on the LEFT: offsets grow by `pad` and every mask stays exact
        q = jnp.pad(q, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    offs = (jnp.asarray(offsets, jnp.int32) + pad).astype(jnp.int32)
    window_arr = jnp.reshape(jnp.asarray(window, jnp.int32), (1,))
    qg = q.reshape(B, Tp, KV, G, hd)
    k = k.transpose(0, 2, 1, 3)                     # [B, KV, Tp, hd]
    v = v.transpose(0, 2, 1, 3)
    nq, nk = Tp // bq, Tp // bk
    scale = 1.0 / math.sqrt(hd)

    prefix = k_pages is not None
    quantized = prefix and k_scale is not None
    nkp = int(block_rows.shape[1]) if prefix else 0
    ps = int(k_pages.shape[2]) if prefix else 0

    def q_map(b, h, qi, ki, *pref):
        return (b, qi, h, 0, 0)

    def kv_map(b, h, qi, ki, off, win, *pref):
        """Clamp dead key blocks into the live range so skipped grid steps
        repeat the previous block index (no fresh HBM->VMEM copy)."""
        qs = qi * bq
        w = win[0]
        lo = jnp.maximum(off[b], jnp.where(w > 0, qs - w + 1, 0))
        lo_blk = jnp.maximum(lo, 0) // bk
        hi_blk = jnp.maximum(qs + bq - 1, 0) // bk
        return (b, h, jnp.clip(ki - nkp, lo_blk, hi_blk), 0)

    def page_of(b, ki, cached, rows):
        """Pool page for prefix step ki, clamped to the lane's live prefix
        pages so dead steps repeat the previous index (DMA elided)."""
        last_live = jnp.maximum((cached[b] - 1) // ps, 0)
        return jnp.maximum(rows[b, jnp.clip(ki, 0, last_live)], 0)

    def kp_map(b, h, qi, ki, off, win, cached, rows):
        return (page_of(b, ki, cached, rows), h, 0, 0)

    def scale_map(b, h, qi, ki, off, win, cached, rows):
        return (page_of(b, ki, cached, rows), 0, 0)

    def o_map(b, h, qi, ki, *pref):
        return (b, qi, h, 0, 0)

    kernel = functools.partial(
        _flash_prefill_kernel, block_q=bq, block_k=bk,
        num_prefix_blocks=nkp, num_k_blocks=nk, page_size=ps,
        q_per_kv=G, quantized=quantized, softcap=float(softcap), scale=scale)

    in_specs = [
        pl.BlockSpec((1, bq, 1, G, hd), q_map),
        pl.BlockSpec((1, 1, bk, hd), kv_map),
        pl.BlockSpec((1, 1, bk, hd), kv_map),
    ]
    inputs = [qg, k, v]
    scalars = [offs, window_arr]
    num_prefetch = 2
    if prefix:
        scalars += [jnp.asarray(cached_lens, jnp.int32),
                    jnp.maximum(jnp.asarray(block_rows, jnp.int32), 0)]
        num_prefetch = 4
        in_specs += [pl.BlockSpec((1, 1, ps, hd), kp_map),
                     pl.BlockSpec((1, 1, ps, hd), kp_map)]
        inputs += [k_pages, v_pages]
        if quantized:
            sl = k_scale.shape[2]
            in_specs += [pl.BlockSpec((1, KV, sl), scale_map),
                         pl.BlockSpec((1, KV, sl), scale_map)]
            inputs += [k_scale, v_scale]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_prefetch,
            grid=(B, KV, nq, nkp + nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, 1, G, hd), o_map),
            scratch_shapes=[
                pltpu.VMEM((bq * G, 1), jnp.float32),
                pltpu.VMEM((bq * G, 1), jnp.float32),
                pltpu.VMEM((bq * G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Tp, KV, G, hd), q.dtype),
        interpret=interpret,
        # stable dispatch identity: the engine's one-prefill-dispatch-per-
        # iteration guarantee is asserted by counting eqns with this name
        # in the traced step (jaxpr_inspect.count_pallas_calls)
        name="flash_prefill",
    )(*scalars, *inputs)
    out = out.reshape(B, Tp, H, hd)
    return out[:, pad:] if pad else out
