"""Pluggable attention backends for the two serving phases.

Decode: the engine's per-token step attends one new query against the
paged KV cache, once per attention layer — the hottest loop in the system.
Prefill: every admitted prompt attends a whole left-padded ``[B, T]``
bucket against itself — the TTFT-critical phase. Both phases register two
implementations under the same names:

  * ``"gather"`` — the jnp reference paths. Decode: materialise the slot's
    whole page range ``[B, max_kv, KV, hd]`` via ``cache.gather_kv`` and
    run dense ``gqa_attend`` (per-step HBM traffic scales with ``max_kv``).
    Prefill: dense ``gqa_attend`` over the bucket, which materialises a
    full ``[B, KV, G, Tq, Tk]`` f32 logits tensor per layer (O(T^2) HBM).
    Simple, and the numerical baseline the Pallas paths are tested against.
  * ``"pallas"`` — the Pallas kernels. Decode: ``kernels.paged_attention``
    (one grid step per lane loops over that lane's live pages only, with
    double-buffered page DMAs, int8 dequant fused; work and traffic scale
    with the *live* KV length). Prefill: ``kernels.flash_prefill`` (tiled
    online softmax; the T x T logits never exist in HBM, key blocks
    outside the causal/window range skip compute and fetch).

Selection: ``ServeConfig.attn_backend`` (threaded through
``models.api.make_model``, which binds the decode callable into ``decode``
and the prefill callable into ``prefill``), overridden by the
``REPRO_ATTN_BACKEND`` environment variable. ``benchmarks/decode_attn.py``
and ``benchmarks/prefill_attn.py`` quantify the tradeoffs.

A decode backend is a callable

    attend(cfg, q, kvc, layer, slot_ids, pos, window) -> [B, 1, H, hd]

where ``q`` is the current token's query heads ``[B, 1, H, hd]``, ``kvc``
the ``PagedKVCache`` (with the token's K/V already written), ``pos`` the
per-lane cache position of that token (-1 for a lane that is not decoding:
it attends nothing, and its output is dropped) and ``window`` a traced
per-layer sliding-window width (0 = full attention).

A prefill backend is a callable

    prefill_attend(cfg, q, k, v, offset, window, prefix=None) -> [B, T, H, hd]

over one layer's freshly projected (RoPE'd) q ``[B, T, H, hd]`` and
k/v ``[B, T, KV, hd]`` for a LEFT-padded prompt bucket; ``offset`` [B] is
the per-lane pad width (first valid column), ``window`` a traced scalar as
above. Softcap comes from ``cfg.attn_softcap``. Rows in the pad region
may be garbage — callers never read them. ``prefix`` is an optional
``PagedPrefix``: per-lane cached-prefix K/V resident in the paged pool
(radix prefix reuse / chunked prefill) that query tiles fold in before the
in-flight suffix keys — the gather backend gathers the pages densely, the
pallas backend streams them through the flash kernel's block-table
prefetch. ``cached_lens`` is per-lane, which is what lets the engine's
batched chunk step put lanes with heterogeneous chunk cursors (and ragged
chunk lengths) into ONE dispatch; the gather reference reduces over a
position-indexed key buffer precisely so that every chunking of a prompt
is bitwise-identical — the oracle the differential scheduler harness
leans on.
"""
from __future__ import annotations

import os
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import cache as cache_lib
from repro.models.layers import gqa_attend

DecodeAttend = Callable[..., jax.Array]
PrefillAttend = Callable[..., jax.Array]


class PagedPrefix(NamedTuple):
    """One layer's cached-prefix view for a prefill batch.

    ``cached_lens[b]`` tokens at absolute positions [0, cached) already
    live in ``k_pages``/``v_pages`` (or the interleaved ``kv_fused`` pool
    when ``ServeConfig.kv_fused_layout`` is on — the split pair is then
    None) through ``block_rows[b]``; the in-flight suffix token at column
    c sits at position ``cached + c - offset``."""
    block_rows: jax.Array                 # [B, max_blocks] int32
    cached_lens: jax.Array                # [B] int32
    k_pages: Optional[jax.Array] = None   # [P, KV, ps, hd] (this layer)
    v_pages: Optional[jax.Array] = None
    k_scale: Optional[jax.Array] = None   # [P, KV, lanes] int8 dequant scales
    v_scale: Optional[jax.Array] = None
    kv_fused: Optional[jax.Array] = None  # [P, KV, 2, ps, hd] fused layout

_REGISTRY: Dict[str, Callable[..., DecodeAttend]] = {}
_PREFILL_REGISTRY: Dict[str, Callable[..., PrefillAttend]] = {}
_UNIFIED_REGISTRY: Dict[str, Callable[..., PrefillAttend]] = {}


def register(name: str):
    def deco(factory):
        _REGISTRY[name] = factory
        return factory
    return deco


def register_prefill(name: str):
    def deco(factory):
        _PREFILL_REGISTRY[name] = factory
        return factory
    return deco


def register_unified(name: str):
    def deco(factory):
        _UNIFIED_REGISTRY[name] = factory
        return factory
    return deco


def available():
    return sorted(_REGISTRY)


def _resolve(name: Optional[str], registry: Dict[str, Callable]) -> str:
    """Resolution order: ``REPRO_ATTN_BACKEND`` env var > ``name`` argument >
    ``"gather"``. Raises ``KeyError`` for unknown names so a typo'd env
    var fails loudly instead of silently serving the slow path."""
    resolved = os.environ.get("REPRO_ATTN_BACKEND") or name or "gather"
    if resolved not in registry:
        raise KeyError(f"unknown attention backend {resolved!r}; "
                       f"available: {available()}")
    return resolved


# ---------------------------------------------------------------------------
# Tensor parallelism: shard_map wrapping over attention heads
# ---------------------------------------------------------------------------
#
# With a ("model",)-axis mesh, every backend body above runs unchanged as a
# shard_map region over the HEAD dims: q/k/v activations and the paged
# pools slice into contiguous per-shard head ranges (whole GQA groups —
# ``distribution.sharding.head_partition``), everything else (block
# tables, positions, windows, traced layer index) is replicated. Heads are
# batch dims of every einsum in every body, so the per-shard math is the
# SAME floating-point program as the single-device kernel on a head slice
# — concatenating shard outputs over heads is bitwise-identical to the
# unsharded dispatch, which is what lets the sharded engine stay a
# drop-in replacement under the bitwise differential harness. The
# attention output is constrained back to replicated before it returns to
# the transformer: the wo projection contracts over heads, and keeping
# that contraction on gathered (full-head) operands preserves the
# single-device reduction order exactly.

def _head_specs():
    from jax.sharding import PartitionSpec as P
    heads = P(None, None, "model", None)     # [B, T, H|KV, hd] activations
    decode_pool = {                          # PagedKVCache leaves, by ndim
        5: P(None, None, "model", None, None),   # [L, P, KV, ps, hd]
        4: P(None, None, "model", None),         # [L, P, KV, lanes] scales
    }
    prefix_pool = {                          # PagedPrefix leaves, by ndim
        4: P(None, "model", None, None),         # [P, KV, ps, hd] (layer)
        3: P(None, "model", None),               # [P, KV, lanes] scales
    }
    return heads, decode_pool, prefix_pool, P


def _tree_specs(tree, by_ndim, P):
    """Spec tree matching ``tree``: head-sharded pools by ndim (the leaf
    ranks are disjoint per container), everything else replicated."""
    return jax.tree.map(lambda x: by_ndim.get(jnp.ndim(x), P()), tree)


def _refuse_fused_sharded(fused_leaf):
    if fused_leaf is not None:
        raise ValueError(
            "mesh_model_size > 1 does not read the fused interleaved KV "
            "layout: kv_fused pages carry K and V of every head in one "
            "row, which has no per-shard slice on the model axis")


def _shard_decode(fn, mesh) -> DecodeAttend:
    from jax.sharding import NamedSharding
    heads, decode_pool, _, P = _head_specs()
    rep = NamedSharding(mesh, P())

    def sharded_attend(cfg, q, kvc, layer, slot_ids, pos, window):
        _refuse_fused_sharded(kvc.kv_fused)

        def body(q_, kvc_, layer_, slots_, pos_, win_):
            return fn(cfg, q_, kvc_, layer_, slots_, pos_, win_)

        att = jax.shard_map(
            body, mesh=mesh,
            in_specs=(heads, _tree_specs(kvc, decode_pool, P),
                      P(), P(), P(), P()),
            out_specs=heads, check_vma=False,
        )(q, kvc, layer, slot_ids, pos, window)
        # gather heads BEFORE the wo contraction (exact: pure concat)
        return jax.lax.with_sharding_constraint(att, rep)

    return sharded_attend


def _shard_prefill(fn, mesh) -> PrefillAttend:
    from jax.sharding import NamedSharding
    heads, _, prefix_pool, P = _head_specs()
    rep = NamedSharding(mesh, P())

    def sharded_prefill(cfg, q, k, v, offset, window, prefix=None):
        if prefix is None:
            def body(q_, k_, v_, off_, win_):
                return fn(cfg, q_, k_, v_, off_, win_, prefix=None)
            in_specs = (heads, heads, heads, P(), P())
            args = (q, k, v, offset, window)
        else:
            _refuse_fused_sharded(prefix.kv_fused)

            def body(q_, k_, v_, off_, win_, pre_):
                return fn(cfg, q_, k_, v_, off_, win_, prefix=pre_)
            in_specs = (heads, heads, heads, P(), P(),
                        _tree_specs(prefix, prefix_pool, P))
            args = (q, k, v, offset, window, prefix)
        att = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=heads, check_vma=False)(*args)
        return jax.lax.with_sharding_constraint(att, rep)

    return sharded_prefill


def _shard_unified(fn, mesh) -> PrefillAttend:
    from jax.sharding import NamedSharding
    heads, _, prefix_pool, P = _head_specs()
    rep = NamedSharding(mesh, P())
    writes_kv = fn.writes_kv

    def sharded_unified(cfg, q, k, v, offset, window, prefix=None):
        if prefix is None:
            raise ValueError("unified attention always attends against the "
                             "paged pool; prefix is mandatory")
        _refuse_fused_sharded(prefix.kv_fused)

        def body(q_, k_, v_, off_, win_, pre_):
            return fn(cfg, q_, k_, v_, off_, win_, prefix=pre_)

        in_specs = (heads, heads, heads, P(), P(),
                    _tree_specs(prefix, prefix_pool, P))
        if writes_kv:
            # (att, k_pages', v_pages'[, k_scale', v_scale']): the kernel
            # epilogue writes each shard's OWN head slice of the pool, so
            # the updated pools come back still sharded on heads
            out_specs = (heads, prefix_pool[4], prefix_pool[4])
            if prefix.k_scale is not None:
                out_specs += (prefix_pool[3], prefix_pool[3])
        else:
            out_specs = heads
        res = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)(
            q, k, v, offset, window, prefix)
        if writes_kv:
            return (jax.lax.with_sharding_constraint(res[0], rep),) \
                + tuple(res[1:])
        return jax.lax.with_sharding_constraint(res, rep)

    sharded_unified.writes_kv = writes_kv
    return sharded_unified


def _maybe_shard(fn, mesh, wrapper):
    """Wrap ``fn`` in ``wrapper`` when the mesh actually shards (model
    axis size > 1); a trivial mesh keeps the exact single-device callable."""
    if mesh is None:
        return fn
    from repro.distribution.sharding import mesh_model_size
    if mesh_model_size(mesh) <= 1:
        return fn
    wrapped = wrapper(fn, mesh)
    return wrapped


def get_backend(name: Optional[str] = None, *,
                pages_per_block: Optional[int] = None,
                mesh=None) -> DecodeAttend:
    """Resolve a decode-attention backend by name (see ``_resolve``).
    ``mesh``: optional ("model",) serving mesh — the body runs as a
    per-shard shard_map region over attention heads."""
    resolved = _resolve(name, _REGISTRY)
    fn = _REGISTRY[resolved](pages_per_block=pages_per_block)
    fn = _maybe_shard(fn, mesh, _shard_decode)
    fn.backend_name = resolved
    return fn


def validate_prefill_tiles(block_q: int, block_k: int) -> None:
    """Model-build-time validation of the flash-prefill tile sizes
    (``ServeConfig.prefill_block_q``/``prefill_block_k``): a bad tile must
    fail at ``make_model``, not as a shape error deep inside the first
    jitted window. TPU lanes want multiples of 8; the wrapper clamps tiles
    to the bucket length, so only the lower bound and alignment matter."""
    for nm, val in (("prefill_block_q", block_q), ("prefill_block_k", block_k)):
        if not isinstance(val, int) or val <= 0:
            raise ValueError(f"{nm} must be a positive int, got {val!r}")
        if val % 8 != 0:
            raise ValueError(f"{nm} must be a multiple of 8 (TPU lane "
                             f"alignment), got {val}")


def get_prefill_backend(name: Optional[str] = None, *,
                        block_q: int = 128,
                        block_k: int = 128, mesh=None) -> PrefillAttend:
    """Resolve a prefill-attention backend by name (same resolution and
    names as ``get_backend`` — one ``ServeConfig.attn_backend`` selects
    both phases). ``mesh`` shards the body over heads as in
    ``get_backend``."""
    resolved = _resolve(name, _PREFILL_REGISTRY)
    validate_prefill_tiles(block_q, block_k)
    fn = _PREFILL_REGISTRY[resolved](block_q=block_q, block_k=block_k)
    fn = _maybe_shard(fn, mesh, _shard_prefill)
    fn.backend_name = resolved
    return fn


@register("gather")
def _make_gather(*, pages_per_block: Optional[int] = None) -> DecodeAttend:
    """Reference path: dense gather + jnp GQA (today's behavior, including
    the REPRO_WINDOW_GATHER hillclimb for sliding-window configs)."""

    def gather_attend(cfg, q, kvc, layer, slot_ids, pos, window):
        B = q.shape[0]
        windowed = (os.environ.get("REPRO_WINDOW_GATHER") == "1"
                    and cfg.sliding_window is not None)
        if windowed:
            k_all, v_all, kv_pos = cache_lib.gather_kv_window(
                kvc, layer, slot_ids, pos, cfg.sliding_window)
        else:
            k_all, v_all = cache_lib.gather_kv(kvc, layer, slot_ids)
            kv_pos = jnp.broadcast_to(jnp.arange(kvc.max_kv)[None, :],
                                      (B, kvc.max_kv))
        kv_valid = kv_pos <= pos[:, None]
        eff_window = jnp.where(window > 0, window,
                               jnp.int32(cfg.sliding_window) if windowed
                               else jnp.int32(2**30))
        return gqa_attend(q, k_all, v_all, q_positions=pos[:, None],
                          k_positions=kv_pos, causal=True, window=eff_window,
                          kv_mask=kv_valid, softcap=cfg.attn_softcap)

    return gather_attend


@register("pallas")
def _make_pallas(*, pages_per_block: Optional[int] = None) -> DecodeAttend:
    """Hot path: the Pallas paged-attention kernel, work and HBM traffic
    bounded by the live KV length (+ sliding-window page skip + fused int8
    dequant)."""

    def pallas_attend(cfg, q, kvc, layer, slot_ids, pos, window):
        if kvc.fused:
            raise ValueError(
                "the split pallas decode backend does not read the fused "
                "interleaved KV layout; kv_fused_layout requires "
                "attn_unified (one ragged dispatch) or the gather backend")
        # head counts come from the ARRAYS, not cfg: inside a shard_map
        # body this callable sees the per-shard head slice
        B, H = q.shape[0], q.shape[2]
        KV, hd = kvc.k_pages.shape[2], kvc.k_pages.shape[4]
        G = H // KV
        # gqa_attend groups head h under kv head h // G — same layout here
        qg = q[:, 0].reshape(B, KV, G, hd)
        quant = {}
        if kvc.quantized:
            quant = dict(k_scale=kvc.k_scale[layer],
                         v_scale=kvc.v_scale[layer])
        att = ops.paged_attention(
            qg, kvc.k_pages[layer], kvc.v_pages[layer],
            kvc.block_table[slot_ids], pos + 1,
            window=jnp.maximum(window, 0).astype(jnp.int32),
            softcap=float(cfg.attn_softcap or 0.0),
            pages_per_block=pages_per_block, **quant)
        return att.reshape(B, 1, H, hd).astype(q.dtype)

    return pallas_attend


@register_prefill("gather")
def _make_gather_prefill(*, block_q: int = 128,
                         block_k: int = 128) -> PrefillAttend:
    """Reference path: dense ``gqa_attend`` over the whole bucket —
    materialises the [B, KV, G, Tq, Tk] logits tensor (today's behavior)."""

    def gather_prefill(cfg, q, k, v, offset, window, prefix=None):
        B, T = q.shape[:2]
        pos_in_seq = jnp.arange(T)[None, :] - offset[:, None]
        kv_mask = pos_in_seq >= 0
        eff_window = jnp.where(window > 0, window, jnp.int32(2**30))
        if prefix is None:
            positions = jnp.maximum(pos_in_seq, 0)
            return gqa_attend(q, k, v, q_positions=positions,
                              k_positions=positions, causal=True,
                              window=eff_window, kv_mask=kv_mask,
                              softcap=cfg.attn_softcap)
        # cached-prefix mode: gather the prefix densely from the paged pool
        # into a POSITION-INDEXED key buffer [B, mb*ps] and scatter the
        # in-flight suffix K/V at their absolute positions. Every chunk of
        # a chunked prefill (and a zero-cache single shot) then reduces
        # over an identically laid-out key axis, so the reference backend
        # is bitwise-reproducible across chunkings — the oracle the
        # equivalence tests pin the flash kernel against.
        kp, vp = cache_lib.gather_pages(
            prefix.k_pages, prefix.v_pages, prefix.block_rows,
            prefix.k_scale, prefix.v_scale, kv_fused=prefix.kv_fused)
        cached = prefix.cached_lens
        mbps = kp.shape[1]
        pos_axis = jnp.arange(mbps)[None, :]                  # [1, mb*ps]
        pre_valid = pos_axis < cached[:, None]
        k_buf = jnp.where(pre_valid[..., None, None], kp.astype(k.dtype), 0)
        v_buf = jnp.where(pre_valid[..., None, None], vp.astype(v.dtype), 0)
        suf_pos = cached[:, None] + pos_in_seq                # [B, T]
        tgt = jnp.where(kv_mask, suf_pos, mbps)               # pads dropped
        b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], tgt.shape)
        k_buf = k_buf.at[b_idx, tgt].set(k, mode="drop")
        v_buf = v_buf.at[b_idx, tgt].set(v, mode="drop")
        total = cached + (T - offset)                         # [B] seq lens
        return gqa_attend(
            q, k_buf, v_buf,
            q_positions=jnp.maximum(suf_pos, 0),
            k_positions=jnp.broadcast_to(pos_axis, (B, mbps)),
            causal=True, window=eff_window,
            kv_mask=pos_axis < total[:, None],
            softcap=cfg.attn_softcap)

    return gather_prefill


@register_prefill("pallas")
def _make_pallas_prefill(*, block_q: int = 128,
                         block_k: int = 128) -> PrefillAttend:
    """Hot path: the flash prefill kernel — tiled online softmax, no T x T
    logits in HBM, causal/sliding-window key-block skip."""

    def pallas_prefill(cfg, q, k, v, offset, window, prefix=None):
        extra = {}
        if prefix is not None:
            if prefix.kv_fused is not None:
                raise ValueError(
                    "the split flash-prefill kernel does not read the fused "
                    "interleaved KV layout; kv_fused_layout requires "
                    "attn_unified (one ragged dispatch) or the gather "
                    "backend")
            extra = dict(k_pages=prefix.k_pages, v_pages=prefix.v_pages,
                         block_rows=prefix.block_rows,
                         cached_lens=prefix.cached_lens,
                         k_scale=prefix.k_scale, v_scale=prefix.v_scale)
        att = ops.flash_prefill_attention(
            q, k, v, offset,
            window=jnp.maximum(window, 0).astype(jnp.int32),
            softcap=float(cfg.attn_softcap or 0.0),
            block_q=block_q, block_k=block_k, **extra)
        return att.astype(q.dtype)

    return pallas_prefill


# ---------------------------------------------------------------------------
# Unified (single-dispatch) attention backends — ``ServeConfig.attn_unified``
# ---------------------------------------------------------------------------
#
# A unified backend keeps the prefill-attend calling convention
# ``attend(cfg, q, k, v, offset, window, prefix)`` but serves BOTH phases in
# one call: decode lanes are rows with q_len = T - offset = 1, prefill
# chunks are ragged rows, dead rows have q_len = 0. The ragged cumulative
# metadata (``cu_q_lens``/``cu_kv_lens``) is derived here from the per-row
# offsets and the prefix's cached lengths, so the transformer needs no new
# operands. ``prefix`` is mandatory — a unified step always attends against
# the paged pool.
#
# The factory result carries ``writes_kv``: True means the backend merges
# the new tokens' K/V into their pool pages itself (the ragged kernel's
# fused epilogue — int8 pools quantise in-kernel with no float staging
# tensor) and returns ``(att, *updated_pools)``; the transformer then skips
# ``cache.write_kv_layer`` for that layer. False (the gather reference)
# returns just ``att`` and leaves the KV write on the jnp path — keeping
# gather as the bitwise oracle for the whole unified step.


def get_unified_backend(name: Optional[str] = None, *,
                        block_q: int = 128,
                        pages_per_block: Optional[int] = None,
                        mesh=None) -> PrefillAttend:
    """Resolve a unified-attention backend by name (same resolution and
    names as ``get_backend`` — one ``ServeConfig.attn_backend`` selects
    the implementation; ``attn_unified`` selects the dispatch shape).
    ``mesh`` shards the ragged body over heads as in ``get_backend`` —
    still ONE attention dispatch per mixed step (the shard_map body
    traces once; every shard runs the same program)."""
    resolved = _resolve(name, _UNIFIED_REGISTRY)
    if pages_per_block is None:
        pages_per_block = 1
    if not isinstance(block_q, int) or block_q <= 0 or block_q % 8 != 0:
        raise ValueError("unified attention block_q (prefill_block_q) must "
                         f"be a positive multiple of 8, got {block_q!r}")
    if not isinstance(pages_per_block, int) or pages_per_block <= 0:
        raise ValueError("attn_pages_per_block must be a positive int, "
                         f"got {pages_per_block!r}")
    fn = _UNIFIED_REGISTRY[resolved](block_q=block_q,
                                     pages_per_block=pages_per_block)
    fn = _maybe_shard(fn, mesh, _shard_unified)
    fn.backend_name = resolved
    return fn


@register_unified("gather")
def _make_gather_unified(*, block_q: int = 128,
                         pages_per_block: int = 1) -> PrefillAttend:
    """Reference path: the prefix-mode gather prefill already handles
    ragged rows (decode = one-token chunk) bitwise-identically to the
    phase-split reference — the cornerstone the unified engine step and
    the ragged kernel are both pinned against."""
    inner = _make_gather_prefill(block_q=block_q, block_k=block_q)

    def gather_unified(cfg, q, k, v, offset, window, prefix=None):
        if prefix is None:
            raise ValueError("unified attention always attends against the "
                             "paged pool; prefix is mandatory")
        return inner(cfg, q, k, v, offset, window, prefix=prefix)

    gather_unified.writes_kv = False
    return gather_unified


@register_unified("pallas")
def _make_pallas_unified(*, block_q: int = 128,
                         pages_per_block: int = 1) -> PrefillAttend:
    """Hot path: ONE ragged kernel dispatch per layer serves decode lanes
    and prefill chunks together — double-buffered page copies, dead-tile
    skip, live-page early exit, sliding-window page skip, fused int8
    dequant AND quantise (KV-write epilogue), optional fused-KV layout."""
    from repro.kernels.ragged_attention import build_cu_lens

    def pallas_unified(cfg, q, k, v, offset, window, prefix=None):
        if prefix is None:
            raise ValueError("unified attention always attends against the "
                             "paged pool; prefix is mandatory")
        T = q.shape[1]
        q_lens = (T - offset).astype(jnp.int32)
        cu_q, cu_kv = build_cu_lens(q_lens, prefix.cached_lens)
        res = ops.ragged_attention(
            q, k, v, cu_q, cu_kv, prefix.block_rows,
            k_pages=prefix.k_pages, v_pages=prefix.v_pages,
            kv_fused=prefix.kv_fused,
            k_scale=prefix.k_scale, v_scale=prefix.v_scale,
            window=jnp.maximum(window, 0).astype(jnp.int32),
            softcap=float(cfg.attn_softcap or 0.0),
            block_q=block_q, pages_per_block=pages_per_block,
            writes_kv=True)
        return (res[0].astype(q.dtype),) + tuple(res[1:])

    pallas_unified.writes_kv = True
    return pallas_unified
