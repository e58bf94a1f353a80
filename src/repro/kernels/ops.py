"""Jitted public wrappers for the Pallas kernels.

Where a kernel runs decides how: on the TPU it always compiles (Mosaic);
on the CPU it runs in Pallas interpret mode (the kernel body executed by
the interpreter — exact, for tests), unless the caller passes
``interpret=False`` to lower it for a described TPU; on any other
platform it raises. ``interpret_mode`` makes that call when a wrapper is
traced, from ``jax.default_backend()``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import flash_prefill as _fp
from repro.kernels import paged_attention as _pa
from repro.kernels import ragged_attention as _ra
from repro.kernels import ring_scan as _rs
from repro.kernels import ssm_scan as _ss


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs interpreted, from the platform: the
    TPU always compiles (asking it to interpret is an error — it would
    hide the device), the CPU interprets unless ``interpret=False`` asks
    for a TPU lowering, and anything else raises."""
    platform = jax.default_backend()
    if platform == "tpu":
        if interpret:
            raise ValueError("Pallas kernels always compile on the TPU; "
                             "interpret mode is for the CPU only")
        return False
    if platform == "cpu":
        return True if interpret is None else bool(interpret)
    raise RuntimeError(f"no Pallas kernel path on platform {platform!r}: "
                       "kernels compile on the TPU and are interpreted "
                       "on the CPU")


def validate_compiled_tiling(*, head_dim: int, block_q: int, block_k: int,
                             pages_per_block: int, attn_unified: bool,
                             where: str = "make_model",
                             interpret: Optional[bool] = None):
    """Reject settings the TPU compiler refuses although interpret mode
    runs them, at ``make_model`` time instead of at the first window.
    A no-op wherever ``interpret_mode(interpret)`` is True. The rules are
    the refusals seen when compiling the kernels for a described v5e
    (tests/test_chip_compile.py keeps the legal shapes compiling):

      * the flash-prefill key block is the second-minor dim of its
        ``(block_k, head_dim)`` tile, which Mosaic needs 8-aligned;
      * the unified ragged kernel copies ``(page_size, head_dim)`` pool
        tiles by DMA, which needs whole 128-lane rows.
    """
    if interpret_mode(interpret):
        return
    errs = []
    if block_q <= 0:
        errs.append(f"prefill_block_q={block_q} must be positive; try 128")
    if block_k <= 0 or block_k % 8 != 0:
        errs.append(
            f"prefill_block_k={block_k} must be a positive multiple of 8: "
            "the key block is the second-minor dim of a (block_k, head_dim) "
            "tile; try 128")
    if pages_per_block is not None and pages_per_block <= 0:
        errs.append(
            f"attn_pages_per_block={pages_per_block} must be positive")
    if attn_unified and head_dim % 128 != 0:
        errs.append(
            f"head_dim={head_dim} is not a multiple of 128: the unified "
            "ragged kernel copies (page_size, head_dim) pool tiles by DMA, "
            "which needs whole 128-lane rows — use the split kernels "
            "(attn_unified=False)")
    if errs:
        raise ValueError(
            f"illegal compiled-mode (interpret=False) kernel tiling at "
            f"{where}:\n  - " + "\n  - ".join(errs))


@functools.partial(jax.jit, static_argnames=("softcap", "pages_per_block",
                                             "interpret"))
def paged_attention(q, k_pages, v_pages, block_table, kv_lens, *,
                    window=0, softcap: float = 0.0,
                    k_scale=None, v_scale=None,
                    pages_per_block: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Decode paged attention over each lane's live pages. ``window`` is a
    dynamic scalar (0 = full) so per-layer window patterns pass through a
    ``lax.scan`` over layers; ``k_scale``/``v_scale`` enable fused int8-KV
    dequant; ``pages_per_block`` is the pages per DMA buffer slot, by
    default as many as fill 256 key rows: a lane's page loop then takes
    one step per 256 tokens whatever the page size (at 16-token pages, 1
    page per slot ran 1.6x slower on a v5e than 8 or 16)."""
    interp = interpret_mode(interpret)
    if pages_per_block is None:
        pages_per_block = max(1, 256 // k_pages.shape[2])
    return _pa.paged_attention(
        q, k_pages, v_pages, block_table, kv_lens,
        window=window, softcap=softcap, k_scale=k_scale, v_scale=v_scale,
        pages_per_block=pages_per_block, interpret=interp)


@functools.partial(jax.jit, static_argnames=("softcap", "block_q", "block_k",
                                             "interpret"))
def flash_prefill_attention(q, k, v, offsets, *, window=0, softcap: float = 0.0,
                            block_q: int = 128, block_k: int = 128,
                            k_pages=None, v_pages=None, block_rows=None,
                            cached_lens=None, k_scale=None, v_scale=None,
                            interpret: Optional[bool] = None):
    """Prefill flash attention over left-padded [B, T] prompts. ``window``
    is a dynamic scalar (0 = full) so per-layer window patterns pass through
    a ``lax.scan`` over layers; key blocks outside the causal/window range
    skip compute and HBM fetch (clamped index map). Optional
    ``k_pages``/``v_pages``/``block_rows``/``cached_lens`` (+ int8
    ``k_scale``/``v_scale``) prepend a cached paged-pool prefix per lane —
    the prefix-reuse / chunked-prefill mode."""
    interp = interpret_mode(interpret)
    return _fp.flash_prefill(
        q, k, v, offsets, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k,
        k_pages=k_pages, v_pages=v_pages, block_rows=block_rows,
        cached_lens=cached_lens, k_scale=k_scale, v_scale=v_scale,
        interpret=interp)


@functools.partial(jax.jit, static_argnames=("softcap", "block_q",
                                             "pages_per_block", "writes_kv",
                                             "interpret"))
def ragged_attention(q, k, v, cu_q_lens, cu_kv_lens, block_tables, *,
                     k_pages=None, v_pages=None, kv_fused=None,
                     k_scale=None, v_scale=None, window=0,
                     softcap: float = 0.0, block_q: int = 128,
                     pages_per_block: int = 4, writes_kv: bool = False,
                     interpret: Optional[bool] = None):
    """Unified ragged attention: ONE dispatch serves decode lanes
    (q_len=1) and prefill chunks (ragged q) in the same grid. Row b
    attends ``cu_kv_lens`` minus ``cu_q_lens`` cached pool tokens plus its
    own left-padded in-flight suffix causally (``window`` is a dynamic
    scalar, 0 = full). ``kv_fused`` selects the interleaved K/V page
    layout (one copy per page instead of two); ``writes_kv=True``
    additionally merges the new tokens into their suffix pages — int8
    pools quantise inside the epilogue, no float staging tensor — and
    returns ``(out, *updated_pools)``."""
    interp = interpret_mode(interpret)
    return _ra.ragged_attention(
        q, k, v, cu_q_lens, cu_kv_lens, block_tables,
        k_pages=k_pages, v_pages=v_pages, kv_fused=kv_fused,
        k_scale=k_scale, v_scale=v_scale, window=window, softcap=softcap,
        block_q=block_q, pages_per_block=pages_per_block,
        writes_kv=writes_kv, interpret=interp)


@functools.partial(jax.jit,
                   static_argnames=("want_state", "block_size", "interpret"))
def ring_scan_blocks(states, arrivals, *, want_state: int,
                     block_size: int = 64, interpret: Optional[bool] = None):
    interp = interpret_mode(interpret)
    return _rs.ring_scan_blocks(states, arrivals, want_state=want_state,
                                block_size=block_size, interpret=interp)


@functools.partial(jax.jit,
                   static_argnames=("want_state", "k", "block_size",
                                    "interpret"))
def ring_select_topk(states, arrivals, *, want_state: int, k: int,
                     block_size: int = 64, interpret: Optional[bool] = None):
    interp = interpret_mode(interpret)
    return _rs.ring_select_topk(states, arrivals, want_state=want_state,
                                k=k, block_size=block_size, interpret=interp)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(x, B_in, C_in, dt, A, h0, *, chunk: int = 64,
                   interpret: Optional[bool] = None):
    interp = interpret_mode(interpret)
    return _ss.ssd_chunk_scan(x, B_in, C_in, dt, A, h0, chunk=chunk,
                              interpret=interp)
