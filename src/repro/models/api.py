"""Unified model API — the Blink engine treats models as opaque through this.

The paper's scheduler "treats the inference graph as an opaque computation —
populating input tensors, launching the graph, and reading output buffers"
(§4.3). This module is that boundary: every architecture exposes the same
four functions + a cache factory, so the engine, launcher and dry-run never
special-case a family.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ServeConfig
from repro.models import attn_backend as attn_backend_lib
from repro.models import cache as cache_lib
from repro.models import encdec as encdec_lib
from repro.models import transformer as tf_lib


class ModelApi(NamedTuple):
    cfg: ModelConfig
    init_params: Callable[[jax.Array], Dict[str, Any]]
    param_specs: Callable[[], Dict[str, Any]]
    train_loss: Callable[..., Any]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]
    make_cache: Callable[..., Dict[str, Any]]
    attn_backend: str = "gather"
    # True when ``prefill_batched`` is bound to a UNIFIED attention backend
    # (one ragged dispatch serves decode lanes + prefill chunks; see
    # ``attn_backend.get_unified_backend``). The engine refuses a
    # ServeConfig.attn_unified mismatch at init.
    attn_unified: bool = False
    # chunked prefill (bucket > VMEM budget): same contract as ``prefill``
    # plus a ``chunk`` kwarg; None for families without paged prefix support
    prefill_chunked: Optional[Callable[..., Any]] = None
    # batched chunk step (mixed-phase scheduler hot path): ONE dispatch
    # advances up to ``ServeConfig.max_prefills_per_step`` PREFILLING lanes
    # by one chunk each — heterogeneous cursors, ragged chunk lengths,
    # per-lane cached prefixes. Signature:
    #   prefill_batched(params, prompts, lens, cache, slot_ids, active,
    #                   cursors) -> (logits [B, V], cache')
    # where ``cursors[b]`` counts lane b's already-resident prompt tokens
    # (cached prefix + completed chunks). None for families that cannot
    # suspend prefill mid-prompt (SSM/hybrid recurrence, enc-dec cross-KV).
    prefill_batched: Optional[Callable[..., Any]] = None
    # Tensor-parallel serving mesh (("model",) axis) the attention backends
    # are shard_mapped over and the KV pool is placed on; None = the
    # single-device engine. The engine refuses a
    # ``ServeConfig.mesh_model_size`` mismatch at init.
    mesh: Optional[Any] = None


def make_model(cfg: ModelConfig, *, attn_backend: Optional[str] = None,
               attn_pages_per_block: Optional[int] = None,
               prefill_block_q: int = 128,
               prefill_block_k: int = 128,
               attn_unified: bool = False,
               kv_fused_layout: bool = False,
               mesh: Optional[Any] = None) -> ModelApi:
    """Build the opaque model API.

    ``attn_backend`` selects the attention implementation for BOTH serving
    phases (see ``repro.models.attn_backend``): the decode-attention
    callable bound into ``decode`` and the prefill-attention callable bound
    into ``prefill``. Precedence: the REPRO_ATTN_BACKEND env var overrides
    everything (including an explicit argument), then this argument, then
    "gather". Callers serving through the engine pass
    ``ServeConfig.attn_backend`` / ``ServeConfig.attn_pages_per_block`` /
    ``ServeConfig.prefill_block_q`` / ``ServeConfig.prefill_block_k``;
    the engine refuses a config/api mismatch at init and the flash-prefill
    tile sizes are validated here, at model-build time.

    ``attn_unified`` rebinds ``prefill_batched`` to a UNIFIED backend
    (``attn_backend.get_unified_backend``): one ragged dispatch serves
    decode lanes (q_len=1 rows) and prefill chunks in the same grid, and
    with the pallas implementation the kernel's epilogue merges the new
    K/V into the pool (so the jnp scatter path is skipped). The other
    entry points keep their split backends — the unified engine step only
    ever calls ``prefill_batched``. ``kv_fused_layout`` makes
    ``make_cache`` allocate the interleaved K/V page pool the unified
    kernel fetches with one copy per page.

    ``mesh`` (a 1-D ``("model",)`` ``jax.sharding.Mesh``) makes the whole
    API tensor-parallel: the attention backends become shard_map regions
    over heads, ``init_params`` places weights sharded per
    ``distribution.sharding.param_pspecs``, and every serving entry point
    gathers weights at use (exact all-gather) so all dense contractions
    keep the single-device reduction order — the sharded engine is
    bitwise-identical to the unsharded one by construction. Head
    divisibility is validated here, at model-build time.
    """
    from repro.kernels import ops as ops_lib
    ops_lib.validate_compiled_tiling(
        head_dim=cfg.resolved_head_dim, block_q=prefill_block_q,
        block_k=prefill_block_k, pages_per_block=attn_pages_per_block,
        attn_unified=attn_unified, where="make_model")
    if mesh is not None:
        from repro.distribution import sharding as shard_lib
        if shard_lib.mesh_model_size(mesh) <= 1:
            mesh = None                       # trivial mesh: seed program
    if mesh is not None:
        shard_lib.validate_head_sharding(
            cfg, shard_lib.mesh_model_size(mesh))
        if kv_fused_layout:
            raise ValueError(
                "a model mesh is incompatible with kv_fused_layout: the "
                "interleaved K/V pool has no per-shard head slice")
    attend = attn_backend_lib.get_backend(
        attn_backend, pages_per_block=attn_pages_per_block, mesh=mesh)
    pre_attend = attn_backend_lib.get_prefill_backend(
        attn_backend, block_q=prefill_block_q, block_k=prefill_block_k,
        mesh=mesh)
    if attn_unified and cfg.arch_type not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"attn_unified requires a paged-KV decoder-only arch "
            f"(dense/moe/vlm), got arch_type={cfg.arch_type!r}")
    if kv_fused_layout and not attn_unified:
        raise ValueError("kv_fused_layout requires attn_unified")

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(mesh, PartitionSpec())

        def gather_params(params):
            # exact: replicating a sharded weight is a pure all-gather, so
            # every contraction below runs on full operands in the same
            # order as the single-device program
            return jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(x, rep), params)
    else:
        gather_params = lambda params: params

    chunked = batched = None
    if cfg.is_encoder_decoder:
        train = lambda params, batch, **kw: encdec_lib.train_loss(
            params, cfg, batch, **kw)
        pre = lambda params, *a, **kw: encdec_lib.prefill(
            gather_params(params), cfg, *a, prefill_attend=pre_attend, **kw)
    else:
        train = lambda params, batch, **kw: tf_lib.train_loss(
            params, cfg, batch, **kw)
        pre = lambda params, *a, **kw: tf_lib.prefill(
            gather_params(params), cfg, *a, prefill_attend=pre_attend, **kw)
        if cfg.arch_type in ("dense", "moe", "vlm"):
            chunked = lambda params, *a, **kw: tf_lib.chunked_prefill(
                gather_params(params), cfg, *a, prefill_attend=pre_attend,
                **kw)
            batched_attend = pre_attend
            if attn_unified:
                batched_attend = attn_backend_lib.get_unified_backend(
                    attn_backend, block_q=prefill_block_q,
                    pages_per_block=attn_pages_per_block, mesh=mesh)
            batched = lambda params, *a, **kw: tf_lib.prefill_batched(
                gather_params(params), cfg, *a,
                prefill_attend=batched_attend, **kw)

    dec = lambda params, *a, **kw: tf_lib.decode(
        gather_params(params), cfg, *a, attend=attend, **kw)

    def init_params(key):
        params = tf_lib.init_params(key, cfg)
        if mesh is not None:
            from repro.distribution import sharding as shard_lib
            specs = shard_lib.param_pspecs(
                cfg, model_size=shard_lib.mesh_model_size(mesh))
            params = jax.device_put(params,
                                    shard_lib.to_named(mesh, specs))
        return params

    def mk_cache(*, num_slots: int, num_pages: int, page_size: int,
                 max_blocks: int, enc_len: int = 0, dtype=None):
        return cache_lib.make_cache(
            cfg, num_slots=num_slots, num_pages=num_pages,
            page_size=page_size, max_blocks=max_blocks, enc_len=enc_len,
            dtype=dtype, kv_fused_layout=kv_fused_layout)

    return ModelApi(
        cfg=cfg,
        init_params=init_params,
        param_specs=lambda: tf_lib.param_specs(cfg),
        train_loss=train,
        prefill=pre,
        decode=dec,
        make_cache=mk_cache,
        attn_backend=attend.backend_name,
        attn_unified=attn_unified,
        prefill_chunked=chunked,
        prefill_batched=batched,
        mesh=mesh,
    )


def cache_for_serve(api: ModelApi, serve: ServeConfig, *, enc_len: int = 0,
                    dtype=None) -> Dict[str, Any]:
    if dtype is None and serve.kv_cache_dtype:
        dtype = jnp.dtype(serve.kv_cache_dtype)
    return api.make_cache(
        num_slots=serve.num_slots, num_pages=serve.num_pages,
        page_size=serve.page_size, max_blocks=serve.pages_per_req,
        enc_len=enc_len, dtype=dtype)
