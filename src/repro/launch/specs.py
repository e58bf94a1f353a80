"""ShapeDtypeStruct input specs per (architecture x input shape) — the
dry-run's stand-ins (weak-type-correct, shardable, no device allocation).

Geometry policy (see DESIGN.md §4):
  * decode shapes lower ``serve_step`` (ONE token, KV cache of seq_len);
  * long_500k runs only for sub-quadratic archs (SSM / hybrid / SWA);
    gemma2 runs it with its global layers restricted to a streaming window
    (beyond-paper extension, documented);
  * [audio]/[vlm] modality frontends are stubs: input_specs provides the
    frame/patch embeddings directly;
  * encoder-decoder prefill/train splits seq_len between encoder frames and
    decoder tokens;
  * serving steps with global_batch >= #(data shards) lower through
    partial-auto shard_map (independent replicas, see launch.steps);
    global_batch=1 (long_500k) lowers as a single TP replica.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.distribution import sharding as shd
from repro.launch import steps as steps_lib
from repro.models.api import make_model
from repro.optim.adamw import AdamW

PAGE_SIZE = 64


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), jnp.dtype(dtype))


@dataclass
class LowerPlan:
    kind: str
    fn: Optional[Callable] = None
    args: Tuple[Any, ...] = ()
    in_shardings: Any = None
    skip_reason: Optional[str] = None


def should_skip(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.supports_long_context:
        if cfg.local_global:
            return None  # gemma2: streaming-window global layers (documented)
        return ("full-attention arch: no sub-quadratic decode path; "
                "long_500k skipped per brief (see DESIGN.md §4)")
    return None


def serve_cache_specs(cfg: ModelConfig, *, num_slots: int, seq_len: int,
                      enc_len: int = 0, kv_dtype="bfloat16",
                      page_size: int = PAGE_SIZE) -> Dict[str, Any]:
    """ShapeDtypeStruct tree shaped like models.cache.make_cache output."""
    from repro.models import ssm as ssm_lib
    from repro.models.cache import PagedKVCache, scale_lanes
    max_blocks = (seq_len + page_size - 1) // page_size
    num_pages = num_slots * max_blocks
    out: Dict[str, Any] = {}
    if cfg.uses_paged_kv:
        L = cfg.num_attn_layers
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        scale = None
        if jnp.dtype(kv_dtype) == jnp.int8:
            scale = sds((L, num_pages, kv, scale_lanes(page_size)),
                        jnp.bfloat16)
        out["kv"] = PagedKVCache(
            k_pages=sds((L, num_pages, kv, page_size, hd), kv_dtype),
            v_pages=sds((L, num_pages, kv, page_size, hd), kv_dtype),
            block_table=sds((num_slots, max_blocks), jnp.int32),
            seq_lens=sds((num_slots,), jnp.int32),
            k_scale=scale, v_scale=scale,
        )
    if cfg.arch_type == "ssm":
        H, hd = ssm_lib.rwkv_heads(cfg)
        out["ssm"] = {
            "wkv": sds((cfg.num_layers, num_slots, H, hd, hd), jnp.float32),
            "shift_att": sds((cfg.num_layers, num_slots, cfg.d_model),
                             cfg.jnp_dtype),
            "shift_ffn": sds((cfg.num_layers, num_slots, cfg.d_model),
                             cfg.jnp_dtype),
        }
    if cfg.arch_type == "hybrid":
        di, H, N = ssm_lib.mamba2_dims(cfg)
        out["ssm"] = {
            "conv": sds((cfg.num_layers, num_slots, cfg.ssm_conv, di),
                        cfg.jnp_dtype),
            "ssm": sds((cfg.num_layers, num_slots, H, cfg.ssm_head_dim, N),
                       jnp.float32),
        }
    if cfg.is_encoder_decoder and enc_len:
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        out["enc_k"] = sds((cfg.num_layers, num_slots, enc_len, kv, hd),
                           cfg.jnp_dtype)
        out["enc_v"] = sds((cfg.num_layers, num_slots, enc_len, kv, hd),
                           cfg.jnp_dtype)
        out["enc_len"] = sds((num_slots,), jnp.int32)
    return out


def dp_size(mesh: Mesh, dp) -> int:
    axes = dp if isinstance(dp, tuple) else (dp,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def build_plan(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
               *, kv_dtype="bfloat16", expert_parallel: bool = False,
               page_size: int = PAGE_SIZE) -> LowerPlan:
    skip = should_skip(cfg, shape)
    if skip:
        return LowerPlan(kind="skip", skip_reason=skip)

    api = make_model(cfg)
    dp = shd.batch_axes(mesh)
    model_size = int(mesh.shape.get("model", 1))
    param_sds = api.param_specs()
    param_shard = shd.to_named(
        mesh, shd.param_pspecs(cfg, model_size=model_size,
                               expert_parallel=expert_parallel))
    B, T = shape.global_batch, shape.seq_len

    # ---------------- train -------------------------------------------------
    if shape.kind == "train":
        if cfg.is_encoder_decoder:
            enc = T // 2
            batch = {
                "tokens": sds((B, T - enc), jnp.int32),
                "labels": sds((B, T - enc), jnp.int32),
                "mask": sds((B, T - enc), jnp.bool_),
                "modal_embeds": sds((B, enc, cfg.d_model), cfg.jnp_dtype),
                "frame_mask": sds((B, enc), jnp.bool_),
            }
        else:
            batch = {
                "tokens": sds((B, T), jnp.int32),
                "labels": sds((B, T), jnp.int32),
                "mask": sds((B, T), jnp.bool_),
            }
            if cfg.num_modal_tokens:
                batch["modal_embeds"] = sds(
                    (B, cfg.num_modal_tokens, cfg.d_model), cfg.jnp_dtype)
        bshard = {
            k: NamedSharding(mesh, P(*([dp] + [None] * (v.ndim - 1))))
            for k, v in batch.items()
        }
        opt = AdamW()
        opt_sds = jax.eval_shape(opt.init, param_sds)
        opt_shard = type(opt_sds)(
            step=NamedSharding(mesh, P()), m=param_shard, v=param_shard)
        return LowerPlan(
            kind="train",
            fn=steps_lib.make_train_step(api, opt),
            args=(param_sds, opt_sds, batch),
            in_shardings=(param_shard, opt_shard, bshard),
        )

    # ---------------- serving shapes ---------------------------------------
    sharded = B % dp_size(mesh, dp) == 0 and B >= dp_size(mesh, dp)
    data_axis = dp if sharded else None

    if shape.kind == "prefill":
        enc = T // 2 if cfg.is_encoder_decoder else 0
        T_dec = T - enc if cfg.is_encoder_decoder else T
        cache = serve_cache_specs(cfg, num_slots=B, seq_len=T, enc_len=enc,
                                  kv_dtype=kv_dtype, page_size=page_size)
        cache_shard = shd.to_named(mesh, shd.cache_pspecs(
            cfg, cache, model_size, data_axis=data_axis))
        args = [param_sds, sds((B, T_dec), jnp.int32), sds((B,), jnp.int32),
                cache, sds((B,), jnp.int32), sds((B,), jnp.bool_)]
        bsp = P(dp) if sharded else P()
        bsp2 = P(dp, None) if sharded else P()
        inshard = [param_shard, NamedSharding(mesh, bsp2),
                   NamedSharding(mesh, bsp), cache_shard,
                   NamedSharding(mesh, bsp), NamedSharding(mesh, bsp)]
        extra = None
        if cfg.is_encoder_decoder:
            extra = sds((B, enc, cfg.d_model), cfg.jnp_dtype)
        elif cfg.num_modal_tokens:
            extra = sds((B, cfg.num_modal_tokens, cfg.d_model), cfg.jnp_dtype)
        if extra is not None:
            args.append(extra)
            inshard.append(NamedSharding(
                mesh, P(dp, None, None) if sharded else P()))
        if sharded:
            fn = steps_lib.make_sharded_prefill_step(
                api, mesh, dp, cache, has_extra=extra is not None)
        else:
            fn = steps_lib.make_prefill_step(api)
        return LowerPlan(kind="prefill", fn=fn, args=tuple(args),
                         in_shardings=tuple(inshard))

    # decode
    enc = 4096 if cfg.is_encoder_decoder else 0
    cache = serve_cache_specs(cfg, num_slots=B, seq_len=T, enc_len=enc,
                              kv_dtype=kv_dtype, page_size=page_size)
    cache_shard = shd.to_named(mesh, shd.cache_pspecs(
        cfg, cache, model_size, data_axis=data_axis))
    bsp = P(dp) if sharded else P()
    args = (param_sds, sds((B,), jnp.int32), cache, sds((B,), jnp.int32),
            sds((B,), jnp.bool_))
    inshard = (param_shard, NamedSharding(mesh, bsp), cache_shard,
               NamedSharding(mesh, bsp), NamedSharding(mesh, bsp))
    if sharded:
        fn = steps_lib.make_sharded_serve_step(api, mesh, dp, cache)
    else:
        fn = steps_lib.make_serve_step(api)
    return LowerPlan(kind="decode", fn=fn, args=args, in_shardings=inshard)
