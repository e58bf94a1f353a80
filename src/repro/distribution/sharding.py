"""Sharding rules: logical param/cache/input axes -> mesh axes.

Mesh axes (DESIGN.md §6):
  * "model" — tensor parallel: attention heads / FFN hidden / vocab / expert
    hidden (baseline) or expert index (expert-parallel hillclimb);
  * "data"  — batch / ring slots / KV pages' owning sequences;
  * "pod"   — second-level data parallelism across pods (training), present
    only on the multi-pod mesh.

Rules are name-based over the param template, MaxText-style: weights get
explicit shardings; interior activations are left to SPMD propagation.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import cache as cache_lib

# param-name -> which dim gets the "model" axis (negative = from the right)
_SHARD_LAST = {
    "wq", "wk", "wv", "wg", "wr",                 # attention / rwkv proj
    "w_gate", "w_up",                             # mlp / moe expert in
    "cm_wk", "cm_wr",                             # rwkv channel-mix in
    "z_proj", "x_proj", "dt_proj",                # mamba in
    "conv_w",                                     # mamba depthwise conv
    "bq", "bk", "bv",                             # qkv biases
    "conv_b",
    "wq_x", "wk_x", "wv_x", "bq_x", "bk_x", "bv_x",  # cross-attn
}
_SHARD_SECOND_LAST = {
    "wo", "w_down", "cm_wv", "out_proj", "wo_x",  # output projections
}
_REPLICATED = {
    "ln", "ln1", "ln2", "ln3", "final_norm", "out_ln",
    "router", "shared_gate",
    "mu_r", "mu_k", "mu_v", "mu_g", "mu_w", "cm_mu_k", "cm_mu_r",
    "w_lora_a", "w_lora_b", "w_decay", "u_bonus",
    "b_proj", "c_proj", "A_log", "D_skip", "dt_bias",
}
# qwen2-moe shared experts: ordinary TP
_SHARD_LAST |= {"ws_gate", "ws_up"}
_SHARD_SECOND_LAST |= {"ws_down"}

_EXPERT_LEAVES = {"w_gate", "w_up", "w_down"}     # [L, E, D, Fe]-shaped


def _shard_dim(shape, dim: int, model_size: int) -> P:
    """Spec sharding ``dim`` on "model" if divisible, else replicate."""
    spec = [None] * len(shape)
    if shape[dim] % model_size == 0:
        spec[dim] = "model"
    return P(*spec)


def _spec_for(name: str, shape, cfg: ModelConfig, model_size: int, *,
              expert_parallel: bool) -> P:
    ndim = len(shape)
    if name == "embed":
        # prefer vocab sharding; some vocabs (92553, 256206) don't divide —
        # fall back to the d_model dim
        if shape[0] % model_size == 0:
            return P("model", None)
        return _shard_dim(shape, 1, model_size)
    if name == "unembed":
        if shape[1] % model_size == 0:
            return P(None, "model")
        return _shard_dim(shape, 0, model_size)
    is_expert = cfg.num_experts and ndim == 4 and name in _EXPERT_LEAVES
    if is_expert and expert_parallel:
        return _shard_dim(shape, 1, model_size)    # shard expert index
    if name in _SHARD_LAST:
        return _shard_dim(shape, -1, model_size)
    if name in _SHARD_SECOND_LAST:
        return _shard_dim(shape, -2, model_size)
    return P()                                     # default: replicate


def param_pspecs(cfg: ModelConfig, *, model_size: int = 16,
                 expert_parallel: bool = False) -> Dict[str, Any]:
    """PartitionSpec tree matching ``transformer.param_specs(cfg)``."""
    from repro.models.transformer import param_specs
    specs = param_specs(cfg)

    def walk(tree):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            else:
                out[k] = _spec_for(k, v.shape, cfg, model_size,
                                   expert_parallel=expert_parallel)
        return out

    return walk(specs)


def kv_head_axis(cfg: ModelConfig, model_size: int):
    """What KV caches shard on "model": "kv" (the KV heads) if divisible,
    else "hd" (head_dim) if divisible, else None (replicate over model)."""
    if cfg.num_kv_heads % model_size == 0:
        return "kv"
    if cfg.resolved_head_dim % model_size == 0:
        return "hd"
    return None


def cache_pspecs(cfg: ModelConfig, cache_tree: Dict[str, Any],
                 model_size: int, *, data_axis="data") -> Dict[str, Any]:
    """PartitionSpec tree for a serve cache bundle.

    data_axis (axis name, tuple, or None): shards the page pool / slots —
    each data shard is an independent serving replica (launch.steps).
    The model axis shards KV heads (or head_dim when heads don't divide)."""
    out: Dict[str, Any] = {}
    if "kv" in cache_tree:
        from repro.models.cache import PagedKVCache
        ax = kv_head_axis(cfg, model_size)
        page_spec = [None] * 5                   # [L, P, KV, ps, hd]
        if ax is not None:
            page_spec[{"kv": 2, "hd": 4}[ax]] = "model"
        page_spec[1] = data_axis                 # page pool: replica-local
        scale_spec = None
        if getattr(cache_tree["kv"], "k_scale", None) is not None:
            sp = [None] * 4                      # [L, P, KV, lanes]
            sp[1] = data_axis
            if ax == "kv":                       # scales have no hd dim
                sp[2] = "model"
            scale_spec = P(*sp)
        out["kv"] = PagedKVCache(
            k_pages=P(*page_spec),
            v_pages=P(*page_spec),
            block_table=P(data_axis, None),
            seq_lens=P(data_axis),
            k_scale=scale_spec,
            v_scale=scale_spec,
        )
    if "ssm" in cache_tree:
        def ssm_spec(leaf):
            # [L, S, ...]: slots on data; best divisible trailing dim on model
            nd = len(leaf.shape)
            spec = [None] * nd
            spec[1] = data_axis
            if nd >= 3:
                cands = [d for d in range(2, nd)
                         if leaf.shape[d] % model_size == 0]
                if cands:
                    best = max(cands, key=lambda d: leaf.shape[d])
                    spec[best] = "model"
            return P(*spec)
        out["ssm"] = jax.tree.map(
            ssm_spec, cache_tree["ssm"],
            is_leaf=lambda x: hasattr(x, "shape"))
    for k in ("enc_k", "enc_v"):
        if k in cache_tree:
            ax = kv_head_axis(cfg, model_size)
            spec = [None] * 5                    # [L, S, enc_len, KV, hd]
            if ax is not None:
                spec[{"kv": 3, "hd": 4}[ax]] = "model"
            spec[1] = data_axis
            out[k] = P(*spec)
    if "enc_len" in cache_tree:
        out["enc_len"] = P(data_axis)
    if cache_lib.EXPERT_COUNTS in cache_tree:   # step counters: replicated
        out[cache_lib.EXPERT_COUNTS] = P()
    return out


def to_named(mesh: Mesh, pspec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))


def batch_axes(mesh: Mesh):
    """Data-parallel axes: ("pod","data") on a multi-pod mesh else "data"."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else "data"


# ---------------------------------------------------------------------------
# Tensor-parallel serving mesh (``ServeConfig.mesh_model_size``)
# ---------------------------------------------------------------------------
#
# The persistent window runs SPMD over a 1-D ("model",) mesh: attention
# heads and the paged KV pool are sharded, ring/allocator/scheduler/
# telemetry state is replicated. Bitwise identity with the single-device
# engine is the acceptance criterion, so only reduction-order-free work is
# genuinely distributed (attention heads are batch dims of every einsum);
# dense projections are STORED sharded per the rules above but gathered at
# use, keeping each output element's contraction on one device.


def head_partition(num_heads: int, model_size: int):
    """Contiguous ``(start, stop)`` head ranges, one per model shard.

    The partition is an exact cover: every head appears in exactly one
    range (the property suite pins this). GQA group alignment follows for
    free: with ``H = KV * G`` and both H and KV divisible by
    ``model_size``, shard i holds q heads ``[i*H/n, (i+1)*H/n)`` and kv
    heads ``[i*KV/n, (i+1)*KV/n)``, and ``h // G`` maps a local q head to
    its local kv head exactly as it does globally."""
    if model_size < 1:
        raise ValueError(f"model_size must be >= 1, got {model_size}")
    if num_heads % model_size != 0:
        raise ValueError(
            f"cannot shard {num_heads} heads over model={model_size}: "
            f"head counts must divide evenly (no ragged shards)")
    per = num_heads // model_size
    return [(i * per, (i + 1) * per) for i in range(model_size)]


def validate_head_sharding(cfg: ModelConfig, model_size: int) -> None:
    """Model-build-time validation of ``mesh_model_size`` against the
    concrete arch — a bad mesh must fail at ``make_model``, not as a
    shape error deep inside the first jitted window."""
    if model_size < 1:
        raise ValueError(
            f"mesh model size must be >= 1, got {model_size}")
    if model_size == 1:
        return
    if cfg.arch_type not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"mesh_model_size > 1 requires a paged-KV decoder-only arch "
            f"(dense/moe/vlm), got arch_type={cfg.arch_type!r}: SSM/"
            f"hybrid recurrent state and enc-dec cross-KV have no model-"
            f"axis layout yet")
    if cfg.num_kv_heads % model_size != 0:
        raise ValueError(
            f"mesh_model_size={model_size} does not divide num_kv_heads="
            f"{cfg.num_kv_heads} ({cfg.name}): the paged KV pool shards "
            f"whole KV heads over the model axis")
    if cfg.num_heads % model_size != 0:
        raise ValueError(
            f"mesh_model_size={model_size} does not divide num_heads="
            f"{cfg.num_heads} ({cfg.name}): query heads shard in whole "
            f"GQA groups over the model axis")


def make_serve_mesh(model_size: int, *, devices=None) -> Optional[Mesh]:
    """1-D ``("model",)`` serving mesh over the first ``model_size``
    devices, or None for the single-device engine (no mesh is built —
    every code path stays exactly the seed single-device program)."""
    if model_size < 1:
        raise ValueError(f"mesh model size must be >= 1, got {model_size}")
    if model_size == 1:
        return None
    devices = list(jax.devices()) if devices is None else list(devices)
    if len(devices) < model_size:
        raise ValueError(
            f"mesh_model_size={model_size} needs at least that many "
            f"devices, have {len(devices)} (on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return Mesh(np.asarray(devices[:model_size]), ("model",))


def mesh_model_size(mesh: Optional[Mesh]) -> int:
    """Size of the mesh's "model" axis (1 for no mesh / no model axis)."""
    if mesh is None:
        return 1
    return int(dict(zip(mesh.axis_names, mesh.devices.shape)
                    ).get("model", 1))
