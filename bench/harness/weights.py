"""Random weights made by the benchmark, from the seed, on the device, in
one jitted call and in the type they are served in.

The shapes are the program's parameter tree (``api.param_specs()``); the
values are the benchmark's own, chosen by leaf name so that every term of
the equations matters: norm gains ``1 + N(0, 0.1)``, QKV biases
``N(0, 0.5)``, the embedding ``N(0, 1/d_model)`` (logits of unit scale when
tied) and every other matrix ``N(0, 1/fan_in)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GAIN_STD = 0.1
BIAS_STD = 0.5


def jax_seed(seed: int) -> int:
    """A 31-bit key for JAX from any whole-number ``--seed``."""
    return int(np.random.default_rng(seed).integers(2 ** 31 - 1))


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _init(name, spec, key):
    shape, dt = spec.shape, spec.dtype
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("ln1", "ln2", "final_norm"):
        return (z * GAIN_STD).astype(dt)
    if name in ("bq", "bk", "bv"):
        return (z * BIAS_STD).astype(dt)
    if name == "embed":
        return (z / np.sqrt(shape[-1])).astype(dt)
    return (z / np.sqrt(shape[-2])).astype(dt)


def make_params(specs, seed: int):
    """Weights for the parameter tree ``specs`` (ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_init(_leaf_name(p), s, k)
                      for (p, s), k in zip(flat, keys)])

    return build(jax.random.PRNGKey(jax_seed(seed)))
