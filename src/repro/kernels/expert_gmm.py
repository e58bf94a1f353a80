"""Grouped expert matmul (Pallas TPU) — the dropless expert layer's hot path.

The expert layer (``models/moe.py``) sorts its routed (row, choice) pairs by
the held expert they chose, so each expert's rows are one contiguous run of
``x``; runs follow each other in expert order and every row past the last
run is dead. This kernel multiplies each run by its expert's weights:

  * rows are cut into ``tm``-row tiles. A *visit* is one (tile, expert)
    pair whose rows overlap; a tile that two runs share is visited once per
    run, consecutively, each visit storing only its own run's rows (the
    megablox formulation). Visits are ordered by expert, so each expert's
    weight blocks are fetched once per column tile and call;
  * the visit grid axis is as long as the live visits, a run-time count
    (``num_visits``), so a dead tile costs nothing: no DMA, no grid step.
    At decode about one (row, choice) slot in eight is live;
  * an expert with no rows has no visit, and its weights are never read;
  * ``w2`` fuses the gated unit: ``silu(x w) * (x w2)``, both products of
    one visit from one ``x`` tile;
  * rows that no visit covers (past the live count, or a run's tail in a
    tile it shares with none) are left unwritten: the caller reads only
    live rows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def visit_plan(group_sizes: jax.Array, rows: int, tm: int):
    """The visits of ``rows`` rows in ``tm``-row tiles, for runs of
    ``group_sizes`` rows laid out in group order from row 0.

    Returns ``(offsets [G+1], visit_group [V], visit_tile [V], num_visits)``
    with ``V = rows // tm + G - 1`` the static bound; entries past
    ``num_visits`` repeat the last visit."""
    G = group_sizes.shape[0]
    tiles_m = rows // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    bound = tiles_m + G - 1
    visit_group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                             total_repeat_length=bound)
    base = jnp.cumsum(tiles) - tiles
    visit_tile = first[visit_group] + jnp.arange(bound, dtype=jnp.int32) \
        - base[visit_group]
    visit_tile = jnp.clip(visit_tile, 0, tiles_m - 1)
    return offsets, visit_group, visit_tile, jnp.sum(tiles)


def _gmm_kernel(offsets_ref, vgroup_ref, vtile_ref, x_ref, w_ref, *refs,
                tm: int, glu: bool):
    w2_ref = refs[0] if glu else None
    o_ref = refs[-1]
    v = pl.program_id(1)
    g = vgroup_ref[v]
    row = vtile_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, o_ref.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    x = x_ref[...]
    y = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    if glu:
        y = jax.nn.silu(y) * jnp.dot(x, w2_ref[...],
                                     preferred_element_type=jnp.float32)
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def column_tile(k: int, n: int, itemsize: int,
                budget: int = 2 * 1024 * 1024) -> int:
    """Columns of a weight block: all ``n`` when a ``[k, n]`` block fits
    ``budget`` bytes, else the widest 128-multiple divisor of ``n`` that
    does (each weight block is double-buffered in VMEM)."""
    if k * n * itemsize <= budget or n % 128:
        return n
    best = 128
    for tn in range(128, n, 128):
        if n % tn == 0 and k * tn * itemsize <= budget:
            best = tn
    return best


def expert_gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
               w2: Optional[jax.Array] = None, tm: int = 128,
               interpret: bool) -> jax.Array:
    """``x`` [M, K] rows grouped by expert (runs of ``group_sizes`` [G]
    from row 0, ``M % tm == 0``); ``w`` (and ``w2``) [G, K, N]. Returns
    [M, N] in ``x``'s type: ``x_run @ w[g]`` for each run, or with ``w2``
    ``silu(x_run @ w[g]) * (x_run @ w2[g])``. Rows past the runs are
    unspecified."""
    M, K = x.shape
    G, _, N = w.shape
    if M % tm:
        raise ValueError(f"rows {M} must be a multiple of the tile {tm}")
    tn = column_tile(K, N, w.dtype.itemsize)
    offsets, vgroup, vtile, n_visits = visit_plan(group_sizes, M, tm)
    glu = w2 is not None

    def x_map(j, v, offsets, vgroup, vtile):
        return vtile[v], 0

    def w_map(j, v, offsets, vgroup, vtile):
        return vgroup[v], 0, j

    def o_map(j, v, offsets, vgroup, vtile):
        return vtile[v], j

    w_spec = pl.BlockSpec((None, K, tn), w_map)
    weights = [w, w2] if glu else [w]
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, glu=glu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, K), x_map)] + [w_spec] * len(weights),
            out_specs=pl.BlockSpec((tm, tn), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_gmm",
    )(offsets, vgroup, vtile, x, *weights)
