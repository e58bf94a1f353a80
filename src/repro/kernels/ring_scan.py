"""Ring-buffer slot-scan kernel (Pallas TPU).

Paper §4.2 "Parallel slot scanning": the persistent scheduler's 256 threads
scan disjoint contiguous slot ranges in parallel and claim pending slots by
CAS. The TPU analogue is a vectorized block scan: the grid tiles the slot
array into contiguous ranges; each grid step reduces its range to
(min arrival, argmin) over slots in the wanted state; the tiny per-block
results are then reduced by the caller (one more vector op) to pick the FCFS
winner — no host involvement, no serialization over slots.

Inputs:
  states   [S] int32 — slot lifecycle codes
  arrivals [S] int32 — admission tickets (monotonic, smaller = earlier)
Output per block: [num_blocks, 2] int32 = (min arrival or INT32_MAX, index).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INT_MAX = jnp.iinfo(jnp.int32).max


def _ring_scan_kernel(states_ref, arrivals_ref, out_ref, *,
                      block_size: int, want_state: int):
    i = pl.program_id(0)
    states = states_ref[...]                        # [block]
    arrivals = arrivals_ref[...]
    eligible = states == want_state
    keyed = jnp.where(eligible, arrivals, INT_MAX)
    min_val = jnp.min(keyed)
    # argmin within block -> global slot index
    local_idx = jnp.argmin(keyed).astype(jnp.int32)
    out_ref[0, 0] = min_val
    out_ref[0, 1] = i * block_size + local_idx


def ring_scan_blocks(states: jax.Array, arrivals: jax.Array, *,
                     want_state: int, block_size: int = 64,
                     interpret: bool) -> jax.Array:
    """[S] -> [S/block, 2] per-block (min arrival, slot index)."""
    S = states.shape[0]
    assert S % block_size == 0, "num_slots must be divisible by block_size"
    nb = S // block_size
    kernel = functools.partial(_ring_scan_kernel, block_size=block_size,
                               want_state=int(want_state))
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_size,), lambda i: (i,)),
            pl.BlockSpec((block_size,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((1, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 2), jnp.int32),
        interpret=interpret,
    )(states.astype(jnp.int32), arrivals.astype(jnp.int32))


def ring_select_topk(states: jax.Array, arrivals: jax.Array, *,
                     want_state: int, k: int, block_size: int = 64,
                     interpret: bool):
    """FCFS top-k pending slots via the block-scan kernel.

    Returns (slot_ids [k] int32, found [k] bool). Iterates k single-winner
    rounds over the per-block reduction (k is small: admit_per_step)."""
    S = states.shape[0]
    taken = jnp.zeros((S,), bool)
    ids = []
    founds = []
    for _ in range(k):
        masked_arr = jnp.where(taken, INT_MAX, arrivals)
        blocks = ring_scan_blocks(states, masked_arr, want_state=want_state,
                                  block_size=block_size, interpret=interpret)
        best = jnp.argmin(blocks[:, 0])
        val = blocks[best, 0]
        idx = blocks[best, 1]
        found = val != INT_MAX
        ids.append(jnp.where(found, idx, -1))
        founds.append(found)
        taken = taken.at[jnp.where(found, idx, S)].set(True, mode="drop")
    return jnp.stack(ids), jnp.stack(founds)
