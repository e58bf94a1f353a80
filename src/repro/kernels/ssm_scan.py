"""Chunked SSD (Mamba-2) kernel (Pallas TPU).

One grid step processes one (batch, head) pair for one chunk of Q timesteps:
intra-chunk "attention-like" term + inter-chunk state propagation, with the
running SSM state [P, N] held in VMEM scratch across the chunk-grid
dimension. This is the TPU-native layout of the SSD algorithm: the [Q, Q]
score matrix and [P, N] state tile map onto the MXU; chunk size is chosen so
the working set (Q*P + Q*N + P*N + Q*Q floats) fits VMEM.

Grid: (B, H, num_chunks) — chunks innermost so the state scratch carries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref,      # [1, 1, Q, P]
                b_ref,      # [1, Q, N]
                c_ref,      # [1, Q, N]
                dt_ref,     # [1, 1, Q]
                a_ref,      # [1, 1]  per-head A (negative)
                h0_ref,     # [1, 1, P, N]
                y_ref,      # [1, 1, Q, P]
                hout_ref,   # [1, 1, P, N]
                state_scr,  # [P, N] f32
                *, num_chunks: int, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)        # [Q, P]
    Bq = b_ref[0].astype(jnp.float32)          # [Q, N]
    Cq = c_ref[0].astype(jnp.float32)          # [Q, N]
    dt = dt_ref[0, 0].astype(jnp.float32)      # [Q]
    A = a_ref[0, 0].astype(jnp.float32)        # scalar

    a = A * dt                                  # [Q] log-decay increments
    cum = jnp.cumsum(a)                         # inclusive
    Q = x.shape[0]

    # intra-chunk scores: (C_t . B_s) * exp(cum_t - cum_s) * dt_s, s <= t
    cb = jnp.dot(Cq, Bq.T, preferred_element_type=jnp.float32)   # [Q, Q]
    delta = cum[:, None] - cum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.where(tri, jnp.exp(delta), 0.0)
    scores = cb * decay * dt[None, :]
    y = jnp.dot(scores, x, preferred_element_type=jnp.float32)   # [Q, P]

    # inter-chunk: y_t += C_t . (exp(cum_t) * h_in)
    h = state_scr[...]                          # [P, N]
    y = y + jnp.exp(cum)[:, None] * jnp.dot(
        Cq, h.T, preferred_element_type=jnp.float32)             # [Q, P]

    # state update: h' = exp(cum_Q) h + sum_s exp(cum_Q - cum_s) dt_s x_s B_s^T
    carry = jnp.exp(cum[-1] - cum) * dt         # [Q]
    dBx = jnp.dot((x * carry[:, None]).T, Bq,
                  preferred_element_type=jnp.float32)            # [P, N]
    state_scr[...] = h * jnp.exp(cum[-1]) + dBx

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _final():
        hout_ref[0, 0] = state_scr[...].astype(hout_ref.dtype)


def ssd_chunk_scan(x: jax.Array,      # [B, T, H, P]
                   B_in: jax.Array,   # [B, T, N]
                   C_in: jax.Array,   # [B, T, N]
                   dt: jax.Array,     # [B, T, H]
                   A: jax.Array,      # [H]
                   h0: jax.Array,     # [B, H, P, N]
                   *, chunk: int = 64, interpret: bool):
    """Returns (y [B, T, H, P] f32, h_final [B, H, P, N] f32)."""
    Bsz, T, H, P = x.shape
    N = B_in.shape[-1]
    Q = min(chunk, T)
    assert T % Q == 0
    nc = T // Q

    # layouts: chunk-major so the innermost grid dim walks chunks
    x_r = x.transpose(0, 2, 1, 3).reshape(Bsz, H, nc, Q, P) \
        .transpose(0, 1, 2, 3, 4)                     # [B,H,nc,Q,P]
    x_r = x_r.reshape(Bsz, H * nc, Q, P)              # flatten for blockspec
    dt_r = dt.transpose(0, 2, 1).reshape(Bsz, H, nc, Q).reshape(Bsz, H * nc, Q)
    b_r = B_in.reshape(Bsz, nc * Q, N)
    c_r = C_in.reshape(Bsz, nc * Q, N)
    a_r = A.reshape(H, 1)
    h0_r = h0.reshape(Bsz, H, P, N)

    grid = (Bsz, H, nc)

    out_shape = [
        jax.ShapeDtypeStruct((Bsz, H * nc, Q, P), jnp.float32),   # y
        jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),        # h_out
    ]

    y, h_out = pl.pallas_call(
        functools.partial(_ssd_kernel, num_chunks=nc, chunk=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h * pl.num_programs(2) + c, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, h, c: (b, h * pl.num_programs(2) + c, 0)),
            pl.BlockSpec((1, 1), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h * pl.num_programs(2) + c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x_r, b_r, c_r, dt_r, a_r, h0_r)

    y = y.reshape(Bsz, H, nc, Q, P).transpose(0, 2, 3, 1, 4).reshape(
        Bsz, T, H, P)
    return y, h_out
