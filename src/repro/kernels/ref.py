"""Pure-jnp oracles for every Pallas kernel in this package.

Tests sweep shapes/dtypes and assert the kernels (interpret mode on CPU,
compiled on TPU) match these references.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

INT_MAX = jnp.iinfo(jnp.int32).max


def _pages_f32(pages, rows, scale=None):
    """Pool rows ``rows`` of a head-major ``[P, KV, ps, hd]`` pool as
    token-major float32 ``[..., ps, KV, hd]``; ``scale`` ([P, KV, lanes],
    token t in lane t) dequantises."""
    ps = pages.shape[2]
    x = jnp.swapaxes(pages[rows], -3, -2).astype(jnp.float32)
    if scale is not None:
        sc = jnp.swapaxes(scale[rows][..., :ps], -2, -1)  # [..., ps, KV]
        x = x * sc.astype(jnp.float32)[..., None]
    return x


def paged_attention_ref(q, k_pages, v_pages, block_table, kv_lens, *,
                        window: int = 0, softcap: float = 0.0,
                        k_scale=None, v_scale=None):
    """q: [B, KV, G, hd]; pages [P, KV, ps, hd]; returns [B, KV, G, hd].

    k_scale/v_scale: optional [P, KV, lanes] int8 dequant scales."""
    B, KV, G, hd = q.shape
    P, _, ps, _ = k_pages.shape
    mb = block_table.shape[1]
    safe = jnp.clip(block_table, 0, P - 1)
    k = _pages_f32(k_pages, safe, k_scale)   # [B, mb, ps, KV, hd]
    v = _pages_f32(v_pages, safe, v_scale)
    k = k.reshape(B, mb * ps, KV, hd)
    v = v.reshape(B, mb * ps, KV, hd)
    qf = q.astype(jnp.float32) / math.sqrt(hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, k)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    kv_pos = jnp.arange(mb * ps)[None, :]
    mask = kv_pos < kv_lens[:, None]
    if window > 0:
        mask &= kv_pos >= (kv_lens[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # zero out fully-masked lanes instead of NaN
    p = jnp.where(jnp.any(mask, axis=1)[:, None, None, None], p, 0.0)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v)
    return out.astype(q.dtype)


def flash_prefill_ref(q, k, v, offsets, *, window: int = 0,
                      softcap: float = 0.0, k_pages=None, v_pages=None,
                      block_rows=None, cached_lens=None, k_scale=None,
                      v_scale=None):
    """q: [B, T, H, hd]; k/v: [B, T, KV, hd]; offsets: [B] left-pad widths.

    Dense causal (windowed) GQA over a left-padded bucket — the oracle for
    ``kernels.flash_prefill``. Output rows in the pad region (column <
    offsets[b]) are zeroed to match the kernel's no-live-keys convention.

    With ``k_pages``/``v_pages``/``block_rows``/``cached_lens`` the oracle
    additionally gathers lane b's cached prefix (``cached_lens[b]`` tokens
    at absolute positions [0, cached)) densely from the paged pool and
    prepends it to the key axis — the reference for the kernel's
    prefix-reuse / chunked-prefill mode."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = (q.astype(jnp.float32) / math.sqrt(hd)).reshape(B, T, KV, G, hd)
    col = jnp.arange(T)[None, :]
    if k_pages is not None:
        P, ps = k_pages.shape[0], k_pages.shape[2]
        safe = jnp.clip(block_rows, 0, P - 1)
        kp = _pages_f32(k_pages, safe, k_scale)  # [B, mb, ps, KV, hd]
        vp = _pages_f32(v_pages, safe, v_scale)
        mbps = kp.shape[1] * ps
        k_all = jnp.concatenate([kp.reshape(B, mbps, KV, hd),
                                 k.astype(jnp.float32)], axis=1)
        v_all = jnp.concatenate([vp.reshape(B, mbps, KV, hd),
                                 v.astype(jnp.float32)], axis=1)
        cached = jnp.asarray(cached_lens, jnp.int32)
        # absolute positions: prefix tokens at [0, cached); suffix column c
        # at cached + c - offset
        q_pos = cached[:, None] + col - offsets[:, None]        # [B, Tq]
        k_pos = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(mbps)[None, :], (B, mbps)),
             q_pos], axis=1)                                    # [B, Tk]
        k_valid = jnp.concatenate(
            [jnp.arange(mbps)[None, :] < cached[:, None],
             col >= offsets[:, None]], axis=1)
        q_valid = col >= offsets[:, None]
        s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k_all)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = (k_pos[:, None, :] <= q_pos[:, :, None]) \
            & k_valid[:, None, :] & q_valid[:, :, None]
        if window > 0:
            mask &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
        s = jnp.where(mask[:, None, None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.any(mask, axis=2)[:, None, None, :, None], p, 0.0)
        out = jnp.einsum("bkgqs,bskh->bqkgh", p, v_all)
        return out.reshape(B, T, H, hd).astype(q.dtype)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k.astype(jnp.float32))
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    q_col = col[:, :, None]                      # [B, Tq, 1]
    k_col = col[:, None, :]                      # [B, 1, Tk]
    mask = (k_col <= q_col) & (k_col >= offsets[:, None, None])
    if window > 0:
        mask &= (q_col - k_col) < window
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, axis=2)[:, None, None, :, None], p, 0.0)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return out.reshape(B, T, H, hd).astype(q.dtype)


def ragged_attention_ref(q, k, v, cu_q_lens, cu_kv_lens, block_tables, *,
                         k_pages=None, v_pages=None, kv_fused=None,
                         k_scale=None, v_scale=None, window: int = 0,
                         softcap: float = 0.0):
    """Oracle for ``kernels.ragged_attention`` (attention output only — the
    KV-write epilogue's reference is ``cache.write_kv_layer``). Derives the
    per-row offsets/cached lengths from the ragged cumulative metadata and
    delegates to the prefix-mode flash oracle; a fused interleaved pool is
    split back into K/V views first."""
    T = q.shape[1]
    cu_q = jnp.asarray(cu_q_lens, jnp.int32)
    cu_kv = jnp.asarray(cu_kv_lens, jnp.int32)
    q_lens = cu_q[1:] - cu_q[:-1]
    cached = (cu_kv[1:] - cu_kv[:-1]) - q_lens
    if kv_fused is not None:
        k_pages = kv_fused[:, :, 0]
        v_pages = kv_fused[:, :, 1]
    return flash_prefill_ref(
        q, k, v, T - q_lens, window=window, softcap=softcap,
        k_pages=k_pages, v_pages=v_pages, block_rows=block_tables,
        cached_lens=cached, k_scale=k_scale, v_scale=v_scale)


def ring_scan_blocks_ref(states, arrivals, *, want_state: int,
                         block_size: int = 64):
    S = states.shape[0]
    nb = S // block_size
    eligible = states == want_state
    keyed = jnp.where(eligible, arrivals, INT_MAX).reshape(nb, block_size)
    min_val = jnp.min(keyed, axis=1)
    local = jnp.argmin(keyed, axis=1).astype(jnp.int32)
    idx = jnp.arange(nb, dtype=jnp.int32) * block_size + local
    return jnp.stack([min_val, idx], axis=1)


def ssd_chunk_scan_ref(x, B_in, C_in, dt, A, h0, *, chunk: int = 64):
    """Reference chunked SSD == repro.models.ssm._ssd_chunk_scan reshaped."""
    from repro.models.ssm import _ssd_chunk_scan
    Bsz, T, H, P = x.shape
    Q = min(chunk, T)
    nc = T // Q

    def rc(t):
        return t.reshape((Bsz, nc, Q) + t.shape[2:]).swapaxes(0, 1)

    ys, h_final = _ssd_chunk_scan(
        A.astype(jnp.float32), rc(x.astype(jnp.float32)),
        rc(B_in.astype(jnp.float32)), rc(C_in.astype(jnp.float32)),
        rc(dt.astype(jnp.float32)), h0.astype(jnp.float32))
    y = ys.swapaxes(0, 1).reshape(Bsz, T, H, P)
    return y, h_final


def ssd_sequential_ref(x, B_in, C_in, dt, A, h0):
    """Step-by-step SSD recurrence — the ground-truth oracle."""
    Bsz, T, H, P = x.shape

    def step(h, inputs):
        xt, bt, ct, dtt = inputs             # [B,H,P], [B,N], [B,N], [B,H]
        decay = jnp.exp(A[None, :] * dtt)
        h = h * decay[..., None, None] + jnp.einsum(
            "bh,bhp,bn->bhpn", dtt, xt, bt)
        y = jnp.einsum("bhpn,bn->bhp", h, ct)
        return h, y

    xs = (x.transpose(1, 0, 2, 3).astype(jnp.float32),
          B_in.transpose(1, 0, 2).astype(jnp.float32),
          C_in.transpose(1, 0, 2).astype(jnp.float32),
          dt.transpose(1, 0, 2).astype(jnp.float32))
    h, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return ys.transpose(1, 0, 2, 3), h
