"""Kernel microbenchmarks: interpret-mode correctness + timing vs oracle.

NOTE: interpret mode executes the kernel body in Python on CPU — timings
measure the *oracle-relative correctness envelope* and host-side dispatch,
not TPU performance. The roofline analysis (benchmarks/roofline.py) is the
performance source of truth for this container.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels import ops, ref


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6, out


def main() -> None:
    keys = jax.random.split(jax.random.PRNGKey(0), 8)

    # paged attention
    B, KV, G, hd, P, ps, mb = 4, 2, 4, 64, 64, 16, 8
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, KV, ps, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, KV, ps, hd), jnp.float32)
    bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
    kv_lens = jnp.array([30, 64, 100, 128])
    us_k, out_k = _time(ops.paged_attention, q, kp, vp, bt, kv_lens)
    us_r, out_r = _time(ref.paged_attention_ref, q, kp, vp, bt, kv_lens)
    err = float(jnp.max(jnp.abs(out_k - out_r)))
    emit("kernel_paged_attention", us_k,
         f"ref_us={us_r:.0f};max_err={err:.1e}")

    # ring scan
    S = 4096   # the paper's ring size
    states = jax.random.randint(keys[4], (S,), 0, 4)
    arrivals = jax.random.permutation(keys[5], S).astype(jnp.int32)
    us_k, out_k = _time(ops.ring_scan_blocks, states, arrivals,
                        want_state=1, block_size=64)
    us_r, out_r = _time(ref.ring_scan_blocks_ref, states, arrivals,
                        want_state=1, block_size=64)
    match = bool(jnp.all(out_k == out_r))
    emit("kernel_ring_scan_4096slots", us_k, f"ref_us={us_r:.0f};match={match}")

    # SSD chunk scan
    Bz, T, H, Pd, N = 2, 128, 4, 64, 64
    x = jax.random.normal(keys[6], (Bz, T, H, Pd)) * 0.5
    B_in = jax.random.normal(keys[7], (Bz, T, N)) * 0.5
    C_in = jax.random.normal(keys[0], (Bz, T, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(keys[1], (Bz, T, H)))
    A = -jnp.exp(jax.random.normal(keys[2], (H,)) * 0.3)
    h0 = jnp.zeros((Bz, H, Pd, N))
    us_k, (y_k, h_k) = _time(ops.ssd_chunk_scan, x, B_in, C_in, dt, A, h0,
                             chunk=64)
    us_r, (y_r, h_r) = _time(ref.ssd_sequential_ref, x, B_in, C_in, dt, A, h0)
    err = float(jnp.max(jnp.abs(y_k - y_r)))
    emit("kernel_ssd_chunk_scan", us_k, f"seq_ref_us={us_r:.0f};max_err={err:.1e}")


if __name__ == "__main__":
    main()
