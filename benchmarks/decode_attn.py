"""Decode-attention backends: gather vs pallas per-step HBM bytes + latency.

The engine's per-token step reads the KV cache once per attention layer.
The "gather" backend materialises the slot's whole provisioned page range —
its per-step HBM traffic scales with ``max_kv`` no matter how short the
live context is. The "pallas" paged-attention kernel streams only live
pages (live-page early exit + sliding-window page skip) — traffic scales
with ``live_len``. This sweep quantifies that gap across
(live_len, max_kv) and writes JSON records that ``benchmarks/report.py``
renders next to the roofline table.

NOTE on latency: this container runs the kernel in interpret mode (Python
emulation), so wall-clock favors the jnp gather path; the byte model is
the performance statement, the timing is the dispatch-overhead envelope.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels import ops, ref
from repro.kernels.ragged_attention import build_cu_lens

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "decode_attn")

# fixed decode geometry (per layer): lanes x kv heads x q-per-kv x head dim
B, KV, G, HD, PS = 4, 2, 4, 64, 16
SWEEP = [  # (live_len, max_kv)
    (16, 256), (128, 256), (256, 256),
    (16, 1024), (128, 1024), (1024, 1024),
]
SMOKE_SWEEP = [(16, 64)]   # REPRO_BENCH_SMOKE=1 (CI dry run)


def gather_bytes(max_kv: int, itemsize: int) -> int:
    """Per-step K+V HBM reads of the gather path (whole block table)."""
    return 2 * B * max_kv * KV * HD * itemsize


def pallas_bytes(live_len: int, itemsize: int, window: int = 0) -> int:
    """Per-step K+V HBM reads of the kernel: live (or windowed) pages only."""
    span = min(live_len, window) if window else live_len
    pages = -(-max(span, 1) // PS)
    return 2 * B * pages * PS * KV * HD * itemsize


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6, out


def main() -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    records = []
    sweep = (SMOKE_SWEEP if os.environ.get("REPRO_BENCH_SMOKE") == "1"
             else SWEEP)
    for live, max_kv in sweep:
        mb = max_kv // PS
        P = B * mb + 1
        q = jax.random.normal(keys[0], (B, KV, G, HD), jnp.float32)
        kp = jax.random.normal(keys[1], (P, KV, PS, HD), jnp.float32)
        vp = jax.random.normal(keys[2], (P, KV, PS, HD), jnp.float32)
        bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
        kv_lens = jnp.full((B,), live, jnp.int32)

        us_p, out_p = _time(ops.paged_attention, q, kp, vp, bt, kv_lens,
                            pages_per_block=2)
        us_g, out_g = _time(ref.paged_attention_ref, q, kp, vp, bt, kv_lens)
        err = float(jnp.max(jnp.abs(out_p - out_g)))

        itemsize = kp.dtype.itemsize
        gb, pb = gather_bytes(max_kv, itemsize), pallas_bytes(live, itemsize)
        rec = {
            "kind": "decode_attn",
            "live_len": live, "max_kv": max_kv,
            "batch": B, "kv_heads": KV, "q_per_kv": G, "head_dim": HD,
            "page_size": PS,
            "gather_bytes_per_step": gb,
            "pallas_bytes_per_step": pb,
            "bytes_ratio": gb / pb,
            "gather_us": us_g, "pallas_us": us_p,
            "max_err": err,
        }
        records.append(rec)
        emit(f"decode_attn_live{live}_max{max_kv}", us_p,
             f"gather_us={us_g:.0f};gather_MB={gb/1e6:.2f};"
             f"pallas_MB={pb/1e6:.2f};bytes_ratio={gb/pb:.1f};"
             f"max_err={err:.1e}")

    # --- ragged unified kernel, decode-shaped (q_len=1 rows) + the
    # pages_per_block autotune sweep. The unified engine serves decode
    # lanes through kernels.ragged_attention; this row checks the decode
    # special case agrees with the dedicated paged kernel and picks the
    # page-fetch granularity (one datapoint: the mid sweep geometry).
    live, max_kv = sweep[min(1, len(sweep) - 1)]
    mb = max_kv // PS
    P = B * mb + 1
    q = jax.random.normal(keys[0], (B, KV, G, HD), jnp.float32)
    kp = jax.random.normal(keys[1], (P, KV, PS, HD), jnp.float32)
    vp = jax.random.normal(keys[2], (P, KV, PS, HD), jnp.float32)
    bt = jax.random.permutation(keys[3], P)[: B * mb].reshape(B, mb)
    kv_lens = jnp.full((B,), live, jnp.int32)
    T = 8  # decode rows ride the ragged grid as q_len=1, left-padded
    q_r = jnp.zeros((B, T, KV * G, HD), jnp.float32
                    ).at[:, -1].set(q.reshape(B, KV * G, HD))
    # the unified path sources the CURRENT token's K/V from the in-batch
    # suffix (pre-pool); mirror the paged setup by copying the pool entry
    # at the newest live position into the suffix row
    pos = live - 1
    page = bt[:, pos // PS]
    k_r = jnp.zeros((B, T, KV, HD), jnp.float32
                    ).at[:, -1].set(kp[page, :, pos % PS])
    v_r = jnp.zeros((B, T, KV, HD), jnp.float32
                    ).at[:, -1].set(vp[page, :, pos % PS])
    cu_q, cu_kv = build_cu_lens(jnp.full((B,), 1, jnp.int32), kv_lens - 1)
    expect = ref.paged_attention_ref(q, kp, vp, bt, kv_lens)
    autotune = []
    for ppb in (1, 2, 4):
        us_r, out_r = _time(ops.ragged_attention, q_r, k_r, v_r, cu_q,
                            cu_kv, bt, k_pages=kp, v_pages=vp, reps=1,
                            block_q=T, pages_per_block=ppb)
        err_r = float(jnp.max(jnp.abs(
            out_r[:, -1].reshape(B, KV, G, HD) - expect)))
        autotune.append({"pages_per_block": ppb, "ragged_us": us_r,
                         "max_err_vs_paged": err_r})
        emit(f"decode_attn_ragged_live{live}_ppb{ppb}", us_r,
             f"max_err_vs_paged={err_r:.1e}")
        assert err_r < 1e-4
    best = min(autotune, key=lambda r: r["ragged_us"])
    records.append({"kind": "decode_attn_ragged_autotune",
                    "live_len": live, "max_kv": max_kv, "block_q": T,
                    "sweep": autotune,
                    "best_pages_per_block": best["pages_per_block"]})
    emit(f"decode_attn_ragged_autotune_live{live}", best["ragged_us"],
         f"best_pages_per_block={best['pages_per_block']}")

    # --- tensor-parallel row (model=2): the decode kernel under the same
    # shard_map layout the SPMD engine uses — q and the page pools sharded
    # over kv heads, block table/lens replicated. Heads are batch dims of
    # the attention contraction, so the sharded output must be BITWISE the
    # single-device kernel's. Skipped (with a note) on one device.
    if jax.device_count() >= 2:
        from jax.sharding import PartitionSpec as Pspec
        from repro.distribution.sharding import make_serve_mesh
        mesh = make_serve_mesh(2)
        body = jax.shard_map(
            lambda q_, kp_, vp_, bt_, lens_: ops.paged_attention(
                q_, kp_, vp_, bt_, lens_, pages_per_block=2),
            mesh=mesh,
            in_specs=(Pspec(None, "model"), Pspec(None, "model"),
                      Pspec(None, "model"), Pspec(), Pspec()),
            out_specs=Pspec(None, "model"), check_vma=False)
        base = ops.paged_attention(q, kp, vp, bt, kv_lens,
                                   pages_per_block=2)
        us_s, out_s = _time(body, q, kp, vp, bt, kv_lens, reps=1)
        equal = bool((np.asarray(out_s) == np.asarray(base)).all())
        assert equal, "sharded decode kernel diverged from single-device"
        records.append({"kind": "decode_attn_sharded", "mesh_model": 2,
                        "live_len": live, "max_kv": max_kv,
                        "sharded_us": us_s, "equal_tokens": equal})
        emit(f"decode_attn_sharded_model2_live{live}", us_s,
             "equal_tokens=1;kv_heads_per_shard=1")
    else:
        emit("decode_attn_sharded_model2", 0.0,
             "skipped=1_device;set_XLA_FLAGS="
             "--xla_force_host_platform_device_count=8")

    if os.environ.get("REPRO_BENCH_SMOKE") != "1":
        # keep the committed sweep datapoints out of CI dry runs
        with open(os.path.join(OUT_DIR, "sweep.json"), "w") as f:
            json.dump(records, f, indent=1)

    # invariants the sweep is meant to demonstrate
    by_live = {}
    for r in records:
        if r["kind"] != "decode_attn":
            continue
        by_live.setdefault(r["live_len"], []).append(r)
    # pallas bytes depend on live_len only; gather bytes on max_kv only
    for live, rs in by_live.items():
        assert len({r["pallas_bytes_per_step"] for r in rs}) == 1
    assert (gather_bytes(1024, 4) == 4 * gather_bytes(256, 4))


if __name__ == "__main__":
    main()
