"""Production serving launcher: the Blink stack for any assigned arch.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --tiny \
        --requests 8 --max-new 12 [--interfere]

Runs synthetic requests through frontend -> ring -> persistent-window
engine, prints per-request metrics + Blink's host-touch count. ``--tiny``
serves the arch's small CPU preset at demo sizes; without it the arch
runs at its published widths with serving sizes the compiled TPU kernels
take (16-token pages, 128-token tiles, prompts up to 1024 tokens).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.distribution import sharding
from repro.frontend.server import BlinkServer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import make_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--window", type=int, default=24)
    ap.add_argument("--interfere", action="store_true")
    ap.add_argument("--attn-backend", default="gather",
                    choices=("gather", "pallas"),
                    help="decode attention backend (REPRO_ATTN_BACKEND "
                         "overrides)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="mixed-phase scheduling: advance at most this "
                         "many prefill tokens per step while decode keeps "
                         "streaming (0 = phase-exclusive legacy policy; "
                         "requires a paged-KV decoder-only arch)")
    ap.add_argument("--attn-unified", action="store_true",
                    help="fold prefill chunks + decode lanes into ONE "
                         "ragged attention dispatch per mixed iteration "
                         "(needs --prefill-chunk)")
    ap.add_argument("--kv-fused-layout", action="store_true",
                    help="interleaved K/V page pool (one copy per prefix "
                         "block; needs --attn-unified, excludes "
                         "--slo-preempt)")
    ap.add_argument("--prefill-chunk-max", type=int, default=0,
                    help="adaptive chunk sizing ceiling: each step's chunk "
                         "budget follows decode-lane occupancy between "
                         "--prefill-block-q (floor) and this ceiling "
                         "(0 = static chunks; requires --prefill-chunk)")
    ap.add_argument("--prefill-block-q", type=int, default=0,
                    help="flash-prefill query tile / adaptive chunk floor "
                         "(0 = default 128, or 8 when --prefill-chunk-max "
                         "is set, so tiny demo prompts stay valid)")
    ap.add_argument("--slo-classes", type=int, default=1,
                    help="number of SLO classes (class 0 = interactive, "
                         "higher = batch); requests are submitted round-"
                         "robin across classes when > 1")
    ap.add_argument("--slo-preempt", action="store_true",
                    help="decode-lane preemption under overload: a blocked "
                         "interactive arrival evicts the worst-slack batch "
                         "victim, whose KV is spilled to a host buffer and "
                         "restored when capacity frees (needs "
                         "--prefill-chunk and --slo-classes >= 2)")
    ap.add_argument("--deadline-policy", default="none",
                    choices=("none", "ttft", "e2e"),
                    help="deadline enforcement: cancel requests past their "
                         "per-class budget (ttft = first token only, e2e = "
                         "whole stream; needs --prefill-chunk)")
    ap.add_argument("--slo-ttft", default="",
                    help="comma list, per-class TTFT budget in steps "
                         "(len == --slo-classes; required when "
                         "--deadline-policy != none)")
    ap.add_argument("--slo-tpot", default="",
                    help="comma list, per-class per-token budget in steps "
                         "(required when --deadline-policy e2e)")
    ap.add_argument("--intake-limit", type=int, default=0,
                    help="reject new submissions once this many requests "
                         "queue at the frontend (0 = unbounded)")
    ap.add_argument("--no-ring-checksum", action="store_true",
                    help="skip payload checksum verification at device "
                         "admission (sequence/commit-flag checks still run)")
    ap.add_argument("--watchdog-steps", type=int, default=0,
                    help="fault a slot making no admission/prefill/decode "
                         "progress for this many consecutive steps "
                         "(0 = off; needs --prefill-chunk)")
    ap.add_argument("--snapshot-every-steps", type=int, default=0,
                    help="take a byte-exact crash-recovery snapshot every "
                         "N steps (0 = off; must be a multiple of "
                         "--window)")
    ap.add_argument("--telemetry", action="store_true",
                    help="device-resident telemetry plane: per-step "
                         "counter rows + per-request event timelines, "
                         "updated in-step with pure array ops (zero host "
                         "callbacks) and drained at window boundaries")
    ap.add_argument("--metrics-out", default="",
                    help="write Prometheus text exposition here at exit "
                         "(implies --telemetry)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of request "
                         "spans here at exit (implies --telemetry)")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="tensor-parallel model-axis size: shard attention "
                         "heads + the paged KV pool over this many devices "
                         "(must divide the arch's KV head count; on CPU "
                         "set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    args = ap.parse_args()

    cfg = get_config(args.arch, tiny=args.tiny)
    # demo sizes for the tiny CPU presets; serving sizes otherwise
    max_prompt, page_size, num_pages = ((32, 8, 160) if args.tiny
                                        else (1024, 16, 2048))
    block_q = args.prefill_block_q or (8 if args.prefill_chunk_max else 128)
    slo_ttft = tuple(int(x) for x in args.slo_ttft.split(",") if x)
    slo_tpot = tuple(int(x) for x in args.slo_tpot.split(",") if x)
    if (args.slo_preempt or args.deadline_policy != "none") \
            and not args.prefill_chunk:
        ap.error("SLO overload control runs in the mixed-phase scheduler: "
                 "pass --prefill-chunk as well")
    if args.watchdog_steps and not args.prefill_chunk:
        ap.error("the stall watchdog runs in the mixed-phase scheduler: "
                 "pass --prefill-chunk as well")
    if args.attn_unified and not args.prefill_chunk:
        ap.error("the unified attention dispatch merges the mixed step's "
                 "two phases: pass --prefill-chunk as well")
    serve = ServeConfig(num_slots=16, max_prompt_len=max_prompt,
                        max_new_tokens=args.max_new, decode_batch=8,
                        window=args.window, admit_per_step=4,
                        page_size=page_size, num_pages=num_pages,
                        eos_token=-1,
                        attn_backend=args.attn_backend,
                        attn_unified=args.attn_unified,
                        kv_fused_layout=args.kv_fused_layout,
                        prefill_chunk_tokens=args.prefill_chunk,
                        prefill_chunk_tokens_max=args.prefill_chunk_max,
                        prefill_block_q=block_q,
                        slo_classes=args.slo_classes,
                        slo_preempt=args.slo_preempt,
                        deadline_policy=args.deadline_policy,
                        slo_ttft_steps=slo_ttft, slo_tpot_steps=slo_tpot,
                        intake_queue_limit=args.intake_limit,
                        ring_checksum=not args.no_ring_checksum,
                        watchdog_steps=args.watchdog_steps,
                        snapshot_every_steps=args.snapshot_every_steps,
                        telemetry=(args.telemetry or bool(args.metrics_out)
                                   or bool(args.trace_out)),
                        mesh_model_size=args.mesh_model)
    enable_compile_cache()
    mesh = sharding.make_serve_mesh(serve.mesh_model_size)
    api = make_model(cfg, attn_backend=serve.attn_backend,
                     attn_pages_per_block=serve.attn_pages_per_block,
                     prefill_block_q=serve.prefill_block_q,
                     prefill_block_k=serve.prefill_block_k,
                     attn_unified=serve.attn_unified,
                     kv_fused_layout=serve.kv_fused_layout,
                     mesh=mesh)
    if mesh is not None:
        print(f"tensor-parallel window: model={serve.mesh_model_size} over "
              f"{[d.id for d in mesh.devices.flat]}")
    params = api.init_params(jax.random.PRNGKey(0))
    jitter = None
    if args.interfere:
        from benchmarks.common import make_jitter
        jitter = make_jitter(0.004)
    srv = BlinkServer(api, serve, params, host_jitter=jitter,
                      enc_len=16 if cfg.is_encoder_decoder else 0)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        srv.submit(rng.integers(3, cfg.vocab_size,
                                int(rng.integers(4, max_prompt * 3 // 4))
                                ).tolist(),
                   max_new=args.max_new,
                   slo_class=i % max(args.slo_classes, 1))
    windows = srv.run_until_idle(max_windows=500)
    wall = time.perf_counter() - t0
    mets = srv.request_metrics()
    toks = sum(m["tokens"] for m in mets)
    print(f"{cfg.name}: {len(mets)} requests, {toks} tokens, "
          f"{windows} windows ({windows} host touches), {wall:.2f}s"
          f" -> {toks/wall:.1f} tok/s (includes first-window compile)")
    for m in sorted(mets, key=lambda m: m["request_id"]):
        tag = "" if m["status"] == "completed" else f" [{m['status']}]"
        print(f"  req {m['request_id']} (class {m['slo_class']}): "
              f"{m['tokens']} tokens, ttft {m['ttft']*1e3:.0f}ms{tag}")
    if serve.telemetry:
        from repro.telemetry.export import span_summaries
        print(f"telemetry: {len(srv.telemetry_rows)} step rows drained, "
              f"step time {srv.step_time_s()*1e3:.2f}ms")
        for line in span_summaries(srv.telemetry_records()):
            print(f"  {line}")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                f.write(srv.metrics_text())
            print(f"wrote Prometheus metrics -> {args.metrics_out}")
        if args.trace_out:
            import json
            with open(args.trace_out, "w") as f:
                json.dump(srv.trace_json(), f)
            print(f"wrote Perfetto trace -> {args.trace_out}")


if __name__ == "__main__":
    main()
