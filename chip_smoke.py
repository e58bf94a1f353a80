"""Smoke run of the serving path on a TPU, through the entry points a user
calls.

    python chip_smoke.py [--seed N]        # one chip: qwen2-1.5b
    python chip_smoke.py --four-chips      # a four-chip host: olmo-1b

One chip: builds qwen2-1.5b at its published widths (28 layers, d_model
1536, 12 query and 2 KV heads, head_dim 128, vocab 151,936, bf16) with
random weights made from ``--seed``, and serves the same requests twice
through ``make_model`` -> ``BlinkServer`` -> ``run_until_idle`` with the
compiled Pallas kernels and mixed-phase scheduling (256-token chunks):
first with the unified ragged kernel, then with the split paged-decode +
flash-prefill pair. Every request must complete with tokens in the
vocabulary, each window program must hold Mosaic kernels
(``tpu_custom_call``: nothing ran interpreted), and the logits of both
kernel paths, after a chunked prefill and after one decode step, must
match the gather reference on the same weights within ``LOGIT_TOL``.

``--four-chips`` runs only the tensor-parallel phase: olmo-1b at published
widths (16 KV heads, so a four-way model axis is legal) served with
``mesh_model_size=4`` over four distinct devices and with
``mesh_model_size=1`` on one, in the same process; greedy token streams
must be equal.

Times printed are from a smoke run, compilation included; they are not
measurements. The last line of output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Logits of a Pallas path may differ from the gather reference by
# at most LOGIT_TOL times the reference's largest |logit|. Both run the
# same bf16 weights and differ only in the attention kernels, whose
# bf16-rounded outputs drift apart layer by layer: on the CPU, at these
# widths, the drift was 0.004 of max |logit| at 2 layers and 0.010 at 8,
# growing like sqrt(depth), so about 0.02 is expected at 28. A wrong page,
# head or mask moves logits by a large share of their range.
LOGIT_TOL = 5e-2
NUM_REQUESTS = 8
PROMPT_LENS = (128, 1024)
MAX_NEW = 32
CHUNK = 256


def make_requests(seed, vocab, n=NUM_REQUESTS, lens=PROMPT_LENS):
    import numpy as np
    rng = np.random.default_rng(seed)
    sizes = rng.integers(lens[0], lens[1] + 1, n)
    return [rng.integers(3, vocab, int(s)).tolist() for s in sizes]


def serve_config(*, unified, mesh_model_size=1, num_pages=2048,
                 max_prompt_len=PROMPT_LENS[1], max_new=MAX_NEW,
                 chunk=CHUNK, block=128, page_size=16):
    from repro.configs.base import ServeConfig
    return ServeConfig(
        num_slots=16, max_prompt_len=max_prompt_len, max_new_tokens=max_new,
        decode_batch=8, window=16, admit_per_step=4, page_size=page_size,
        num_pages=num_pages, eos_token=-1, attn_backend="pallas",
        attn_unified=unified, prefill_chunk_tokens=chunk,
        max_prefills_per_step=2, prefill_block_q=block,
        prefill_block_k=block, mesh_model_size=mesh_model_size)


def build_api(cfg, serve, mesh=None):
    from repro.models.api import make_model
    return make_model(cfg, attn_backend=serve.attn_backend,
                      attn_pages_per_block=serve.attn_pages_per_block,
                      prefill_block_q=serve.prefill_block_q,
                      prefill_block_k=serve.prefill_block_k,
                      attn_unified=serve.attn_unified, mesh=mesh)


def serve_phase(name, api, serve, params, requests):
    """Serve ``requests`` to completion; returns ({request_id: tokens},
    the server). Fails unless every request completed with ``max_new``
    in-vocabulary tokens."""
    from repro.frontend.server import BlinkServer
    srv = BlinkServer(api, serve, params)
    for p in requests:
        srv.submit(p, max_new=serve.max_new_tokens)
    t0 = time.perf_counter()
    windows = srv.run_until_idle(max_windows=200)
    wall = time.perf_counter() - t0
    done = srv.frontend.done
    out = {rid: list(r.output) for rid, r in sorted(done.items())}
    vocab = api.cfg.vocab_size
    bad = [rid for rid, r in done.items()
           if r.status != "completed" or len(r.output) != serve.max_new_tokens
           or not all(0 <= t < vocab for t in r.output)]
    ntok = sum(len(t) for t in out.values())
    print(f"[{name}] {len(out)}/{len(requests)} requests, {ntok} tokens, "
          f"{windows} windows, {wall:.1f} s wall incl. compile (smoke run, "
          f"not a measurement)")
    if len(out) != len(requests) or bad:
        raise RuntimeError(f"[{name}] requests not served in full: "
                           f"{bad or 'missing'}")
    return out, srv


def check_compiled(name, srv):
    """The window program this server ran must hold Mosaic kernels."""
    window = srv.windows.select(srv.windows.max_pending_len(srv.state.ring))
    t0 = time.perf_counter()
    hlo = window.lower(srv.params, srv.state).compile().as_text()
    n = hlo.count("tpu_custom_call")
    print(f"[{name}] compiled window holds {n} tpu_custom_call ops "
          f"({time.perf_counter() - t0:.1f} s to lower and compile again)")
    if n == 0:
        raise RuntimeError(f"[{name}] no Mosaic kernel in the window program")


def chunked_logits(api, serve, params, prompts, lens, chunk):
    """Last-token logits of left-padded ``prompts`` prefilled in ``chunk``
    pieces through ``api.prefill_batched`` (the mixed-phase engine's entry
    point), so each piece after the first reads its prefix from the pool."""
    import jax
    import jax.numpy as jnp
    B, T = prompts.shape
    cache = wired_cache(api, serve, B)
    step = jax.jit(api.prefill_batched)
    slots, col = jnp.arange(B), jnp.arange(chunk)[None, :]
    logits = None
    for i in range(-(-T // chunk)):
        clen = jnp.clip(lens - i * chunk, 0, chunk)
        src = col - (chunk - clen)[:, None] + (T - lens)[:, None] + i * chunk
        toks = jnp.where(col >= (chunk - clen)[:, None],
                         jnp.take_along_axis(prompts, jnp.clip(src, 0, T - 1),
                                             axis=1), 0)
        lg, cache = step(params, toks, clen, cache, slots, clen > 0,
                         jnp.minimum(lens, i * chunk))
        logits = lg if logits is None else jnp.where((clen > 0)[:, None],
                                                     lg, logits)
    return logits, cache


def decode_logits(api, params, tok, cache, lens, chunk):
    """Logits of one decode step on ``tok`` [B] after the prompts in
    ``cache``: the split decode kernel through ``api.decode``, or, for the
    unified api, a one-token row of ``api.prefill_batched`` (how the
    unified engine decodes)."""
    import jax
    import jax.numpy as jnp
    B = tok.shape[0]
    slots, active = jnp.arange(B), jnp.ones(B, bool)
    if api.attn_unified:
        toks = jnp.zeros((B, chunk), jnp.int32).at[:, -1].set(tok)
        return jax.jit(api.prefill_batched)(
            params, toks, jnp.ones(B, jnp.int32), cache, slots, active,
            lens)[0]
    return jax.jit(api.decode)(params, tok, cache, slots, active)[0]


def wired_cache(api, serve, B):
    """A serving cache whose first ``B`` slots own disjoint page runs."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models.api import cache_for_serve
    cache = cache_for_serve(api, serve)
    ppr = serve.pages_per_req
    bt = np.full((serve.num_slots, ppr), -1, np.int32)
    for b in range(B):
        bt[b] = np.arange(b * ppr, (b + 1) * ppr)
    cache["kv"] = dataclasses.replace(cache["kv"], block_table=jnp.asarray(bt))
    return cache


def logits_phase(cfg, params, requests, serve_u, serve_s, apis, n=2):
    """Logits of the unified and split Pallas paths against the gather
    reference on the first ``n`` requests: after a chunked prefill, and
    after one decode step on the reference's next token."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.api import make_model
    reqs = requests[:n]
    T = max(len(p) for p in reqs)
    T = -(-T // serve_u.prefill_chunk_tokens) * serve_u.prefill_chunk_tokens
    prompts = np.zeros((n, T), np.int32)
    for b, p in enumerate(reqs):
        prompts[b, T - len(p):] = p
    prompts = jnp.asarray(prompts)
    lens = jnp.asarray([len(p) for p in reqs], jnp.int32)
    gather = make_model(cfg, attn_backend="gather")
    ref, cache = jax.jit(gather.prefill)(params, prompts, lens,
                                         wired_cache(gather, serve_u, n),
                                         jnp.arange(n), jnp.ones(n, bool))
    tok = jnp.argmax(ref, axis=-1).astype(jnp.int32)
    refs = {"prefill": ref,
            "decode": decode_logits(gather, params, tok, cache, lens, 0)}
    worst = 0.0
    for name, api, serve in (("unified", apis[0], serve_u),
                             ("split", apis[1], serve_s)):
        chunk = serve.prefill_chunk_tokens
        got, cache = chunked_logits(api, serve, params, prompts, lens, chunk)
        gots = {"prefill": got,
                "decode": decode_logits(api, params, tok, cache, lens, chunk)}
        for step in ("prefill", "decode"):
            want = np.asarray(refs[step], np.float32)
            have = np.asarray(gots[step], np.float32)
            scale = float(np.abs(want).max())
            err = float(np.abs(have - want).max())
            agree = float(np.mean(have.argmax(-1) == want.argmax(-1)))
            print(f"[logits:{name}:{step}] prompts "
                  f"{np.asarray(lens).tolist()}: max |pallas - gather| = "
                  f"{err:.4g}, max |gather| = {scale:.4g}, tolerance "
                  f"{LOGIT_TOL} x {scale:.4g} = {LOGIT_TOL * scale:.4g}; "
                  f"argmax agreement {agree:.2f}")
            if not np.isfinite(have).all() or err > LOGIT_TOL * scale:
                raise RuntimeError(f"[logits:{name}:{step}] outside "
                                   "tolerance")
            worst = max(worst, err)
    return worst


def one_chip(seed):
    import jax
    from repro.configs.registry import get_config
    cfg = get_config("qwen2-1.5b")
    requests = make_requests(seed, cfg.vocab_size)
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
          f"head_dim {cfg.resolved_head_dim}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}; {len(requests)} requests of "
          f"{sorted(len(p) for p in requests)} prompt tokens, {MAX_NEW} new")
    serve_u, serve_s = serve_config(unified=True), serve_config(unified=False)
    api_u, api_s = build_api(cfg, serve_u), build_api(cfg, serve_s)
    params = api_u.init_params(jax.random.PRNGKey(seed))
    out_u, srv = serve_phase("unified", api_u, serve_u, params, requests)
    check_compiled("unified", srv)
    del srv
    out_s, srv = serve_phase("split", api_s, serve_s, params, requests)
    check_compiled("split", srv)
    del srv
    same = sum(out_u[r] == out_s[r] for r in out_u)
    print(f"[serve] unified and split token streams equal for {same}/"
          f"{len(out_u)} requests (reported, not checked: the kernels "
          f"round differently)")
    logits_phase(cfg, params, requests, serve_u, serve_s, (api_u, api_s))


def four_chips(seed):
    import gc

    import jax
    from repro.configs.registry import get_config
    from repro.distribution.sharding import make_serve_mesh
    cfg = get_config("olmo-1b")
    requests = make_requests(seed, cfg.vocab_size)
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}; "
          f"{len(requests)} requests, {MAX_NEW} new tokens")
    mesh = make_serve_mesh(4)
    ids = sorted({d.id for d in mesh.devices.flat})
    print(f"mesh model=4 over devices {ids}")
    if len(ids) != 4:
        raise RuntimeError("the model axis does not span four devices")
    outs = {}
    for size in (4, 1):
        serve = serve_config(unified=True, mesh_model_size=size,
                             num_pages=1024)
        api = build_api(cfg, serve, mesh if size > 1 else None)
        params = api.init_params(jax.random.PRNGKey(seed))
        outs[size], srv = serve_phase(f"model={size}", api, serve, params,
                                      requests)
        pool = srv.state.cache["kv"].k_pages
        print(f"[model={size}] KV pool {pool.shape} on "
              f"{len(pool.sharding.device_set)} device(s)")
        if len(pool.sharding.device_set) != size:
            raise RuntimeError("the KV pool is not spread over the mesh")
        del srv, params, api, pool
        gc.collect()
    same = sum(outs[4][r] == outs[1][r] for r in outs[1])
    print(f"[four-chips] token streams equal for {same}/{len(outs[1])} "
          f"requests")
    if same != len(outs[1]):
        raise RuntimeError("sharded and unsharded token streams differ")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device tensor-parallel phase")
    args = ap.parse_args()
    if os.environ.pop("REPRO_ATTN_BACKEND", None):
        print("ignoring REPRO_ATTN_BACKEND: the smoke pins its backends")

    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"jax {jax.__version__}: {len(devices)} device(s), platform "
          f"{d0.platform}, kind {d0.device_kind}")
    if d0.platform != "tpu":
        print("no TPU found: this smoke run needs one", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(f"smoke run finished in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
