"""The work the served tokens needed between two window boundaries, from
each request's prompt length, prefill cursor and generated count.

A prompt is prefilled in chunks of ``chunk`` tokens from position 0; each
chunk is one attention row whose queries see the keys up to their own
position. The first token comes with the last chunk. Each later token is
one decode row whose query sees the prompt and the tokens before it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

Progress = Dict[int, Tuple[int, int]]     # request id -> (prefilled, tokens)


@dataclass
class Work:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    emitted_tokens: int = 0
    chunk_ctx: int = 0          # keys seen, summed over chunk queries
    decode_ctx: int = 0         # ... and over decode queries
    chunk_bytes: float = 0.0    # least HBM traffic of the chunk rows
    decode_bytes: float = 0.0   # ... and of the decode rows
    useful_flops: float = 0.0

    @property
    def attn_ctx(self) -> int:
        return self.chunk_ctx + self.decode_ctx


def progress(tracked, srv) -> Progress:
    """Each tracked request's (prefill cursor, tokens read) right now."""
    done_len = np.asarray(srv.state.ring.prefill_done_len)
    slot_of = {r.request_id: s for s, r in srv.frontend.in_flight.items()}
    out = {}
    for rid, t in tracked.items():
        g = len(t.times)
        if g:
            d = t.prompt_len
        elif rid in slot_of:
            d = int(done_len[slot_of[rid]])
        else:
            d = 0
        out[rid] = (d, g)
    return out


def between(code, prompt_lens: Dict[int, int], a: Progress, b: Progress,
            chunk: int) -> Work:
    w = Work()
    for rid, (d1, g1) in b.items():
        d0, g0 = a.get(rid, (0, 0))
        plen = prompt_lens[rid]
        for s in range(d0, d1, chunk):
            e = min(s + chunk, plen)
            w.chunk_ctx += (e * (e + 1) - s * (s + 1)) // 2
            w.chunk_bytes += code.attn_bytes(e, e - s)
        w.prefill_tokens += d1 - d0
        for g in range(max(g0, 1), g1):
            w.decode_ctx += plen + g
            w.decode_bytes += code.attn_bytes(plen + g, 1)
        w.decode_tokens += max(0, g1 - max(g0, 1))
        w.emitted_tokens += g1 - g0
    w.useful_flops = (2.0 * code.matmul_params()
                      * (w.prefill_tokens + w.decode_tokens)
                      + 2.0 * code.head_params() * w.emitted_tokens
                      + code.attn_flops(w.attn_ctx))
    return w
