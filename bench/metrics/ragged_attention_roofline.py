"""Roofline share of the unified ``ragged_attention`` kernel, which serves
every attention row of a step: prefill chunks and decode lanes. The work is
what the served tokens needed, from each request's prompt length, prefill
cursor and generated count at the traced stretch's two ends
(``bench/harness/work.py``): each row reads its K/V once, reads its
queries, writes its outputs and its new K/V."""
from bench.harness.roofline import share


def read(run):
    w = run.work
    return share(run, "ragged_attention", run.code.attn_flops(w.attn_ctx),
                 w.chunk_bytes + w.decode_bytes)
