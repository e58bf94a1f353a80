"""Roofline share of the split path's ``flash_prefill`` chunk kernel, over
the prefill chunks' work (each chunk's queries against their causal
context, its K/V read once, queries read, outputs and new K/V written;
``bench/harness/work.py``)."""
from bench.harness.roofline import share


def read(run):
    w = run.work
    return share(run, "flash_prefill", run.code.attn_flops(w.chunk_ctx),
                 w.chunk_bytes)
