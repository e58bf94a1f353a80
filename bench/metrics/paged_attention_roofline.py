"""Roofline share of the split path's ``paged_attention`` decode kernel,
over the decode rows' work (each lane's K/V read once, its query read,
its output and new K/V written; ``bench/harness/work.py``)."""
from bench.harness.roofline import share


def read(run):
    w = run.work
    return share(run, "paged_attention", run.code.attn_flops(w.decode_ctx),
                 w.decode_bytes)
