"""Roofline share of the grouped expert matmul (``expert_gmm``): its work
over the traced windows, from the program's expert counters
(``bench/harness/expert_counts.py``) counted by the configuration's
``expert_flops`` and ``expert_bytes``, against the kernel's summed device
time."""
from bench.harness.expert_counts import counted
from bench.harness.roofline import share


def read(run):
    got = counted(run)
    if got is None:
        return None
    rows, hit, _ = got
    code = run.code
    return share(run, "expert_gmm", code.expert_flops(rows),
                 code.expert_bytes(hit, rows))
