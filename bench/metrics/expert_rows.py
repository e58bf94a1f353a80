"""Per-expert load: the mean of routed rows per held expert per layer and
engine step over the traced windows (the ``expert_rows`` counter over
steps x layers x experts held), to set against what each expert would see
in the deployment the configuration stands for."""
from bench.harness.expert_counts import counted


def read(run):
    got = counted(run)
    if got is None or not got[2]:
        return None
    rows, hit, steps = got
    code = run.code
    return rows / (steps * code.L * code.HELD), (
        f"{rows} routed rows on held experts over {steps} steps; "
        f"{hit} (layer, call) expert hits, {rows / max(hit, 1):.4f} rows "
        f"per hit expert")
