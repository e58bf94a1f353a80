"""Batch occupancy: the mean of the ``decode_lanes`` counter (lanes that
decoded in the step) over every step of the window launches the program
recorded in the traced stretch. The detail checks the counter rows
against the reader, window by window: the rows' summed ``tokens`` against
the tokens the next poll delivered; and gives lanes times steps per
second, over the windows alone and over the stretch with its boundaries,
to set beside ``output_tokens_per_s``."""
from bench.harness import program_spans


def read(run):
    s = program_spans.stretch(run)
    lanes_col = program_spans.counter_column("decode_lanes")
    tokens_col = program_spans.counter_column("tokens")
    if s is None or lanes_col is None:
        return None
    wins = s.windows()
    if not wins:
        return None
    steps = sum(len(w.rows) for w in wins)
    lanes = sum(int(w.rows[:, lanes_col].sum()) for w in wins) / steps
    delivered = {p.parent.attrs.get("window"): p.attrs.get("tokens")
                 for p in s.spans("blink.poll") if p.parent is not None}
    equal = sum(1 for w in wins
                if int(w.rows[:, tokens_col].sum()) == delivered.get(w.index))
    busy_s = sum(w.t_ready - w.t_launch for w in wins)
    return lanes, (
        f"{steps} steps in {len(wins)} windows; rows' tokens equal the "
        f"poll's in {equal} of {len(wins)} windows; lanes x steps/s = "
        f"{lanes * steps / busy_s:.4f} tokens/s over the windows, "
        f"{lanes * steps / (s.hi - s.lo):.4f} with their boundaries")
