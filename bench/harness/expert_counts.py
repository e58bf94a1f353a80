"""The expert layers' counters over a traced stretch: the program's
counter-row columns ``expert_rows`` (routed pairs on held experts) and
``experts_hit`` (held experts given a row, per layer and call), each
summed over layers within a step by the program. A program whose rows
have no such columns gives nothing."""
from __future__ import annotations

from typing import Optional, Tuple

from . import program_spans


def counted(run) -> Optional[Tuple[int, int, int]]:
    """``(expert_rows, experts_hit, steps)`` summed over the counter rows
    of the windows launched in the traced stretch, or None."""
    s = program_spans.stretch(run)
    rows_col = program_spans.counter_column("expert_rows")
    hit_col = program_spans.counter_column("experts_hit")
    if s is None or rows_col is None or hit_col is None:
        return None
    wins = s.windows()
    if not wins:
        return None
    rows = sum(int(w.rows[:, rows_col].sum()) for w in wins)
    hit = sum(int(w.rows[:, hit_col].sum()) for w in wins)
    return rows, hit, sum(len(w.rows) for w in wins)
