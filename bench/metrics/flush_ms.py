"""Host time in the submission flush (``blink.flush``: the slot refresh
and one eager ring write per request handed in), summed over the traced
boundaries and divided by their number, in milliseconds. The detail gives
the ring writes per boundary and the milliseconds each took
(``blink.ring_write``), and how much of the boundary the program's spans
explain."""
from bench.harness import program_spans


def read(run):
    s = program_spans.stretch(run)
    if s is None:
        return None
    writes = s.spans("blink.ring_write")
    per_write = 1e3 * sum(w.t1 - w.t0 for w in writes) / max(len(writes), 1)
    return s.per_boundary_ms("blink.flush"), (
        f"{len(writes) / s.boundaries:.3f} ring writes per boundary, "
        f"{per_write:.4f} ms each; " + program_spans.remainder(run, s))
