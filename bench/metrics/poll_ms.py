"""Host time in the token poll (``blink.poll``: the device reads, the
reader loop, the prefix commit and the slot releases), summed over the
traced boundaries and divided by their number, in milliseconds. The detail
gives the device reads (``blink.poll_read``) and the slot releases
(``blink.release``) per boundary apart, and how much of the boundary the
program's spans explain."""
from bench.harness import program_spans


def read(run):
    s = program_spans.stretch(run)
    if s is None:
        return None
    polls = s.spans("blink.poll")
    released = sum(p.attrs.get("completed", 0) for p in polls)
    return s.per_boundary_ms("blink.poll"), (
        f"poll_read {s.per_boundary_ms('blink.poll_read'):.4f} ms, release "
        f"{s.per_boundary_ms('blink.release'):.4f} ms per boundary "
        f"({released / s.boundaries:.3f} slots released); "
        + program_spans.remainder(run, s))
