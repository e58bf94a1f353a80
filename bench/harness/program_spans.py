"""What the program records about itself, cut to a traced stretch: the
``blink.*`` host spans and the per-window counter rows of the program's
process-wide recorder (``repro.telemetry.host``).

The stretch runs from the start of the first traced boundary to the end of
the last (``run.windows``); a span belongs to it when it starts inside.
A program that keeps no such recorder gives nothing, and the readers built
on this module then report nothing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

#: The parts of a boundary: the children of ``blink.run_window`` other
#: than the window's launch and wait.
BOUNDARY_PARTS = ("blink.evict", "blink.flush", "blink.select",
                  "blink.drain", "blink.poll", "blink.overload",
                  "blink.snapshot")


def program_recorder():
    """The program's process-wide span recorder, or None."""
    try:
        from repro.telemetry import host
    except ImportError:
        return None
    return host.recorder()


def counter_column(name: str) -> Optional[int]:
    try:
        from repro.telemetry import state
    except ImportError:
        return None
    return state.COL.get(name)


@dataclass
class Stretch:
    rec: object
    lo: float
    hi: float
    boundaries: int

    def spans(self, name: str) -> list:
        return self.rec.closed(name, self.lo, self.hi)

    def seconds(self, name: str) -> float:
        return sum(s.t1 - s.t0 for s in self.spans(name))

    def per_boundary_ms(self, name: str) -> float:
        return 1e3 * self.seconds(name) / self.boundaries

    def windows(self) -> list:
        """The recorder's window launches inside the stretch, with rows."""
        return [w for w in self.rec.windows
                if self.lo <= w.t_launch <= self.hi and w.rows is not None]


def stretch(run) -> Optional[Stretch]:
    """The traced stretch of ``run``, or None when the program recorded
    no boundary there."""
    if not run.windows:
        return None
    rec = program_recorder()
    if rec is None:
        return None
    s = Stretch(rec, run.windows[0].start, run.windows[-1].end,
                len(run.windows))
    return s if s.spans("blink.run_window") else None


def remainder(run, s: Stretch) -> str:
    """How much of the boundary time (the benchmark's span around each
    ``run_window`` minus ``window_wall``) the boundary's ``blink.*`` parts
    explain, per boundary."""
    host_ms = 1e3 * sum(w.end - w.start - w.wall for w in run.windows) \
        / s.boundaries
    parts_ms = sum(s.per_boundary_ms(n) for n in BOUNDARY_PARTS)
    own_ms = sum(1e3 * (r.t1 - r.t0) for r in s.spans("blink.run_window")) \
        / s.boundaries - s.per_boundary_ms("blink.launch") \
        - s.per_boundary_ms("blink.wait")
    share = 100.0 * parts_ms / host_ms if host_ms > 0 else float("nan")
    return (f"boundary {host_ms:.4f} ms (blink.run_window less launch and "
            f"wait {own_ms:.4f} ms); blink.* parts {parts_ms:.4f} ms "
            f"({share:.2f}%), unattributed {host_ms - parts_ms:.4f} ms")


def first_tokens(s: Stretch) -> List[Tuple[float, float, float]]:
    """``(ring_wall, end of the first token's step, first_token_wall)`` for
    each first token a poll in the stretch delivered, whose step the
    step→wall map places."""
    out = []
    for poll in s.spans("blink.poll"):
        owner = poll.root().attrs.get("owner")     # the polling server's
        for _rid, ring_wall, step, wall in poll.attrs.get("first_tokens", ()):
            end = s.rec.step_end(step, owner)
            if end is not None and ring_wall >= 0:
                out.append((ring_wall, end, wall))
    return out
