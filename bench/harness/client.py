"""The client side of a run: drives a ``BlinkServer`` with a traffic mix
and records, for every request, when it was due and when each of its
tokens reached the reader.

Open loop: due times come from the seed. At each window boundary every
request whose due time has passed is submitted; when nothing is queued or
in flight the client sleeps until the next due time. Latencies are timed
from the due time, so a stall is charged to every request it delays.

Saturated: whenever fewer than ``backlog`` requests wait at a boundary,
more are handed in, so the engine never runs short of work. A request's
due time is when it was handed in.

The measured window is ``[t_open, t_close)``. Traffic starts ``lead_s``
before it. After it closes an open loop keeps its arrivals going until
every request due in the window has finished, or ``cap_s`` has passed.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

clock = time.perf_counter


@dataclass
class Tracked:
    index: int
    rid: int
    due: float
    prompt_len: int
    max_new: int
    times: List[float] = field(default_factory=list)


@dataclass
class Window:
    start: float            # host clock when run_window was called
    end: float              # ... and when it returned
    wall: float             # the server's own timing of the device window
    in_system: int          # requests queued or in flight after it


@dataclass
class RunLog:
    t_open: float
    t_close: float
    tracked: Dict[int, Tracked]          # by request id
    windows: List[Window]
    late_s: List[float]                  # submit time minus due time
    drained: bool = True


class Client:
    def __init__(self, srv, requests, traffic: dict):
        self.srv = srv
        self.requests = requests
        self.traffic = traffic
        self.tracked: Dict[int, Tracked] = {}
        self.windows: List[Window] = []
        self.late: List[float] = []
        self._next = 0

    def on_token(self, slot, _idx, _tok):
        req = self.srv.frontend.in_flight.get(slot)
        t = self.tracked.get(req.request_id) if req is not None else None
        if t is not None:           # warm-up requests are not tracked
            t.times.append(clock())

    def _submit(self, due: float) -> None:
        r = self.requests[self._next % len(self.requests)]
        self._next += 1
        now = clock()
        rid = self.srv.submit(r.prompt.tolist(), max_new=r.max_new)
        self.tracked[rid] = Tracked(r.index, rid, due, len(r.prompt),
                                    r.max_new)
        self.late.append(now - due)

    def _window(self, on_boundary) -> None:
        t0 = clock()
        self.srv.run_window()
        t1 = clock()
        fe = self.srv.frontend
        self.windows.append(Window(t0, t1, self.srv.window_wall[-1],
                                   len(fe.queue) + len(fe.in_flight)))
        if on_boundary is not None:
            on_boundary(t1)

    def run(self, t_open: float, seconds: float, cap_s: float,
            on_boundary: Optional[Callable[[float], None]] = None) -> RunLog:
        t_close = t_open + seconds
        t_start = t_open - float(self.traffic.get("lead_s", 0.0))
        if self.traffic["kind"] == "open_loop":
            drained = self._open_loop(t_start, t_open, t_close, cap_s,
                                      on_boundary)
        else:
            drained = self._saturated(t_start, t_close, on_boundary)
        return RunLog(t_open, t_close, self.tracked, self.windows,
                      self.late, drained)

    def _open_loop(self, t_start, t_open, t_close, cap_s, on_boundary):
        gaps = np.array([r.gap_s for r in self.requests])
        due = t_start + np.cumsum(gaps)
        if due[-1] < t_close:
            raise ValueError("the traffic's schedule ends before the "
                             "window closes: raise its 'requests'")
        fe = self.srv.frontend
        n = len(due)
        while True:
            now = clock()
            while self._next < n and due[self._next] <= now:
                self._submit(float(due[self._next]))
            if now >= t_close:
                pending = [t for t in self.tracked.values()
                           if t_open <= t.due < t_close
                           and t.rid not in fe.done]
                if not pending:
                    return True
                if now >= t_close + cap_s:
                    return False
            if fe.idle:
                if self._next >= n:
                    return True
                time.sleep(max(0.0, float(due[self._next]) - clock()))
                continue
            self._window(on_boundary)

    def _saturated(self, t_start, t_close, on_boundary):
        backlog = int(self.traffic["backlog_per_slot"]) \
            * self.srv.serve.num_slots
        fe = self.srv.frontend
        while clock() < t_start:
            time.sleep(t_start - clock())
        while clock() < t_close:
            while len(fe.queue) < backlog:
                self._submit(clock())
            self._window(on_boundary)
        return True
