"""Host span recorder: the host half of the telemetry plane.

The device plane (``telemetry/state.py``) counts what the window program
does per step; this module times what the host does between windows, and
maps device step stamps onto the host clock.

* **Spans.** ``Recorder.span(name, **attrs)`` records ``name``, start and
  end on ``time.perf_counter()`` (the clock of ``Request.*_wall``), its
  attributes, the span that encloses it, and how many programs were
  compiled while it was the innermost open span. Each span also enters
  ``jax.profiler.TraceAnnotation(name)`` while a profiler is running, so
  it lands on the host plane of the same trace as the device ops; with no
  profiler that is one flag check. Nothing here runs inside a jitted
  program.
* **Step→wall map.** ``Recorder.window`` records, per window, its first
  step, its step count and the host times at launch and when the result
  was ready, plus the drained counter rows. A step stamp ``k`` of that
  window ends at about ``t_launch + (k - first_step + 1) / steps ×
  (t_ready - t_launch)``: uniform inside one window only, so the error is
  bounded by one window's step spread.

Both logs are ``deque``s of fixed length, so a long-running server keeps
constant memory. One recorder serves the process (``recorder()``), the
way a logger does; a caller that wants its own passes one to
``BlinkServer``. Servers that share a recorder tell their entries apart
by an owner token (``new_owner()``): each window record carries it, and
so does each ``blink.run_window`` span, the outermost span of a
boundary.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional

import jax
import jax.monitoring
import numpy as np

#: The compile event counted against the innermost open span: one per
#: program lowered, whether the compile cache then holds it or not.
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

TraceAnnotation = jax.profiler.TraceAnnotation
clock = time.perf_counter

_open = threading.local()          # per thread: the stack of open spans
_listening = False
_owners = itertools.count()


def new_owner() -> int:
    """A fresh owner token: a server takes one at start and at each
    reset, so its step→wall lookups see only its own windows."""
    return next(_owners)


def _open_spans() -> list:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


def _on_duration_event(name: str, _secs: float, **_kw) -> None:
    if name == COMPILE_EVENT:
        stack = _open_spans()
        if stack:
            stack[-1].compiles += 1


def _listen_for_compiles() -> None:
    """Register the compile listener once per process."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _listening = True


class Span:
    """One timed host interval. Used as a context manager; the body may
    add to ``attrs``."""
    __slots__ = ("name", "t0", "t1", "attrs", "compiles", "parent", "_log",
                 "_ann")

    def __init__(self, log: deque, name: str, attrs: dict):
        self.name, self.attrs, self._log = name, attrs, log
        self.t0 = self.t1 = None
        self.compiles = 0
        self.parent: Optional[Span] = None
        self._ann = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def root(self) -> "Span":
        """The outermost span enclosing this one (itself if none)."""
        sp = self
        while sp.parent is not None:
            sp = sp.parent
        return sp

    def __enter__(self) -> "Span":
        stack = _open_spans()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self._log.append(self)
        self.t0 = clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = clock()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        _open_spans().pop()

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, t0={self.t0}, t1={self.t1}, "
                f"attrs={self.attrs}, compiles={self.compiles})")


@dataclass
class WindowRecord:
    """One window launch: its steps and when they ran on the host clock."""
    index: int
    first_step: int
    steps: int
    t_launch: float
    t_ready: float
    rows: Optional[np.ndarray] = None    # [steps, N_COUNTERS], by step
    owner: Optional[int] = None          # the server's token (new_owner)

    def holds(self, step: int) -> bool:
        return self.first_step <= step < self.first_step + self.steps

    def step_seconds(self) -> float:
        return (self.t_ready - self.t_launch) / self.steps

    def step_end(self, step: int) -> float:
        return self.t_launch + (step - self.first_step + 1) \
            * self.step_seconds()


class Recorder:
    """Bounded logs of host spans and window launches."""

    def __init__(self, max_spans: int = 1 << 16, max_windows: int = 1 << 13):
        _listen_for_compiles()
        self.spans: deque = deque(maxlen=max_spans)
        self.windows: deque = deque(maxlen=max_windows)
        self._n_windows = 0

    def span(self, name: str, **attrs) -> Span:
        return Span(self.spans, name, attrs)

    def window(self, first_step: int, steps: int, t_launch: float,
               t_ready: float, owner: Optional[int] = None) -> WindowRecord:
        rec = WindowRecord(self._n_windows, first_step, steps, t_launch,
                           t_ready, owner=owner)
        self._n_windows += 1
        self.windows.append(rec)
        return rec

    # -- the step→wall map -------------------------------------------------
    def window_of(self, step: int,
                  owner: Optional[int] = None) -> Optional[WindowRecord]:
        """The newest recorded window of ``owner`` (of anyone, if None)
        that ran ``step``: a replay after a restore supersedes the run it
        replaced."""
        for w in reversed(self.windows):
            if w.holds(step) and (owner is None or w.owner == owner):
                return w
        return None

    def step_end(self, step: int,
                 owner: Optional[int] = None) -> Optional[float]:
        """Host time at which step ``step`` ended, or None if no recorded
        window of ``owner`` ran it."""
        w = self.window_of(step, owner)
        return None if w is None else w.step_end(step)

    def step_seconds(self, step: int,
                     owner: Optional[int] = None) -> Optional[float]:
        """Duration of one step of the window that ran ``step``."""
        w = self.window_of(step, owner)
        return None if w is None else w.step_seconds()

    # -- reading -------------------------------------------------------------
    def closed(self, name: Optional[str] = None,
               lo: float = float("-inf"),
               hi: float = float("inf")) -> List[Span]:
        """Closed spans (of ``name``, if given) that started in
        ``[lo, hi]``."""
        return [s for s in self.spans
                if s.t1 is not None and lo <= s.t0 <= hi
                and (name is None or s.name == name)]

    def owned(self, owner: int) -> List[Span]:
        """Closed spans recorded under ``owner``'s boundaries: those whose
        outermost span carries ``owner``."""
        return [s for s in self.closed()
                if s.root().attrs.get("owner") == owner]


def summarize(spans: Iterable[Span]) -> Dict[str, dict]:
    """Per span name: count, mean and p90 seconds, and compiles."""
    by: Dict[str, list] = {}
    compiles: Dict[str, int] = {}
    for s in spans:
        if s.t1 is None:
            continue
        by.setdefault(s.name, []).append(s.seconds)
        compiles[s.name] = compiles.get(s.name, 0) + s.compiles
    return {n: {"count": len(xs), "mean_s": float(np.mean(xs)),
                "p90_s": float(np.percentile(xs, 90)),
                "compiles": compiles[n]}
            for n, xs in by.items()}


def summary_lines(spans: Iterable[Span]) -> List[str]:
    """One line per span name, for a final report."""
    return [f"{n:<18} count={v['count']:>5} mean={v['mean_s'] * 1e3:.3f}ms "
            f"p90={v['p90_s'] * 1e3:.3f}ms compiles={v['compiles']}"
            for n, v in sorted(summarize(spans).items())]


_RECORDER: Optional[Recorder] = None


def recorder() -> Recorder:
    """The process-wide recorder, made on first use."""
    global _RECORDER
    if _RECORDER is None:
        _RECORDER = Recorder()
    return _RECORDER
