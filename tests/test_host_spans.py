"""Host spans, the per-window step→wall map and the always-on counter
rows (``repro.telemetry.host``), on a tiny CPU server.

  * the recorder's logs stay within their bounds;
  * one ``run_window`` records its ``blink.*`` spans nested as documented,
    with their attributes, and ``window_wall`` is the launch plus the
    wait;
  * each span enters a profiler ``TraceAnnotation`` while a profiler runs,
    and lands on the host plane of a real trace;
  * a compile is charged to the innermost open span: a new prompt
    length's ring write to its ``blink.ring_write``;
  * the step→wall map puts every first token inside the window that
    emitted it, between its ring write and its poll;
  * ``ring.prefill_step`` equals ``token_step[:, 0]`` and so
    ``Request.first_token_step``, on the split and the unified path;
  * with the event log off, each window's counter rows count exactly the
    tokens the reader delivered at the boundary after it.
  * servers that share a recorder map steps through their own windows,
    and each one's trace holds only its own spans.
"""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.configs.registry import TINY_ARCHS
from repro.frontend.server import BlinkServer
from repro.models.api import make_model
from repro.telemetry import host as tel_host
from repro.telemetry import state as tel_state

SERVE = ServeConfig(num_slots=8, max_prompt_len=32, max_new_tokens=8,
                    decode_batch=4, window=4, admit_per_step=2,
                    page_size=4, num_pages=64, eos_token=-1,
                    prefill_chunk_tokens=8, max_prefills_per_step=2,
                    prefill_block_q=8, prefill_block_k=8)
PATHS = {"split": False, "unified": True}
BOUNDARY = ("blink.flush", "blink.select", "blink.launch", "blink.wait",
            "blink.drain", "blink.poll")


@pytest.fixture(autouse=True)
def _gather_backend(monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)


def _server(unified: bool, recorder, **serve_kw):
    serve = dataclasses.replace(SERVE, attn_unified=unified, **serve_kw)
    api = make_model(TINY_ARCHS["qwen2-1.5b"], attn_unified=unified,
                     prefill_block_q=8, prefill_block_k=8)
    params = api.init_params(jax.random.PRNGKey(0))
    return BlinkServer(api, serve, params, recorder=recorder)


def _prompts(n, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 512, int(rng.integers(3, 30))).tolist()
            for _ in range(n)]


@pytest.fixture(scope="module", params=sorted(PATHS))
def served(request):
    """A short staggered run per path. After each boundary it keeps, for
    every slot that has emitted a token, ``(prefill_step,
    token_step[:, 0])``, and for every first token the poll delivered,
    ``(the step the poll recorded, Request.first_token_step,
    token_step[slot, 0])``."""
    rec = tel_host.Recorder()
    srv = _server(PATHS[request.param], rec)
    prompts = _prompts(7)
    slots, firsts = [], []
    for i in range(40):
        if i < len(prompts):
            srv.submit(prompts[i], max_new=int(2 + i % 5))
        elif srv.frontend.idle:
            break
        srv.run_window()
        ring = srv.state.ring
        emitted = np.asarray(ring.generated) > 0
        pf = np.asarray(ring.prefill_step)
        t0 = np.asarray(ring.token_step)[:, 0]
        slots.extend(zip(pf[emitted].tolist(), t0[emitted].tolist()))
        poll = rec.closed("blink.poll")[-1]
        fe = srv.frontend
        live = {r.request_id: r for r in fe.in_flight.values()}
        for rid, _ring_wall, step, _wall in poll.attrs["first_tokens"]:
            req = fe.done.get(rid) or live[rid]
            firsts.append((step, req.first_token_step, int(t0[req.slot])))
    assert srv.frontend.idle and len(srv.frontend.done) == len(prompts)
    return srv, rec, slots, firsts


def test_recorder_stays_within_its_bound():
    rec = tel_host.Recorder(max_spans=8, max_windows=4)
    for i in range(20):
        with rec.span("blink.x", i=i):
            pass
    for i in range(10):
        rec.window(4 * i, 4, float(i), i + 0.5)
    assert len(rec.spans) == 8 and len(rec.windows) == 4
    assert [s.attrs["i"] for s in rec.spans] == list(range(12, 20))
    assert [w.index for w in rec.windows] == [6, 7, 8, 9]
    # steps of windows that fell out of the log map to nothing
    assert rec.step_end(0) is None and rec.step_end(39) == pytest.approx(9.5)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_spans_nest_and_carry_attributes(path):
    rec = tel_host.Recorder()
    srv = _server(PATHS[path], rec)
    for p in _prompts(3):
        srv.submit(p, max_new=3)
    srv.run_window()
    (top,) = rec.closed("blink.run_window")
    assert top.parent is None and top.attrs["window"] == 0
    kids = [s for s in rec.closed() if s.parent is top]
    assert [s.name for s in kids] == list(BOUNDARY)
    for s in rec.closed():
        assert top.t0 <= s.t0 <= s.t1 <= top.t1, s
    (flush,) = rec.closed("blink.flush")
    writes = rec.closed("blink.ring_write")
    assert flush.attrs["written"] == len(writes) == 3
    assert all(w.parent is flush for w in writes)
    assert sorted(w.attrs["prompt_len"] for w in writes) == \
        sorted(len(p) for p in _prompts(3))
    (poll,) = rec.closed("blink.poll")
    assert {s.name for s in rec.closed() if s.parent is poll} == \
        {"blink.poll_read", "blink.release"}
    assert set(poll.attrs) == {"tokens", "completed", "first_tokens"}
    (launch,) = rec.closed("blink.launch")
    (wait,) = rec.closed("blink.wait")
    assert srv.window_wall == [wait.t1 - launch.t0]
    (win,) = rec.windows
    assert (win.first_step, win.steps) == (0, SERVE.window)
    assert (win.t_launch, win.t_ready) == (launch.t0, wait.t1)
    assert win.rows.shape == (SERVE.window, tel_state.N_COUNTERS)
    assert list(win.rows[:, tel_state.COL["step"]]) == [0, 1, 2, 3]
    srv.run_window()                   # the next window's rows, in order
    assert list(rec.windows[1].rows[:, tel_state.COL["step"]]) == [4, 5, 6, 7]
    assert tel_state.stacks_rows(srv.serve) == (path == "split")


class _FakeAnnotation:
    enabled = True
    log = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_each_span_enters_a_trace_annotation(monkeypatch):
    monkeypatch.setattr(tel_host, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    rec = tel_host.Recorder()
    with rec.span("blink.a"):
        with rec.span("blink.b"):
            pass
    assert _FakeAnnotation.log == [("enter", "blink.a"), ("enter", "blink.b"),
                                   ("exit", "blink.b"), ("exit", "blink.a")]
    # no profiler running: a flag check, no annotation
    monkeypatch.setattr(_FakeAnnotation, "enabled", False)
    with rec.span("blink.c"):
        pass
    assert len(_FakeAnnotation.log) == 4
    assert [s.name for s in rec.closed()] == ["blink.a", "blink.b",
                                              "blink.c"]


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    rec = tel_host.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("blink.profiled"):
            jax.block_until_ready(jax.numpy.ones(8) + 1)
    finally:
        jax.profiler.stop_trace()
    with rec.span("blink.after"):
        pass
    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    assert paths
    names = {e.name
             for plane in jax.profiler.ProfileData.from_file(paths[0]).planes
             for line in plane.lines for e in line.events}
    assert "blink.profiled" in names and "blink.after" not in names


def test_a_new_prompt_lengths_ring_write_compile_is_attributed():
    rec = tel_host.Recorder()
    srv = _server(False, rec)
    srv.submit([5] * 11, max_new=2)
    srv.run_window()
    jax.clear_caches()
    for n in (13, 13, 14):
        srv.submit([5] * n, max_new=2)
    srv.run_window()
    first, again, new = [s for s in rec.closed("blink.ring_write")][1:]
    assert first.compiles > 0 and new.compiles > 0
    assert again.compiles == 0
    (_, flush) = rec.closed("blink.flush")
    assert flush.compiles == 0          # charged to the innermost span


def test_step_wall_map_puts_every_first_token_inside_its_window(served):
    srv, rec, _, _ = served
    reqs = list(srv.frontend.done.values())
    for req in reqs:
        w = rec.window_of(req.first_token_step)
        assert w is not None
        end = rec.step_end(req.first_token_step)
        assert w.t_launch < end <= w.t_ready + 1e-9
        assert req.ring_wall <= w.t_launch
        assert w.t_ready <= req.first_token_wall
    # windows tile the steps: consecutive, non-overlapping in time
    ws = list(rec.windows)
    for a, b in zip(ws, ws[1:]):
        assert b.first_step == a.first_step + a.steps
        assert a.t_ready < b.t_launch


def test_prefill_step_equals_first_token_step(served):
    srv, _, slots, firsts = served
    assert slots and all(pf == t0 for pf, t0 in slots)
    assert len(firsts) == len(srv.frontend.done)
    assert all(a == b == c for a, b, c in firsts), firsts


def test_counter_rows_count_the_tokens_the_reader_delivered(served):
    srv, rec, _, _ = served
    assert not srv.serve.telemetry and srv.state.telemetry.ev_code is None
    windows = {w.index: w for w in rec.windows}
    polls = rec.closed("blink.poll")
    assert len(polls) == len(windows)
    col = tel_state.COL["tokens"]
    for poll in polls:
        w = windows[poll.parent.attrs["window"]]
        assert int(w.rows[:, col].sum()) == poll.attrs["tokens"]
    delivered = sum(len(r.output) for r in srv.frontend.done.values())
    assert srv.counter_totals.sums[col] == delivered > 0
    assert srv.counter_totals.steps == sum(w.steps for w in rec.windows)


def test_servers_sharing_a_recorder_map_steps_through_their_own_windows():
    rec = tel_host.Recorder()
    a, b = _server(False, rec), _server(False, rec)
    for srv in (a, b):
        srv.submit(_prompts(1)[0], max_new=2)
    a.run_window()
    b.run_window()                     # both windows ran steps 0..3
    wa, wb = rec.windows
    assert (wa.owner, wb.owner) == (a.owner, b.owner) and a.owner != b.owner
    assert rec.step_end(1, a.owner) == wa.step_end(1) < wb.t_launch
    assert rec.step_end(1, b.owner) == wb.step_end(1)
    assert rec.step_end(1) == wb.step_end(1)       # anyone's: the newest
    for srv, other in ((a, b), (b, a)):
        mine = rec.owned(srv.owner)
        assert mine and not set(map(id, mine)) & set(
            map(id, rec.owned(other.owner)))
        host = [e for e in srv.trace_json()["traceEvents"]
                if e.get("cat") == "host"]
        assert len(host) == len(mine)
    # a reset restarts the steps under a new token: the old run's
    # windows no longer answer for them
    old = a.owner
    a.reset()
    assert a.owner not in (old, b.owner)
    assert rec.step_end(1, a.owner) is None
    assert rec.step_end(1, old) == wa.step_end(1)
