"""Telemetry plane: CPU-free invariants, metric extraction, exporters.

The structural claims (the ones that make this telemetry "CPU-free"):

  * instrumenting the engine step adds ZERO host callbacks — no
    ``io_callback`` / ``debug_callback`` primitives anywhere in the traced
    computation, telemetry on or off;
  * it adds ZERO kernel dispatches — the ``pallas_call`` count of the
    traced step is identical with telemetry on and off (counters are pure
    jnp arithmetic fused into the window executable);
  * restoring a crash-recovery snapshot rewinds the drained telemetry
    with the engine, so a killed-and-restored serve emits the same
    counter rows and event timelines as an unkilled run.

Plus the host-side layers: ``metrics.request_records`` /
``metrics.from_ring`` covering non-completed terminals and excluding
preempt stalls from ITL, and the Prometheus / Perfetto exporters.

Device-vs-host telemetry stream differentials live with the scheduler
differentials in ``tests/test_scheduler_diff.py``.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax

from repro.configs.base import ServeConfig
from repro.configs.registry import TINY_ARCHS
from repro.core import engine as eng
from repro.core import ring_buffer as rb
from repro.jaxpr_inspect import count_primitives
from repro.models.api import make_model
from repro.telemetry import export as tel_export
from repro.telemetry import host as tel_host
from repro.telemetry import state as tel_state
from repro.telemetry.metrics import from_ring, request_records

SERVE = ServeConfig(num_slots=8, max_prompt_len=24, max_new_tokens=8,
                    decode_batch=4, window=1, admit_per_step=2,
                    page_size=4, num_pages=28, eos_token=-1,
                    prefill_chunk_tokens=8, max_prefills_per_step=1)

_CALLBACK_PRIMS = ("io_callback", "debug_callback", "pure_callback")


# --- structural invariants: zero callbacks, zero extra dispatches ------------


def _prim_counts(serve: ServeConfig, backend: str) -> dict:
    api = make_model(TINY_ARCHS["qwen2-1.5b"], attn_backend=backend,
                     prefill_block_q=serve.prefill_block_q,
                     prefill_block_k=serve.prefill_block_k)
    params = api.init_params(jax.random.PRNGKey(0))
    step_fn = eng.make_engine_step(api, serve)
    state = eng.init_engine_state(api, serve, seed=0)
    return count_primitives(lambda p, s: step_fn(p, s), params, state,
                            names=("pallas_call",) + _CALLBACK_PRIMS)


def test_telemetry_adds_no_callbacks_and_no_dispatches():
    """Trace the mixed engine step (pallas backend, so kernel dispatches
    are countable) with the event log off and on (the counter rows are
    in both): the instrumented step must carry exactly the same number of
    ``pallas_call`` sites and zero host callback primitives — the
    telemetry plane is fused arithmetic, not a readback."""
    prev = os.environ.get("REPRO_ATTN_BACKEND")
    os.environ["REPRO_ATTN_BACKEND"] = "pallas"   # outranks CI matrix env
    try:
        off = _prim_counts(SERVE, "pallas")
        on = _prim_counts(dataclasses.replace(SERVE, telemetry=True),
                          "pallas")
    finally:
        if prev is None:
            os.environ.pop("REPRO_ATTN_BACKEND", None)
        else:
            os.environ["REPRO_ATTN_BACKEND"] = prev
    assert off["pallas_call"] > 0          # the count is not vacuous
    assert on["pallas_call"] == off["pallas_call"], (on, off)
    for prim in _CALLBACK_PRIMS:
        assert on[prim] == 0 and off[prim] == 0, (prim, on, off)


@pytest.mark.parametrize("chunk", [8, 0])
def test_telemetry_no_callbacks_ambient_backend(chunk):
    """Same zero-callback claim on whatever backend the CI matrix leg
    selected, for BOTH step flavors (mixed and phase-exclusive)."""
    serve = dataclasses.replace(SERVE, prefill_chunk_tokens=chunk,
                                telemetry=True)
    api = make_model(TINY_ARCHS["qwen2-1.5b"])
    params = api.init_params(jax.random.PRNGKey(0))
    step_fn = eng.make_engine_step(api, serve)
    state = eng.init_engine_state(api, serve, seed=0)
    counts = count_primitives(lambda p, s: step_fn(p, s), params, state,
                              names=_CALLBACK_PRIMS)
    assert all(v == 0 for v in counts.values()), counts


# --- metrics: terminal coverage + preempt-stall exclusion --------------------


class _FakeRing:
    """Minimal stand-in carrying the stamp arrays request_records reads."""

    def __init__(self, n_slots, max_new):
        self.token_step = np.full((n_slots, max_new), -1, np.int32)
        self.submit_step = np.zeros(n_slots, np.int32)
        self.generated = np.zeros(n_slots, np.int32)
        self.request_id = np.arange(n_slots, dtype=np.int32)
        self.slot_state = np.full(n_slots, rb.EMPTY, np.int32)


def _fake_events(n_slots, per_slot):
    E = 8
    code = np.zeros((n_slots, E), np.int32)
    step = np.full((n_slots, E), -1, np.int32)
    count = np.zeros(n_slots, np.int32)
    for s, evs in per_slot.items():
        for j, (c, st) in enumerate(evs):
            code[s, j], step[s, j] = c, st
        count[s] = len(evs)
    return code, step, count


def test_request_records_cover_non_completed_terminals():
    """CANCELLED and FAULTED slots with partial output get records tagged
    with their terminal state (they used to be silently skipped), and a
    zero-output FAULTED slot still appears — with no latency fields."""
    ring = _FakeRing(4, 8)
    ring.slot_state[:] = [rb.DECODE_COMPLETED, rb.CANCELLED, rb.FAULTED,
                          rb.DECODE_PROCESSING]
    ring.generated[:3] = [3, 2, 0]
    ring.submit_step[:] = [1, 1, 2, 0]
    ring.token_step[0, :3] = [4, 5, 6]
    ring.token_step[1, :2] = [3, 4]
    recs = {r["terminal"]: r for r in request_records(ring)}
    assert set(recs) == {"DECODE_COMPLETED", "CANCELLED", "FAULTED"}
    assert recs["CANCELLED"]["n_tokens"] == 2
    assert recs["CANCELLED"]["ttft_steps"] == 2      # partial output counts
    assert recs["FAULTED"]["ttft_steps"] is None
    m = from_ring(ring)
    assert sorted(m.ttft_steps) == [2, 3]            # cancelled included


def test_itl_excludes_preempt_restore_gap():
    """A token gap spanning a preempted->resumed episode is charged only
    its decode steps: the stall (visible separately as events/counters)
    is subtracted from ITL and TPOT."""
    ring = _FakeRing(2, 8)
    ring.slot_state[:] = rb.DECODE_COMPLETED
    ring.generated[:] = 3
    ring.submit_step[:] = 0
    ring.token_step[0, :3] = [2, 3, 10]      # preempted at 4, resumed at 9
    ring.token_step[1, :3] = [2, 3, 4]       # untouched control
    events = _fake_events(2, {0: [(tel_state.EV_PREEMPTED, 4),
                                  (tel_state.EV_RESUMED, 9)]})
    recs = {r["slot"]: r for r in request_records(ring, events=events)}
    assert recs[0]["itl_steps"] == [1, 2]    # 7-step gap minus 5-step stall
    assert recs[0]["tpot_steps"] == 1.5
    assert recs[1]["itl_steps"] == [1, 1]
    # without the event log the stall is (conservatively) charged
    raw = {r["slot"]: r for r in request_records(ring)}
    assert raw[0]["itl_steps"] == [1, 7]


# --- exporters ---------------------------------------------------------------


def _sample_record():
    return {"slot": 2, "request_id": 7, "terminal": "completed",
            "n_tokens": 3, "submit_step": 0,
            "events": [("submitted", 0), ("admitted", 1),
                       ("first_token", 3), ("preempted", 4),
                       ("resumed", 6), ("completed", 8)],
            "ttft_steps": 3, "tpot_steps": 1.5, "itl_steps": [1, 2]}


def _uniform_map(step_s=0.01, steps=16):
    """A recorder whose one window runs steps 0.. at ``step_s`` each, from
    host time 0: step k ends at (k + 1) * step_s."""
    rec = tel_host.Recorder()
    rec.window(0, steps, 0.0, steps * step_s)
    return rec


def test_prometheus_text_exposition():
    rows = np.zeros((3, tel_state.N_COUNTERS), np.int64)
    rows[:, tel_state.COL["step"]] = [0, 1, 2]
    rows[:, tel_state.COL["tokens"]] = [2, 3, 4]
    rows[:, tel_state.COL["free_pages"]] = [10, 9, 8]
    rows[:, tel_state.COL["decode_lanes"]] = [1, 2, 2]
    text = tel_export.prometheus_text(tel_state.CounterTotals.of(rows),
                                      records=[_sample_record()],
                                      recorder=_uniform_map(0.01))
    assert "blink_steps_total 3" in text
    assert "blink_tokens_total 9" in text                # summed counter
    assert "blink_free_pages 8" in text                  # last-row gauge
    assert 'blink_ttft_seconds{quantile="p50"} 0.03' in text
    assert 'blink_tpot_seconds{quantile="p50"} 0.015' in text
    # exposition-format hygiene: every sample line parses as "name value",
    # and every metric is preceded by its HELP and TYPE lines
    seen_meta = set()
    for line in text.splitlines():
        if line.startswith("# HELP") or line.startswith("# TYPE"):
            seen_meta.add(line.split()[2])
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name.split("{")[0] in seen_meta, line


def test_perfetto_trace_spans():
    tr = tel_export.perfetto_trace([_sample_record()], _uniform_map(0.01))
    json.dumps(tr)                                       # serializable
    spans = {e["name"]: e for e in tr["traceEvents"] if e["ph"] == "X"}
    assert set(spans) == {"queued", "prefill", "decode"}
    us = 0.01 * 1e6
    # a stamp sits at the end of its step: step k at (k + 1) steps
    assert spans["queued"]["ts"] == pytest.approx(1 * us)
    assert spans["queued"]["dur"] == pytest.approx(1 * us)
    assert spans["prefill"]["ts"] == pytest.approx(2 * us)
    assert spans["prefill"]["dur"] == pytest.approx(2 * us)
    assert spans["decode"]["ts"] == pytest.approx(4 * us)
    assert spans["decode"]["dur"] == pytest.approx(5 * us)
    instants = {e["name"] for e in tr["traceEvents"] if e["ph"] == "i"}
    assert {"preempted", "resumed"} <= instants
    assert all(e["tid"] == 2 for e in tr["traceEvents"]
               if e["ph"] in "Xi")
    # the map is per window: a slower second window stretches only the
    # stamps inside it
    rec = _uniform_map(0.01, steps=4)
    rec.window(4, 8, 0.1, 0.1 + 8 * 0.05)      # steps 4-11, 50 ms each
    spans = {e["name"]: e for e in tel_export.perfetto_trace(
        [_sample_record()], rec)["traceEvents"] if e["ph"] == "X"}
    assert spans["prefill"]["dur"] == pytest.approx(2 * us)      # steps 1-3
    assert spans["decode"]["ts"] == pytest.approx(4 * us)
    # first token at the end of step 3 (0.04 s), completion at the end of
    # step 8 (0.1 s + 5 steps of 50 ms): the boundary wait is in between
    assert spans["decode"]["dur"] == pytest.approx((0.35 - 0.04) * 1e6)


def test_span_summaries_lines():
    (line,) = tel_export.span_summaries([_sample_record()])
    assert "req   7" in line and "completed" in line
    assert "queued=1" in line and "prefill=2" in line and "decode=5" in line


# --- snapshot/restore: telemetry rewinds with the engine ---------------------


def test_restore_replays_identical_telemetry():
    """Kill-and-restore with telemetry on: the restored run's drained
    counter rows and event timelines are identical to the unkilled run's
    (the telemetry state rides the engine snapshot; the server-side drain
    accumulators rewind with it)."""
    from repro.frontend.server import BlinkServer

    api = make_model(TINY_ARCHS["qwen2-1.5b"])
    params = api.init_params(jax.random.PRNGKey(0))
    serve = dataclasses.replace(SERVE, num_pages=48, window=2,
                                snapshot_every_steps=2, telemetry=True)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(3, 512, int(rng.integers(4, 20))).tolist()
               for _ in range(4)]

    def run(kill_at):
        rec = tel_host.Recorder()
        srv = BlinkServer(api, serve, params, recorder=rec)
        ids = [srv.submit(p, max_new=6) for p in prompts]
        if kill_at:
            for _ in range(kill_at):
                srv.run_window()
            srv.restore_snapshot()
        srv.run_until_idle(max_windows=200)
        outs = {r: tuple(srv.frontend.done[r].output) for r in ids}
        # the rows each step ran with, a replayed window superseding the
        # run it replaced
        by_step = {w.first_step: w.rows for w in rec.windows}
        rows = np.concatenate([by_step[k] for k in sorted(by_step)])
        totals = srv.counter_totals
        return (outs, rows, (totals.steps, totals.sums.tolist(),
                             totals.last.tolist()),
                {r: srv._request_events.get(r, []) for r in ids})

    ref_outs, ref_rows, ref_totals, ref_events = run(kill_at=0)
    got_outs, got_rows, got_totals, got_events = run(kill_at=3)
    assert ref_outs == got_outs
    assert ref_rows.shape == got_rows.shape
    assert (ref_rows == got_rows).all()
    assert ref_totals == got_totals
    assert ref_totals[0] == len(ref_rows)
    assert ref_events == got_events
