"""Table 6 / Figs. 6-7 reproduction: pre-saturation latency + throughput.

Offered-load sweep (Poisson arrivals, ShareGPT-like length distribution
scaled to smoke size) against both engines in ISOLATION. Reports P99 TTFT,
P99 TPOT (device-step-derived, converted with measured step time) and
completed-request throughput. The paper's claim: Blink has the lowest
pre-saturation latency envelope and the highest plateau.

The Blink run serves with the device telemetry plane on and extracts its
latency numbers from the Prometheus exporter over the drained counter
rows + per-request event records — the same path a scrape would read —
rather than peeking at raw ring stamps.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import bench_model, bench_serve_config, emit
from repro.core import engine as eng
from repro.core import ring_buffer as rb
from repro.core.host_engine import HostEngine
from repro.data.pipeline import make_prompts, sharegpt_like_trace
from repro.telemetry import export as tel_export
from repro.telemetry import host as host_tel
from repro.telemetry import state as tel_lib
from repro.telemetry.metrics import percentiles, request_records

N_REQ = 16
RATES = [2.0, 6.0, 16.0]    # requests per second of *simulated* time
SIM_STEP_S = 0.05           # one decode step of the reference H100 ~ tens of
                            # ms at tiny-model CPU speed; fixed for both


def trace_for(rate, api):
    trace = sharegpt_like_trace(N_REQ, rate, seed=42, mean_in=12.0,
                                mean_out=8.0, max_in=24, max_out=12)
    prompts = make_prompts(trace, api.cfg.vocab_size, seed=1)
    arrivals = [int(t.arrival_s / SIM_STEP_S) for t in trace]
    outs = [max(2, t.output_len) for t in trace]
    return prompts, outs, arrivals


def run_blink(api, params, serve, prompts, outs, arrivals):
    serve = dataclasses.replace(serve, telemetry=True)
    window_fn = eng.make_serve_window(api, serve)
    state = eng.init_engine_state(api, serve)
    state = window_fn(params, state)         # warm
    state = eng.init_engine_state(api, serve)
    pending = list(zip(range(N_REQ), prompts, outs, arrivals))
    t0 = time.perf_counter()
    completed = set()
    tel_rows, drained = [], 0
    while len(completed) < N_REQ:
        step_now = int(state.step)
        ring = state.ring
        for i, p, o, a in list(pending):
            if a <= step_now:
                ring = rb.submit_request(ring, i % serve.num_slots,
                                         tokens=list(p), request_id=i,
                                         max_new=o, arrival=a, step=step_now)
                pending.remove((i, p, o, a))
        state = dataclasses.replace(state, ring=ring)
        state = window_fn(params, state)
        # window-boundary drain, like BlinkServer: the per-step counter
        # ring is window-deep, so one read per window loses nothing
        rows = np.asarray(state.telemetry.rows)
        cur = int(state.step)
        for s in range(max(drained, cur - rows.shape[0]), cur):
            tel_rows.append(rows[s % rows.shape[0]].copy())
        drained = cur
        st = np.asarray(state.ring.slot_state)
        for s in np.where(st == rb.DECODE_COMPLETED)[0]:
            completed.add(int(s))
        if step_now > 20000:
            break
    wall = time.perf_counter() - t0
    steps = int(state.step)
    recs = request_records(state.ring, sorted(completed),
                           events=state.telemetry)
    return recs, np.stack(tel_rows), steps, wall


def _scrape(text: str) -> dict:
    """Parse sample lines of a Prometheus text exposition."""
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, val = line.rsplit(" ", 1)
        out[name] = float(val)
    return out


def run_host(api, params, serve, prompts, outs, arrivals, jitter=None):
    host = HostEngine(api, serve, params, jitter=jitter)
    # warm both step functions (compile excluded from timing)
    host.submit([5, 6, 7], max_new=2)
    host.run_until_idle()
    host.reset()
    pending = list(zip(range(N_REQ), prompts, outs, arrivals))
    ttft_steps, tpot_steps, done = [], [], 0
    submit_step = {}
    first_step = {}
    last_step = {}
    counts = {}
    t0 = time.perf_counter()
    while done < N_REQ and host.step_count < 20000:
        for i, p, o, a in list(pending):
            if a <= host.step_count:
                s = host.submit(list(p), max_new=o, arrival=a)
                if s >= 0:
                    submit_step[s] = host.step_count
                    first_step.pop(s, None)   # clear stale slot telemetry
                    last_step.pop(s, None)
                    counts.pop(s, None)
                    pending.remove((i, p, o, a))
        before = {s: host.generated[s] for s in submit_step}
        host.step()
        for s in list(submit_step):
            if host.generated[s] > before.get(s, 0):
                if s not in first_step:
                    first_step[s] = host.step_count
                last_step[s] = host.step_count
                counts[s] = int(host.generated[s])
            if host.slot_state[s] == rb.DECODE_COMPLETED:
                ttft_steps.append(first_step[s] - submit_step[s])
                if counts[s] > 1:
                    tpot_steps.append(
                        (last_step[s] - first_step[s]) / (counts[s] - 1))
                host.drain(s)
                del submit_step[s]
                done += 1
    wall = time.perf_counter() - t0
    return ttft_steps, tpot_steps, host.step_count, wall


def main() -> None:
    api, params = bench_model()
    serve = bench_serve_config()
    for rate in RATES:
        prompts, outs, arrivals = trace_for(rate, api)
        recs, rows, steps_b, wall_b = run_blink(api, params, serve, prompts,
                                                outs, arrivals)
        # latency = scheduler steps x that engine's MEASURED step time —
        # the step count captures queueing (identical policy); the step time
        # captures where the scheduler runs (the architectural difference).
        # The Blink numbers come off the exporter: render the drained
        # telemetry into the Prometheus text format and scrape it back.
        st_b = wall_b / max(steps_b, 1)
        # one uniform step time over the whole run, as for the host
        # baseline: a single window spanning every step
        clock = host_tel.Recorder(max_spans=1, max_windows=1)
        clock.window(0, max(steps_b, 1), 0.0, wall_b)
        scraped = _scrape(tel_export.prometheus_text(
            tel_lib.CounterTotals.of(rows), records=recs, recorder=clock))
        p99_ttft_b = scraped['blink_ttft_seconds{quantile="p99"}']
        p99_tpot_b = scraped['blink_tpot_seconds{quantile="p99"}']
        tok_b = scraped["blink_tokens_total"]
        assert tok_b == sum(r["n_tokens"] for r in recs), \
            "counter rows disagree with per-request token counts"
        h_ttft, h_tpot, steps_h, wall_h = run_host(
            api, params, serve, prompts, outs, arrivals)
        st_h = wall_h / max(steps_h, 1)
        ttft_h = percentiles([t * st_h for t in h_ttft])
        tpot_h = percentiles([t * st_h for t in h_tpot])
        emit(f"table6_rate{rate:g}_blink", st_b * 1e6,
             f"p99_ttft_ms={p99_ttft_b*1e3:.1f};"
             f"p99_tpot_ms={p99_tpot_b*1e3:.2f};"
             f"tput_tok_s={tok_b/wall_b:.1f}")
        emit(f"table6_rate{rate:g}_hostbase", st_h * 1e6,
             f"p99_ttft_ms={ttft_h['p99']*1e3:.1f};"
             f"p99_tpot_ms={tpot_h['p99']*1e3:.2f};"
             f"tput_tok_s={sum(outs)/wall_h:.1f}")


if __name__ == "__main__":
    main()
