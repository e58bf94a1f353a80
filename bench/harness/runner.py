"""One run of one cell: set-up, the measured window, the check, and the
result line.

Set-up (``setup_s``) runs from the start of the process to the start of
traffic: the TPU runtime, the compile cache, the weights, and a warm-up
that compiles or loads every program the window will run (the window
program, and the ring write for every prompt length the traffic holds) and
serves two requests to the end through the same server. Then traffic
starts, ``lead_s`` before the window opens, so the window sees a loaded
engine.

With ``trace`` on, a stretch of about ``TRACE_S`` seconds in the middle of
the window is traced with the JAX profiler and the per-layer metrics are
read from it; otherwise the end-to-end metrics are reported.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
from dataclasses import dataclass
from typing import Optional

import numpy as np

from . import check as check_lib
from . import peaks as peaks_lib
from . import spec as spec_lib
from . import trace as trace_lib
from . import traffic as traffic_lib
from . import weights as weights_lib
from . import work as work_lib
from .client import Client, clock

TRACE_S = 3.0           # seconds of the window that a traced run traces
DRAIN_CAP_S = 120.0     # how long past the close a due request may take
TRACE_DIR = spec_lib.ROOT / ".bench_trace"
CACHE_DIR = spec_lib.ROOT / ".jax_cache"


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def process_age_s() -> float:
    """Seconds since this process started, from /proc."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def note(msg: str) -> None:
    print(msg, flush=True)


def percentile(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


@dataclass
class RunView:
    """What a per-layer metric reader sees."""
    windows: list
    serve: object
    trace: Optional[trace_lib.Reduced]
    work: work_lib.Work
    peaks: dict
    code: object


class Tracer:
    """Traces the windows that start within ``[lo, lo + TRACE_S)``."""

    def __init__(self, client, lo: float):
        self.client, self.lo = client, lo
        self.t_start = None
        self.first = self.last = None
        self.before = self.after = None
        self.annotation = None

    def on_boundary(self, t: float) -> None:
        import jax
        d = self.client
        if self.t_start is None and t >= self.lo:
            self.before = work_lib.progress(d.tracked, d.srv)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            # no Python tracer: it would record every call of the host's
            # boundary work and slow it down
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=_profile_options())
            self.annotation = jax.profiler.TraceAnnotation(
                trace_lib.WINDOW_SPAN)
            self.annotation.__enter__()
            self.t_start, self.first = clock(), len(d.windows)
        elif self.last is None and self.t_start is not None \
                and t >= self.t_start + TRACE_S:
            self.annotation.__exit__(None, None, None)
            self.last = len(d.windows)
            jax.profiler.stop_trace()
            self.after = work_lib.progress(d.tracked, d.srv)

    def close(self) -> None:
        if self.t_start is not None and self.last is None:
            self.on_boundary(math.inf)


def _annotate(obj, method: str, span: str) -> None:
    import jax
    fn = getattr(obj, method)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(span):
            return fn(*a, **k)
    setattr(obj, method, wrapped)


def _compile_counter():
    import jax.monitoring
    count = [0]

    def listen(name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            count[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return count


def _device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def warm_up(srv, traffic: dict, chunk: int) -> dict:
    """Compile or load, before the window, every program it will run.
    Returns the seconds each part took."""
    import jax
    from repro.core import ring_buffer as rb
    took = {}
    t = clock()
    ring = srv.state.ring
    for n in traffic_lib.distinct_prompt_lengths(traffic):
        # a list of ints, as the client hands prompts in
        ring2 = rb.submit_request(ring, 0,
                                  tokens=[traffic_lib.FIRST_TOKEN] * n,
                                  request_id=0, max_new=1, arrival=0)
    jax.block_until_ready(ring2.input_arena)
    del ring2
    took["ring_writes_s"] = clock() - t
    for n in (chunk // 2, chunk + 1):
        srv.submit([traffic_lib.FIRST_TOKEN] * n, max_new=2)
    t = clock()
    srv.run_window()
    took["first_window_s"] = clock() - t
    t = clock()
    srv.run_until_idle(max_windows=100)
    if not srv.frontend.idle:
        raise RuntimeError("the warm-up requests did not finish")
    took["warm_windows_s"] = clock() - t
    return took


def backlog_growth(log) -> float:
    """Least-squares slope, in requests per second, of the requests queued
    or in flight at the window boundaries inside the measured window: near
    0 when the engine keeps up, positive when the queue grows."""
    pts = [(w.end, w.in_system) for w in log.windows
           if log.t_open <= w.end < log.t_close]
    if len(pts) < 3:
        return float("nan")
    t, n = np.asarray(pts, np.float64).T
    return float(np.polyfit(t - t[0], n, 1)[0])


def _judge(log, fe, requests, tr: dict, vocab: int):
    """The requests the cell judges, how many of them failed, and the
    finished ones as (prompt, served tokens) for the check. An open loop
    judges every request due in the window; a saturated cell every
    request finished by the close, lead-in included, since its requests
    outlive a short window. A judged request fails unless it completed
    with exactly its ``max_new`` tokens, each in the vocabulary."""
    if tr["kind"] == "open_loop":
        judged = [t for t in log.tracked.values()
                  if log.t_open <= t.due < log.t_close]
    else:
        judged = [t for t in log.tracked.values()
                  if t.rid in fe.done
                  and fe.done[t.rid].finish_wall < log.t_close]
    failed, served = 0, []
    for t in judged:
        r = fe.done.get(t.rid)
        if (r is not None and r.status == "completed"
                and len(r.output) == t.max_new
                and all(0 <= x < vocab for x in r.output)):
            served.append(check_lib.Served(requests[t.index].prompt,
                                           list(r.output)))
        else:
            failed += 1
    return judged, failed, served


def end_to_end(log) -> dict:
    """Every end-to-end reading the harness knows, over this run's log."""
    due = [t for t in log.tracked.values()
           if log.t_open <= t.due < log.t_close]
    cap = log.t_close + DRAIN_CAP_S
    ttft = [((t.times[0] if t.times else cap) - t.due) * 1e3 for t in due]
    tpot = [(t.times[-1] - t.times[0]) / (len(t.times) - 1) * 1e3
            for t in due if len(t.times) >= 2]
    # tokens delivered between the first and the last window boundary
    # inside the measured window, over the time between them
    bounds = [w.end for w in log.windows
              if log.t_open <= w.end < log.t_close]
    tok_s = float("nan")
    if len(bounds) >= 2:
        lo, hi = bounds[0], bounds[-1]
        n = sum(1 for t in log.tracked.values() for x in t.times
                if lo < x <= hi)
        tok_s = n / (hi - lo)
    out = {"output_tokens_per_s": tok_s, "due_in_window": len(due),
           "backlog_growth_per_s": backlog_growth(log)}
    for q in (50, 90, 95):
        if ttft:
            out[f"ttft_p{q}_ms"] = percentile(ttft, q)
        if tpot:
            out[f"tpot_p{q}_ms"] = percentile(tpot, q)
    return out


@dataclass
class Outcome:
    result: dict            # the result line
    readings: dict          # every end-to-end reading, for earlier lines


def run_cell(cell: spec_lib.Cell, seed: int, seconds: float, trace: bool,
             t_proc0: float, control: bool = False) -> Outcome:
    """One run. With ``control`` the reference computed in the
    configuration's ``correct.control`` format takes the program's place
    in the check: the gap compared, and so ``correct``, is that of the
    tokens it puts first on the same sample. The program's own gap is
    then a reading beside it (``readings["program_logit_gap"]``)."""
    import jax
    setup = {}
    jax.devices()
    setup["process_and_tpu_runtime_s"] = clock() - t_proc0
    if jax.devices()[0].platform != "cpu":
        # every program, the ring writes too, so a second run compiles none
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from repro.configs.base import ServeConfig
    from repro.frontend.server import BlinkServer
    from repro.models.api import make_model

    code, data, tr = cell.config.code, cell.config.data, cell.traffic
    cfg = code.model_config()
    serve = ServeConfig(**data["serve"])
    api = make_model(cfg, attn_backend=serve.attn_backend,
                     attn_pages_per_block=serve.attn_pages_per_block,
                     prefill_block_q=serve.prefill_block_q,
                     prefill_block_k=serve.prefill_block_k,
                     attn_unified=serve.attn_unified)
    t = clock()
    params = weights_lib.make_params(api.param_specs(), seed)
    jax.block_until_ready(params)
    setup["params_s"] = clock() - t

    requests = traffic_lib.generate(tr, seed, cfg.vocab_size)
    client = Client(None, requests, tr)
    t = clock()
    srv = BlinkServer(api, serve, params, on_token=client.on_token,
                      seed=weights_lib.jax_seed(seed))
    client.srv = srv
    setup["engine_state_s"] = clock() - t
    setup.update(warm_up(srv, tr, serve.prefill_chunk_tokens))
    compiles = _compile_counter()
    tracer = None
    if trace:
        for m, span in (("flush_submissions", "bench.flush_submissions"),
                        ("poll", "bench.poll")):
            _annotate(srv.frontend, m, span)
        _annotate(srv, "run_window", "bench.run_window")
        _annotate(client, "_submit", "bench.submit")
    t_traffic = clock()
    setup_s = t_traffic - t_proc0
    t_open = t_traffic + float(tr.get("lead_s", 0.0))
    if trace:
        tracer = Tracer(client, t_open + max(0.0, (seconds - TRACE_S) / 2))
    log = client.run(t_open, seconds, DRAIN_CAP_S,
                     on_boundary=tracer.on_boundary if tracer else None)
    if tracer:
        tracer.close()
    in_window = compiles[0]
    device = _device_info(jax, cell.chips)

    note("setup: " + ", ".join(f"{k} {v:.4f}" for k, v in setup.items())
         + f", total setup_s {setup_s:.4f}")
    late = np.asarray(log.late_s) * 1e3
    note(f"generator lateness (submit minus due): mean {late.mean():.4f} ms,"
         f" p99 {percentile(late, 99):.4f} ms, max {late.max():.4f} ms "
         f"over {late.size} requests")
    note(f"windows: {len(log.windows)}, compiles after set-up: {in_window}, "
         f"drained: {log.drained}")

    fe = srv.frontend
    judged, failed, served = _judge(log, fe, requests, tr, cfg.vocab_size)
    statuses = {}
    for t in log.tracked.values():
        r = fe.done.get(t.rid)
        s = r.status if r is not None else "unfinished"
        statuses[s] = statuses.get(s, 0) + 1
    note(f"requests judged: {len(judged)}, failed: {failed}; all tracked by "
         f"status: {statuses}")

    e2e = end_to_end(log)
    e2e["setup_s"] = setup_s
    note("all end-to-end readings: " + json.dumps(e2e))
    metrics, breakdown = {}, None
    if not trace:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is None or not math.isfinite(v):
                continue
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    elif tracer.before is None:
        note("no window started inside the stretch to trace")
    else:
        red = trace_lib.reduce_dir(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        prompt_lens = {rid: t.prompt_len for rid, t in log.tracked.items()}
        work = work_lib.between(code, prompt_lens, tracer.before,
                                tracer.after, serve.prefill_chunk_tokens)
        view = RunView(log.windows[tracer.first:tracer.last], serve, red,
                       work, peaks_lib.for_kind(device["kind"]), code)
        note(f"traced: {len(view.windows)} windows, {red.window_s:.6f} s, "
             f"device busy {red.busy_s:.6f} s; work {work}")
        for m in cell.per_layer:
            got = spec_lib.metric_reader(m["name"]).read(view)
            if isinstance(got, tuple):
                got, detail = got
                note(f"{m['name']}: {detail}")
            if got is None:
                continue
            metrics[m["name"]] = {"value": float(got), "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = {"device_ops": [[n, s] for n, s in red.top_ops(10)],
                     "idle_gaps": [[n, s] for n, s in red.idle_gaps]}

    # --- the check, once the program's state is freed -----------------
    del srv, client, fe, log
    if tracer:
        tracer.client = None
    gc.collect()
    limit = float(data["correct"]["max_logit_gap"])
    t = clock()
    sample = check_lib.sample(served, seed, int(data["correct"]["tokens"]))
    gap = check_lib.widest_gap(code, params, sample, serve.max_new_tokens)
    e2e["program_logit_gap"] = gap
    if control:
        gap = check_lib.widest_gap(code, params, sample,
                                   serve.max_new_tokens,
                                   control=data["correct"]["control"])
        e2e["control_logit_gap"] = gap
        note(f"control ({data['correct']['control']}) in the program's "
             f"place; the program's own gap on this sample: "
             f"{e2e['program_logit_gap']}")
    note(f"check: {len(sample)} requests, "
         f"{sum(len(s.output) for s in sample)} served tokens against the "
         f"float32 reference in {clock() - t:.2f} s")
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = (failed == 0 and len(judged) > 0 and bool(sample)
               and gap <= limit)
    result = {"correct": correct, "attempted": len(judged), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return Outcome(result, e2e)
