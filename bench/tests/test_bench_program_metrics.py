"""The readers of the program's own spans and counter rows
(``flush_ms``, ``poll_ms``, ``first_token_lag_ms``,
``ring_to_first_token_ms``, ``decode_lanes``) on the traced view of a
short run at test size on the CPU, and on a program that keeps no span
recorder, where each reports nothing."""
import math
import sys
from pathlib import Path

import pytest

from bench.harness import program_spans, runner, spec, traffic
from bench.harness import weights as weights_lib
from bench.harness.client import Client, clock

DATA = Path(__file__).parents[1] / "tests" / "data"
READERS = ("flush_ms.chat", "poll_ms.chat", "first_token_lag_ms.chat",
           "ring_to_first_token_ms.chat", "decode_lanes.backlog")


@pytest.fixture(scope="module")
def view():
    """A 3-s open-loop run of the tiny configuration, traced view over the
    boundaries of its middle."""
    from repro.configs.base import ServeConfig
    from repro.frontend.server import BlinkServer
    from repro.models.api import make_model

    cfg_files = spec.load_config("tiny", DATA)
    code, data = cfg_files.code, cfg_files.data
    tr = traffic.load(DATA / "traffic" / "tiny-chat.json")
    cfg = code.model_config()
    serve = ServeConfig(**data["serve"])
    api = make_model(cfg, attn_backend=serve.attn_backend,
                     prefill_block_q=serve.prefill_block_q,
                     prefill_block_k=serve.prefill_block_k,
                     attn_unified=serve.attn_unified)
    seed = 2 ** 31 + 5
    params = weights_lib.make_params(api.param_specs(), seed)
    client = Client(None, traffic.generate(tr, seed, cfg.vocab_size), tr)
    srv = BlinkServer(api, serve, params, on_token=client.on_token)
    client.srv = srv
    runner.warm_up(srv, tr, serve.prefill_chunk_tokens)
    log = client.run(clock() + tr["lead_s"], 3.0, 60.0)
    n = len(log.windows)
    assert n >= 8
    return runner.RunView(log.windows[n // 4: 3 * n // 4], serve, None,
                          None, None, code)


@pytest.fixture(autouse=True)
def _pinned_backend(monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_program_spans(view, name):
    value, detail = spec.metric_reader(name).read(view)
    assert math.isfinite(value) and value >= 0, (name, value)
    assert detail and "nan" not in detail, detail


def test_boundary_readers_sum_their_spans_per_boundary(view):
    s = program_spans.stretch(view)
    assert s.boundaries == len(view.windows)
    flush, _ = spec.metric_reader("flush_ms.chat").read(view)
    poll, _ = spec.metric_reader("poll_ms.chat").read(view)
    assert len(s.spans("blink.run_window")) == s.boundaries
    assert flush == pytest.approx(1e3 * s.seconds("blink.flush")
                                  / s.boundaries)
    assert poll == pytest.approx(1e3 * s.seconds("blink.poll")
                                 / s.boundaries)
    # the parts lie inside run_window, less its launch and wait, which
    # lies inside the benchmark's own span less window_wall
    parts = sum(s.seconds(n) for n in program_spans.BOUNDARY_PARTS)
    own = s.seconds("blink.run_window") - s.seconds("blink.launch") \
        - s.seconds("blink.wait")
    host = sum(w.end - w.start - w.wall for w in view.windows)
    assert 0 < parts <= own <= host


def test_first_token_readers_split_the_wait(view):
    s = program_spans.stretch(view)
    firsts = program_spans.first_tokens(s)
    assert firsts
    for ring_wall, end, wall in firsts:
        assert ring_wall < end < wall
    lag, _ = spec.metric_reader("first_token_lag_ms.chat").read(view)
    ring, _ = spec.metric_reader("ring_to_first_token_ms.chat").read(view)
    mean_total = 1e3 * sum(w - r for r, _e, w in firsts) / len(firsts)
    assert lag + ring == pytest.approx(mean_total)


def test_decode_lanes_rows_match_the_reader(view):
    lanes, detail = spec.metric_reader("decode_lanes.backlog").read(view)
    assert 0 < lanes <= view.serve.decode_batch
    n = len(program_spans.stretch(view).windows())
    assert n == len(view.windows)
    assert f"equal the poll's in {n} of {n} windows" in detail


@pytest.mark.parametrize("name", READERS)
def test_reader_reports_nothing_without_the_program_recorder(
        view, name, monkeypatch):
    import repro.telemetry
    monkeypatch.delattr(repro.telemetry, "host", raising=False)
    monkeypatch.setitem(sys.modules, "repro.telemetry.host", None)
    assert program_spans.program_recorder() is None
    assert spec.metric_reader(name).read(view) is None
