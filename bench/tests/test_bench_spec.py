"""The harness finds configurations, traffic mixes and metric readers by
the names in BENCHMARK.json: a new one is new files and entries, with no
edit to the harness."""
import json
import shutil
from pathlib import Path

import pytest

from bench.harness import spec

BENCH = Path(__file__).parents[1]
ROOT = BENCH.parent


def test_every_cell_of_the_benchmark_loads():
    b = spec.load_benchmark()
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config.code.model_config().name == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert hasattr(spec.metric_reader(m["name"]), "read")
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_new_cell_traffic_and_metric_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    for ext in ("json", "py"):
        shutil.copy(BENCH / "tests" / "data" / "configs" / f"tiny.{ext}",
                    bench / "configs" / f"tiny.{ext}")
    (bench / "traffic" / "burst.json").write_text(json.dumps({
        "kind": "open_loop", "rate_per_s": 3.0, "requests": 10,
        "prompt_len": {"median": 16, "sigma": 0.5, "min": 4, "max": 32},
        "output_len": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}}))
    (bench / "metrics" / "queue_ms.py").write_text(
        "def read(run):\n    return 42.0\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tiny.burst", "config": "tiny",
                       "traffic": "burst", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "ttft_p90_ms", "unit": "ms",
                        "workloads": ["tiny.burst"]},
                       {"name": "output_tokens_per_s", "unit": "tokens/s",
                        "workloads": ["other.cell"]}],
        "per_layer": [{"name": "queue_ms.burst", "unit": "ms",
                       "moves": "ttft_p90_ms",
                       "workloads": ["tiny.burst"]}]}))
    cell = spec.load_cell("tiny.burst", root=tmp_path)
    assert cell.traffic["rate_per_s"] == 3.0
    assert cell.config.code.model_config().d_model == 64
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "ttft_p90_ms"]
    reader = spec.metric_reader("queue_ms.burst", bench_dir=bench)
    assert reader.read(None) == 42.0


def test_a_reader_of_its_own_name_comes_first(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "step_ms.py").write_text(
        "def read(run):\n    return 1.0\n")
    (tmp_path / "metrics" / "step_ms.special.py").write_text(
        "def read(run):\n    return 2.0\n")
    assert spec.metric_reader("step_ms.x", tmp_path).read(None) == 1.0
    assert spec.metric_reader("step_ms.special", tmp_path).read(None) == 2.0
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("nothing.here", tmp_path)


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell")
