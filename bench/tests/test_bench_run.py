"""The benchmark's command refuses to run without a TPU or without the
program, and a run at test size on the CPU (kernels interpreted, the chip
check skipped) comes out correct, while the control and a token altered
where it is produced come out not correct."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench.harness import runner, spec, traffic

BENCH = Path(__file__).parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_machine_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def _tiny_cell():
    b = spec.load_benchmark()
    e2e = [m for m in b["end_to_end"]
           if "qwen2-1.5b.chat" in m.get("workloads", ["qwen2-1.5b.chat"])]
    return spec.Cell("tiny.chat", 1, spec.load_config("tiny", DATA),
                     "tiny-chat", traffic.load(DATA / "traffic" /
                                               "tiny-chat.json"), e2e, [])


@pytest.fixture(autouse=True)
def _pinned_backend(monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)


def test_tiny_run_is_correct_and_its_control_is_not():
    seed = 2 ** 31 + 17
    r = runner.run_cell(_tiny_cell(), seed, 2.0, False,
                        time.perf_counter()).result
    json.dumps(r)
    assert list(r)[-1] == "checks"
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    assert {"setup_s", "ttft_p50_ms"} <= set(r["metrics"])
    gap = r["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    # the control in the program's place: the same comparison turns it down
    out = runner.run_cell(_tiny_cell(), seed, 2.0, False,
                          time.perf_counter(), control=True)
    c = out.result
    assert not c["correct"] and c["failed"] == 0
    assert c["checks"]["logit_gap"]["value"] > c["checks"]["logit_gap"]["limit"]
    assert c["checks"]["logit_gap"]["value"] == \
        out.readings["control_logit_gap"]
    assert out.readings["program_logit_gap"] <= gap["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.core import engine
    sample = engine.sample_tokens

    def altered(key, logits, *a, **k):
        return (sample(key, logits, *a, **k) + 1) % logits.shape[-1]
    monkeypatch.setattr(engine, "sample_tokens", altered)
    r = runner.run_cell(_tiny_cell(), 5, 2.0, False,
                        time.perf_counter()).result
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]
