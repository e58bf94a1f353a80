"""The sparse-expert configuration: its operation and byte counts against
values worked by hand, its parameter count against the share's memory
budget, the expert counters' readers on a recorded stretch, and a run at
test size on the CPU (kernels interpreted) that comes out correct while
its fp8 control does not."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import runner, spec, traffic
from bench.tests.test_bench_run import BENCH

DATA = BENCH / "tests" / "data"


@pytest.fixture(autouse=True)
def _pinned_backend(monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)


def test_qwen3_counts_by_hand():
    code = spec.load_config("qwen3-30b-a3b").code
    # 24 x (attention 2048*4096 + 2*2048*512 + 4096*2048 = 18,874,368,
    #       router 2048*128 = 262,144, and 8 x 16/128 = 1 expert of
    #       3*2048*768 = 4,718,592)
    assert code.matmul_params() == 572_522_496
    assert code.head_params() == 2048 * 151_936
    # 4*128*32*24 = 393,216 per key
    assert code.attn_flops(1000) == 393_216_000
    assert code.attn_flops(sum(range(257, 513))) == 38_705_037_312
    # 24*2*(2*4*128*1000 + 2*32*128 + 2*4*128)
    assert code.attn_bytes(1000, 1) == 49_594_368
    assert code.attn_bytes(512, 256) == 138_412_032
    # 6*2048*768 per routed row; 10 hit experts of 3*2048*768 bf16
    # weights and 100 rows read and written at 2048 bf16
    assert code.expert_flops(100) == 943_718_400
    assert code.expert_bytes(10, 100) == 95_191_040


def test_qwen3_share_holds_the_stated_parameters():
    """24 layers x (attention, router, 16 experts) plus embedding and
    untied head: 2.89 B parameters, 5.79 GB in bf16."""
    from repro.models.transformer import count_params
    cfg = spec.load_config("qwen3-30b-a3b").code.model_config()
    assert cfg.held_experts == 16 and cfg.num_experts == 128
    assert cfg.qk_norm and not cfg.tie_embeddings
    assert count_params(cfg) == 2_893_651_968


def _recorded(rows, L, held):
    """A program recorder holding one window's counter rows, and a run
    whose traced stretch covers it."""
    win = SimpleNamespace(rows=rows, t_launch=1.5)
    span = SimpleNamespace(t0=1.0, t1=2.0)
    rec = SimpleNamespace(windows=[win], closed=lambda name, lo, hi: [span])
    return rec, SimpleNamespace(
        windows=[SimpleNamespace(start=1.0, end=2.0)],
        code=SimpleNamespace(L=L, HELD=held))


def test_expert_readers_on_recorded_rows(monkeypatch):
    from bench.harness import program_spans
    from repro.telemetry import state
    rows = np.zeros((4, state.N_COUNTERS), np.int64)
    rows[:, state.COL["expert_rows"]] = [96, 100, 104, 100]
    rows[:, state.COL["experts_hit"]] = [30, 32, 32, 34]
    rec, run = _recorded(rows, L=2, held=4)
    monkeypatch.setattr(program_spans, "program_recorder", lambda: rec)
    got = spec.metric_reader("expert_rows.moe").read(run)
    # 400 rows over 4 steps x 2 layers x 4 experts
    assert got[0] == pytest.approx(12.5)
    from bench.harness import expert_counts
    assert expert_counts.counted(run) == (400, 128, 4)


def _tiny_moe_cell():
    b = spec.load_benchmark()
    e2e = [m for m in b["end_to_end"]
           if "qwen2-1.5b.chat" in m.get("workloads", ["qwen2-1.5b.chat"])]
    return spec.Cell("tiny-moe.chat", 1, spec.load_config("tiny-moe", DATA),
                     "tiny-chat", traffic.load(DATA / "traffic" /
                                               "tiny-chat.json"), e2e, [])


def test_tiny_moe_run_is_correct_and_its_control_is_not():
    seed = 2 ** 31 + 29
    r = runner.run_cell(_tiny_moe_cell(), seed, 2.0, False,
                        time.perf_counter()).result
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    gap = r["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    out = runner.run_cell(_tiny_moe_cell(), seed, 2.0, False,
                          time.perf_counter(), control=True)
    c = out.result
    assert not c["correct"] and c["failed"] == 0
    assert c["checks"]["logit_gap"]["value"] > \
        c["checks"]["logit_gap"]["limit"]
    assert out.readings["program_logit_gap"] <= gap["limit"]
