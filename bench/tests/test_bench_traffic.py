"""The traffic generator: one seed, one list of requests; every seed the
same lengths and gaps in another order; lengths within their clips."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness import traffic

TRAFFIC = sorted((Path(__file__).parents[1] / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 987654321


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    spec = traffic.load(path)
    a = traffic.generate(spec, BIG_SEED, 50_000)
    b = traffic.generate(spec, BIG_SEED, 50_000)
    assert len(a) == len(b) == spec["requests"]
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.gap_s) == (y.max_new, y.gap_s)


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_lengths_within_clips_and_seeds_share_the_schedule(path):
    spec = traffic.load(path)
    runs = [traffic.generate(spec, s, 50_000) for s in (1, BIG_SEED)]
    for reqs in runs:
        p = np.array([len(r.prompt) for r in reqs])
        o = np.array([r.max_new for r in reqs])
        assert p.min() >= spec["prompt_len"]["min"]
        assert p.max() <= spec["prompt_len"]["max"]
        assert o.min() >= spec["output_len"]["min"]
        assert o.max() <= spec["output_len"]["max"]
        assert all(r.prompt.min() >= traffic.FIRST_TOKEN for r in reqs)
        assert all(r.prompt.max() < 50_000 for r in reqs)
        assert set(p) <= set(traffic.distinct_prompt_lengths(spec))
    sched = lambda reqs: [(len(r.prompt), r.max_new, r.gap_s) for r in reqs]
    assert sched(runs[0]) == sched(runs[1])
    assert not np.array_equal(runs[0][0].prompt, runs[1][0].prompt)
    other = traffic.generate(dict(spec, schedule_seed=1), 1, 50_000)
    for k in range(3):
        assert sorted(x[k] for x in sched(other)) == \
            sorted(x[k] for x in sched(runs[0]))
    assert sched(other) != sched(runs[0])


def test_open_loop_gaps_average_to_the_rate():
    spec = json.loads((Path(__file__).parents[1] / "traffic"
                       / "chat.json").read_text())
    gaps = [r.gap_s for r in traffic.generate(spec, 3, 1000)]
    assert np.mean(gaps) == pytest.approx(1 / spec["rate_per_s"], rel=0.02)


def test_lognormal_median_and_grid():
    dist = {"median": 512, "sigma": 0.8, "min": 32, "max": 4096, "grid": 32}
    x = traffic.lengths(1001, dist)
    assert np.median(x) == 512
    assert (x % 32 == 0).all()
