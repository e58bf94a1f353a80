"""The arithmetic of the tools that set the cells' numbers: the knee
judgement of ``bench/knee.py`` and the spread of ``bench/sets.py``."""
import importlib.util
from pathlib import Path

import pytest

BENCH = Path(__file__).parents[1]


def _load(name):
    spec = importlib.util.spec_from_file_location(f"bench_tool_{name}",
                                                  BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LIMITS = {"ttft_p90_multiple": 5, "tpot_p90_multiple": 4,
          "max_queue_growth_requests": 3, "max_queue_growth_share": 0.15,
          "fraction": 0.8}


def _row(rate, ttft90=4000.0, tpot90=150.0, growth=0.0, due=40, failed=0):
    return {"rate_per_s": rate, "failed": failed, "ttft_p50_ms": 3000.0,
            "ttft_p90_ms": ttft90, "tpot_p50_ms": 120.0,
            "tpot_p90_ms": tpot90, "backlog_growth_per_s": growth,
            "due_in_window": due}


@pytest.mark.parametrize("rows, knee", [
    ([_row(0.2), _row(0.5), _row(1.0)], 1.0),
    # the queue grows at 1.0 by 0.2 req/s x 50 s = 10 > max(3, 6)
    ([_row(0.2), _row(0.5), _row(1.0, growth=0.2)], 0.5),
    # a tail over its multiple of the lone request's median
    ([_row(0.2), _row(0.5, tpot90=481.0), _row(1.0)], None),
    ([_row(0.2), _row(0.5), _row(1.0, ttft90=15001.0)], 0.5),
    ([_row(0.2), _row(0.5, failed=1), _row(1.0)], None),
    # two runs at each rate: a rate passes only if both do
    ([_row(0.2), _row(0.5), _row(0.5), _row(1.0), _row(1.0, growth=0.2)],
     0.5),
    ([_row(0.2), _row(0.5), _row(0.5), _row(1.0), _row(1.0)], 1.0),
    # a rate that passes above one that failed does not raise the knee
    ([_row(0.2), _row(0.5), _row(1.0, failed=1), _row(1.2)], 0.5),
])
def test_knee_is_the_last_rate_every_lower_rate_also_passed(rows, knee):
    got = _load("knee").judge(rows, LIMITS, 50.0)
    assert got["knee_rate_per_s"] == knee
    assert got["ttft_p90_limit_ms"] == 15000.0
    assert got["tpot_p90_limit_ms"] == 480.0
    if knee is not None:
        assert got["cell_rate_per_s"] == round(0.8 * knee, 3)


def test_spread_is_the_quartile_distance_over_the_median():
    sets = _load("sets")
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    # statistics.quantiles(n=4) of these: 10.75, 12.5, 14.25
    assert sets.spread(xs) == pytest.approx((14.25 - 10.75) / 12.5)
    recs = [{"set": s, "trace": 0, "result": {"metrics": {
        "m": {"value": v}}}} for s in "AB" for v in xs]
    got = sets.summary(recs)["m"]
    assert got["A"]["median"] == 12.5
    assert got["widest_spread"] == pytest.approx(0.28)
