"""The trace reduction on a small trace in the profiler's own format."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench.harness import trace

DATA = Path(__file__).parent / "data" / "trace.textproto"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_planes(ProfileData.from_text_proto(
        DATA.read_text()).planes)


def test_busy_is_the_union_of_device_ops_inside_the_window(reduced):
    assert reduced.window_s == pytest.approx(100e-9)
    assert reduced.busy_s == pytest.approx(65e-9)
    assert 1 - reduced.busy_s / reduced.window_s == pytest.approx(0.35)


def test_kernel_time_sums_its_events_by_name(reduced):
    assert reduced.kernel_seconds("ragged_attention") == pytest.approx(35e-9)
    assert reduced.kernel_seconds("paged_attention") == 0


def test_control_flow_is_busy_but_not_an_operation(reduced):
    kinds = dict(reduced.top_ops())
    assert "while" not in kinds
    assert kinds == pytest.approx({"ragged_attention": 35e-9,
                                   "fusion": 10e-9,
                                   "convolution_multiply_fusion": 10e-9})


def test_idle_time_is_summed_by_the_host_span_around_it(reduced):
    assert reduced.idle_gaps == pytest.approx([
        ("bench.flush_submissions", 20e-9), ("bench.poll", 10e-9),
        ("bench.run_window", 5e-9)])


@pytest.mark.parametrize("name, kind", [
    ("%fusion.273 = s32[96] fusion(s32[128,96] %a)", "fusion"),
    ("%ragged_attention.10 = (bf16[2]) custom-call(s32[34] %b)",
     "ragged_attention"),
    ("%copy-start.26 = (s32[28]) copy-start(s32[28] %g)", "copy-start"),
    ("custom-call", "custom-call"),
])
def test_op_kind(name, kind):
    assert trace.op_kind(name) == kind


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert trace.union_length(iv) == 30
    assert trace.gaps(iv, 0, 50) == [(20, 30), (40, 50)]


def test_a_trace_without_the_window_span_is_refused():
    text = DATA.read_text().replace('"bench.traced_window"', '"other"')
    with pytest.raises(ValueError, match="traced_window"):
        trace.reduce_planes(ProfileData.from_text_proto(text).planes)
