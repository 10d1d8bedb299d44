"""The trace reduction on a small trace recorded on one TPU v5e: two steps,
each a 1024^3 float32 GEMM by the Pallas kernel (256 tiles) and one by XLA's
dot, inside ``bench.step`` spans, 2 ms apart."""

import os

import pytest

from bench import trace
from bench.families import kernel_variants

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(FIXTURE)


def test_busy_and_idle_over_the_window(summary):
    # no bench.window span: the window runs from the first device op to the last
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(4.873451e-3, rel=1e-6)
    assert summary.busy_s == pytest.approx(166.851e-6, rel=1e-6)
    assert 0.96 < summary.idle_share < 0.97


def test_device_time_per_operation(summary):
    ops = dict(summary.top_ops())
    assert list(ops)[:2] == ["matmul:tpu_custom_call", "fusion:kOutput"]
    assert ops["matmul:tpu_custom_call"] == pytest.approx(116.714e-6, rel=1e-6)
    assert sum(ops.values()) == pytest.approx(summary.busy_s, rel=1e-6)


def test_kernel_events_by_pattern(summary):
    pallas = summary.kernel(kernel_variants.KERNELS["pallas_matmul"])
    xla = summary.kernel(kernel_variants.KERNELS["xla_dot"])
    assert pallas == (2, pytest.approx(116.714e-6, rel=1e-6))
    assert xla == (2, pytest.approx(38.057e-6, rel=1e-6))


def test_idle_time_by_host_span(summary):
    idle = dict(summary.top_idle())
    assert set(idle) == {"bench.step", "other"}
    assert sum(idle.values()) == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)


@pytest.mark.parametrize("busy,lo,hi,want", [
    ([(1, 2), (3, 4)], 0, 5, [(0, 1), (2, 3), (4, 5)]),
    ([(0, 5)], 0, 5, []),
    ([], 1, 2, [(1, 2)]),
])
def test_gaps(busy, lo, hi, want):
    assert trace.gaps(busy, lo, hi) == want


def test_merge_clip_and_overlap():
    assert trace.merge([(3, 4), (1, 2), (1.5, 3.5)]) == [(1, 4)]
    assert trace.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]
    assert trace.overlap([(0, 2), (3, 5)], [(1, 4)]) == pytest.approx(2.0)


def test_base_name():
    assert trace.base_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop") \
        == "fusion:kLoop"
    assert trace.base_name("copy.3") == "copy"
