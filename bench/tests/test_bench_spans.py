"""The per-layer metrics that read the campaign's ``timings``, which the
program's own spans fill, on the CPU at a tiny size."""

import pytest

from bench import harness
from bench.peaks import PEAKS
from bench.tests.test_bench_harness import tiny

V5E = PEAKS["TPU v5 lite"]
SPAN_METRICS = {"warmup_ms_per_instance": "warmup_s", "sample_ms_per_instance": "sample_s",
                "analyse_ms_per_instance": "analyse_s"}


def window_of(tmp_path, timings=None):
    cell = tiny("kernel_matmul", 128)
    cell.per_layer = [{"name": n, "unit": "ms/instance"} for n in
                      ["build_ms_per_instance", "step_ms_per_instance", *SPAN_METRICS]]
    seen = harness.Observed()
    census = harness.Census(cell, harness.sweep_spec(cell), V5E, str(tmp_path),
                            harness.Spans(False))
    census.run_round(0, 5, seen)
    if timings is not None:
        seen.timings = timings
    return cell, harness.Window(cell, seen, 1.0, {"seconds": 0.0, "hits": 0, "misses": 0}, V5E)


def test_span_metrics_split_build_and_step(tmp_path):
    cell, window = window_of(tmp_path)
    m = {k: v["value"] for k, v in harness.read_metrics(cell.per_layer, window).items()}
    t = window.seen.timings
    for name, key in SPAN_METRICS.items():
        assert m[name] == pytest.approx(1e3 * t[key] / 3)
    assert m["warmup_ms_per_instance"] <= m["build_ms_per_instance"]
    assert (m["sample_ms_per_instance"] + m["analyse_ms_per_instance"]
            <= m["step_ms_per_instance"])


def test_span_metrics_are_silent_without_the_spans(tmp_path):
    # a program without the spans fills only the campaign loop's own keys
    cell, window = window_of(tmp_path, {"build_s": 1.0, "step_s": 1.0, "record_s": 0.1,
                                        "append_s": 0.1, "steps": 3.0, "records": 3.0})
    assert set(harness.read_metrics(cell.per_layer, window)) == {
        "build_ms_per_instance", "step_ms_per_instance"}
