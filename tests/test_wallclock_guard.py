"""WallClockTimer minimum-measurable-time guard: sub-dispatch-cost
workloads get an automatic inner-repeat loop (mean per-call time), slow
workloads stay single-call, and wall-clock census records surface the
chosen counts. A calibration call that settles on one call per sample is
kept as the workload's first sample; under inner repeats it is discarded."""

import time

import pytest

from repro.core.measure import WallClockTimer
from repro.core.spans import collect


class _Ready:
    """A result whose ``block_until_ready`` returns at once."""

    def block_until_ready(self):
        return self


def _slow_first_call(calls, first_s, rest_s):
    """A blocking workload whose first call sleeps ``first_s``, the rest
    ``rest_s``; ``calls`` counts them."""

    def workload():
        calls.append(1)
        time.sleep(first_s if len(calls) == 1 else rest_s)
        return _Ready()

    return workload


def test_fast_workload_gets_inner_repeats():
    timer = WallClockTimer({"fast": lambda: None}, check_blocking=False,
                           min_time_s=1e-3)
    samples = timer.measure_many("fast", 3)
    assert len(samples) == 3
    r = timer.inner_repeats["fast"]
    assert r > 1
    # per-call means: orders of magnitude under the floor even repeated
    assert all(0.0 <= s < 1e-3 for s in samples)


def test_slow_workload_stays_single_call():
    timer = WallClockTimer({"slow": lambda: time.sleep(2e-3)},
                           check_blocking=False, min_time_s=1e-3)
    s = timer.measure("slow")
    assert timer.inner_repeats["slow"] == 1
    assert s >= 2e-3


def test_guard_disabled_with_zero_floor():
    timer = WallClockTimer({"fast": lambda: None}, check_blocking=False,
                           min_time_s=0.0)
    timer.measure("fast")
    assert timer.inner_repeats["fast"] == 1


def test_repeat_count_is_capped():
    timer = WallClockTimer({"fast": lambda: None}, check_blocking=False,
                           min_time_s=10.0)  # absurd floor
    timer.measure("fast")
    assert timer.inner_repeats["fast"] == WallClockTimer.MAX_INNER_REPEATS


def test_calibration_happens_once():
    calls = []
    timer = WallClockTimer({"w": lambda: calls.append(1)},
                           check_blocking=False, min_time_s=0.0)
    timer.measure_many("w", 2)
    n_after_first = len(calls)
    timer.measure_many("w", 2)
    # second batch: exactly 2 calls, no re-calibration
    assert len(calls) == n_after_first + 2


def test_blocking_check_still_enforced():
    class FakeAsync:
        def block_until_ready(self):
            time.sleep(2e-3)

    timer = WallClockTimer({"async": FakeAsync})
    with collect({}) as t:
        with pytest.raises(RuntimeError, match="not blocking"):
            timer.measure("async")
    assert t == {} and "async" not in timer.inner_repeats


@pytest.mark.parametrize("check_blocking", [True, False])
def test_a_single_call_calibration_is_the_first_sample(check_blocking):
    calls = []
    timer = WallClockTimer({"w": _slow_first_call(calls, 0.05, 1e-3)},
                           check_blocking=check_blocking, min_time_s=1e-4)
    with collect({}) as t:
        samples = timer.measure_many("w", 4)
    assert timer.inner_repeats["w"] == 1
    assert len(calls) == len(samples) == 4
    assert samples[0] >= 0.05 > max(samples[1:])
    assert t == {"calibration_samples_kept": 1}

    with collect({}) as t:
        assert len(timer.measure_many("w", 2)) == 2
    assert len(calls) == 6 and t == {}


def test_an_inner_repeat_calibration_is_discarded():
    calls = []
    timer = WallClockTimer({"fast": lambda: calls.append(1)},
                           check_blocking=False, min_time_s=1e-3)
    with collect({}) as t:
        samples = timer.measure_many("fast", 3)
    r = timer.inner_repeats["fast"]
    assert r > 1 and len(samples) == 3
    assert len(calls) == 1 + 3 * r
    assert "calibration_samples_kept" not in t


def test_the_blocking_check_keeps_the_accepted_attempt():
    """The first attempt returns before its result is ready; the second
    blocks inside the call, and its time is the first sample."""

    class Late:
        def block_until_ready(self):
            time.sleep(5e-3)

    calls = []

    def workload():
        calls.append(1)
        if len(calls) == 1:
            return Late()
        time.sleep(0.03)
        return _Ready()

    timer = WallClockTimer({"w": workload})
    with collect({}) as t:
        (sample,) = timer.measure_many("w", 1)
    assert len(calls) == 2 and sample >= 0.03
    assert timer.inner_repeats["w"] == 1
    assert t == {"calibration_samples_kept": 1}


def test_wall_clock_census_record_surfaces_inner_repeats():
    """End to end through the sweep layer: a wall_clock census record on a
    sub-floor workload family carries the chosen counts and the device
    kind that measured it (and deterministic backends never grow either
    field)."""
    from repro.core.sweep import SweepSpec, build_sweep_session, record_from_session

    spec = SweepSpec(
        name="wc", backend="wall_clock", n_shards=1, max_measurements=6,
        families={"bilinear": {"sizes": [8], "per_size": 1}},
    )
    inst = spec.expand()[0]
    session = build_sweep_session(spec, inst)
    while session.step():
        pass
    record = record_from_session(session, spec)
    assert record["device_kind"] == "cpu"  # the device that measured it
    assert "inner_repeats" in record
    assert set(record["inner_repeats"]) == set(record["flops"])
    assert all(r >= 1 for r in record["inner_repeats"].values())
    # the deterministic backends must NOT carry the field (byte-identity)
    det = SweepSpec(
        name="wc", backend="cost_model", n_shards=1, max_measurements=6,
        families={"bilinear": {"sizes": [8], "per_size": 1}},
    )
    session = build_sweep_session(det, det.expand()[0])
    while session.step():
        pass
    det_record = record_from_session(session, det)
    assert "inner_repeats" not in det_record
    assert "device_kind" not in det_record
