"""The harness on the CPU at a tiny size: its rounds, metrics and check, and
that the check comes out false when the timed path is broken underneath. The
command itself refuses a device that is not a TPU."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness
from bench.peaks import PEAKS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
V5E = PEAKS["TPU v5 lite"]
PER_LAYER = ["build_ms_per_instance", "trace_lower_share", "step_ms_per_instance",
             "measurements_per_alg", "inner_repeat_share", "store_ms_per_instance",
             "device_idle_share", "pallas_matmul_roofline", "xla_dot_roofline"]


def tiny(config: str, size: int) -> harness.Cell:
    cfg = harness.load_json(os.path.join(ROOT, "bench", "configs", f"{config}.json"))
    cfg["check"]["instances"] = 2
    # the CPU multiplies float32 operands as they are
    cfg["operands"] = "float32"
    return harness.Cell(
        name=f"tiny.{config}", config=cfg, traffic={"size": size, "pool": 3},
        end_to_end=[{"name": "instances_per_min", "unit": "instances/min"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[{"name": n, "unit": "%"} for n in PER_LAYER])


CELLS = [("paper_chain", 32), ("kernel_matmul", 128)]


def run(cell, tmp_path, trace=False, seconds=0.5, seed=2**31 + 11):
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(), V5E,
                            workdir=str(tmp_path))


@pytest.mark.parametrize("config,size", CELLS)
def test_a_sound_run_is_correct(config, size, tmp_path):
    result = run(tiny(config, size), tmp_path)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] % 3 == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"instances_per_min", "setup_s"}
    assert result["metrics"]["instances_per_min"]["value"] > 0
    assert result["checks"]["err"]["value"] < 1e-6


def test_window_is_whole_rounds_and_metrics_read_it(tmp_path):
    cell = tiny("paper_chain", 32)
    spec = harness.sweep_spec(cell)
    census = harness.Census(cell, spec, V5E, str(tmp_path), harness.Spans(False))
    seen = harness.Observed()
    for r in range(2):
        census.run_round(r, 9, seen, keep_index=1 if r == 0 else None)
    assert seen.rounds == 2 and len(seen.records) == 6 == len(seen.rows)
    assert list(seen.kept) == ["chain-n4-r0-i001"]
    window = harness.Window(cell, seen, 2.0, {"seconds": 0.5, "hits": 0, "misses": 0}, V5E)
    metrics = harness.read_metrics(cell.per_layer, window)
    # the trace's metrics have nothing to read without a trace
    assert set(metrics) == set(PER_LAYER) - {"device_idle_share", "pallas_matmul_roofline",
                                             "xla_dot_roofline"}
    assert metrics["trace_lower_share"]["value"] == pytest.approx(25.0)
    mpa = np.mean([r["measurements_per_alg"] for r in seen.records.values()])
    assert metrics["measurements_per_alg"]["value"] == pytest.approx(mpa)
    t = seen.timings
    assert metrics["build_ms_per_instance"]["value"] == pytest.approx(1e3 * t["build_s"] / 6)


def test_sample_is_drawn_from_the_seed():
    cell = tiny("kernel_matmul", 128)
    a, b = harness.sample_round_keeps(cell, 1), harness.sample_round_keeps(cell, 1)
    assert a == b and sorted(a) == [0, 1] and all(0 <= i < 3 for i in a.values())


# ------------------------------------------------- the timed path, broken ---


def _altered_answers(monkeypatch):
    """An answer altered where it is produced: one entry of one algorithm's
    output is off by the output's largest magnitude, as a wrong tile or
    index would leave it."""
    import repro.core.sweep as sweep

    real = sweep.instance_entry

    def entry(inst):
        flops, meta, build = real(inst)

        def broken():
            fns = build()
            name = sorted(fns)[-1]
            good = fns[name]

            def altered():
                out = np.array(good())
                out.flat[0] += np.max(np.abs(out))
                return out
            fns[name] = altered
            return fns
        return flops, meta, broken

    monkeypatch.setattr(sweep, "instance_entry", entry)


def _half_the_pool(monkeypatch):
    """Half of the batch left out: half of each round's records are never
    appended, while the round still counts its whole pool."""
    import repro.core.sweep as sweep

    real = sweep.ShardStore.append_records
    monkeypatch.setattr(sweep.ShardStore, "append_records",
                        lambda self, records: real(self, list(records)[::2]))


def _unmeasured(monkeypatch):
    """A step that returns its state unchanged: the timer reports times
    without running the algorithms."""
    from repro.core.measure import WallClockTimer

    monkeypatch.setattr(WallClockTimer, "measure_many", lambda self, name, m: [1e-9] * m)


def _control(monkeypatch, family_module):
    """The reference in bfloat16 put in the program's place."""
    import repro.core.sweep as sweep

    real = sweep.instance_entry

    def entry(inst):
        flops, meta, build = real(inst)
        answers = family_module.control(inst.params)

        def controlled():
            # the program's work still runs, so times stay real; the answers
            # are the control's
            fns = build()
            return {name: (lambda fn=fn, out=answers[name]: (fn(), out)[1])
                    for name, fn in fns.items()}
        return flops, meta, controlled

    monkeypatch.setattr(sweep, "instance_entry", entry)


FAULTS = {"altered": _altered_answers, "half_the_pool": _half_the_pool,
          "unmeasured": _unmeasured}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("config,size", CELLS)
def test_a_broken_timed_path_is_not_correct(fault, config, size, tmp_path, monkeypatch):
    FAULTS[fault](monkeypatch)
    result = run(tiny(config, size), tmp_path)
    assert not result["correct"]
    assert result["failed"] > 0


@pytest.mark.parametrize("config,size", CELLS)
def test_the_control_is_not_correct(config, size, tmp_path, monkeypatch):
    cell = tiny(config, size)
    _control(monkeypatch, cell.family)
    result = run(cell, tmp_path)
    assert not result["correct"]


def test_the_command_refuses_a_device_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "matmul.n1024", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "nothing measured" in proc.stderr
