"""The program's spans read from a profiler trace (``bench/program_trace.py``).

``census_n1024.xplane.pb`` is a traced ``matmul.n1024`` census recorded on
one TPU v5e (``bench.harness.run_cell`` with a 1 s window: 3 rounds, 24
instances), reduced to what the two trace readers read: the device plane's
``XLA Modules`` and ``XLA Ops`` lines, and the host plane's ``bench.*``,
``campaign.*`` and ``session.*`` spans with their ``uid``. ``small.xplane.pb``
(see ``test_bench_trace.py``) holds no program span: two programs,
``jit_matmul`` (the Pallas kernel) and ``jit_dot`` (XLA's dot)."""

import os
import shutil
import tempfile

import pytest

from bench import harness, program_trace, trace
from bench.families import kernel_variants
from bench.metrics import sample_idle_share
from bench.peaks import PEAKS

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
CENSUS = os.path.join(FIXTURES, "census_n1024.xplane.pb")
SPANS = {"campaign.build", "campaign.step", "campaign.save", "campaign.record",
         "campaign.append", "session.warmup", "session.first", "session.sample",
         "session.analyse"}


@pytest.fixture(scope="module")
def small():
    return program_trace.read(os.path.join(FIXTURES, "small.xplane.pb"))


@pytest.fixture(scope="module")
def census():
    return program_trace.read(CENSUS)


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(CENSUS)


def test_program_spans_over_the_window(census, summary):
    assert census.window_s == pytest.approx(summary.window_s, rel=1e-12)
    assert census.busy_s == pytest.approx(summary.busy_s, rel=1e-12)
    assert set(census.spans) == SPANS
    # 24 instances: one build, warm-up and first call each; one sample and
    # one analysis per engine step
    assert [len(census.spans[n]) for n in ("campaign.build", "session.warmup",
                                           "session.first")] == [24, 24, 24]
    steps = len(census.spans["campaign.step"])
    assert len(census.spans["session.sample"]) == len(census.spans["session.analyse"]) == steps
    inner = census.seconds("session.sample") + census.seconds("session.analyse")
    assert 0.9 * census.seconds("campaign.step") < inner < census.seconds("campaign.step")
    assert census.seconds("session.warmup") < census.seconds("campaign.build")
    for name in SPANS:
        assert 0 <= census.busy_in[name] <= census.seconds(name)


def test_idle_share_of_the_timed_samples(census):
    share = census.idle_share("session.sample")
    assert share == pytest.approx(1 - census.busy_in["session.sample"]
                                  / census.seconds("session.sample"))
    # calls of 0.1-0.7 ms whose kernels run for tens of us: mostly dispatch
    assert 0.6 < share < 1.0


def test_idle_time_by_innermost_program_span(census):
    idle = census.idle_by_span
    assert sum(idle.values()) == pytest.approx(census.window_s - census.busy_s, rel=1e-9)
    assert set(idle) <= SPANS | {"other"}
    assert [k for k, _ in census.top_idle(3)] == ["session.sample", "session.warmup",
                                                 "session.first"]
    assert idle["other"] < 0.1 * sum(idle.values())


def test_device_seconds_by_program(census, summary):
    programs = dict(census.top_programs())
    assert list(programs)[:2] == ["jit_matmul", "jit_xla_dot"]
    assert sum(programs.values()) == pytest.approx(census.busy_s, rel=1e-6)
    # the Pallas GEMM is still the site's only custom call, under its tiles' names
    count, seconds = summary.kernel(kernel_variants.KERNELS["pallas_matmul"])
    assert count and seconds == pytest.approx(programs["jit_matmul"], rel=1e-6)
    assert {k for k, _ in summary.top_ops() if k.endswith(":tpu_custom_call")} == {
        "matmul_128x128x128:tpu_custom_call", "matmul_256x256x256:tpu_custom_call",
        "matmul_512x512x512:tpu_custom_call"}


def run_window(fixture, tmp_path, monkeypatch):
    """The window of a traced run whose trace is ``fixture``, left where
    ``bench/run.py`` leaves it: in a ``bench-*`` work directory of the
    temporary directory."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    run = tmp_path / "bench-run" / "trace" / "plugins" / "profile" / "1"
    run.mkdir(parents=True)
    shutil.copy(fixture, run / "host.xplane.pb")
    return harness.Window(None, harness.Observed(), 1.0, {}, PEAKS["TPU v5 lite"],
                          trace.summarize(str(run / "host.xplane.pb")))


def test_sample_idle_share_reads_the_runs_own_trace(monkeypatch, tmp_path, capsys, census):
    window = run_window(CENSUS, tmp_path, monkeypatch)
    assert sample_idle_share.read(window) == pytest.approx(
        100 * census.idle_share("session.sample"))
    err = capsys.readouterr().err
    assert "# idle by program span: session.sample " in err
    assert "# device seconds by program: jit_matmul " in err


def test_the_run_trace_is_taken_only_where_it_is_the_summarys(monkeypatch, tmp_path):
    window = run_window(CENSUS, tmp_path, monkeypatch)
    assert program_trace.of_run(window.trace) is not None
    other = trace.TraceSummary(window_s=window.trace.window_s * 2,
                               busy_s=window.trace.busy_s, n_devices=1)
    assert program_trace.of_run(other) is None
    assert program_trace.of_run(None) is None


def test_sample_idle_share_is_silent_without_program_spans(monkeypatch, tmp_path, capsys):
    # the trace of a program without the spans
    window = run_window(os.path.join(FIXTURES, "small.xplane.pb"), tmp_path, monkeypatch)
    assert sample_idle_share.read(window) is None
    assert "idle by program span" not in capsys.readouterr().err


def test_a_trace_without_program_spans(small):
    summary = trace.summarize(os.path.join(FIXTURES, "small.xplane.pb"))
    assert small.window_s == pytest.approx(summary.window_s, rel=1e-12)
    assert small.busy_s == pytest.approx(summary.busy_s, rel=1e-12)
    assert small.spans == {} and small.idle_share("session.sample") is None
    # all idle time lies outside every program span
    assert small.idle_by_span == {"other": pytest.approx(small.window_s - small.busy_s)}


def test_device_seconds_by_program_from_the_modules_line(small):
    programs = dict(small.top_programs())
    assert set(programs) == {"jit_matmul", "jit_dot"}
    assert programs["jit_matmul"] == pytest.approx(116.714e-6, rel=1e-6)
    assert sum(programs.values()) == pytest.approx(small.busy_s, rel=1e-6)


@pytest.mark.parametrize("intervals,want", [
    ([], [(0, 10, "other")]),
    ([(1, 9, "a"), (2, 4, "b"), (3, 3.5, "c"), (5, 6, "d")],
     [(0, 1, "other"), (1, 2, "a"), (2, 3, "b"), (3, 3.5, "c"), (3.5, 4, "b"),
      (4, 5, "a"), (5, 6, "d"), (6, 9, "a"), (9, 10, "other")]),
    # back to back, and a child that ends with its parent
    ([(0, 5, "a"), (5, 10, "b"), (7, 10, "c")],
     [(0, 5, "a"), (5, 7, "b"), (7, 10, "c")]),
])
def test_innermost(intervals, want):
    assert program_trace.innermost(intervals, 0, 10) == want
