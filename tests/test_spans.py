"""Program spans (``repro.core.spans``): timings into the active sink, the
profiler's trace, and stable names on the timed programs.

The campaign loop's stages and the sessions' build and step report into one
``timings`` dict and, while a profiler runs, onto its trace's host plane,
where the device's operations name the jitted program they ran in."""

import glob
import json
import os
import subprocess
import sys

import pytest

from repro.core.spans import collect, named, span
from repro.core.sweep import SweepSpec, run_shard

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
OLD_KEYS = {"build_s", "step_s", "record_s", "append_s", "steps", "records"}
NEW_KEYS = {"warmup_s", "first_s", "sample_s", "analyse_s", "save_s"}


def _chain_spec(**kw):
    base = dict(
        name="spans", backend="wall_clock", n_shards=1, chunk_size=2,
        max_measurements=6,
        families={"chain": {"count": 2, "n_matrices": [3], "lo": 8, "hi": 24}},
    )
    base.update(kw)
    return SweepSpec(**base)


# ------------------------------------------------------------------ sink ---

def test_spans_sum_into_the_active_sink_and_nest():
    t = {}
    with collect(t):
        with span("campaign.step", "step_s") as outer:
            for _ in range(3):
                with span("session.sample", "sample_s", uid="u1") as inner:
                    pass
        with span("campaign.step", "step_s"):
            pass
    assert set(t) == {"step_s", "sample_s"}
    assert inner.seconds >= 0 and outer.seconds >= inner.seconds
    assert t["sample_s"] <= outer.seconds <= t["step_s"]


def test_no_sink_or_no_key_records_nothing():
    with span("campaign.step", "step_s") as s:
        pass
    assert s.seconds >= 0
    t = {}
    with collect(t):
        with span("campaign.save"):
            pass
    assert t == {}


def test_collect_restores_the_outer_sink():
    outer, inner = {}, {}
    with collect(outer):
        with collect(inner):
            with span("a", "a_s"):
                pass
        with span("b", "b_s"):
            pass
    with span("c", "c_s"):
        pass
    assert set(inner) == {"a_s"} and set(outer) == {"b_s"}


def test_a_span_that_raises_still_records():
    t = {}
    with collect(t), pytest.raises(KeyError):
        with span("session.sample", "sample_s") as s:
            raise KeyError("boom")
    assert t["sample_s"] == s.seconds >= 0


def test_named_renames_the_program():
    def fn(a, b):
        return a + b

    prog = named("chain_algorithm3", fn)
    assert prog.__name__ == "chain_algorithm3" and prog(2, 3) == 5
    assert fn.__name__ == "fn"


def test_a_site_built_again_traces_nothing_again():
    """The matmul site's programs (XLA's dot under its variant's name, the
    Pallas kernel) are built once per process: a second instance of the
    site finds every program compiled."""
    import jax

    from repro.autotune.variants import matmul_blocks_site

    traced = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            traced.append(secs)

    site = matmul_blocks_site(m=128, k=128, n=128, blocks=((128, 128, 128),))
    site.workloads(seed=1)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        matmul_blocks_site(m=128, k=128, n=128, blocks=((128, 128, 128),)).workloads(seed=2)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert traced == []


# -------------------------------------------------------------- campaign ---

def test_campaign_timings_hold_the_stage_keys(tmp_path):
    spec = _chain_spec(eps=-1.0)   # never converges: every session steps
    run_shard(spec, str(tmp_path), 0)
    (path,) = glob.glob(os.path.join(str(tmp_path), "shard-*.timings.json"))
    with open(path) as fh:
        t = json.load(fh)
    assert OLD_KEYS | NEW_KEYS <= set(t)
    assert t["records"] == 2 and t["steps"] == 4
    assert t["sample_s"] + t["analyse_s"] <= t["step_s"]
    assert t["warmup_s"] + t["first_s"] <= t["build_s"]
    assert all(t[k] > 0 for k in NEW_KEYS)


def test_cost_model_campaign_with_spans_needs_no_jax():
    """The spans sit in the loop of every census; a cost-model census
    worker still never imports jax."""
    code = """
import sys, tempfile
from repro.core.sweep import SweepSpec, run_shard
spec = SweepSpec(name="cm", backend="cost_model", n_shards=1, max_measurements=6,
                 families={"chain": {"count": 3, "n_matrices": [3], "lo": 8, "hi": 24},
                           "kernel_variants": {"sites": ["matmul"], "sizes": [32],
                                               "per_size": 1}})
store = run_shard(spec, tempfile.mkdtemp(), 0)
assert len(store.records) == 4
assert "jax" not in sys.modules, "jax imported on the cost_model path"
print("OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


# ---------------------------------------------------------------- trace ---

def _events(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    return [(line.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
            for plane in data.planes for line in plane.lines for ev in line.events]


def test_spans_and_program_names_reach_the_profiler_trace(tmp_path):
    import jax

    spec = _chain_spec(families={
        "chain": {"count": 2, "n_matrices": [3], "lo": 8, "hi": 24},
        "kernel_variants": {"sites": ["matmul"], "sizes": [128], "per_size": 1}})
    log_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(log_dir)
    try:
        run_shard(spec, str(tmp_path / "census"), 0)
    finally:
        jax.profiler.stop_trace()
    events = _events(log_dir)
    steps = [(a, b) for _, name, a, b, _ in events if name == "campaign.step"]
    samples = [(a, b, stats) for _, name, a, b, stats in events if name == "session.sample"]
    assert steps and samples
    uids = {inst.uid for inst in spec.expand()}
    for a, b, stats in samples:
        assert stats.get("uid") in uids
        assert any(lo <= a and b <= hi for lo, hi in steps)
    for name in ("campaign.build", "session.warmup", "session.first", "session.analyse",
                 "campaign.save", "campaign.record", "campaign.append"):
        assert any(ev[1] == name for ev in events), name
    modules = {stats.get("hlo_module") for *_, stats in events if "hlo_op" in stats}
    chains = {m for m in modules if m and m.startswith("jit_chain_algorithm")}
    assert chains and "jit_xla_dot" in modules, sorted(m for m in modules if m)
