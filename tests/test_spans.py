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

from repro.core.programs import ProgramCache, named
from repro.core.spans import collect, count, span
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


def test_count_adds_to_the_active_sink_only():
    count("programs_built")
    t = {}
    with collect(t):
        count("programs_built")
        count("programs_reused", 3)
        count("programs_reused")
    assert t == {"programs_built": 1, "programs_reused": 4}


def test_a_program_cache_builds_once_per_key_and_drops_the_oldest():
    cache, built, t = ProgramCache(maxsize=2), [], {}

    def build(key):
        return lambda: built.append(key) or object()

    with collect(t):
        a = cache.get("a", build("a"))
        assert cache.get("a", build("a")) is a
        cache.get("b", build("b"))
        cache.get("a", build("a"))       # "a" is now the newest
        cache.get("c", build("c"))       # drops "b"
        assert cache.get("a", build("a")) is a
        cache.get("b", build("b"))
    assert built == ["a", "b", "c", "b"]
    assert t == {"programs_built": 4, "programs_reused": 3}
    cache.clear()
    with collect(t):
        assert cache.get("a", build("a")) is not a
    assert t["programs_built"] == 5


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


def _traced_while(build):
    """``build()``'s result, its counters and the names of the functions it
    traced."""
    import jax

    traced, t = [], {}

    def listen(event, secs, fun_name="", **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            traced.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with collect(t):
            out = build()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return out, t, traced


def _chain_workloads(lo, hi, seed):
    from repro.core.family import InstanceSpec, get_family

    inst = InstanceSpec(index=0, uid=f"chain-{seed}", family="chain",
                        params={"n_matrices": 4, "lo": lo, "hi": hi, "seed": seed})
    flops, _, build = get_family("chain").entry(inst)
    assert len(flops) == 6
    return build


def test_a_shared_chain_program_retraces_per_shape():
    """Other dims reuse the programs kept under the same name and steps
    (names follow the FLOPs order, so a few steps differ), which trace again
    for the new shapes and still compute the chain's product."""
    import numpy as np

    from repro.core.programs import PROGRAMS
    from repro.expressions.algorithms import make_chain_inputs, reference_product
    from repro.expressions.instances import random_instance

    PROGRAMS.clear()
    _traced_while(_chain_workloads(32, 32, seed=1))
    table, t, traced = _traced_while(_chain_workloads(8, 24, seed=3))
    assert t["programs_reused"] >= 1 and t["programs_built"] + t["programs_reused"] == 6
    assert sorted(n for n in traced if n.startswith("chain_")) == sorted(
        f"chain_{name}" for name in table)
    dims = random_instance(4, 8, 24, seed=3).dims
    assert len(set(dims)) > 1
    ref = np.asarray(reference_product(make_chain_inputs(dims, seed=3)))
    for name, fn in table.items():
        out = np.asarray(fn())
        assert out.shape == (dims[0], dims[-1]), name
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4, err_msg=name)


def test_a_layer_instances_spans_carry_its_layer():
    from repro.core.session import MeasurementSession
    from repro.core.spans import instance_args
    from repro.core.measure import CostModelTimer

    assert instance_args("u", {"size": 8}) == {"uid": "u"}
    assert instance_args("u", {"layer": "full"}) == {"uid": "u", "layer": "full"}
    session = MeasurementSession("u", ["a"], CostModelTimer({"a": 1.0}),
                                 meta={"params": {"layer": "sliding"}})
    assert session._span_args() == {"uid": "u", "layer": "sliding"}


def _gram(seed):
    from repro.expressions.generalized import FAMILIES

    return FAMILIES["gram"](n=32).workloads(seed)


def _attention_layer(seed):
    from repro.core.family import InstanceSpec, get_family

    inst = InstanceSpec(index=0, uid=f"attn-{seed}", family="kernel_variants", params={
        "site": "attention", "config": "tiny-attention", "layer": "sliding",
        "size": 512, "seed": seed})
    return get_family("kernel_variants").entry(inst)[2]()


def _matmul_site(seed):
    from repro.autotune.variants import matmul_blocks_site

    return matmul_blocks_site(m=128, k=128, n=128, blocks=((128, 128, 128),)).workloads(seed)


def _moe_site(seed):
    from repro.autotune.variants import moe_dispatch_site

    return moe_dispatch_site(tokens=64, d=32, e=4, top_k=2, d_ff=16).workloads(seed)


def _ssd_site(seed):
    from repro.autotune.variants import ssd_chunk_site

    return ssd_chunk_site(b=1, s=64, h=2, p=8, n=8, chunks=(16, 32)).workloads(seed)


def _explainer_segments(seed):
    """Two kernel segments of an explanation, warmed as the explainer warms
    them."""
    from repro.core.programs import warm
    from repro.explain.decompose import KernelSpec, build_kernel_workload

    segments = {k.label: build_kernel_workload(k, seed=seed)
                for k in (KernelSpec("gemm", (8, 4, 2)), KernelSpec("syrk", (8, 4)))}
    warm(segments)
    return segments


#: case -> (the table of instance ``seed``, the program names of a table)
SECOND_INSTANCES = {
    "chain": (lambda seed: _chain_workloads(32, 32, seed)(),
              lambda table: [f"chain_{name}" for name in table]),
    "gram": (_gram, list),
    "attention_layer": (_attention_layer,
                        lambda table: [f"attention_{name}" for name in table]),
    "matmul_site": (_matmul_site, lambda table: ["matmul", "xla_dot"]),
    "moe_site": (_moe_site, list),
    "ssd_site": (_ssd_site, list),
    "explainer_segment": (_explainer_segments, lambda table: ["kernel_gemm", "kernel_syrk"]),
}


@pytest.mark.parametrize("case", sorted(SECOND_INSTANCES))
def test_a_second_instance_builds_nothing(case, request):
    """Every program is built once per process: a second instance of a
    site, with other data, builds and traces nothing, and each table warms
    every algorithm once. Each flash variant's build counts its grid steps."""
    from repro.core.programs import PROGRAMS

    if case == "attention_layer":
        request.getfixturevalue("tiny_attention_model")
    build, programs = SECOND_INSTANCES[case]
    PROGRAMS.clear()
    first, t1, traced1 = _traced_while(lambda: build(1))
    second, t2, traced2 = _traced_while(lambda: build(2))
    names = programs(first)
    assert set(first) == set(second) and len(first) >= 2
    assert t1.pop("programs_built") == len(names) and "programs_reused" not in t1
    assert sorted(x for x in traced1 if x in names) == sorted(names)
    assert t2.pop("programs_reused") == len(names) and "programs_built" not in t2
    assert traced2 == []
    assert t1.pop("warm_calls") == t2.pop("warm_calls") == len(first)
    if case == "attention_layer":
        # 8 heads; 4 + 2 + 1 steps of the capped tilings, all live at s=512
        assert len(first) == 7
        assert t2 == {"flash_grid_steps": 56, "flash_live_steps": 56} == t1
    else:
        assert t1 == t2 == {}


# -------------------------------------------------------------- campaign ---

def test_campaign_timings_hold_the_stage_keys(tmp_path):
    spec = _chain_spec(eps=-1.0)   # never converges: every session steps
    run_shard(spec, str(tmp_path), 0)
    (path,) = glob.glob(os.path.join(str(tmp_path), "shard-*.timings.json"))
    with open(path) as fh:
        t = json.load(fh)
    assert OLD_KEYS | NEW_KEYS <= set(t)
    assert t["records"] == 2 and t["steps"] == 4
    assert t.get("programs_built", 0) + t.get("programs_reused", 0) == 4  # 2 x 2 algorithms
    assert t["warm_calls"] == 4
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
    assert chains and {"jit_xla_dot", "jit_matmul"} <= modules, sorted(m for m in modules if m)
