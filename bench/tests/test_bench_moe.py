"""The MoE adapter against the program. At Trinity-Mini's widths the
adapter's routing and FLOP tables are the program's on 20 seeds; on the CPU
at a small size (d 128, top-2 of 8 experts of width 64; a registered model
config of that size stands in for Trinity-Mini, the Pallas grouped matmul
interpreted) the reference is a float64 evaluation's, every variant matches
it, and planted faults read false at the configuration's own limit."""

import os
import statistics
import time

import numpy as np
import pytest

from bench import harness
from bench.families import moe_variants as mv
from bench.peaks import PEAKS
from bench.trace import TraceSummary

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
V5E = PEAKS["TPU v5 lite"]
CONFIG = harness.load_json(os.path.join(ROOT, "bench", "configs", "trinity_mini_moe.json"))
TRAFFIC = harness.load_json(os.path.join(ROOT, "bench", "traffic",
                                         "trinity_moe_prefill8192.json"))
#: the cell's own limit on ``err``
LIMIT = CONFIG["check"]["err_max"]
TINY_TRAFFIC = {"size": 256, "pool": 4, "bias_scale": 0.3}


def tiny_config():
    cfg = harness.load_json(os.path.join(ROOT, "bench", "configs", "trinity_mini_moe.json"))
    cfg.update(model="tiny-moe", hidden_size=128, num_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=64)
    cfg["check"]["instances"] = 2
    return cfg


def tiny_cell():
    return harness.Cell(
        name="tiny.moe", config=tiny_config(), traffic=TINY_TRAFFIC,
        end_to_end=[{"name": "instances_per_min", "unit": "instances/min"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[{"name": n, "unit": "%"} for n in
                   ("gmm_live_row_share", "gmm_roofline", "route_ms_per_instance",
                    "measurements_per_alg")])


def tiny_params(seed=5):
    return dict(mv.rows(tiny_config(), TINY_TRAFFIC, seed, 0)[1][1], seed=seed)


def program_entry(params):
    from repro.core.family import InstanceSpec, get_family
    from repro.core.sweep import instance_entry

    flops, _, build = instance_entry(InstanceSpec(0, "x", mv.FAMILY, dict(params)))
    return flops, get_family(mv.FAMILY).decompose(params), build


def test_rows_are_stage_zeros_moe_layers_at_published_widths():
    rows = mv.rows(CONFIG, TRAFFIC, 2**33 + 1, 0)
    assert [p["layer_index"] for _, p in rows] == [2, 3, 4, 5]
    assert [p["layer_index"] for _, p in mv.rows(CONFIG, TRAFFIC, 1, -1)] == [2, 3, 4, 5]
    p = rows[0][1]
    assert (p["size"], p["d_model"], p["experts"], p["top_k"], p["expert_d_ff"],
            p["shared_d_ff"], p["route_scale"], p["layer"]) == (
        8192, 2048, 128, 8, 1024, 1024, 2.826, "moe")
    assert len({p["seed"] for _, p in rows}) == 4
    assert mv.rows(CONFIG, TRAFFIC, 2**33 + 1, 0) == rows


def test_traffic_skews_the_routing_as_stated():
    """On 5 seeds, the hottest expert holds 2.5-3.5x the mean load (median),
    no expert is empty, and the capacity gather runs 2048 slots."""
    ratios = []
    for seed in range(5):
        for _, p in mv.rows(CONFIG, TRAFFIC, seed, 0):
            load = mv.loads(p)
            ratios.append(load.max() / load.mean())
            assert load.min() > 0 and mv.executed_rows("gather_capacity", p) == 128 * 2048
    assert 2.5 <= statistics.median(ratios) <= 3.5


def test_routing_and_flop_table_are_the_programs_on_20_seeds():
    from repro.autotune.variants import moe_routing

    for seed in range(20):
        (_, params), = mv.rows(CONFIG, TRAFFIC, 1000 + seed, 0)[seed % 4:seed % 4 + 1]
        flops, decomp, _ = program_entry(params)
        mine = {name: sum(w.flops for w in ws) for name, ws in mv.gemms(params).items()}
        assert mine == flops
        assert mine == {name: sum(k.flops for k in ks) for name, ks in decomp.items()}
        assert min(mine, key=mine.get) == "ragged_dot"
        ids, weights = mv.routing(params)
        program = moe_routing(8192, 128, 8, params["seed"], layer=params["layer_index"],
                              bias_scale=0.05, route_scale=2.826, norm_topk=True)
        assert np.array_equal(ids, program.ids) and np.array_equal(weights, program.weights)


def _float64_layer(params, *, drop=None, scale=1.0, shared_gate=None):
    """The layer in float64 numpy from the adapter's bfloat16 x and weights,
    silu(g) * h rounded to bfloat16 (the stated operand precision); a
    planted fault: ``drop`` one (token, slot) assignment, ``scale`` the
    routing weights, or a sigmoid ``shared_gate`` [d] on the shared expert."""
    import jax.numpy as jnp

    (x,) = mv.inputs(params)
    x = np.asarray(x, np.float64)
    ids, weights = mv.routing(params)
    weights = weights.astype(np.float64) * scale
    if drop is not None:
        weights[drop] = 0.0
    e = int(params["experts"])
    out = np.zeros_like(x)
    for i in range(e + 1):
        wg, wi, wo = (np.asarray(w, np.float64) for w in mv.expert_weights(params, i))
        g, h = x @ wg, x @ wi
        a = np.asarray(jnp.asarray(g / (1.0 + np.exp(-g)) * h, jnp.float32).astype(jnp.bfloat16),
                       np.float64)
        y = a @ wo
        if i == e:
            gate = 1.0 if shared_gate is None else 1.0 / (1.0 + np.exp(-(x @ shared_gate)))
            out += y * (gate if np.isscalar(gate) else gate[:, None])
        else:
            out += (weights * (ids == i)).sum(axis=1)[:, None] * y
    return out


def test_reference_is_the_float64_layer():
    params = tiny_params(9)
    got = mv.reference(params, "bfloat16")["ragged_dot"]
    want = _float64_layer(params)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-6


def test_every_variant_matches_the_reference_and_the_control_does_not(tiny_moe_model):
    params = tiny_params()
    flops, _, build = program_entry(params)
    assert flops == {name: sum(w.flops for w in ws) for name, ws in mv.gemms(params).items()}
    answers = {name: np.asarray(fn(), np.float32) for name, fn in build().items()}
    assert set(answers) == set(mv.algorithms(params))
    errs = harness.answer_errors(mv, params, "bfloat16", answers)
    assert max(errs.values()) < LIMIT, errs
    control = harness.answer_errors(mv, params, "bfloat16", mv.control(params))
    assert min(control.values()) > LIMIT, control


@pytest.mark.parametrize("fault", ["dropped_assignment", "no_route_scale",
                                   "gated_shared_expert"])
def test_planted_faults_read_false_at_the_limit(fault):
    params = tiny_params(7)
    d = int(params["d_model"])
    planted = {
        "dropped_assignment": dict(drop=(17, 1)),
        "no_route_scale": dict(scale=1.0 / float(params["route_scale"])),
        "gated_shared_expert": dict(
            shared_gate=np.random.default_rng(0).standard_normal(d) / np.sqrt(d)),
    }[fault]
    sound = harness.answer_errors(mv, params, "bfloat16",
                                  {"ragged_dot": _float64_layer(params)})
    assert sound["ragged_dot"] < LIMIT / 10
    errs = harness.answer_errors(mv, params, "bfloat16",
                                 {"ragged_dot": _float64_layer(params, **planted)})
    assert errs["ragged_dot"] > LIMIT, errs


def test_a_sound_run_is_correct(tiny_moe_model, tmp_path):
    result = harness.run_cell(tiny_cell(), 2**31 + 3, 0.5, False, time.perf_counter(), V5E,
                              workdir=str(tmp_path))
    assert result["correct"], result["checks"]
    assert result["attempted"] % 4 == 0 and result["failed"] == 0
    assert result["checks"]["err"]["value"] < LIMIT


def _window_of_one_round(tmp_path, keep=0):
    cell = tiny_cell()
    census = harness.Census(cell, harness.sweep_spec(cell), V5E, str(tmp_path),
                            harness.Spans(False))
    seen = harness.Observed()
    census.run_round(0, 2**31 + 3, seen, keep_index=keep)
    return cell, seen


def test_the_control_and_a_dropped_row_read_false_through_the_check(tiny_moe_model,
                                                                     tmp_path):
    """The bfloat16 control in the program's place, and the program's own
    answer with one token's row zeroed, fail the cell's ``err`` limit in the
    harness's verdict, where the program's answers pass it."""
    cell, seen = _window_of_one_round(tmp_path)
    (uid, fns), = seen.kept.items()
    out = {name: np.asarray(fn(), np.float32) for name, fn in fns.items()}
    sound = harness.judge(cell, seen, {uid: out}, "cpu")
    assert sound.correct and sound.checks["err"]["max"] == LIMIT
    verdict = harness.judge(cell, seen, {uid: mv.control(seen.rows[uid])}, "cpu")
    assert not verdict.correct and verdict.checks["err"]["value"] > LIMIT
    dropped = dict(out)
    dropped["gmm_128"] = out["gmm_128"].copy()
    dropped["gmm_128"][3] = 0.0
    verdict = harness.judge(cell, seen, {uid: dropped}, "cpu")
    assert not verdict.correct and "gmm_128" in verdict.problems[0]


def test_the_metrics_read_the_window(tiny_moe_model, tmp_path):
    from repro.autotune.variants import moe_routing

    moe_routing.cache_clear()       # the routing is timed where it is computed
    cell, seen = _window_of_one_round(tmp_path, keep=None)
    rows = list(seen.rows.values())
    assigned = 3 * 4 * 256 * 2                      # 3 gmm variants x 4 instances
    executed = sum(mv.executed_rows(f"gmm_{tm}", p) for p in rows for tm in mv.GMM_TM)
    assert seen.timings["gmm_assigned_rows"] == assigned
    assert seen.timings["gmm_executed_rows"] == executed
    assert seen.timings["route_s"] > 0
    least = sum(mv.gmm_least_seconds(p, 128, 64, V5E.flops, V5E.hbm_bw) for p in rows) / 4
    trace = TraceSummary(window_s=1.0, busy_s=0.5, n_devices=1, op_events=[
        ("%gmm_128x128x64 = f32[512,64]{1,0:T(8,128)} custom-call(...)", 4 * least),
        ("%gmm_128x128x64.1 = f32[512,64]{1,0:T(8,128)} custom-call(...)", 4 * least),
        ("%fusion.3 = f32[] fusion(), metadata={op_name=\"moe_gmm_128\"}", 1.0)])
    window = harness.Window(cell, seen, 2.0, {"seconds": 0.0, "hits": 0, "misses": 0}, V5E,
                            trace)
    metrics = harness.read_metrics(cell.per_layer, window)
    assert metrics["gmm_live_row_share"]["value"] == pytest.approx(100.0 * assigned / executed)
    assert metrics["gmm_roofline"]["value"] == pytest.approx(25.0)
    assert metrics["route_ms_per_instance"]["value"] == pytest.approx(
        1e3 * seen.timings["route_s"] / 4)
    window.trace = TraceSummary(window_s=1.0, busy_s=0.5, n_devices=1)
    for key in ("gmm_executed_rows", "route_s"):
        seen.timings.pop(key)
    assert set(harness.read_metrics(cell.per_layer, window)) == {"measurements_per_alg"}


def test_gmm_least_seconds_is_the_calls_floor():
    """The gate/up call (result f wide, k = d) and the down call (result d
    wide, k = f) at Trinity-Mini's widths: compute-bound, 2 k n per
    executed row over the chip's peak."""
    (_, params), = mv.rows(CONFIG, TRAFFIC, 3, 0)[:1]
    rows = mv.executed_rows("gmm_128", params)
    up = mv.gmm_least_seconds(params, 128, 1024, V5E.flops, V5E.hbm_bw)
    down = mv.gmm_least_seconds(params, 128, 2048, V5E.flops, V5E.hbm_bw)
    assert up == pytest.approx(2.0 * 2048 * 1024 * rows / V5E.flops)
    assert down == pytest.approx(up)
