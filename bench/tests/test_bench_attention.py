"""The attention adapter against the program, on the CPU at a small size:
s=512, window 128, 8 query heads over 1 kv head of 128 (a registered model
config of that size stands in for Trinity-Mini), Pallas interpreted. The
adapter's FLOP tables are the program's and the explainer's, every variant
of both layer kinds matches the plain reference, the bfloat16 control does
not, and the check reads false for a planted fault."""

import os
import time

import numpy as np
import pytest

from bench import harness
from bench.families import attention_variants as av
from bench.peaks import PEAKS
from bench.trace import TraceSummary

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
V5E = PEAKS["TPU v5 lite"]
MODEL = "tiny-attention"  # the ``tiny_attention_model`` fixture's config
#: the cell's own limit on ``err``
LIMIT = harness.load_json(os.path.join(
    ROOT, "bench", "configs", "trinity_mini_attention.json"))["check"]["err_max"]


def tiny_config():
    cfg = harness.load_json(os.path.join(ROOT, "bench", "configs",
                                         "trinity_mini_attention.json"))
    cfg.update(model=MODEL, num_attention_heads=8, num_key_value_heads=1, sliding_window=128)
    cfg["check"]["instances"] = 2
    return cfg


def tiny_cell():
    return harness.Cell(
        name="tiny.attention", config=tiny_config(), traffic={"size": 512, "pool": 4},
        end_to_end=[{"name": "instances_per_min", "unit": "instances/min"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[{"name": n, "unit": "%"} for n in
                   ("flash_live_step_share", "flash_attention_roofline",
                    "measurements_per_alg")])


def layer_params(layer, seed=5):
    (uid, params), = [r for r in av.rows(tiny_config(), {"size": 512, "pool": 4}, seed, 0)
                      if r[1]["layer"] == layer][:1]
    return dict(params, seed=seed)


def program(params):
    from repro.core.family import InstanceSpec, get_family
    from repro.core.sweep import instance_entry

    inst = InstanceSpec(0, "x", av.FAMILY, dict(params))
    flops, _, build = instance_entry(inst)
    return flops, get_family(av.FAMILY).decompose(params), build


def test_rows_are_one_layer_period_and_setup_one_of_each_kind():
    cfg = harness.load_json(os.path.join(ROOT, "bench", "configs",
                                         "trinity_mini_attention.json"))
    traffic = harness.load_json(os.path.join(ROOT, "bench", "traffic",
                                             "trinity_prefill8192.json"))
    rows = av.rows(cfg, traffic, 2**33 + 1, 0)
    assert [p["layer"] for _, p in rows] == ["sliding", "sliding", "sliding", "full"]
    assert [p["layer"] for _, p in av.rows(cfg, traffic, 1, -1)] == ["sliding", "full"]
    p = rows[0][1]
    assert (p["size"], p["heads"], p["kv_heads"], p["head_dim"], p["window"]) == (
        8192, 32, 4, 128, 2048)
    assert rows[3][1]["window"] is None and p["config"] == "trinity-mini"
    assert len({p["seed"] for _, p in rows}) == 4
    assert av.rows(cfg, traffic, 2**33 + 1, 0) == rows


@pytest.mark.parametrize("layer", ["sliding", "full"])
def test_flop_table_is_the_programs_and_the_explainers(tiny_attention_model, layer):
    params = layer_params(layer)
    flops, decomp, _ = program(params)
    mine = {name: sum(w.flops for w in ws) for name, ws in av.gemms(params).items()}
    assert mine == {k: float(v) for k, v in flops.items()}
    assert mine == {name: sum(k.flops for k in ks) for name, ks in decomp.items()}


@pytest.mark.parametrize("layer", ["sliding", "full"])
def test_flop_table_at_trinity_widths(layer):
    """The published widths at s=8192, counted pair by pair: the shares of
    the rectangle that the kernel's block predicate gives."""
    cfg = harness.load_json(os.path.join(ROOT, "bench", "configs",
                                         "trinity_mini_attention.json"))
    (_, params), = [r for r in av.rows(cfg, {"size": 8192, "pool": 4}, 1, -1)
                    if r[1]["layer"] == layer]
    rect = 4.0 * 8192**2 * 32 * 128
    shares = {name: ws[0].flops / rect for name, ws in av.gemms(params).items()}
    if layer == "sliding":
        assert shares == {"flash_128x512": 0.2734375, "flash_256x512": 0.2734375,
                          "flash_512x1024": 0.328125, "local_chunked": 0.28125,
                          "chunked": 1.0}
    else:
        assert shares == {"flash_128x512": 0.53125, "flash_256x512": 0.53125,
                          "flash_512x1024": 0.5625, "chunked": 1.0}


def test_live_blocks_agree_with_the_kernels_predicate():
    from repro.kernels.flash_attention.flash_attention import grid_steps

    for s, bq, bk, window in [(8192, 128, 512, 2048), (8192, 512, 1024, None),
                              (2048, 256, 512, 512), (512, 128, 512, 128)]:
        live, _ = grid_steps(s, s, block_q=bq, block_k=bk, window=window)
        assert av.live_blocks(s, bq, bk, window) == live


@pytest.mark.parametrize("layer", ["sliding", "full"])
def test_every_variant_matches_the_reference_and_the_control_does_not(tiny_attention_model,
                                                                     layer):
    params = layer_params(layer)
    _, _, build = program(params)
    answers = {name: np.asarray(fn(), np.float32) for name, fn in build().items()}
    assert set(answers) == set(av.algorithms(params))
    errs = harness.answer_errors(av, params, "bfloat16", answers)
    assert max(errs.values()) < LIMIT, errs
    control = harness.answer_errors(av, params, "bfloat16", av.control(params))
    assert min(control.values()) > LIMIT, control


def test_reference_is_float32_attention_per_head():
    """The reference against a float64 numpy evaluation of the same
    softmax(q k^T / sqrt(d)) v with the window mask and GQA by index."""
    params = {"size": 256, "heads": 4, "kv_heads": 2, "head_dim": 128, "window": 64,
              "seed": 9}
    q, k, v = (np.asarray(x, np.float64) for x in av.inputs(params))
    mask = av.visible(256, 64)
    want = np.empty_like(q)
    for i in range(4):
        scores = q[0, :, i] @ k[0, :, i // 2].T / np.sqrt(128)
        scores = np.where(mask, scores, -np.inf)
        p = np.exp(scores - scores.max(axis=1, keepdims=True))
        want[0, :, i] = (p / p.sum(axis=1, keepdims=True)) @ v[0, :, i // 2]
    got = av.reference(params, "bfloat16")["chunked"]
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-6


def test_a_sound_run_is_correct(tiny_attention_model, tmp_path):
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


def test_planted_faults_read_false(tiny_attention_model, tmp_path):
    """A wrong answer, and a record with the shared-math FLOP table (every
    variant at the rectangle), each make the check false."""
    cell, seen = _window_of_one_round(tmp_path)
    answers = {uid: {name: np.asarray(fn(), np.float32) for name, fn in fns.items()}
               for uid, fns in seen.kept.items()}
    assert harness.judge(cell, seen, answers, "cpu").correct
    (uid, out), = answers.items()
    wrong = dict(out, flash_128x512=out["flash_128x512"][:, ::-1])
    verdict = harness.judge(cell, seen, {uid: wrong}, "cpu")
    assert not verdict.correct and verdict.checks["err"]["value"] > LIMIT
    rec = seen.records[uid]
    rect = max(rec["flops"].values())
    seen.records[uid] = dict(rec, flops={name: rect for name in rec["flops"]})
    verdict = harness.judge(cell, seen, answers, "cpu")
    assert not verdict.correct and verdict.checks["bad_records"]["value"] == 1


@pytest.mark.parametrize("keep", [0, 3], ids=["sliding", "full"])
def test_the_control_reads_false_through_the_check(tiny_attention_model, tmp_path, keep):
    """The bfloat16 control in the program's place fails the cell's own
    ``err`` limit in the harness's verdict, where the program's answers of
    the same instance pass it."""
    cell, seen = _window_of_one_round(tmp_path, keep)
    (uid, fns), = seen.kept.items()
    assert seen.rows[uid]["layer"] == ("sliding" if keep == 0 else "full")
    sound = harness.judge(cell, seen, {uid: {name: np.asarray(fn(), np.float32)
                                             for name, fn in fns.items()}}, "cpu")
    assert sound.correct and sound.checks["err"]["max"] == LIMIT
    verdict = harness.judge(cell, seen, {uid: av.control(seen.rows[uid])}, "cpu")
    assert not verdict.correct and verdict.checks["err"]["value"] > LIMIT
    assert all("off the reference" in p for p in verdict.problems)


def test_the_metrics_read_the_window(tiny_attention_model, tmp_path):
    cell, seen = _window_of_one_round(tmp_path, keep=None)
    # 3 sliding + 1 full layer; each flash tiling capped to s=512 runs 4, 2
    # and 1 steps per head, all live
    assert seen.timings["flash_grid_steps"] == seen.timings["flash_live_steps"] == 4 * 8 * 7
    params = next(iter(seen.rows.values()))
    least = av.flash_least_seconds(params, 128, 128, 512, V5E.flops, V5E.hbm_bw)
    trace = TraceSummary(window_s=1.0, busy_s=0.5, n_devices=1, op_events=[
        ("%flash_swa128_128x512 = bf16[8,512,128]{2,1,0} custom-call(...)", 4 * least),
        ("%flash_swa128_128x512.1 = bf16[8,512,128]{2,1,0} custom-call(...)", 4 * least),
        ("%fusion.3 = f32[] fusion(), metadata={op_name=\"attention_flash_128x512\"}", 1.0)])
    window = harness.Window(cell, seen, 2.0, {"seconds": 0.0, "hits": 0, "misses": 0}, V5E,
                            trace)
    metrics = harness.read_metrics(cell.per_layer, window)
    assert metrics["flash_live_step_share"]["value"] == pytest.approx(100.0)
    assert metrics["flash_attention_roofline"]["value"] == pytest.approx(25.0)
    window.trace = TraceSummary(window_s=1.0, busy_s=0.5, n_devices=1)
    seen.timings.pop("flash_grid_steps")
    assert set(harness.read_metrics(cell.per_layer, window)) == {"measurements_per_alg"}
