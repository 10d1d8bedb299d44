"""Trinity-Mini's MoE layer against a plain reference, on the CPU at the
``SMOKE`` widths (d 64, top-2 of 8 experts of width 32, one shared expert of
width 32): the model path's sigmoid router and its dispatches, which drop
no token under this config; every algorithm of the ``moe`` census site (the
Pallas grouped matmul interpreted); the router's equations by hand; no
dropped token under a routing that loads one expert with most tokens; and
the site's FLOP table against megablox's own tile metadata."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.autotune.variants import (GMM_TM, moe_algorithms, moe_layer_site, moe_routing,
                                     sigmoid_topk)
from repro.configs import get_config
from repro.core.spans import collect
from repro.models.layers import split_params
from repro.models.moe import apply_moe, init_moe

SMOKE = get_config("trinity-mini", smoke=True)
HI = jax.lax.Precision.HIGHEST


def reference_moe(x, logits, bias, routed, shared, *, k, route_scale, norm_topk=True,
                  round_a=None):
    """The published MoE layer in plain float32 ``jax.numpy`` at ``HIGHEST``,
    with no dispatch trick: sigmoid scores, the top ``k`` of scores + bias,
    the chosen scores renormalised and scaled, every expert on every token
    weighted by its routing weight (zero off the top k), plus the ungated
    shared expert. ``round_a``: the dtype silu(g) * h is rounded to before
    the down projection, as the site's precision states (None: kept)."""
    x = x.astype(jnp.float32)
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, ids = jax.lax.top_k(scores + bias, k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * route_scale
    t, e = scores.shape
    combine = jnp.zeros((t, e), jnp.float32).at[jnp.arange(t)[:, None], ids].add(w)

    def ffn(wg, wi, wo):
        a = jax.nn.silu(jnp.dot(x, wg.astype(jnp.float32), precision=HI)) * jnp.dot(
            x, wi.astype(jnp.float32), precision=HI)
        if round_a is not None:
            a = a.astype(round_a).astype(jnp.float32)
        return jnp.dot(a, wo.astype(jnp.float32), precision=HI)

    out = ffn(shared["wg"], shared["wi"], shared["wo"])
    for i in range(e):
        out = out + combine[:, i:i + 1] * ffn(routed["wg"][i], routed["wi"][i], routed["wo"][i])
    return out


def rel_err(out, ref):
    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))


# ------------------------------------------------------------ the model path ---


@pytest.mark.parametrize("bias", ["skewed", "one_expert_hot"])
@pytest.mark.parametrize("dispatch", ["dense", "gather"])
def test_apply_moe_sigmoid_router_matches_the_reference(dispatch, bias):
    """SMOKE's layer through ``apply_moe`` (float32, a random selection
    bias) against the reference on the router GEMM's own logits; at
    ``one_expert_hot`` the bias sends every token to expert 0, and the
    config's ``gather`` (no drops: sorted grouped GEMMs) keeps them all."""
    cfg = SMOKE
    assert (cfg.moe_score_func, cfg.moe_route_scale, cfg.moe_shared_gate,
            cfg.moe_drop_tokens) == ("sigmoid", 2.826, False, False)
    params, _ = split_params(init_moe(cfg, jax.random.PRNGKey(3)))
    assert "gate" not in params["shared"] and params["expert_bias"].shape == (cfg.n_experts,)
    params["expert_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (cfg.n_experts,))
    if bias == "one_expert_hot":
        params["expert_bias"] = params["expert_bias"].at[0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, cfg.d_model), jnp.float32)
    y, _ = apply_moe(cfg, params, x, dispatch=dispatch)
    x2d = x.reshape(-1, cfg.d_model)
    logits = jnp.dot(x2d, params["router"], precision=HI)
    ref = reference_moe(x2d, logits, params["expert_bias"], params, params["shared"],
                        k=cfg.top_k, route_scale=cfg.moe_route_scale)
    assert rel_err(y.reshape(-1, cfg.d_model), ref) < 1e-5


def test_softmax_configs_keep_their_gather_and_gated_shared_expert():
    """A softmax config (Qwen2-MoE's form: gated shared expert) is as it
    was: no selection bias, the gate in the tree, gather and dense agree
    where the capacity holds every token, and the gather drops the tokens
    that overflow its capacity."""
    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    assert (cfg.moe_score_func, cfg.moe_route_scale, cfg.moe_shared_gate,
            cfg.moe_drop_tokens) == ("softmax", 1.0, True, True)
    params, _ = split_params(init_moe(cfg, jax.random.PRNGKey(0)))
    assert "gate" in params["shared"] and "expert_bias" not in params
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.d_model), jnp.float32)
    dense, _ = apply_moe(cfg, params, x, dispatch="dense")
    roomy = cfg.replace(moe_capacity_factor=cfg.n_experts / cfg.top_k)
    gather, _ = apply_moe(roomy, params, x, dispatch="gather")
    assert rel_err(gather, dense) < 1e-5
    # a flat router ties every expert, so top-k sends all 16 tokens to the
    # first k experts, past the default capacity
    flat = dict(params, router=jnp.zeros_like(params["router"]))
    dense, _ = apply_moe(cfg, flat, x, dispatch="dense")
    gather, _ = apply_moe(cfg, flat, x, dispatch="gather")
    assert rel_err(gather, dense) > 0.1


def test_router_equations_by_hand():
    """Three tokens over four experts, top 2: the bias picks, the scores
    weigh, the weights sum to ``route_scale``; the model's device router
    and the site's host router agree with the arithmetic."""
    logits = np.array([[0.0, 1.0, -1.0, 2.0],
                       [0.5, 0.5, 0.5, 0.5],
                       [3.0, -2.0, 0.0, 1.0]])
    bias = np.array([0.0, 0.0, 0.6, -0.5])
    sig = lambda v: 1.0 / (1.0 + math.exp(-v))
    # token 0: choice = [.5, .731, .869, .381] -> experts 2, 1
    # token 1: all scores .622; choice [.622, .622, 1.222, .122] -> 2, then 0 (lower index)
    # token 2: choice = [.953, .119, 1.1, .231] -> 2, 0
    want_ids = [[2, 1], [2, 0], [2, 0]]
    want_w = []
    for row, (i, j) in zip(logits, want_ids):
        si, sj = sig(row[i]), sig(row[j])
        want_w.append([2.826 * si / (si + sj + 1e-20), 2.826 * sj / (si + sj + 1e-20)])
    ids, w = sigmoid_topk(logits, bias, 2, 2.826)
    assert ids.tolist() == want_ids
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    np.testing.assert_allclose(w.sum(axis=1), 2.826, rtol=1e-6)
    from repro.models.moe import _routing

    cfg = SMOKE.replace(d_model=4, n_experts=4, top_k=2)
    params = {"router": jnp.eye(4), "expert_bias": jnp.asarray(bias, jnp.float32)}
    _, top_w, top_i, _ = _routing(cfg, params, jnp.asarray(logits, jnp.float32))
    assert np.asarray(top_i).tolist() == want_ids
    np.testing.assert_allclose(np.asarray(top_w), want_w, rtol=1e-5)


# ------------------------------------------------------------- the census site ---


def smoke_site(seed=3, layer=1, bias_scale=0.3, tokens=256):
    cfg = SMOKE
    return moe_layer_site(t=tokens, d=cfg.d_model, e=cfg.n_experts, k=cfg.top_k,
                          f=cfg.resolved_moe_d_ff, shared_f=cfg.resolved_shared_d_ff,
                          layer=layer, seed=seed, route_scale=cfg.moe_route_scale,
                          bias_scale=bias_scale)


def site_reference(site, seed, layer, bias_scale):
    """The reference on the site's inputs: its logits and bias redrawn by
    the site's recipe, its weights, silu(g) * h rounded to bfloat16 as the
    site's precision states."""
    from repro.autotune.variants import MOE_WEIGHT_SEED, moe_layer_weights

    cfg = SMOKE
    x, _, _, routing = site.make_inputs(seed)
    t = x.shape[0]
    logits = np.random.default_rng(seed).standard_normal((t, cfg.n_experts))
    bias = bias_scale * np.random.default_rng([MOE_WEIGHT_SEED, layer]).standard_normal(
        cfg.n_experts)
    routed, shared = moe_layer_weights(layer, cfg.d_model, cfg.n_experts, cfg.resolved_moe_d_ff,
                                       cfg.resolved_shared_d_ff)
    ref = reference_moe(x, jnp.asarray(logits, jnp.float32), jnp.asarray(bias, jnp.float32),
                        routed, shared, k=cfg.top_k, route_scale=cfg.moe_route_scale,
                        round_a=jnp.bfloat16)
    return ref, routing


@pytest.mark.parametrize("bias_scale", [0.3, 10.0], ids=["skewed", "one_expert_hot"])
def test_every_site_algorithm_matches_the_reference(bias_scale):
    """Every algorithm, the gmm tilings interpreted, against the reference;
    at ``one_expert_hot`` the bias sends every token to the same two of the
    eight experts (all of them to one slot each), and nothing drops."""
    site = smoke_site(bias_scale=bias_scale)
    ref, routing = site_reference(site, 3, 1, bias_scale)
    if bias_scale > 1:
        assert sorted(routing.loads.tolist())[-2:] == [256, 256]
        assert routing.capacity == 256
    table = site.workloads(3)
    assert list(table) == moe_algorithms()
    for name, fn in table.items():
        out = fn()
        assert out.dtype == jnp.float32 and out.shape == ref.shape
        assert rel_err(out, ref) < 1e-4, name


def test_flop_table_counts_megablox_tiles():
    """The gmm variants' FLOPs are 6 d f per row the kernel executes: the
    tiles ``make_group_metadata`` counts for the routing's group sizes,
    times the m tile; ragged_dot is the assignments', the minimum."""
    from repro.kernels.gmm.gmm import make_group_metadata

    cfg = SMOKE
    site = smoke_site()
    routing = site.make_inputs(3)[3]
    flops = site.flops_table()
    d, f, sf, t, k = cfg.d_model, cfg.resolved_moe_d_ff, cfg.resolved_shared_d_ff, 256, cfg.top_k
    shared = 6.0 * d * sf * t
    for tm in GMM_TM:
        _, tiles = make_group_metadata(
            group_sizes=jnp.asarray(routing.loads, jnp.int32), m=t * k, tm=tm,
            start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=cfg.n_experts,
            visit_empty_groups=False)
        assert flops[f"gmm_{tm}"] == 6.0 * d * f * int(tiles) * tm + shared
    assert flops["ragged_dot"] == 6.0 * d * f * t * k + shared
    assert flops["dense"] == 6.0 * d * f * t * cfg.n_experts + shared
    assert min(flops, key=flops.get) == "ragged_dot"
    assert sorted(flops.values()).count(flops["ragged_dot"]) == 1


def test_tiles_visited_is_make_group_metadatas_count():
    from repro.kernels.gmm.gmm import make_group_metadata, tiles_visited

    rng = np.random.default_rng(0)
    for trial in range(20):
        e, tm = int(rng.integers(2, 17)), int(rng.choice([8, 128, 256]))
        m = tm * int(rng.integers(1, 9))
        sizes = rng.multinomial(m, rng.dirichlet(np.full(e, 0.3))).astype(np.int32)
        _, tiles = make_group_metadata(group_sizes=jnp.asarray(sizes), m=m, tm=tm,
                                       start_group=jnp.zeros((), jnp.int32),
                                       num_nonzero_groups=e, visit_empty_groups=False)
        assert tiles_visited(sizes, tm) == int(tiles), (sizes, tm)


def test_entry_and_explainer_see_each_variants_flops(tiny_moe_model):
    """Through the census family: the instance's FLOP table, the explainer's
    kernels and the site's own table agree; building counts the gmm rows
    and the routing once."""
    from repro.core.family import InstanceSpec, get_family
    from repro.core.sweep import instance_entry

    params = {"site": "moe", "config": "tiny-moe", "layer": "moe", "layer_index": 3,
              "size": 256, "seed": 11, "bias_scale": 0.3}
    timings = {}
    with collect(timings):
        flops, meta, build = instance_entry(InstanceSpec(0, "x", "kernel_variants", params))
        decomp = get_family("kernel_variants").decompose(params)
        table = build()
    assert set(table) == set(flops) == set(moe_algorithms())
    assert {n: sum(k.flops for k in ks) for n, ks in decomp.items()} == flops
    assert {k[0] for ks in meta["kernels"].values() for k in ks} == {"ggemm", "gather", "gemm"}
    site = get_family("kernel_variants").variant_site(params)
    assert site.flops_table() == flops
    assert timings["moe_assignments"] == 256 * 2
    assert timings["gmm_assigned_rows"] == 3 * 256 * 2
    from repro.kernels.gmm.gmm import tiles_visited

    routing = moe_routing(256, 8, 2, 11, layer=3, bias_scale=0.3, route_scale=2.826,
                          norm_topk=True)
    assert timings["moe_peak_load"] == routing.loads.max()
    assert timings["gmm_executed_rows"] == sum(tiles_visited(routing.loads, tm) * tm
                                               for tm in GMM_TM)
    assert timings["route_s"] > 0


def test_moe_site_needs_a_config_and_keeps_the_default_grid():
    from repro.core.family import TOY_KERNEL_SITES, get_family

    fam = get_family("kernel_variants")
    with pytest.raises(ValueError, match="name a config"):
        fam.expand_grid({"sites": ["moe"], "sizes": [256]})
    default = fam.expand_grid({"sizes": [64], "per_size": 1})
    assert [i.params["site"] for i in default] == list(TOY_KERNEL_SITES)


def test_kernel_config_expands_the_models_moe_layers():
    """``--kernel-sites moe --kernel-config trinity-mini``: one instance per
    MoE layer and seed at the published widths."""
    from repro.core.family import get_family

    rows = get_family("kernel_variants").expand_grid(
        {"sites": ["moe"], "sizes": [8192], "per_size": 1, "config": "trinity-mini"})
    assert len(rows) == 32
    assert rows[2].uid == "kernel_variants-moe-trinity-mini-l02-n8192-s000"
    assert rows[2].params == {
        "site": "moe", "config": "trinity-mini", "layer": "moe", "layer_index": 2,
        "size": 8192, "seed": 0, "d_model": 2048, "experts": 128, "top_k": 8,
        "expert_d_ff": 1024, "shared_d_ff": 1024, "route_scale": 2.826}
    with pytest.raises(ValueError, match="not a multiple"):
        get_family("kernel_variants").expand_grid(
            {"sites": ["moe"], "sizes": [1000], "config": "trinity-mini"})
    with pytest.raises(ValueError, match="not a sigmoid-routed"):
        get_family("kernel_variants").expand_grid(
            {"sites": ["moe"], "sizes": [8192], "config": "qwen2-moe-a2.7b"})
