"""End-to-end system tests: sharded training, elastic resume, serving,
and the full paper pipeline on real measurements."""

import os
import tempfile

import pytest

# distributed system tests need >1 device; set BEFORE jax import
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.core import (  # noqa: E402
    WallClockTimer,
    flops_discriminant_test,
    initial_hypothesis_by_time,
    measure_and_rank,
)
from repro.data import DataConfig, SyntheticLM  # noqa: E402
from repro.distributed.sharding import (  # noqa: E402
    batch_spec,
    make_plan,
    state_specs,
    tree_shardings,
)
from repro.expressions import (  # noqa: E402
    chain_site,
    flops_table,
    get_instance,
)
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import (  # noqa: E402
    ForwardOptions,
    ModelConfig,
    init_lm_params,
    init_lm_state,
    lm_forward,
)
from repro.serve.engine import ServingEngine, make_prefill, make_serve_step  # noqa: E402
from repro.train.elastic import ElasticConfig, ElasticTrainer  # noqa: E402
from repro.train.optimizer import AdamW, cosine_schedule  # noqa: E402
from repro.train.trainer import init_train_state, make_train_step  # noqa: E402

CFG = ModelConfig(
    name="sys-test", n_layers=4, d_model=64, n_heads=8, n_kv_heads=4,
    d_ff=128, vocab_size=512, dtype="float32", param_dtype="float32",
)


def _sharded_params(cfg, mesh):
    params, axes = init_lm_params(cfg, jax.random.PRNGKey(0))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    plan = make_plan(cfg, mesh, mode="train")
    return jax.device_put(params, tree_shardings(plan, axes, shapes)), plan


def test_sharded_training_loss_decreases():
    mesh = make_mesh(n_pods=1, dp=2, tp=4)
    params, _ = _sharded_params(CFG, mesh)
    optimizer = AdamW(schedule=cosine_schedule(1e-3, 5, 100))
    state = init_train_state(CFG, optimizer, params)
    step_fn = make_train_step(CFG, optimizer, ForwardOptions(attn_impl="reference"),
                              num_microbatches=2)
    jstep = jax.jit(step_fn, donate_argnums=(0,))
    data = SyntheticLM(DataConfig(vocab_size=512, seq_len=64, global_batch=8))
    bspec = NamedSharding(mesh, batch_spec(mesh, 8, 1))
    losses = []
    with mesh:
        for step in range(8):
            batch = {k: jax.device_put(v, bspec) for k, v in data.batch(step).items()}
            state, metrics = jstep(state, batch)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_sharded_decode_matches_dense():
    mesh = make_mesh(n_pods=1, dp=2, tp=4)
    params, plan = _sharded_params(CFG, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 24), 0, 512)
    state = init_lm_state(CFG, 8, 32)
    st_shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    state = jax.device_put(state, state_specs(CFG, plan, st_shapes, 8))
    pre = jax.jit(make_prefill(CFG))
    stp = jax.jit(make_serve_step(CFG))
    with mesh:
        _, state = pre(params, state, tokens=tokens[:, :23])
        lg, _ = stp(params, state, tokens[:, 23:24], jnp.int32(23))
    dense_logits, _ = lm_forward(CFG, jax.device_get(params), tokens=tokens)
    ref = np.asarray(dense_logits[:, 23])
    err = np.max(np.abs(np.asarray(lg) - ref)) / (np.max(np.abs(ref)) + 1e-9)
    assert err < 5e-2, err


def test_elastic_train_survives_membership_change():
    mesh_fn = lambda n_hosts: make_mesh(n_pods=1, dp=n_hosts, tp=2)
    with tempfile.TemporaryDirectory() as d:
        data = SyntheticLM(DataConfig(vocab_size=512, seq_len=32, global_batch=8))
        optimizer = AdamW(schedule=cosine_schedule(1e-3, 2, 50))
        trainer = ElasticTrainer(
            cfg=CFG, optimizer=optimizer, data=data,
            ckpt=CheckpointManager(d, keep=3),
            make_mesh_fn=mesh_fn,
            opts=ForwardOptions(attn_impl="reference"),
            elastic_cfg=ElasticConfig(checkpoint_every=4),
        )
        trainer.start(
            n_hosts=4,
            init_params_fn=lambda: init_lm_params(CFG, jax.random.PRNGKey(0))[0],
        )
        # lose half the hosts before step 6
        history = trainer.run(12, membership_events={6: 2})
        steps = [h["step"] for h in history]
        assert steps == list(range(12))
        losses = [h["loss"] for h in history]
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()
        # after the re-mesh the dp width is 2
        assert trainer.mesh.shape["data"] == 2


def test_generation_deterministic_greedy():
    cfg = CFG.replace(vocab_size=128)
    params, _ = init_lm_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_len=32, temperature=0.0)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 128)
    out1 = engine.generate(prompts, n_new=8)
    out2 = engine.generate(prompts, n_new=8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 16)


def test_full_paper_pipeline_on_chain_instance():
    """Measure -> filter -> rank -> FLOPs test on a real instance: the
    system-level behaviour the paper defines."""
    inst = get_instance("fig3_75", smoke=True)
    algs = inst.algorithms()
    flops = flops_table(algs)
    workloads = chain_site(inst.dims).workloads(seed=0)
    timer = WallClockTimer(workloads)
    single = {n: timer.measure(n) for n in workloads}
    res = measure_and_rank(
        initial_hypothesis_by_time(single), timer,
        m_per_iteration=3, eps=0.03, max_measurements=24,
    )
    rep = flops_discriminant_test(res, flops)
    assert res.measurements_per_alg <= 24
    assert set(res.ranks) == set(flops)
    assert rep.reason in ("none", "faster_outside_min_flops", "min_flops_split")
