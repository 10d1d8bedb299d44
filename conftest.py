"""Fixtures shared by the program's tests (``tests/``) and the benchmark's
(``bench/tests/``)."""

import pytest


@pytest.fixture
def tiny_attention_model(monkeypatch):
    """A registered model config ``tiny-attention`` with a Trinity-Mini-like
    layer pattern (3 sliding + 1 full) at a CPU size: 8 query heads over 1
    kv head of 128, window 128."""
    import repro.configs as configs
    from repro.models import ModelConfig

    model = ModelConfig(name="tiny-attention", n_layers=4, d_model=256, n_heads=8,
                        n_kv_heads=1, head_dim=128, sliding_window=128,
                        global_attn_every_n_layers=4)
    real = configs.get_config
    monkeypatch.setattr(configs, "get_config", lambda name, smoke=False: (
        model if name == model.name else real(name, smoke)))
    return model


@pytest.fixture
def tiny_moe_model(monkeypatch):
    """A registered model config ``tiny-moe`` with Trinity-Mini's MoE
    (sigmoid top-k with a selection bias, ``route_scale`` 2.826, one
    ungated shared expert) at a CPU size: d 128, top-2 of 8 experts of
    width 64, a shared expert of width 64, 8 layers that all hold an MoE."""
    import repro.configs as configs
    from repro.models import ModelConfig

    model = ModelConfig(name="tiny-moe", family="moe", n_layers=8, d_model=128, n_heads=4,
                        n_kv_heads=1, head_dim=32, d_ff=256, vocab_size=512, n_experts=8,
                        top_k=2, n_shared_experts=1, moe_d_ff=64, shared_d_ff=64,
                        moe_score_func="sigmoid", moe_route_scale=2.826,
                        moe_shared_gate=False, dtype="float32", param_dtype="float32")
    real = configs.get_config
    monkeypatch.setattr(configs, "get_config", lambda name, smoke=False: (
        model if name == model.name else real(name, smoke)))
    return model
