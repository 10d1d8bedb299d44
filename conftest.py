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
