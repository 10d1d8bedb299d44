"""Per-arch smoke tests + model-stack invariants (brief deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models import (
    ForwardOptions,
    ModelConfig,
    attention_chunked,
    attention_local_chunked,
    attention_reference,
    audio_frame_embeds,
    encdec_decode_step,
    encdec_forward,
    encdec_prefill,
    init_encdec_params,
    init_encdec_state,
    init_lm_params,
    init_lm_state,
    lm_decode_step,
    lm_forward,
    lm_prefill,
    merge_vision_embeds,
    param_counts,
    ssd_chunked,
    ssd_reference,
    training_flops,
    vision_patch_embeds,
)
from repro.models.layers import embed_tokens


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_forward_step(arch):
    """REDUCED config of each family: one forward step, shapes + no NaNs."""
    cfg = get_config(arch, smoke=True)
    cfg.validate()
    key = jax.random.PRNGKey(0)
    b, s = 2, 32
    if cfg.is_encoder_decoder:
        params, _ = init_encdec_params(cfg, key)
        enc = audio_frame_embeds(cfg, b, cfg.encoder_seq)
        dec = jax.random.randint(jax.random.PRNGKey(1), (b, 16), 0, cfg.vocab_size)
        logits, aux = encdec_forward(cfg, params, enc, dec)
        assert logits.shape == (b, 16, cfg.vocab_size)
    elif cfg.frontend == "vision_stub":
        params, _ = init_lm_params(cfg, key)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (b, 64), 0, cfg.vocab_size)
        te = embed_tokens(cfg, params["embed"], tokens)
        embeds = merge_vision_embeds(cfg, te, vision_patch_embeds(cfg, b, 16))
        logits, aux = lm_forward(cfg, params, embeds=embeds)
        assert logits.shape == (b, 64, cfg.vocab_size)
    else:
        params, _ = init_lm_params(cfg, key)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
        logits, aux = lm_forward(cfg, params, tokens=tokens)
        assert logits.shape == (b, s, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits))), f"{arch}: NaN logits"


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_train_grad_step(arch):
    """One loss+grad step per reduced config: finite loss, finite grads."""
    from repro.train.trainer import LossConfig, make_loss_fn

    cfg = get_config(arch, smoke=True)
    key = jax.random.PRNGKey(0)
    b, s = 2, 16
    if cfg.is_encoder_decoder:
        params, _ = init_encdec_params(cfg, key)
        batch = {
            "enc_embeds": audio_frame_embeds(cfg, b, cfg.encoder_seq),
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab_size),
        }
    elif cfg.frontend == "vision_stub":
        params, _ = init_lm_params(cfg, key)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
        te = embed_tokens(cfg, params["embed"], tokens)
        batch = {
            "embeds": merge_vision_embeds(cfg, te, vision_patch_embeds(cfg, b, 8)),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab_size),
        }
    else:
        params, _ = init_lm_params(cfg, key)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab_size),
        }
    loss_fn = make_loss_fn(cfg, ForwardOptions(attn_impl="reference"), LossConfig())
    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.square(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0.0


@pytest.mark.parametrize(
    "arch", [a for a in ARCH_NAMES if a != "whisper-tiny"]
)
def test_smoke_decode_consistency(arch):
    """prefill + decode logits == full-forward logits (per family)."""
    cfg = get_config(arch, smoke=True)
    key = jax.random.PRNGKey(0)
    b, s = 2, 24
    params, _ = init_lm_params(cfg, key)
    if cfg.frontend == "vision_stub":
        tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
        embeds = embed_tokens(cfg, params["embed"], tokens)
        logits, _ = lm_forward(cfg, params, embeds=embeds)
        state = init_lm_state(cfg, b, s + 8)
        _, state = lm_prefill(cfg, params, state, embeds=embeds[:, : s - 1])
    else:
        tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
        logits, _ = lm_forward(cfg, params, tokens=tokens)
        state = init_lm_state(cfg, b, s + 8)
        _, state = lm_prefill(cfg, params, state, tokens=tokens[:, : s - 1])
    lg, state = lm_decode_step(cfg, params, state, tokens[:, s - 1 : s], jnp.int32(s - 1))
    ref = logits[:, s - 1, :]
    err = float(jnp.max(jnp.abs(lg - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert err < 5e-2, f"{arch}: decode relerr {err}"


def test_whisper_decode_consistency():
    cfg = get_config("whisper-tiny", smoke=True)
    params, _ = init_encdec_params(cfg, jax.random.PRNGKey(0))
    b = 2
    enc = audio_frame_embeds(cfg, b, cfg.encoder_seq)
    dec = jax.random.randint(jax.random.PRNGKey(2), (b, 8), 0, cfg.vocab_size)
    logits, _ = encdec_forward(cfg, params, enc, dec)
    st = init_encdec_state(cfg, b, 16, cfg.encoder_seq)
    st = encdec_prefill(cfg, params, st, enc)
    for t in range(4):
        lg, st = encdec_decode_step(cfg, params, st, dec[:, t : t + 1], jnp.int32(t))
    ref = logits[:, 3, :]
    err = float(jnp.max(jnp.abs(lg - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert err < 5e-2


# ----------------------------------------------------- attention variants --

def _qkv(b=2, s=128, h=4, kv=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    return q, k, v


def test_attention_variants_agree():
    """grouped == broadcast == chunked (mathematically equivalent)."""
    q, k, v = _qkv()
    ref_g = attention_reference(q, k, v, gqa="grouped")
    ref_b = attention_reference(q, k, v, gqa="broadcast")
    chk = attention_chunked(q, k, v, q_block=32, kv_block=64)
    np.testing.assert_allclose(np.asarray(ref_b), np.asarray(ref_g), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(ref_g), rtol=2e-4, atol=2e-4)


def test_local_chunked_matches_masked_reference():
    q, k, v = _qkv(s=256)
    window = 48
    ref = attention_reference(q, k, v, window=window)
    loc = attention_local_chunked(q, k, v, window=window, q_block=32)
    np.testing.assert_allclose(np.asarray(loc), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("qb,kb", [(16, 32), (32, 32), (64, 128)])
def test_chunked_blocksizes_equivalent(qb, kb):
    q, k, v = _qkv(s=128)
    ref = attention_reference(q, k, v)
    out = attention_chunked(q, k, v, q_block=qb, kv_block=kb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_sliding_window_decode_ring_buffer():
    """Windowed decode with a ring cache == full-cache windowed decode."""
    cfg = ModelConfig(
        name="ring", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
        vocab_size=128, sliding_window=8, dtype="float32", param_dtype="float32",
    )
    params, _ = init_lm_params(cfg, jax.random.PRNGKey(0))
    b, s = 1, 30
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, 128)
    logits, _ = lm_forward(cfg, params, tokens=tokens, opts=ForwardOptions(attn_impl="reference"))
    # ring cache is rounded up to >= window+1: force tiny max_len anyway
    state = init_lm_state(cfg, b, max_len=s + 2)
    _, state = lm_prefill(cfg, params, state, tokens=tokens[:, : s - 1])
    lg, _ = lm_decode_step(cfg, params, state, tokens[:, s - 1 : s], jnp.int32(s - 1))
    err = float(jnp.max(jnp.abs(lg - logits[:, s - 1]))) / float(jnp.max(jnp.abs(logits[:, s - 1])))
    assert err < 5e-2, err


# --------------------------------------------------------------- SSD -------

@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_ssd_chunked_equals_sequential(chunk):
    b, s, h, p, n = 2, 64, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bm = jax.random.normal(ks[3], (b, s, 1, n))
    cm = jax.random.normal(ks[4], (b, s, 1, n))
    y_ref, st_ref = ssd_reference(x, dt, a_log, bm, cm)
    y, st = ssd_chunked(x, dt, a_log, bm, cm, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref), rtol=3e-4, atol=3e-4)


def test_ssd_state_carry_composes():
    """Running two halves with carried state == one full run."""
    b, s, h, p, n = 1, 32, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bm = jax.random.normal(ks[3], (b, s, 1, n))
    cm = jax.random.normal(ks[4], (b, s, 1, n))
    y_full, st_full = ssd_reference(x, dt, a_log, bm, cm)
    y1, st1 = ssd_reference(x[:, :16], dt[:, :16], a_log, bm[:, :16], cm[:, :16])
    y2, st2 = ssd_reference(
        x[:, 16:], dt[:, 16:], a_log, bm[:, 16:], cm[:, 16:], init_state=st1
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(y_full),
        rtol=2e-4, atol=2e-4,
    )
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full), rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------- flops -------

def test_param_counts_match_actual_tree():
    for arch in ("granite-8b", "qwen2-moe-a2.7b", "mamba2-1.3b"):
        cfg = get_config(arch, smoke=True)
        params, _ = init_lm_params(cfg, jax.random.PRNGKey(0))
        actual = sum(x.size for x in jax.tree.leaves(params))
        analytic = param_counts(cfg).total
        # analytic skips norm scales — must agree within 1.5%
        assert abs(actual - analytic) / actual < 0.015, (arch, actual, analytic)


def test_training_flops_scale_linearly_in_tokens():
    cfg = get_config("granite-8b", smoke=False)
    f1 = training_flops(cfg, 8, 1024)
    f2 = training_flops(cfg, 16, 1024)
    assert abs(f2 / f1 - 2.0) < 1e-6


def test_global_attention_period_places_global_last_in_the_unit():
    """Trinity-Mini's ``global_attn_every_n_layers`` 4: three sliding
    layers, then one full, 8 units over 32 layers; gemma2's period 2 keeps
    its [local, global] unit."""
    from repro.models.config import LayerKind

    local, full = LayerKind.ATTN_LOCAL, LayerKind.ATTN
    trinity = get_config("trinity-mini")
    assert [sp.kind for sp in trinity.pattern_unit()] == [local, local, local, full]
    assert trinity.n_units == 8 and trinity.global_attn_every_n_layers == 4
    gemma = get_config("gemma2-27b")
    assert [sp.kind for sp in gemma.pattern_unit()] == [local, full]
    assert gemma.global_attn_every_n_layers == 2 and gemma.n_units == 23
    assert get_config("granite-8b").global_attn_every_n_layers == 0
    assert {sp.kind for sp in get_config("granite-8b").pattern_unit()} == {full}
    with pytest.raises(ValueError, match="pattern unit"):
        trinity.replace(n_layers=30).pattern_unit()
