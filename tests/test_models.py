"""Per-arch smoke tests + decode/forward consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch
from repro.models.transformer import TransformerLM

KEY = jax.random.PRNGKey(0)


# The biggest smoke configs dominate tier-1 wall-clock (5-12 s each, almost
# all jit compile); they run in the non-gating slow lane instead.
_HEAVY_ARCHES = {
    "hymba-1.5b",
    "whisper-large-v3",
    "llama4-maverick-400b-a17b",
    "gemma2-27b",
    "h2o-danube-1.8b",
}


@pytest.mark.parametrize(
    "arch_id",
    [
        pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY_ARCHES
        else a
        for a in ARCH_IDS
    ],
)
def test_arch_smoke_forward_and_decode(arch_id):
    """Assignment: reduced same-family config, one forward + one decode
    step on CPU, output shapes + no NaNs."""
    spec = get_arch(arch_id)
    cfg = spec.smoke
    model = TransformerLM(cfg)
    params, axes = model.init(KEY)
    B, S = 2, 32
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    fe = None
    if cfg.frontend == "vision":
        fe = jax.random.normal(KEY, (B, cfg.frontend_seq, cfg.d_model))
    elif cfg.frontend == "audio":
        fe = jax.random.normal(KEY, (B, cfg.encoder_seq, cfg.d_model))
    hidden, aux = jax.jit(
        lambda p, t, f: model.forward(p, t, frontend_embeds=f)
    )(params, tokens, fe)
    logits = model.logits(params, hidden)
    assert logits.shape == (B, S, cfg.vocab)
    assert not jnp.isnan(logits.astype(jnp.float32)).any()
    state = model.init_decode_state(B, 64)
    if cfg.n_encoder_layers:
        _, state = jax.jit(
            lambda p, t, st, f: model.prefill(p, t, st, frontend_embeds=f)
        )(params, tokens, state, fe)
    lg, state = jax.jit(model.decode_step)(params, state, tokens[:, 0])
    assert lg.shape == (B, cfg.vocab)
    assert not jnp.isnan(lg.astype(jnp.float32)).any()
    assert int(state.length[0]) >= 1


@pytest.mark.parametrize(
    "arch_id",
    [
        "qwen2.5-3b",
        "mamba2-2.7b",
        pytest.param("h2o-danube-1.8b", marks=pytest.mark.slow),
        pytest.param("gemma2-27b", marks=pytest.mark.slow),
        pytest.param("hymba-1.5b", marks=pytest.mark.slow),
        pytest.param("dbrx-132b", marks=pytest.mark.slow),
    ],
)
def test_prefill_matches_forward(arch_id):
    """Teacher-forcing equivalence: prefill's last-token logits == the full
    forward's last-position logits (fp32 smoke configs)."""
    cfg = dataclasses.replace(get_arch(arch_id).smoke, dtype=jnp.float32)
    model = TransformerLM(cfg)
    params, _ = model.init(KEY)
    B, S = 2, 16
    tokens = jax.random.randint(KEY, (B, S), 1, cfg.vocab)
    hidden, _ = model.forward(params, tokens)
    full_logits = model.logits(params, hidden)[:, -1, :]
    state = model.init_decode_state(B, 32)
    pre_logits, state = model.prefill(params, tokens, state)
    np.testing.assert_allclose(
        np.asarray(pre_logits), np.asarray(full_logits), atol=2e-3, rtol=2e-3
    )


@pytest.mark.parametrize(
    "arch_id",
    [
        "qwen2.5-3b",
        "mamba2-2.7b",
        "gemma2-27b",  # local/global windows, soft-capped scores
        "llama4-maverick-400b-a17b",  # dense/MoE pairs in one stack
        pytest.param("hymba-1.5b", marks=pytest.mark.slow),
        pytest.param("whisper-large-v3", marks=pytest.mark.slow),
    ],
)
def test_decode_step_matches_forward(arch_id):
    """prefill(t) + decode(token_t) == forward(t+1 tokens) last logits.
    Sliding windows are cut to 4 so that they mask part of the 9 tokens;
    expert capacity holds every token, since the forward's capacity drops
    have no counterpart in a one-token decode."""
    cfg = dataclasses.replace(get_arch(arch_id).smoke, dtype=jnp.float32)
    if cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=4)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg,
                                  capacity_factor=cfg.n_experts / cfg.top_k)
    model = TransformerLM(cfg)
    params, _ = model.init(KEY)
    B, S = 1, 9
    tokens = jax.random.randint(KEY, (B, S), 1, cfg.vocab)
    frames = None  # an encoder-decoder's cross-attention memory
    if cfg.n_encoder_layers:
        frames = jax.random.normal(KEY, (B, cfg.encoder_seq, cfg.d_model))
    state = model.init_decode_state(B, 32)
    _, state = model.prefill(params, tokens[:, :-1], state,
                             frontend_embeds=frames)
    dec_logits, _ = model.decode_step(params, state, tokens[:, -1])
    hidden, _ = model.forward(params, tokens, frontend_embeds=frames)
    ref = model.logits(params, hidden)[:, -1, :]
    np.testing.assert_allclose(
        np.asarray(dec_logits), np.asarray(ref), atol=3e-3, rtol=3e-3
    )


def test_param_counts_match_published_sizes():
    expected = {
        "hymba-1.5b": (1.4e9, 1.8e9),
        "stablelm-12b": (11.5e9, 12.6e9),
        "qwen2.5-3b": (2.8e9, 3.4e9),
        "h2o-danube-1.8b": (1.6e9, 2.0e9),
        "gemma2-27b": (26e9, 28.5e9),
        "dbrx-132b": (125e9, 136e9),
        "llama4-maverick-400b-a17b": (380e9, 420e9),
        "mamba2-2.7b": (2.5e9, 2.9e9),
        "llama31-8b": (7.5e9, 8.5e9),
    }
    for arch_id, (lo, hi) in expected.items():
        n = get_arch(arch_id).config.param_count()
        assert lo < n < hi, f"{arch_id}: {n/1e9:.2f}B not in [{lo},{hi}]"


def test_moe_active_params():
    dbrx = get_arch("dbrx-132b").config
    assert 33e9 < dbrx.active_param_count() < 40e9
    l4 = get_arch("llama4-maverick-400b-a17b").config
    assert 15e9 < l4.active_param_count() < 19e9


def test_sliding_window_masks_old_tokens():
    """A token beyond the window must not influence attention output."""
    cfg = dataclasses.replace(get_arch("h2o-danube-1.8b").smoke,
                              dtype=jnp.float32, sliding_window=4)
    model = TransformerLM(cfg)
    params, _ = model.init(KEY)
    B, S = 1, 12
    t1 = jax.random.randint(KEY, (B, S), 1, cfg.vocab)
    t2 = t1.at[0, 0].set((int(t1[0, 0]) % (cfg.vocab - 1)) + 1)  # differs at pos 0
    h1, _ = model.forward(params, t1)
    h2, _ = model.forward(params, t2)
    # position 11 only sees positions >= 8 (window 4): identical output
    np.testing.assert_allclose(
        np.asarray(h1[:, -1]), np.asarray(h2[:, -1]), atol=1e-5
    )
