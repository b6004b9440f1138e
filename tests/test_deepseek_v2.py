"""deepseek-v2-lite on the serving path, at smoke widths on the CPU.

The program (``TransformerLM``'s interleaved stack with its MLA layers and
leading dense layer, the held-expert layer with DeepSeek's router,
``ServingEngine``) against the chip benchmark's plain float32 reference
(``benchmarks/chip/reference/deepseek_v2.py``), both given the same seeded
random weights through the benchmark driver's layout map.

Tolerances are relative to the largest reference logit.  Float32 program
and reference differ by rounding alone (the absorbed decode sums in
another order than the expanded form): 1.9e-6 of that logit in the
forward pass, 2.9e-6 through prefill and decode.  The limit, 2e-5, leaves
about 7 times that, and a bfloat16 program (7e-2 here) fails it;
``test_bfloat16_program_fails_the_tolerance`` keeps that true.
"""

from __future__ import annotations

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.append(BENCH)

import cell as cell_lib  # noqa: E402
import counts_deepseek  # noqa: E402
from reference import deepseek_v2 as ref  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models.layers import yarn_range  # noqa: E402
from repro.models.transformer import TransformerLM  # noqa: E402

DRIVER = cell_lib.driver("serve_deepseek")
#: Float32 program against float32 reference, relative to the largest
#: reference logit (see the module's docstring).
RTOL = 2e-5
#: The smoke YaRN's original context: decode positions run past it.
ORIGINAL = 16
SEQ, PROMPT = 40, 12


def published():
    return cell_lib.load_json(os.path.join(BENCH, "configs",
                                           "deepseek-v2-lite.json"))


def smoke_cfg(dtype="float32", held=(0, 16)):
    """The benchmark's configuration file at smoke widths: a dense lead
    layer and four expert layers, 16 experts of which ``held`` are held,
    top-4; YaRN over an original 16 positions.  Weights drawn with a
    standard deviation of 0.2, so that logits are of order 1."""
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=32, moe_intermediate_size=32,
               intermediate_size=96, router_experts=16,
               held_experts=list(held), n_routed_experts=held[1] - held[0],
               num_experts_per_tok=4, num_hidden_layers=5, vocab_size=256,
               initializer_range=0.2,
               torch_dtype=dtype)
    cfg["rope_scaling"] = {**cfg["rope_scaling"], "factor": 4,
                           "original_max_position_embeddings": ORIGINAL}
    return cfg


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(3), (2, SEQ), 0,
                                         256))


@pytest.fixture(scope="module")
def reference_logits(tokens):
    cfg = smoke_cfg()
    w = ref.init_weights(cfg, 7)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(w, tokens, ref.hm_of(cfg)))


def program(dtype):
    cfg = smoke_cfg(dtype)
    model = TransformerLM(DRIVER.model_config(cfg))
    w = ref.init_weights(smoke_cfg(), 7)
    params = DRIVER.to_program(jax.tree.map(
        lambda a: a.astype(getattr(jnp, dtype)), w))
    return model, params


def forward_err(dtype, tokens, reference_logits):
    model, params = program(dtype)
    hidden, _ = jax.jit(model.forward)(params, jnp.asarray(tokens))
    got = np.asarray(model.logits(params, hidden), np.float32)
    return np.abs(got - reference_logits).max() / np.abs(reference_logits).max()


def test_published_yarn_constants():
    """YaRN's ramp over the 32 rope frequencies runs from 10 to 23, and
    the softmax scale is 192^-0.5 * mscale(40, 0.707)^2, in the program
    and in the reference alike."""
    cfg = get_arch("deepseek-v2-lite").config
    assert yarn_range(64, 10000.0, cfg.yarn) == (10, 23)
    consts = cfg.mla_args()
    assert consts["scale"] == pytest.approx(0.114721, abs=5e-7)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert consts["scale"] == pytest.approx(192 ** -0.5 * mscale ** 2,
                                            rel=1e-12)
    assert consts["rope_scale"] == 1.0
    y = ref.yarn(ref.dims(published()))
    assert (y["low"], y["high"]) == (10, 23)
    assert y["softmax_scale"] == pytest.approx(consts["scale"], rel=1e-12)
    np.testing.assert_allclose(consts["inv_freq"], y["inv_freq"], rtol=1e-6)
    # Below the ramp a frequency is kept, above it divided by 40.
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(y["inv_freq"][:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(y["inv_freq"][23:], base[23:] / 40, rtol=1e-6)


def test_forward_matches_reference(tokens, reference_logits):
    assert forward_err("float32", tokens, reference_logits) <= RTOL


def test_bfloat16_program_fails_the_tolerance(tokens, reference_logits):
    assert forward_err("bfloat16", tokens, reference_logits) > RTOL


def test_prefill_then_decode_matches_reference(tokens, reference_logits):
    """Prefill a 12-token prompt (the expanded form), then decode through
    the latent cache (the absorbed form) to position 39, past the smoke
    YaRN's original 16 positions, against the reference's full forward."""
    model, params = program("float32")
    state = model.init_decode_state(2, 64)
    logits, state = jax.jit(model.prefill)(
        params, jnp.asarray(tokens[:, :PROMPT]), state)
    got = [np.asarray(logits)]
    step = jax.jit(model.decode_step)
    for j in range(PROMPT, SEQ - 1):
        logits, state = step(params, state, jnp.asarray(tokens[:, j]))
        got.append(np.asarray(logits))
    want = reference_logits[:, PROMPT - 1:SEQ - 1].swapaxes(0, 1)
    err = np.abs(np.stack(got) - want).max() / np.abs(want).max()
    assert err <= RTOL
    assert list(np.asarray(state.length)) == [SEQ - 1] * 2
    assert SEQ - 2 > ORIGINAL


def test_absorbed_decode_matches_expanded_form():
    """One MLA layer: a token decoded in the absorbed form against the
    latent cache that the expanded form wrote gives what the expanded form
    gives over the whole sequence, for slots of different lengths (the
    second slot's new row overwrites a position the prefill filled)."""
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite").smoke,
                              dtype=jnp.float32)
    p, _ = attn.mla_init(jax.random.PRNGKey(0), cfg.d_model, cfg.n_q_heads,
                         kv_lora_rank=cfg.kv_lora_rank,
                         nope_dim=cfg.qk_nope_head_dim,
                         rope_dim=cfg.qk_rope_head_dim,
                         v_dim=cfg.v_head_dim, dtype=jnp.float32)
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                           p["kv_norm"].shape)
    consts = cfg.mla_args()
    s = 24
    h = jax.random.normal(jax.random.PRNGKey(2), (2, s, cfg.d_model))
    new = jax.random.normal(jax.random.PRNGKey(3), (2, 1, cfg.d_model))
    lengths = jnp.asarray([s, s - 5], jnp.int32)
    cache = {"c_kv": jnp.zeros((3, 2, 32, cfg.kv_lora_rank)),
             "k_pe": jnp.zeros((3, 2, 32, cfg.qk_rope_head_dim))}
    with jax.default_matmul_precision("highest"):
        pos = jnp.broadcast_to(jnp.arange(s), (2, s))
        _, cache = attn.mla_full(p, h, pos, cache, 1, **consts)
        got, cache = attn.mla_cached(p, new, cache, jnp.int32(1), lengths,
                                     **consts)
        for b in range(2):
            n = int(lengths[b])
            seq = jnp.concatenate([h[b:b + 1, :n], new[b:b + 1]], axis=1)
            want, _ = attn.mla_full(p, seq, jnp.arange(n + 1)[None],
                                    **consts)
            np.testing.assert_allclose(np.asarray(got[b, 0]),
                                       np.asarray(want[0, -1]),
                                       rtol=0, atol=1e-5)
    # The latent cache of the other layers is untouched.
    assert not np.any(np.asarray(cache["c_kv"][0]))
    assert not np.any(np.asarray(cache["k_pe"][2]))


class m_key(dict):
    """The reference's dims as a static (hashable) jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def held_moe(lw, held):
    """One layer's reference weights as the program's expert layer holding
    experts ``held`` of them (gate and up laid out [E, F, D])."""
    lo, hi = held
    return {"router": lw["router"],
            "w_gate": lw["w_gate"][lo:hi].swapaxes(-2, -1),
            "w_up": lw["w_up"][lo:hi].swapaxes(-2, -1),
            "w_down": lw["w_down"][lo:hi],
            "shared": {"w_gate": lw["sh_gate"], "w_up": lw["sh_up"],
                       "w_down": lw["sh_down"]}}


@pytest.mark.parametrize("width", [2, 4], ids=["8_shares", "4_shares"])
def test_expert_shares_add_up_to_the_whole_layer(width):
    """Shares of ``width`` experts each, routed by a softmax over all 16
    experts: their parts, with the shared experts (which every share
    computes) counted once, add up to the uncut reference's expert
    layer."""
    cfg = smoke_cfg()
    m = ref.dims(cfg)
    lw = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                      ref.init_weights(cfg, 11)["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 12, m["d"]))
    apply = jax.jit(moe_lib.held_moe_apply,
                    static_argnames=("top_k", "held", "router"))
    shares = m["E"] // width
    with jax.default_matmul_precision("highest"):
        shared = jax.jit(ref.shared_experts)(h, lw)
        whole = jax.jit(ref.experts, static_argnums=2)(h, lw, m_key(m)) \
            + shared
        total = (1 - shares) * shared
        for s in range(shares):
            held = (width * s, width * s + width)
            part, _ = apply(held_moe(lw, held), h, top_k=m["k"], held=held,
                            router="softmax_topk")
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=0, atol=1e-5 * float(jnp.abs(whole).max()))


def test_decode_state_holds_only_the_latent_cache():
    """The benchmark's share at full width (shapes only): the normed latent
    and the rope key of all 27 layers, no per-head K/V, no SSM state, and
    the held experts touched of the 26 expert layers."""
    model = TransformerLM(DRIVER.model_config(published()))
    st = jax.eval_shape(lambda: model.init_decode_state(16, 12288))
    assert set(st.kv) == {"c_kv", "k_pe"}
    assert st.kv["c_kv"].shape == (27, 16, 12288, 512)
    assert st.kv["k_pe"].shape == (27, 16, 12288, 64)
    assert st.kv["c_kv"].dtype == st.kv["k_pe"].dtype == jnp.bfloat16
    assert st.ssm is None and st.cross_kv is None
    assert st.experts_touched.shape == (26,)


def test_published_and_held_parameter_counts():
    cfg = get_arch("deepseek-v2-lite").config
    assert cfg.param_count() == 15_706_484_224
    assert dataclasses.replace(cfg, experts_held=(0, 8)).param_count() \
        == 3_110_989_312
    assert TransformerLM(DRIVER.model_config(published())).cfg \
        .param_count() == 3_110_989_312
    assert counts_deepseek.param_count(published(), published=True) \
        == 15_706_484_224
    assert counts_deepseek.param_count(published()) == 3_110_989_312
    assert counts_deepseek.latent_bytes_per_token(published()) == 31_104
