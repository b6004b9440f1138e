"""granite-4.0-h-small on the serving path, at smoke widths on the CPU.

The program (``TransformerLM``'s interleaved stack, the held-expert layer,
``ServingEngine``) against the chip benchmark's plain float32 reference
(``benchmarks/chip/reference/granite_hybrid.py``), both given the same
seeded random weights through the benchmark driver's layout map.

Tolerances are relative to the largest reference logit.  Float32 program
and reference differ by rounding alone (the chunked SSD scan against the
stepped recurrence): about 3e-7 of that logit.  The limit, 1e-5, leaves
30 times that, and a bfloat16 program (about 3e-2 here) fails it;
``test_bfloat16_program_fails_the_tolerance`` keeps that true.
"""

from __future__ import annotations

import dataclasses
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
from reference import granite_hybrid as ref  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models.transformer import TransformerLM  # noqa: E402
from repro.serving.engine import (  # noqa: E402
    EngineConfig,
    Request,
    ServingEngine,
    TieredServingCluster,
)

DRIVER = cell_lib.driver("serve_granite")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
#: Float32 program against float32 reference, relative to the largest
#: reference logit (see the module's docstring).
RTOL = 1e-5
SEQ, PROMPT = 40, 24


def smoke_cfg(dtype="float32", held=(0, 16)):
    """The benchmark's configuration file at smoke widths: one period,
    16 experts of which ``held`` are held, top-4.  Weights drawn with a
    standard deviation of 0.2, so that logits are of order 1."""
    cfg = cell_lib.load_json(os.path.join(BENCH, "configs",
                                          "granite-4.0-h-small.json"))
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=32, shared_intermediate_size=64,
               router_experts=16, held_experts=list(held),
               num_local_experts=held[1] - held[0], num_experts_per_tok=4,
               mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
               mamba_chunk_size=16, layer_types=PERIOD, num_hidden_layers=10,
               vocab_size=256, attention_multiplier=1 / 16,
               initializer_range=0.2, torch_dtype=dtype)
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


def test_forward_matches_reference(tokens, reference_logits):
    assert forward_err("float32", tokens, reference_logits) <= RTOL


def test_bfloat16_program_fails_the_tolerance(tokens, reference_logits):
    assert forward_err("bfloat16", tokens, reference_logits) > RTOL


@pytest.mark.parametrize("prompt", [PROMPT, 32], ids=["ragged", "chunks"])
def test_prefill_then_decode_matches_reference(prompt, tokens,
                                               reference_logits):
    """Prefill, then decode through the two-kind state, against the
    reference's full forward pass.  A prompt of 24 positions with a
    16-position chunk pads the last chunk inside the scan: that its state
    still agrees shows no padded position enters the recurrence."""
    model, params = program("float32")
    state = model.init_decode_state(2, 64)
    logits, state = jax.jit(model.prefill)(params,
                                           jnp.asarray(tokens[:, :prompt]),
                                           state)
    got = [np.asarray(logits)]
    step = jax.jit(model.decode_step)
    for j in range(prompt, SEQ - 1):
        logits, state = step(params, state, jnp.asarray(tokens[:, j]))
        got.append(np.asarray(logits))
    want = reference_logits[:, prompt - 1:SEQ - 1].swapaxes(0, 1)
    err = np.abs(np.stack(got) - want).max() / np.abs(want).max()
    assert err <= RTOL
    assert list(np.asarray(state.length)) == [SEQ - 1] * 2


class m_key(dict):
    """The reference's dims as a static (hashable) jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def held_moe(lw, held, shared=True):
    """One layer's reference weights as the program's expert layer holding
    experts ``held`` of them (gate and up laid out [E, F, D])."""
    lo, hi = held
    out = {"router": lw["router"],
           "w_gate": lw["w_gate"][lo:hi].swapaxes(-2, -1),
           "w_up": lw["w_up"][lo:hi].swapaxes(-2, -1),
           "w_down": lw["w_down"][lo:hi]}
    if shared:
        out["shared"] = {"w_gate": lw["sh_gate"], "w_up": lw["sh_up"],
                         "w_down": lw["sh_down"]}
    return out


def test_expert_shares_add_up_to_the_whole_layer():
    """Eight shares of two experts each: their parts, with the shared MLP
    (which every share computes) counted once, add up to the uncut
    reference's expert layer."""
    cfg = smoke_cfg()
    m = ref.dims(cfg)
    lw = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                      ref.init_weights(cfg, 11)["mamba"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 12, m["d"]))
    apply = jax.jit(moe_lib.held_moe_apply, static_argnames=("top_k", "held"))
    with jax.default_matmul_precision("highest"):
        shared = jax.jit(ref.shared_mlp)(h, lw)
        whole = jax.jit(ref.experts, static_argnums=2)(h, lw, m_key(m)) + shared
        total = -7 * shared
        for s in range(8):
            held = (2 * s, 2 * s + 2)
            part, _ = apply(held_moe(lw, held), h, top_k=m["k"], held=held)
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=0, atol=1e-5 * float(jnp.abs(whole).max()))


def test_every_token_routed_to_one_held_expert_is_kept():
    """Dropless: every token's router picks held expert 0 (its logit is
    made the largest), and each still gets that expert's full
    contribution, as the reference's per-token sum gives it; capacity-
    bounded dispatch would drop most of them."""
    m = ref.dims(smoke_cfg(held=(0, 2)))
    lw = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                      ref.init_weights(smoke_cfg(), 13)["mamba"])
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (1, 64, m["d"])))
    lw["router"] = lw["router"].at[:, 0].set(10.0)
    logits = jnp.einsum("bsd,de->bse", h, lw["router"])
    assert bool(jnp.all(jnp.argmax(logits, axis=-1) == 0))
    moe = held_moe(lw, (0, 2), shared=False)
    with jax.default_matmul_precision("highest"):
        got, touched = jax.jit(moe_lib.held_moe_apply,
                               static_argnames=("top_k", "held"))(
            moe, h, top_k=m["k"], held=(0, 2))
        want = jax.jit(ref.experts, static_argnums=2)(h, lw, m_key(m))
    assert int(touched) >= 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_decode_state_holds_each_kind_only_where_it_lives():
    """The benchmark's share at full width (shapes only): K/V for the 2
    attention layers, SSM and conv state for the 18 Mamba layers, and
    nothing of one kind for the layers of the other."""
    cfg = cell_lib.load_json(os.path.join(BENCH, "configs",
                                          "granite-4.0-h-small.json"))
    model = TransformerLM(DRIVER.model_config(cfg))
    st = jax.eval_shape(lambda: model.init_decode_state(16, 8192))
    assert st.kv["k"].shape == st.kv["v"].shape == (2, 16, 8192, 8, 128)
    assert st.ssm["h"].shape == (18, 16, 128, 64, 128)
    assert st.ssm["h"].dtype == jnp.float32
    assert st.ssm["conv"].shape == (18, 16, 3, 8448)
    assert st.cross_kv is None
    assert st.experts_touched.shape == (20,)
    per_slot = sum(x.size // 16 * x.dtype.itemsize
                   for x in jax.tree.leaves(st.ssm))
    assert per_slot == 18 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)


def test_published_and_held_parameter_counts():
    published = get_arch("granite-4.0-h-small").config
    assert published.param_count() == 32_207_337_984
    cut = dataclasses.replace(published, n_layers=20,
                              layer_types=published.layer_types[:20],
                              experts_held=(0, 9))
    assert cut.param_count() == 4_418_340_096


def _engine(cfg, name="e", slots=2):
    model = TransformerLM(cfg)
    params = jax.jit(lambda k: model.init(k)[0])(jax.random.PRNGKey(0))
    return ServingEngine(EngineConfig(name=name, model=cfg, max_slots=slots,
                                      max_len=64), params)


def test_engine_charges_state_by_layer_kind():
    """K/V bytes per token count only the attention layers; each active
    slot's SSM and conv state is charged, read and written, every step;
    the decode steps' held experts feed ``moe.experts_touched``."""
    from repro.obs.metrics import default_registry

    cfg = get_arch("granite-4.0-h-small").smoke
    eng = _engine(cfg)
    assert eng.kv_bytes_per_token == 2 * 2 * 16 * 1 * 2  # 1 attention layer
    dims = cfg.ssm_dims
    state = 9 * (dims["n_heads"] * dims["head_dim"] * dims["d_state"] * 4
                 + (dims["d_conv"] - 1) * dims["conv_dim"] * 2)
    assert eng.ssm_bytes_per_slot == state
    counter = default_registry().counter("moe.experts_touched")
    for i in range(2):
        eng.submit(Request(rid=i, prompt=[1, 2, 3, 4, 5], max_new_tokens=3))
    cluster = TieredServingCluster([eng])
    steps = 0
    while not eng.finished:
        before = counter.value
        cluster.run(max_ticks=1)  # one decode step: the queue is admitted
        steps += 1
        touched = np.sum(jax.device_get(eng.state.experts_touched))
        assert counter.value - before == touched > 0
        if steps == 1:
            wb, kvb = eng.step_bytes()
            assert wb == eng.param_bytes
            assert kvb == 2 * (6 * eng.kv_bytes_per_token + 2 * state)
    assert steps == 2 and len(eng.done) == 2


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "granite-4.0-h-small"])
def test_engine_lengths_mirror_the_device(arch):
    """After every tick the host mirror of the slots' lengths equals the
    state's ``length``, and ``step_bytes`` gives what the formula over the
    device's lengths gives, through admissions, completions and an
    overflow of ``max_len``."""
    eng = _engine(get_arch(arch).smoke, slots=3)
    for i, (plen, n) in enumerate([(5, 3), (9, 60), (3, 6), (7, 2), (4, 5)]):
        eng.submit(Request(rid=i, prompt=list(range(1, plen + 1)),
                           max_new_tokens=n))
    cluster = TieredServingCluster([eng])
    ticks = 0
    while not eng.finished:
        cluster.run(max_ticks=1)
        ticks += 1
        lengths = np.asarray(jax.device_get(eng.state.length))
        np.testing.assert_array_equal(eng._lengths, lengths)
        kvb = sum(int(lengths[i]) * eng.kv_bytes_per_token
                  + 2 * eng.ssm_bytes_per_slot
                  for i in range(3) if eng._active[i])
        assert eng.step_bytes() == (eng.param_bytes, kvb)
    assert len(eng.done) == 5
    long = next(r for r in eng.done if r.rid == 1)
    assert len(long.output) == 64 - 9  # stopped by max_len, not by 60


def test_dense_engine_accounting_is_unchanged():
    """qwen2.5-3b (smoke): K/V over every layer, no recurrent state."""
    cfg = get_arch("qwen2.5-3b").smoke
    eng = _engine(cfg)
    assert eng.kv_bytes_per_token == 2 * cfg.n_kv_heads * cfg.head_dim \
        * cfg.n_layers * 2
    assert eng.ssm_bytes_per_slot == 0
    assert eng.state.experts_touched is None
