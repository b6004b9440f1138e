"""Compile-only checks of the chip's programs against a described TPU v5e.

Nothing runs: each test lowers and compiles at real size for a v5e chip
that is described, not attached, so the chip's compiler refuses here what
it would refuse there (fast memory overuse, misaligned blocks, a program
that does not fit HBM).  The topology is described inside a fixture, never
while a module is imported: only one process at a time may load the TPU
compiler's library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.launch.serve import MAX_LEN, SLOTS
from repro.memsim.batched import fluid, kernel
from repro.models.transformer import TransformerLM

#: HBM one v5e chip offers a program, as its compiler counts it.
V5E_HBM_BYTES = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as ex:
        pytest.skip(f"no v5e:2x2 topology can be described here: {ex}")
    # A program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_fused_window_solver_compiles_for_v5e(one_chip):
    solver = kernel.build_fused_solver(fluid._N_OUTER, fluid._DAMP,
                                       interpret=False)
    args = kernel.fused_solver_args(1024, 4, 4, sharding=one_chip)
    assert "tpu_custom_call" in solver.lower(*args).compile().as_text()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_qwen_serving_step_fits_one_v5e(one_chip, step):
    """The serving cluster's full-width steps fit one chip's HBM next to
    the second weight copy the cluster keeps there (the HBM engine's
    resident weights while the host engine runs on its fetched copy).

    A lower bound on what the cluster needs: it leaves out the engines'
    decode states and what weight initialisation and transfers hold, so the
    chip's peak in ``chip_smoke.py`` is higher (``PERF.md``, section 5)."""
    model = TransformerLM(get_arch("qwen2.5-3b").config)
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))[0]))
    if step == "decode":
        state = _on(one_chip, jax.eval_shape(
            lambda: model.init_decode_state(SLOTS, MAX_LEN)))
        token = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
        lowered = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
            params, state, token)
    else:
        def prefill(p, tokens):
            state1 = model.init_decode_state(1, MAX_LEN)
            return model.prefill(p, tokens, state1)

        tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32, sharding=one_chip)
        lowered = jax.jit(prefill).lower(params, tokens)
    mem = lowered.compile().memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    program = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert program <= V5E_HBM_BYTES
    assert program + weights <= V5E_HBM_BYTES


def test_granite_share_decode_fits_one_v5e(one_chip):
    """The granite-4.0-h-small share the chip benchmark serves (layers
    0-19, experts 0-8 of 72): one decode step at 16 slots of 8192
    positions, weights and the donated two-kind state included, fits one
    chip's HBM and updates the state in place."""
    import dataclasses

    published = get_arch("granite-4.0-h-small").config
    cfg = dataclasses.replace(published, n_layers=20,
                              layer_types=published.layer_types[:20],
                              experts_held=(0, 9))
    model = TransformerLM(cfg)
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))[0]))
    state = _on(one_chip, jax.eval_shape(
        lambda: model.init_decode_state(16, 8192)))
    token = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    mem = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, state, token).compile().memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes - 2 ** 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= V5E_HBM_BYTES


def test_qwen_decode_updates_its_cache_in_place(one_chip):
    """The chat cells' decode (qwen2.5-3b, 16 slots of 2560 positions)
    carries its stacked K/V through the layer scan and writes one row per
    layer where it lies: the donated state is aliased whole, and the
    program holds no second copy of the cache (1.51 GB) as temp."""
    model = TransformerLM(get_arch("qwen2.5-3b").config)
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))[0]))
    state = _on(one_chip, jax.eval_shape(
        lambda: model.init_decode_state(16, 2560)))
    token = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    mem = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, state, token).compile().memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes - 2 ** 20
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def _deepseek_share(one_chip, slots=16, max_len=12288):
    """The deepseek-v2-lite share the chip benchmark serves (all 27
    layers, experts 0-7 of 64): its model, weights and serving state."""
    import dataclasses

    cfg = dataclasses.replace(get_arch("deepseek-v2-lite").config,
                              experts_held=(0, 8))
    model = TransformerLM(cfg)
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))[0]))
    state = _on(one_chip, jax.eval_shape(
        lambda: model.init_decode_state(slots, max_len)))
    return model, params, state


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_deepseek_share_serving_fits_one_v5e(one_chip):
    """The share's weights (6.22 GB), its latent cache at 16 slots of
    12288 positions (6.12 GB) and its largest step, an 8192-token prefill
    with its one-slot state, fit one chip's HBM together; the decode
    step's donated state is aliased whole, and the step holds no second
    copy of the cache (the latent cache is read where it lies)."""
    model, params, state = _deepseek_share(one_chip)
    token = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    dec = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, state, token).compile().memory_analysis()
    assert dec.alias_size_in_bytes >= _nbytes(state) - 2 ** 20
    assert dec.temp_size_in_bytes < 64 * 2 ** 20

    def prefill(p, tokens):
        return model.prefill(p, tokens, model.init_decode_state(1, 12288))

    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    pre = jax.jit(prefill).lower(params, tokens).compile().memory_analysis()
    assert _nbytes(params) + _nbytes(state) + pre.temp_size_in_bytes \
        + pre.output_size_in_bytes <= V5E_HBM_BYTES


def test_deepseek_insert_writes_one_slot_in_place(one_chip):
    """The engine's admission (``insert_slot``, jitted with the state
    donated) writes the admitted slot where the state lies: the whole
    latent cache is aliased, and the program holds no copy of it."""
    from repro.serving.engine import insert_slot

    model, _, state = _deepseek_share(one_chip)
    state1 = _on(one_chip, jax.eval_shape(
        lambda: model.init_decode_state(1, 12288)))
    index = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    mem = jax.jit(insert_slot, donate_argnums=(0,)).lower(
        state, state1, index, index).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(state) - 2 ** 20
    assert mem.temp_size_in_bytes < 2 ** 20
