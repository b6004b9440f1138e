"""The decode math the chip runs, against the naive oracles of
``tests/oracles.py``: cached attention (``attention.attend_cached`` for a
uniform stack, ``attention.attend_cached_stacked`` for granite's hybrid
stack) and the chunked SSD scan (``ssm.ssd_chunked``), swept over shapes
and dtypes."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from oracles import decode_attention_ref, ssd_scan_ref
from repro.models.attention import (
    attend_cached,
    attend_cached_stacked,
    project_qkv,
)
from repro.models.ssm import ssd_chunked

ROPE_THETA = 10_000.0


def _decode_case(seed, b, hq, hkv, dh, s, dtype, layers=None):
    """Random attention params whose output projection is the identity, so
    the layer returns the attention's output head by head; one token per
    slot, a random K/V cache (``layers`` of them stacked, if given) and each
    slot's length, the index its new row lands at."""
    rng = np.random.default_rng(seed)
    d = hq * dh

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    params = {"wq": normal(d, hq, dh, scale=d ** -0.5),
              "wk": normal(d, hkv, dh, scale=d ** -0.5),
              "wv": normal(d, hkv, dh, scale=d ** -0.5),
              "bq": normal(hq, dh), "bk": normal(hkv, dh),
              "bv": normal(hkv, dh),
              "wo": jnp.eye(d, dtype=dtype).reshape(hq, dh, d)}
    lead = (layers,) if layers else ()
    x = normal(b, 1, d)
    cache = {"k": normal(*lead, b, s, hkv, dh),
             "v": normal(*lead, b, s, hkv, dh)}
    length = jnp.asarray(rng.integers(0, s, b), jnp.int32)
    return params, x, cache, length


@partial(jax.jit, static_argnames=("window", "softcap"))
def _oracle(params, x, k, v, length, window=1 << 30, softcap=None):
    """The cache with each slot's new row written at its length, and the
    naive attention of the token over the positions up to it."""
    q, k_new, v_new = project_qkv(params, x, length[:, None],
                                  rope_theta=ROPE_THETA)
    slots = jnp.arange(x.shape[0])
    k = k.at[slots, length].set(k_new[:, 0].astype(k.dtype))
    v = v.at[slots, length].set(v_new[:, 0].astype(v.dtype))
    b, _, hq, dh = q.shape
    hkv = k.shape[2]
    out = decode_attention_ref(
        q[:, 0].reshape(b, hkv, hq // hkv, dh),
        jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), length + 1,
        window=window, softcap=softcap)
    return out.reshape(b, 1, hq * dh), k, v


_attend = jax.jit(partial(attend_cached, rope_theta=ROPE_THETA),
                  static_argnames=("window", "softcap_value"))
_attend_stacked = jax.jit(partial(attend_cached_stacked,
                                  rope_theta=ROPE_THETA))


def _close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,dh,s",
    [
        (1, 4, 4, 64, 128),   # MHA
        (2, 8, 2, 64, 256),   # GQA 4:1
        (2, 16, 2, 128, 512),  # qwen-like 8:1
        (1, 25, 5, 64, 128),  # hymba: 25 heads, G=5
        (2, 20, 20, 64, 128),  # whisper MHA-20
    ],
)
def test_decode_attention_sweep(dtype, b, hq, hkv, dh, s):
    params, x, cache, length = _decode_case(0, b, hq, hkv, dh, s, dtype)
    y, new = _attend(params, x, cache, length, window=1 << 30)
    ref, k, v = _oracle(params, x, cache["k"], cache["v"], length)
    _close(y, ref, 2e-2 if dtype == jnp.bfloat16 else 1e-5)
    np.testing.assert_array_equal(new["k"], k)
    np.testing.assert_array_equal(new["v"], v)


@pytest.mark.parametrize("window,softcap", [(64, None), (1 << 30, 50.0),
                                            (32, 30.0)])
def test_decode_attention_window_softcap(window, softcap):
    b, hq, hkv, dh, s = 2, 8, 4, 64, 256
    params, x, cache, _ = _decode_case(1, b, hq, hkv, dh, s, jnp.float32)
    length = jnp.array([s - 1, s // 3 - 1], jnp.int32)
    y, _ = _attend(params, x, cache, length, window=window,
                   softcap_value=softcap)
    ref, _, _ = _oracle(params, x, cache["k"], cache["v"], length,
                        window=window, softcap=softcap)
    _close(y, ref, 1e-5)


@given(
    b=st.integers(1, 3),
    hkv=st.sampled_from([1, 2, 4]),
    group=st.sampled_from([1, 2, 5]),
    s_blocks=st.integers(1, 4),
)
@settings(max_examples=20, deadline=None)
def test_decode_attention_property(b, hkv, group, s_blocks):
    dh, s = 32, 32 * s_blocks
    hq = hkv * group
    params, x, cache, length = _decode_case(b * 131 + hq, b, hq, hkv, dh, s,
                                            jnp.float32)
    y, _ = _attend(params, x, cache, length, window=1 << 30)
    ref, _, _ = _oracle(params, x, cache["k"], cache["v"], length)
    _close(y, ref, 2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layer", [0, 2])
def test_decode_attention_stacked_cache(layer, dtype):
    """Granite's decode attention: one layer of a cache that stacks three
    attention layers is written and read; the others stay as they were."""
    b, hq, hkv, dh, s = 2, 8, 2, 64, 128
    params, x, cache, length = _decode_case(4, b, hq, hkv, dh, s, dtype,
                                            layers=3)
    y, new = _attend_stacked(params, x, cache, jnp.int32(layer), length)
    ref, k, v = _oracle(params, x, cache["k"][layer], cache["v"][layer],
                        length)
    _close(y, ref, 2e-2 if dtype == jnp.bfloat16 else 1e-5)
    np.testing.assert_array_equal(new["k"], cache["k"].at[layer].set(k))
    np.testing.assert_array_equal(new["v"], cache["v"].at[layer].set(v))


def _ssd_inputs(key, b, s, h, p, n, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    x = (jax.random.normal(ks[0], (b, s, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    bm = jax.random.normal(ks[2], (b, s, 1, n)) * 0.3
    cm = jax.random.normal(ks[3], (b, s, 1, n)) * 0.3
    a = -jnp.exp(jax.random.normal(ks[4], (h,)) * 0.3)
    return x, bm, cm, dt, a


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [
        (1, 64, 2, 32, 16, 16),
        (2, 128, 4, 32, 16, 32),
        (1, 256, 2, 64, 128, 64),  # mamba2-class state
    ],
)
def test_ssd_scan_sweep(b, s, h, p, n, chunk, dtype):
    x, bm, cm, dt, a = _ssd_inputs(jax.random.PRNGKey(2), b, s, h, p, n,
                                   dtype)
    y, _ = ssd_chunked(x, bm, cm, dt, a, chunk=chunk)
    yref = jnp.moveaxis(
        ssd_scan_ref(jnp.moveaxis(x, 2, 1).astype(jnp.float32),
                     jnp.moveaxis(dt, 2, 1),
                     jnp.stack([bm[:, :, 0], cm[:, :, 0]], 2), a), 1, 2)
    _close(y, yref, 5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_scan_state_carries_across_chunks():
    """Same sequence, different chunk sizes => identical output (the state
    must carry exactly from chunk to chunk)."""
    x, bm, cm, dt, a = _ssd_inputs(jax.random.PRNGKey(3), 1, 128, 2, 32, 16)
    y32, h32 = ssd_chunked(x, bm, cm, dt, a, chunk=32)
    y128, h128 = ssd_chunked(x, bm, cm, dt, a, chunk=128)
    _close(y32, y128, 1e-4)
    _close(h32, h128, 1e-4)
