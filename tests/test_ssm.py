"""Mamba2 SSD: chunked scan vs naive recurrence; decode consistency."""

import jax
import jax.numpy as jnp
import numpy as np

from oracles import ssd_scan_ref
from repro.models.ssm import (
    init_ssm_state,
    ssd_chunked,
    ssm_dims,
    ssm_forward,
    ssm_init,
    ssm_step,
)

KEY = jax.random.PRNGKey(0)


def test_ssd_chunked_matches_naive_recurrence():
    b, s, h, p, n = 2, 64, 4, 16, 8
    ks = jax.random.split(KEY, 5)
    xs = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    bm = jax.random.normal(ks[1], (b, s, 1, n)) * 0.3
    cm = jax.random.normal(ks[2], (b, s, 1, n)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[4], (h,)) * 0.3)
    y, final = ssd_chunked(xs, bm, cm, dt, a, chunk=16)
    yref = jnp.moveaxis(
        ssd_scan_ref(
            jnp.moveaxis(xs, 2, 1), jnp.moveaxis(dt, 2, 1),
            jnp.stack([bm[:, :, 0], cm[:, :, 0]], 2), a,
        ), 1, 2,
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), atol=1e-4,
                               rtol=1e-3)


def test_ssd_chunk_size_invariance():
    b, s, h, p, n = 1, 64, 2, 16, 8
    ks = jax.random.split(KEY, 5)
    xs = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    bm = jax.random.normal(ks[1], (b, s, 1, n)) * 0.3
    cm = jax.random.normal(ks[2], (b, s, 1, n)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[4], (h,)) * 0.3)
    y8, f8 = ssd_chunked(xs, bm, cm, dt, a, chunk=8)
    y32, f32_ = ssd_chunked(xs, bm, cm, dt, a, chunk=32)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), atol=1e-4)
    np.testing.assert_allclose(np.asarray(f8), np.asarray(f32_), atol=1e-4)


def test_ssm_decode_matches_forward():
    """Step-by-step recurrence equals the chunked full forward."""
    d = 64
    dims = ssm_dims(d, expand=2, head_dim=16, d_state=8, n_groups=1)
    params, _ = ssm_init(KEY, d, dims, jnp.float32)
    b, s = 1, 12
    x = jax.random.normal(jax.random.PRNGKey(7), (b, s, d),
                          jnp.float32) * 0.3
    full = ssm_forward(params, x, dims, chunk=4)
    state = init_ssm_state(b, dims, jnp.float32)
    outs = []
    for t in range(s):
        y, state = ssm_step(params, x[:, t : t + 1], state, dims)
        outs.append(y)
    step_out = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(step_out), np.asarray(full), atol=2e-3, rtol=2e-3
    )
