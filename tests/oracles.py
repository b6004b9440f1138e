"""Pure-jnp oracles for the models' decode attention and SSD scan.

These are deliberately *naive* implementations (full-materialization
attention; token-by-token SSD recurrence) — independent of the blocked
model code, so its bugs cannot hide behind shared logic.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38


def decode_attention_ref(
    q: jax.Array,  # [B, Hkv, G, Dh]
    k: jax.Array,  # [B, Hkv, S, Dh]
    v: jax.Array,  # [B, Hkv, S, Dh]
    lengths: jax.Array,  # [B] int32
    *,
    window: int = 1 << 30,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    dh = q.shape[-1]
    s = k.shape[2]
    if scale is None:
        scale = dh**-0.5
    scores = jnp.einsum(
        "bhgd,bhsd->bhgs", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    pos = jnp.arange(s)[None, :]  # [1, S]
    length = lengths[:, None]  # [B, 1]
    valid = (pos < length) & (length - 1 - pos < window)  # [B, S]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_scan_ref(
    x: jax.Array,  # [B, H, S, P]
    dt: jax.Array,  # [B, H, S] f32
    bc: jax.Array,  # [B, S, 2, N]
    a: jax.Array,  # [H] f32 (negative)
) -> jax.Array:
    """Token-by-token SSD recurrence (the ground-truth semantics):

        h_t = exp(a * dt_t) h_{t-1} + dt_t * B_t x_t^T
        y_t = C_t . h_t
    """
    b, h, s, p = x.shape
    n = bc.shape[-1]
    xf = x.astype(jnp.float32)
    bmat = bc[:, :, 0, :].astype(jnp.float32)  # [B, S, N]
    cmat = bc[:, :, 1, :].astype(jnp.float32)

    def step(hstate, t_inputs):
        xt, dtt, bt, ct = t_inputs  # [B,H,P], [B,H], [B,N], [B,N]
        decay = jnp.exp(dtt * a[None, :])  # [B, H]
        upd = jnp.einsum("bhp,bn->bhpn", dtt[..., None] * xt, bt)
        hstate = hstate * decay[..., None, None] + upd
        yt = jnp.einsum("bhpn,bn->bhp", hstate, ct)
        return hstate, yt

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    xs = (
        jnp.moveaxis(xf, 2, 0),  # [S, B, H, P]
        jnp.moveaxis(dt.astype(jnp.float32), 2, 0),  # [S, B, H]
        jnp.moveaxis(bmat, 1, 0),  # [S, B, N]
        jnp.moveaxis(cmat, 1, 0),  # [S, B, N]
    )
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 2).astype(x.dtype)  # [B, H, S, P]
