"""Mamba2 — state-space duality (SSD) blocks (arXiv:2405.21060).

The chunked SSD algorithm: split the sequence into chunks of Q tokens; within
a chunk the recurrence collapses to an attention-like quadratic contraction,
across chunks a small [H, P, N] state is carried by a scan.  It is written
in jnp and runs as such on every platform (lowering-friendly: one lax.scan
over chunks nested inside the layer scan).

Decode is the pure recurrence: O(1) state per token, which is why the
long_500k shape runs here.  In a hybrid stack (granite-4.0-h) the serving
cache holds two kinds of state side by side: K/V rows only for the
attention layers, and this recurrent ``h`` and conv window only for the
Mamba layers, each slot's fixed-size block read and written on every
decode step (``PERF.md``, section 4, the granite-4.0-h-small cell).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Axes, Params, dense_init, rmsnorm


def ssm_dims(d_model: int, *, expand: int = 2, head_dim: int = 64,
             d_state: int = 128, n_groups: int = 1, d_conv: int = 4) -> Dict[str, int]:
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    conv_dim = d_inner + 2 * n_groups * d_state
    return dict(
        d_inner=d_inner,
        n_heads=n_heads,
        head_dim=head_dim,
        d_state=d_state,
        n_groups=n_groups,
        d_conv=d_conv,
        conv_dim=conv_dim,
        d_in_proj=2 * d_inner + 2 * n_groups * d_state + n_heads,
    )


def ssm_init(
    key, d_model: int, dims: Dict[str, int], dtype, *, stacked: Optional[int] = None
) -> Tuple[Params, Axes]:
    kin, kconv, kdt, kout = jax.random.split(key, 4)
    lead = (stacked,) if stacked else ()
    lead_ax = ("layers",) if stacked else ()
    h, di = dims["n_heads"], dims["d_inner"]
    params: Params = {
        "in_proj": dense_init(kin, d_model, lead + (d_model, dims["d_in_proj"]), dtype),
        "conv_w": dense_init(
            kconv, dims["d_conv"], lead + (dims["d_conv"], dims["conv_dim"]), dtype
        ),
        "conv_b": jnp.zeros(lead + (dims["conv_dim"],), dtype),
        "A_log": jnp.zeros(lead + (h,), jnp.float32)
        + jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)),
        "D": jnp.ones(lead + (h,), jnp.float32),
        "dt_bias": jnp.zeros(lead + (h,), jnp.float32)
        + jnp.log(jnp.expm1(jnp.full((h,), 0.01, jnp.float32))),
        "norm": jnp.zeros(lead + (di,), dtype),
        "out_proj": dense_init(kout, di, lead + (di, d_model), dtype),
    }
    axes: Axes = {
        "in_proj": lead_ax + ("embed", "ssm_proj"),
        "conv_w": lead_ax + ("conv", "ssm_conv_dim"),
        "conv_b": lead_ax + ("ssm_conv_dim",),
        "A_log": lead_ax + ("ssm_heads",),
        "D": lead_ax + ("ssm_heads",),
        "dt_bias": lead_ax + ("ssm_heads",),
        "norm": lead_ax + ("ssm_inner",),
        "out_proj": lead_ax + ("ssm_inner", "embed"),
    }
    return params, axes


def _causal_depthwise_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: [B, S, C]; w: [K, C] depthwise causal conv along S."""
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        pad,
        w[:, None, :],  # [K, 1, C]
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
    )
    return out + b


def _split_proj(params: Params, x: jax.Array, dims: Dict[str, int]):
    di, gn, h = dims["d_inner"], dims["n_groups"] * dims["d_state"], dims["n_heads"]
    zxbcdt = jnp.einsum("bsd,dk->bsk", x, params["in_proj"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : di + di + 2 * gn]
    dt = zxbcdt[..., di + di + 2 * gn :]  # [B,S,H]
    return z, xbc, dt


def _prep_inputs(params: Params, xbc_conv: jax.Array, dt: jax.Array,
                 dims: Dict[str, int]):
    di, g, n = dims["d_inner"], dims["n_groups"], dims["d_state"]
    h, p = dims["n_heads"], dims["head_dim"]
    xs = xbc_conv[..., :di]
    bmat = xbc_conv[..., di : di + g * n]
    cmat = xbc_conv[..., di + g * n :]
    b_, s_ = xs.shape[0], xs.shape[1]
    xs = xs.reshape(b_, s_, h, p)
    bmat = bmat.reshape(b_, s_, g, n)
    cmat = cmat.reshape(b_, s_, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # [B,S,H]
    a = -jnp.exp(params["A_log"])  # [H]
    return xs, bmat, cmat, dt, a


def ssd_chunked(
    xs: jax.Array,  # [B,S,H,P]
    bmat: jax.Array,  # [B,S,G,N]
    cmat: jax.Array,  # [B,S,G,N]
    dt: jax.Array,  # [B,S,H] (post-softplus, fp32)
    a: jax.Array,  # [H] (negative, fp32)
    *,
    chunk: int = 128,
    initial_state: Optional[jax.Array] = None,  # [B,H,P,N]
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan: lax.scan over chunks carrying the [B,H,P,N] state,
    with the quadratic intra-chunk term computed *inside* the scan body so
    peak temporaries are per-chunk ([B,Q,Q,H]).

    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    b, s, h, p = xs.shape
    g, n = bmat.shape[2], bmat.shape[3]
    chunk = min(chunk, s)
    s_orig = s
    if s % chunk != 0:
        # Zero-pad to a chunk multiple: dt=0 makes padded steps exact
        # no-ops (decay exp(0)=1, zero state contribution).
        pad = chunk - s % chunk
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nc, q = s // chunk, chunk
    rep = h // g  # heads per group
    mask = jnp.tril(jnp.ones((q, q), bool))

    # Chunked views, scanned over the chunk axis (placed leading).
    xs_c = jnp.moveaxis(xs.reshape(b, nc, q, h, p), 1, 0)
    b_c = jnp.moveaxis(bmat.reshape(b, nc, q, g, n), 1, 0)
    c_c = jnp.moveaxis(cmat.reshape(b, nc, q, g, n), 1, 0)
    dt_c = jnp.moveaxis(dt.reshape(b, nc, q, h), 1, 0)

    h0 = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )

    def body(carry, inp):
        x_q, b_q, c_q, dt_q = inp  # [B,Q,H,P], [B,Q,G,N], [B,Q,G,N], [B,Q,H]
        da = dt_q * a  # [B,Q,H]
        cum = jnp.cumsum(da, axis=1)  # [B,Q,H]

        # Intra-chunk quadratic term.
        rel = cum[:, :, None, :] - cum[:, None, :, :]  # [B,Q,Q,H]
        decay = jnp.where(mask[None, :, :, None], jnp.exp(rel), 0.0)
        scores = jnp.einsum("bqgn,bkgn->bqkg", c_q, b_q)  # [B,Q,Q,G]
        scores = jnp.repeat(scores, rep, axis=-1)  # [B,Q,Q,H]
        w = (scores * decay).astype(x_q.dtype)
        dx = (dt_q[..., None] * x_q.astype(jnp.float32)).astype(x_q.dtype)
        y_q = jnp.einsum("bqkh,bkhp->bqhp", w, dx)

        # Inter-chunk contribution from the carried state.
        c_heads = jnp.repeat(c_q, rep, axis=2)  # [B,Q,H,N]
        y_q = y_q + jnp.einsum(
            "bqhn,bhpn->bqhp", jnp.exp(cum)[..., None] * c_heads, carry
        ).astype(x_q.dtype)

        # State update: new = decay_total * old + sum_q tail[q] dt[q] B[q] x[q]^T.
        tail = jnp.exp(cum[:, -1:, :] - cum)  # [B,Q,H]
        b_heads = jnp.repeat(b_q, rep, axis=2)  # [B,Q,H,N]
        weighted_x = (tail * dt_q)[..., None] * x_q.astype(jnp.float32)  # [B,Q,H,P]
        s_chunk = jnp.einsum("bqhp,bqhn->bhpn", weighted_x, b_heads)
        total_decay = jnp.exp(jnp.sum(da, axis=1))  # [B,H]
        new_carry = carry * total_decay[:, :, None, None] + s_chunk
        return new_carry, y_q

    final, y = jax.lax.scan(body, h0, (xs_c, b_c, c_c, dt_c))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, h, p)
    return y[:, :s_orig], final


def ssm_forward(
    params: Params,
    x: jax.Array,  # [B,S,D]
    dims: Dict[str, int],
    *,
    chunk: int = 128,
    eps: float = 1e-6,
) -> jax.Array:
    z, xbc, dt_raw = _split_proj(params, x, dims)
    xbc = jax.nn.silu(_causal_depthwise_conv(xbc, params["conv_w"], params["conv_b"]))
    xs, bmat, cmat, dt, a = _prep_inputs(params, xbc, dt_raw, dims)
    y, _ = ssd_chunked(xs, bmat, cmat, dt, a, chunk=chunk)
    b, s = x.shape[0], x.shape[1]
    y = y.reshape(b, s, dims["d_inner"])
    y = y + (params["D"].repeat(dims["head_dim"]) * xs.reshape(b, s, -1).astype(
        jnp.float32)).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), params["norm"], eps)
    return jnp.einsum("bsi,id->bsd", y, params["out_proj"])


# ---------------------------------------------------------------------------
# Decode path (recurrent single-step)
# ---------------------------------------------------------------------------


def init_ssm_state(batch: int, dims: Dict[str, int], dtype=jnp.float32
                   ) -> Dict[str, jax.Array]:
    return {
        "h": jnp.zeros(
            (batch, dims["n_heads"], dims["head_dim"], dims["d_state"]), jnp.float32
        ),
        "conv": jnp.zeros((batch, dims["d_conv"] - 1, dims["conv_dim"]), dtype),
    }


SSM_STATE_AXES = {"h": ("batch", "ssm_heads", "ssm_head_dim", "ssm_state"),
                  "conv": ("batch", "conv", "ssm_conv_dim")}


def ssm_step(
    params: Params,
    x: jax.Array,  # [B,1,D]
    state: Dict[str, jax.Array],
    dims: Dict[str, int],
    eps: float = 1e-6,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b = x.shape[0]
    g, h = dims["n_groups"], dims["n_heads"]
    rep = h // g
    z, xbc, dt_raw = _split_proj(params, x, dims)  # [B,1,*]
    # Conv over the rolling window [conv_state | new].
    window = jnp.concatenate([state["conv"], xbc], axis=1)  # [B,K,conv]
    conv_out = jnp.einsum("bkc,kc->bc", window, params["conv_w"]) + params["conv_b"]
    conv_out = jax.nn.silu(conv_out)[:, None, :]  # [B,1,conv]
    new_conv = window[:, 1:, :]
    xs, bmat, cmat, dt, a = _prep_inputs(params, conv_out, dt_raw, dims)
    # Single-step recurrence.
    dt1 = dt[:, 0]  # [B,H]
    da = jnp.exp(dt1 * a)  # [B,H]
    b1 = jnp.repeat(bmat[:, 0], rep, axis=1)  # [B,H,N]
    c1 = jnp.repeat(cmat[:, 0], rep, axis=1)  # [B,H,N]
    x1 = xs[:, 0].astype(jnp.float32)  # [B,H,P]
    new_h = state["h"] * da[:, :, None, None] + jnp.einsum(
        "bhp,bhn->bhpn", dt1[:, :, None] * x1, b1
    )
    y = jnp.einsum("bhpn,bhn->bhp", new_h, c1)  # [B,H,P]
    y = y + params["D"][None, :, None] * x1
    y = y.reshape(b, 1, dims["d_inner"]).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), params["norm"], eps)
    out = jnp.einsum("bsi,id->bsd", y, params["out_proj"])
    return out, {"h": new_h, "conv": new_conv}
