"""Shared layer primitives: norms, RoPE, MLPs, embeddings.

Parameters are plain nested dicts of jnp arrays; every init function returns
``(params, axes)`` where ``axes`` mirrors the param tree with tuples of
*logical* axis names consumed by :mod:`repro.distributed.sharding`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]
Axes = Dict[str, Any]

# ---------------------------------------------------------------------------
# Initializers (all take an explicit key; variance-scaled).
# ---------------------------------------------------------------------------


def _normal(key, shape, dtype, scale: float):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def dense_init(key, in_dim: int, shape, dtype) -> jax.Array:
    return _normal(key, shape, dtype, in_dim**-0.5)


def embed_init(key, shape, dtype) -> jax.Array:
    return _normal(key, shape, dtype, 1.0)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(dim: int, dtype) -> Tuple[jax.Array, Tuple[str, ...]]:
    return jnp.zeros((dim,), dtype=dtype), ("embed",)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # "zero-centered" scale (gemma-style 1+w); w init 0 => identity.
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: Optional[jax.Array] = None,
              eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    x = x * (1.0 + scale.astype(jnp.float32))
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)  # [head_dim/2]


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, Dh]; positions: broadcastable to [..., S]."""
    freqs = rope_frequencies(x.shape[-1], theta)  # [Dh/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, Dh/2]
    sin = jnp.sin(angles)[..., None, :]  # [..., S, 1, Dh/2]
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's stretch of RoPE to ``factor`` times the context it was
    trained on (Peng et al., arXiv:2309.00071), with DeepSeek's ``mscale``
    pair."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_range(dim: int, theta: float, yarn: Yarn) -> Tuple[int, int]:
    """The frequencies ``[low, high]`` over which YaRN ramps from keeping
    them (below ``low``: ``beta_fast`` or more turns over the original
    context) to dividing them by ``factor`` (above ``high``: fewer than
    ``beta_slow`` turns)."""

    def at(turns):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(at(yarn.beta_fast)), 0),
            min(math.ceil(at(yarn.beta_slow)), dim - 1))


def yarn_frequencies(dim: int, theta: float, yarn: Yarn) -> np.ndarray:
    """YaRN's ``dim / 2`` inverse frequencies (float32)."""
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_range(dim, theta, yarn)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (base / yarn.factor * ramp + base * (1 - ramp)).astype(np.float32)


def apply_rope_pairs(x: jax.Array, positions: jax.Array, inv_freq,
                     scale: float) -> jax.Array:
    """RoPE on interleaved pairs ``(2i, 2i+1)``, each turned by frequency
    ``i`` (DeepSeek's layout).  x: [B, S, ..., D]; positions: [B, S];
    cos and sin are multiplied by ``scale``."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x0, x1 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def mlp_init(key, d_model: int, d_ff: int, dtype, stacked: Optional[int] = None
             ) -> Tuple[Params, Axes]:
    kg, ku, kd = jax.random.split(key, 3)
    lead = (stacked,) if stacked else ()
    lead_ax = ("layers",) if stacked else ()
    params = {
        "w_gate": dense_init(kg, d_model, lead + (d_model, d_ff), dtype),
        "w_up": dense_init(ku, d_model, lead + (d_model, d_ff), dtype),
        "w_down": dense_init(kd, d_ff, lead + (d_ff, d_model), dtype),
    }
    axes = {
        "w_gate": lead_ax + ("embed", "ffn"),
        "w_up": lead_ax + ("embed", "ffn"),
        "w_down": lead_ax + ("ffn", "embed"),
    }
    return params, axes


def mlp_apply(params: Params, x: jax.Array, activation: str = "silu") -> jax.Array:
    gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    if activation == "silu":
        act = jax.nn.silu(gate)
    elif activation == "gelu":
        act = jax.nn.gelu(gate, approximate=True)
    else:
        raise ValueError(activation)
    return jnp.einsum("...f,fd->...d", act * up, params["w_down"])


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embedding_init(key, vocab: int, d_model: int, dtype) -> Tuple[jax.Array, Tuple]:
    return embed_init(key, (vocab, d_model), dtype), ("vocab", "embed")


def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def unembed(x: jax.Array, table: jax.Array) -> jax.Array:
    """Logits via the (possibly tied) embedding table: [..., D] -> [..., V]."""
    return jnp.einsum("...d,vd->...v", x, table)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """gemma2 logit soft-capping: cap * tanh(x / cap)."""
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)
