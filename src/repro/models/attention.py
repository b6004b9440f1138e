"""Grouped-query attention with sliding windows, soft-capping and KV caches.

One implementation serves every assigned attention arch:

  * GQA head grouping (n_q_heads % n_kv_heads == 0), optional QKV biases
    (qwen2.5) and per-head QK-norm (stablelm-2).
  * Per-layer *dynamic* attention windows: the window size is a traced
    scalar, so a scan over layers can alternate local/global (gemma2) or
    SWA/full (hymba, h2o-danube) without breaking layer-stacking.  A window
    >= S is full causal attention.
  * Logit soft-capping (gemma2).
  * Serving: ``attend_cached_stacked`` runs one-token decode against one
    layer of a cache that stacks every attention layer of the model,
    writing each slot's new row in place and masking by its length (and
    the layer's window); every stack's decode carries that cache.
  * Latent attention (MLA, DeepSeek-V2): K and V of every head expand
    from one shared latent per position, and only the normed latent and
    one roped key shared by the heads are cached.  ``mla_full`` (prefill,
    training) expands them; ``mla_cached`` (decode) absorbs each head's
    key expansion into its query and its value expansion into its output,
    so a step reads the latent cache as it lies.

Every path is the jnp einsums of this module, on every platform: XLA
compiles them for the TPU as it does for the CPU, and no hand-written
kernel takes their place.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import (
    Axes,
    Params,
    apply_rope,
    apply_rope_pairs,
    dense_init,
    rmsnorm,
)

NEG_INF = -2.3819763e38  # large negative for masking (bf16-safe)


def attention_init(
    key,
    d_model: int,
    n_q: int,
    n_kv: int,
    head_dim: int,
    dtype,
    *,
    stacked: Optional[int] = None,
    qkv_bias: bool = False,
    qk_norm: bool = False,
) -> Tuple[Params, Axes]:
    kq, kk, kv, ko = jax.random.split(key, 4)
    lead = (stacked,) if stacked else ()
    lead_ax = ("layers",) if stacked else ()
    params: Params = {
        "wq": dense_init(kq, d_model, lead + (d_model, n_q, head_dim), dtype),
        "wk": dense_init(kk, d_model, lead + (d_model, n_kv, head_dim), dtype),
        "wv": dense_init(kv, d_model, lead + (d_model, n_kv, head_dim), dtype),
        "wo": dense_init(ko, n_q * head_dim, lead + (n_q, head_dim, d_model), dtype),
    }
    axes: Axes = {
        "wq": lead_ax + ("embed", "q_heads", "head_dim"),
        "wk": lead_ax + ("embed", "kv_heads", "head_dim"),
        "wv": lead_ax + ("embed", "kv_heads", "head_dim"),
        "wo": lead_ax + ("q_heads", "head_dim", "embed"),
    }
    if qkv_bias:
        params["bq"] = jnp.zeros(lead + (n_q, head_dim), dtype)
        params["bk"] = jnp.zeros(lead + (n_kv, head_dim), dtype)
        params["bv"] = jnp.zeros(lead + (n_kv, head_dim), dtype)
        axes["bq"] = lead_ax + ("q_heads", "head_dim")
        axes["bk"] = lead_ax + ("kv_heads", "head_dim")
        axes["bv"] = lead_ax + ("kv_heads", "head_dim")
    if qk_norm:
        params["q_norm"] = jnp.zeros(lead + (head_dim,), dtype)
        params["k_norm"] = jnp.zeros(lead + (head_dim,), dtype)
        axes["q_norm"] = lead_ax + ("head_dim",)
        axes["k_norm"] = lead_ax + ("head_dim",)
    return params, axes


def project_qkv(
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    *,
    rope_theta: Optional[float],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, S, D] -> q [B,S,Hq,Dh], k/v [B,S,Hkv,Dh] (RoPE applied)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if "q_norm" in params:
        q = rmsnorm(q, params["q_norm"])
        k = rmsnorm(k, params["k_norm"])
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def _grouped_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B,S,Hq,Dh] x k [B,T,Hkv,Dh] -> scores [B,Hq,S,T] with GQA groups."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    q = q.reshape(b, s, hkv, group, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k)
    return scores.reshape(b, hkv * group, s, k.shape[1])


def _grouped_values(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs [B,Hq,S,T] x v [B,T,Hkv,Dh] -> [B,S,Hq,Dh]."""
    b, hq, s, t = probs.shape
    hkv = v.shape[2]
    group = hq // hkv
    probs = probs.reshape(b, hkv, group, s, t)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, hq, v.shape[3])


#: Above this sequence length, attend_full processes queries in row blocks
#: of this size, bounding live score buffers to [B, H, Q_BLOCK, S] — the
#: jnp flash-attention analogue.
Q_BLOCK = 1024


def _attention_core(
    q: jax.Array,  # [B,Sq,Hq,Dh] (pre-scaled)
    k: jax.Array,  # [B,T,Hkv,Dh]
    v: jax.Array,  # [B,T,Hkv,Dh]
    qpos: jax.Array,  # [B,Sq]
    tpos: jax.Array,  # [B,T]
    *,
    window: jax.Array,
    softcap_value: Optional[float],
    causal: bool,
    dtype,
) -> jax.Array:
    scores = _grouped_scores(q, k)  # [B,Hq,Sq,T]
    if softcap_value is not None:
        scores = softcap_value * jnp.tanh(scores / softcap_value)
    sp = qpos[:, :, None]  # [B,Sq,1]
    tp = tpos[:, None, :]  # [B,1,T]
    if causal:
        mask = (tp <= sp) & (sp - tp < window)
    else:
        mask = jnp.abs(sp - tp) < window
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
    return _grouped_values(probs, v)  # [B,Sq,Hq,Dh]


def attend_full(
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    *,
    rope_theta: Optional[float],
    window: jax.Array,
    softcap_value: Optional[float] = None,
    causal: bool = True,
    query_scale: Optional[float] = None,
    q_block: int = Q_BLOCK,
) -> jax.Array:
    """Full-sequence attention (training / prefill).  ``window`` is a traced
    scalar: key t attends to query s iff 0 <= s - t < window (causal) —
    window >= S means dense causal; non-causal encoders pass causal=False.

    For S > q_block, queries are processed in blocks via lax.map so the
    [B, H, S, S] score tensor never materializes (exact, not approximate)."""
    s = x.shape[1]
    dh = params["wq"].shape[-1]
    q, k, v = project_qkv(params, x, positions, rope_theta=rope_theta)
    scale = query_scale if query_scale is not None else dh**-0.5
    q = q * scale
    if s <= q_block or s % q_block != 0:
        out = _attention_core(
            q, k, v, positions, positions,
            window=window, softcap_value=softcap_value, causal=causal,
            dtype=x.dtype,
        )
    else:
        nb = s // q_block
        b, _, hq, _ = q.shape
        q_c = q.reshape(b, nb, q_block, hq, dh).swapaxes(0, 1)
        pos_c = positions.reshape(b, nb, q_block).swapaxes(0, 1)

        def one(args):
            qc, pc = args
            return _attention_core(
                qc, k, v, pc, positions,
                window=window, softcap_value=softcap_value, causal=causal,
                dtype=x.dtype,
            )

        # Per-block checkpoint: the map's backward otherwise saves every
        # block's probs simultaneously — the full S^2 buffer again.
        out = jax.lax.map(jax.checkpoint(one), (q_c, pos_c))
        out = out.swapaxes(0, 1).reshape(b, s, hq, dh)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def attend_cross(
    params: Params,
    x: jax.Array,
    memory_k: jax.Array,
    memory_v: jax.Array,
    *,
    q_block: int = 0,
) -> jax.Array:
    """Cross-attention against precomputed encoder K/V (whisper decoder)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    b, s, hq, dh = q.shape
    q = q * dh**-0.5
    q_block = q_block or Q_BLOCK

    def core(qc):
        scores = _grouped_scores(qc, memory_k)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
            x.dtype
        )
        return _grouped_values(probs, memory_v)

    if s <= q_block or s % q_block != 0:
        out = core(q)
    else:
        nb = s // q_block
        q_c = q.reshape(b, nb, q_block, hq, dh).swapaxes(0, 1)
        out = jax.lax.map(jax.checkpoint(core), q_c)
        out = out.swapaxes(0, 1).reshape(b, s, hq, dh)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def project_memory_kv(params: Params, memory: jax.Array) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", memory, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, params["wv"])
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    return k, v


# ---------------------------------------------------------------------------
# Decode path (one new token against a KV cache)
# ---------------------------------------------------------------------------


def attend_cached_stacked(
    params: Params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    layer: jax.Array,
    length: jax.Array,
    *,
    rope_theta: Optional[float],
    window: Optional[jax.Array] = None,
    softcap_value: Optional[float] = None,
    query_scale: Optional[float] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against the K/V of every attention layer of a
    stack, ``cache`` k/v: [L, B, S_max, Hkv, Dh].  The new row of each
    slot is written in place at ``[layer, slot, length[slot]]``, and the
    token attends to the positions up to it: to the last ``window`` of
    them when a window (a traced scalar) is given, with its scores
    soft-capped at ``softcap_value`` when one is.  Returns ([B, 1, D],
    cache)."""
    b = x.shape[0]
    positions = length[:, None]  # [B,1]
    q, k_new, v_new = project_qkv(params, x, positions, rope_theta=rope_theta)
    slots = jnp.arange(b)
    cache = {
        "k": cache["k"].at[layer, slots, length].set(
            k_new[:, 0].astype(cache["k"].dtype)),
        "v": cache["v"].at[layer, slots, length].set(
            v_new[:, 0].astype(cache["v"].dtype)),
    }
    k = jax.lax.dynamic_index_in_dim(cache["k"], layer, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache["v"], layer, keepdims=False)
    dh = q.shape[-1]
    scale = query_scale if query_scale is not None else dh**-0.5
    scores = _grouped_scores(q * scale, k)  # [B,Hq,1,S_max]
    if softcap_value is not None:
        scores = softcap_value * jnp.tanh(scores / softcap_value)
    t = jnp.arange(k.shape[1])[None, :]  # [1,S_max]
    valid = t <= positions  # [B,S_max]
    if window is not None:
        valid = valid & (positions - t < window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = _grouped_values(probs, v)  # [B,1,Hq,Dh]
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache


# ---------------------------------------------------------------------------
# Latent attention (MLA)
# ---------------------------------------------------------------------------


def mla_init(
    key,
    d_model: int,
    n_heads: int,
    *,
    kv_lora_rank: int,
    nope_dim: int,
    rope_dim: int,
    v_dim: int,
    dtype,
    stacked: Optional[int] = None,
) -> Tuple[Params, Axes]:
    """DeepSeek-V2's attention with no query compression: ``wq``
    (``q_proj``), ``wkv_a`` (``kv_a_proj_with_mqa``: the latent, then the
    shared rope key), ``kv_norm`` (``kv_a_layernorm``), ``w_uk`` and
    ``w_uv`` (``kv_b_proj``'s key and value halves, [r, H, dim]) and
    ``wo``."""
    kq, ka, kk, kv, ko = jax.random.split(key, 5)
    lead = (stacked,) if stacked else ()
    lead_ax = ("layers",) if stacked else ()
    r = kv_lora_rank
    params: Params = {
        "wq": dense_init(kq, d_model,
                         lead + (d_model, n_heads, nope_dim + rope_dim), dtype),
        "wkv_a": dense_init(ka, d_model, lead + (d_model, r + rope_dim), dtype),
        "kv_norm": jnp.zeros(lead + (r,), dtype),
        "w_uk": dense_init(kk, r, lead + (r, n_heads, nope_dim), dtype),
        "w_uv": dense_init(kv, r, lead + (r, n_heads, v_dim), dtype),
        "wo": dense_init(ko, n_heads * v_dim, lead + (n_heads, v_dim, d_model),
                         dtype),
    }
    axes: Axes = {
        "wq": lead_ax + ("embed", "q_heads", "head_dim"),
        "wkv_a": lead_ax + ("embed", "kv_lora"),
        "kv_norm": lead_ax + ("kv_lora",),
        "w_uk": lead_ax + ("kv_lora", "q_heads", "head_dim"),
        "w_uv": lead_ax + ("kv_lora", "q_heads", "head_dim"),
        "wo": lead_ax + ("q_heads", "head_dim", "embed"),
    }
    return params, axes


def _mla_project(params: Params, x: jax.Array, positions: jax.Array, *,
                 inv_freq, rope_scale: float, eps: float):
    """x [B, S, D] -> roped query halves q_nope [B,S,H,n], q_pe [B,S,H,p],
    the normed latent c [B,S,r] and the roped shared key k_pe [B,S,p]."""
    r = params["w_uk"].shape[-3]
    nope = params["w_uk"].shape[-1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    kv = jnp.einsum("bsd,dc->bsc", x, params["wkv_a"])
    c = rmsnorm(kv[..., :r], params["kv_norm"], eps)
    q_pe = apply_rope_pairs(q[..., nope:], positions, inv_freq, rope_scale)
    k_pe = apply_rope_pairs(kv[..., r:], positions, inv_freq, rope_scale)
    return q[..., :nope], q_pe, c, k_pe


def mla_full(
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[Dict[str, jax.Array]] = None,
    layer: Optional[jax.Array] = None,
    *,
    inv_freq,
    rope_scale: float,
    scale: float,
    eps: float,
    q_block: int = Q_BLOCK,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Causal MLA over a whole sequence in its expanded form: per-head K
    (``c @ w_uk`` beside the shared rope key) and V (``c @ w_uv``), scores
    scaled by ``scale``, queries in blocks of ``q_block`` rows.  With a
    ``cache`` (c_kv [L, B, S_max, r], k_pe [L, B, S_max, p]) the latent
    and rope key of every position are written into layer ``layer``.
    Returns ([B, S, D], cache)."""
    b, s, _ = x.shape
    q_nope, q_pe, c, k_pe = _mla_project(
        params, x, positions, inv_freq=inv_freq, rope_scale=rope_scale,
        eps=eps)
    if cache is not None:
        cache = {n: jax.lax.dynamic_update_slice(
            cache[n], t.astype(cache[n].dtype)[None], (layer, 0, 0, 0))
            for n, t in (("c_kv", c), ("k_pe", k_pe))}
    k_nope = jnp.einsum("bsr,rhn->bshn", c, params["w_uk"])
    v = jnp.einsum("bsr,rhv->bshv", c, params["w_uv"])
    q_nope, q_pe = q_nope * scale, q_pe * scale
    tpos = positions[:, None, :]  # [B,1,T]

    def core(qn, qp, qpos):
        scores = (jnp.einsum("bqhn,bthn->bhqt", qn, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhp,btp->bhqt", qp, k_pe,
                               preferred_element_type=jnp.float32))
        mask = tpos <= qpos[:, :, None]  # [B,Q,T]
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqt,bthv->bqhv", probs.astype(x.dtype), v)

    if s <= q_block or s % q_block != 0:
        out = core(q_nope, q_pe, positions)
    else:
        nb = s // q_block

        def blocks(t):
            return t.reshape((b, nb, q_block) + t.shape[2:]).swapaxes(0, 1)

        out = jax.lax.map(jax.checkpoint(lambda a: core(*a)),
                          (blocks(q_nope), blocks(q_pe), blocks(positions)))
        out = out.swapaxes(0, 1).reshape((b, s) + out.shape[3:])
    return jnp.einsum("bshv,hvd->bsd", out, params["wo"]), cache


def mla_cached(
    params: Params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    layer: jax.Array,
    length: jax.Array,
    *,
    inv_freq,
    rope_scale: float,
    scale: float,
    eps: float,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token MLA decode in its absorbed form against layer ``layer``
    of the stacked latent cache (c_kv [L, B, S_max, r], k_pe [L, B,
    S_max, p]).  Each slot's latent and rope key are written in place at
    ``[layer, slot, length[slot]]``; each head's query is mapped through
    its ``w_uk`` into the latent (no merged weight is stored), scored
    against the cached latents plus ``q_pe . k_pe``, the latents summed by
    the softmax and mapped out through the head's ``w_uv``.  Returns
    ([B, 1, D], cache)."""
    b = x.shape[0]
    positions = length[:, None]  # [B,1]
    q_nope, q_pe, c, k_pe = _mla_project(
        params, x, positions, inv_freq=inv_freq, rope_scale=rope_scale,
        eps=eps)
    slots = jnp.arange(b)
    cache = {n: cache[n].at[layer, slots, length].set(
        t[:, 0].astype(cache[n].dtype)) for n, t in (("c_kv", c),
                                                     ("k_pe", k_pe))}
    lat = jax.lax.dynamic_index_in_dim(cache["c_kv"], layer, keepdims=False)
    rope = jax.lax.dynamic_index_in_dim(cache["k_pe"], layer, keepdims=False)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0] * scale, params["w_uk"])
    scores = (jnp.einsum("bhr,btr->bht", q_lat, lat,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhp,btp->bht", q_pe[:, 0] * scale, rope,
                           preferred_element_type=jnp.float32))
    t = jnp.arange(lat.shape[1])[None, :]  # [1,S_max]
    scores = jnp.where((t <= positions)[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bht,btr->bhr", probs, lat)
    out = jnp.einsum("bhr,rhv->bhv", ctx, params["w_uv"])
    return jnp.einsum("bhv,hvd->bd", out, params["wo"])[:, None], cache
