"""Plain float32 reference of a Qwen2 decoder (the Qwen2.5 family).

The published architecture, written out in ``jax.numpy``: token embedding;
per layer RMSNorm, grouped-query attention with biases on q, k and v,
rotary embeddings (theta from the config, rotate-half convention), a
causal softmax scaled by ``head_dim ** -0.5``, the output projection and a
residual; RMSNorm, a SwiGLU MLP and a residual; a final RMSNorm and logits
from the tied embedding.  No cache, no kernels, no batching tricks: the
whole sequence goes through each layer at once, one layer at a time,
float32 with ``highest`` matmul precision.  It imports nothing of the
program under test.

The weights are the seed's (:func:`weight_maker`), drawn as the family's
``config.json`` initialises them (``initializer_range``); the benchmark
hands the program the same draw in its own layout, and the reference
makes its copy anew from the seed.  They are stored in the served dtype
and widened to float32 layer by layer.

``quant="fp8"`` is the control one step below the served bfloat16: every
projection and the logits take both operands rounded to float8 e4m3 with
a scale per row and per output channel.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _dims(cfg: Dict) -> Dict[str, int]:
    d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(L=int(cfg["num_hidden_layers"]), d=d, hq=hq,
                hkv=int(cfg["num_key_value_heads"]),
                dh=int(cfg.get("head_dim") or d // hq),
                ff=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def seed_key(seed: int):
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def weight_maker(cfg: Dict):
    """``make(key)``: stacked per-layer weights and the tied embedding in
    the served dtype, drawn as the family initialises them: every matrix
    and the embedding normal with standard deviation
    ``initializer_range``, biases 0, RMSNorm scales 1."""
    m = _dims(cfg)
    L, d, hq, hkv, dh, ff, V = (m[k] for k in
                                ("L", "d", "hq", "hkv", "dh", "ff", "V"))
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    std = float(cfg["initializer_range"])

    def normal(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def make(key):
        ks = jax.random.split(key, 8)
        return {
            "embed": normal(ks[0], (V, d)),
            "wq": normal(ks[1], (L, d, hq, dh)),
            "wk": normal(ks[2], (L, d, hkv, dh)),
            "wv": normal(ks[3], (L, d, hkv, dh)),
            "wo": normal(ks[4], (L, hq, dh, d)),
            "bq": jnp.zeros((L, hq, dh), dtype),
            "bk": jnp.zeros((L, hkv, dh), dtype),
            "bv": jnp.zeros((L, hkv, dh), dtype),
            "ln1": jnp.ones((L, d), dtype),
            "ln2": jnp.ones((L, d), dtype),
            "w_gate": normal(ks[5], (L, d, ff)),
            "w_up": normal(ks[6], (L, d, ff)),
            "w_down": normal(ks[7], (L, ff, d)),
            "norm": jnp.ones((d,), dtype),
        }

    return make


def init_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """The seed's weights, made on the device in one jitted call."""
    return jax.jit(weight_maker(cfg))(seed_key(seed))


def _q8(x: jax.Array, axis) -> jax.Array:
    """``x`` rounded to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, x, w, quant: Optional[str], w_in_axes):
    if quant == "fp8":
        x = _q8(x, -1)
        w = _q8(w, w_in_axes)
    return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]  # [S, dh/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("hm", "quant"))
def _layer(x, w, i, hm, quant):
    m = dict(hm)
    lw = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
          .astype(jnp.float32) for k, v in w.items() if k not in
          ("embed", "norm")}
    b, s, _ = x.shape
    pos = jnp.arange(s)
    h = _rms(x, lw["ln1"], m["eps"])
    q = _mm("bsd,dhk->bshk", h, lw["wq"], quant, 0) + lw["bq"]
    k = _mm("bsd,dhk->bshk", h, lw["wk"], quant, 0) + lw["bk"]
    v = _mm("bsd,dhk->bshk", h, lw["wv"], quant, 0) + lw["bv"]
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    g = m["hq"] // m["hkv"]
    q = q.reshape(b, s, m["hkv"], g, m["dh"]) * m["dh"] ** -0.5
    sc = jnp.einsum("bskgd,btkd->bkgst", q, k,
                    precision=jax.lax.Precision.HIGHEST)
    causal = pos[None, :] <= pos[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    a = jnp.einsum("bkgst,btkd->bskgd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    a = a.reshape(b, s, m["hq"], m["dh"])
    x = x + _mm("bshk,hkd->bsd", a, lw["wo"], quant, (0, 1))
    h = _rms(x, lw["ln2"], m["eps"])
    gate = _mm("bsd,df->bsf", h, lw["w_gate"], quant, 0)
    up = _mm("bsd,df->bsf", h, lw["w_up"], quant, 0)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lw["w_down"],
                   quant, 0)


@jax.jit
def _embed(w, tokens):
    return jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(w, x, rows, eps, quant):
    """Logits of the final-normed hidden rows ``x[rows]``."""
    h = _rms(x[rows], w["norm"].astype(jnp.float32), eps)
    e = w["embed"].astype(jnp.float32)
    return _mm("rd,vd->rv", h, e, quant, 1)


def hidden(w, tokens: np.ndarray, hm, quant: Optional[str] = None):
    """Last-layer hidden states ``[B, S, d]`` of padded token rows."""
    x = _embed(w, jnp.asarray(tokens, jnp.int32))
    for i in range(dict(hm)["L"]):
        x = _layer(x, w, jnp.int32(i), hm, quant)
    return x


def served_logits(cfg: Dict, seed: int,
                  seqs: Sequence[Tuple[List[int], List[int]]], *,
                  max_len: int, control: bool = False, rows: int = 128):
    """For each request (prompt, served tokens), at each position that
    produced a served token: the reference's best logit (``best``) and its
    logit of the token served (``served``).  With ``control``, also the
    fp8 control's top logit (``ctl_top``) and the reference's logit of the
    token the control ranks first (``ctl_pick``)."""
    m = _dims(cfg)
    hm = tuple(sorted(m.items()))
    w = init_weights(cfg, seed)
    toks = np.zeros((len(seqs), max_len), np.int32)
    for i, (prompt, out) in enumerate(seqs):
        s = prompt + out[:-1]
        toks[i, : len(s)] = s
    with jax.default_matmul_precision("highest"):
        xs = {None: hidden(w, toks, hm)}
        if control:
            xs["fp8"] = hidden(w, toks, hm, quant="fp8")
    res = []
    for i, (prompt, out) in enumerate(seqs):
        n = len(out)
        pos = np.zeros(rows, np.int32)
        pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        at = (jnp.full(rows, i, jnp.int32), jnp.asarray(pos))
        served = jnp.asarray(np.pad(np.asarray(out, np.int32),
                                    (0, rows - n)))[:, None]
        logits = _logits(w, xs[None], at, m["eps"], None)
        r = {"best": logits.max(axis=1),
             "served": jnp.take_along_axis(logits, served, 1)[:, 0]}
        if control:
            lc = _logits(w, xs["fp8"], at, m["eps"], "fp8")
            pick = jnp.argmax(lc, axis=1)[:, None]
            r["ctl_top"] = lc.max(axis=1)
            r["ctl_pick"] = jnp.take_along_axis(logits, pick, 1)[:, 0]
        res.append({k: np.asarray(v, np.float64)[:n] for k, v in r.items()})
    return res
