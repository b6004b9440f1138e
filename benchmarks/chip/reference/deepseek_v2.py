"""Plain float32 reference of a DeepSeek-V2 decoder (``deepseek_v2``).

The published architecture (DeepSeek's ``modeling_deepseek.py``, as the
Hugging Face ``modeling_deepseek_v2`` port writes it), in ``jax.numpy``:
the token embedding; then each layer:

* RMSNorm; latent attention in its expanded form: the query ``q_proj``
  per head split into a part without RoPE (``qk_nope_head_dim``) and one
  with it (``qk_rope_head_dim``); ``kv_a_proj_with_mqa`` to a latent of
  ``kv_lora_rank`` and one rope key shared by the heads; the latent
  through ``kv_a_layernorm`` and ``kv_b_proj`` to each head's key part
  and value; RoPE on interleaved pairs with YaRN's frequencies; causal
  softmax attention over the concatenated [nope, rope] query and key,
  scaled by ``(nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2``;
  ``o_proj``; added to the residual;
* RMSNorm; the first ``first_k_dense_replace`` layers a dense SwiGLU of
  ``intermediate_size``, the rest the expert layer: a softmax over every
  routed expert's logit, the ``num_experts_per_tok`` largest
  probabilities as gates (``norm_topk_prob`` false and
  ``routed_scaling_factor`` 1, as DeepSeek-V2-Lite has them: no
  renormalisation, no scaling), each expert a SwiGLU, plus the shared
  experts as one SwiGLU of ``n_shared_experts`` times the expert width;
  added to the residual;

a final RMSNorm and logits from the untied ``lm_head``.

No cache, no absorption, no kernels: the whole sequence goes through each
layer at once, one layer at a time, float32 with ``highest`` matmul
precision (attention in blocks of query rows, which is exact).  It
imports nothing of the program under test.

The expert layer holds the share the configuration names
(``held_experts``, of ``router_experts`` routed over): a token routed to
an expert outside it gets nothing from that expert, as in the program.

The weights are the seed's (:func:`weight_maker`), drawn as the family
initialises them, stored in the served dtype and widened to float32 layer
by layer.  ``quant="fp8"`` is the control one step below the served
bfloat16: every projection (the router's too) and the logits take both
operands rounded to float8 e4m3 with a scale per row and per output
channel.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn


def dims(cfg: Dict) -> Dict:
    if cfg["norm_topk_prob"] or float(cfg["routed_scaling_factor"]) != 1:
        raise ValueError("renormalised or scaled gates are not modelled")
    rs = cfg["rope_scaling"]
    lo, hi = cfg["held_experts"]
    ff = int(cfg["moe_intermediate_size"])
    return dict(
        d=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]), r=int(cfg["kv_lora_rank"]),
        L=int(cfg["num_hidden_layers"]),
        lead=int(cfg["first_k_dense_replace"]),
        dff=int(cfg["intermediate_size"]), ff=ff,
        sff=int(cfg["n_shared_experts"]) * ff,
        E=int(cfg["router_experts"]), k=int(cfg["num_experts_per_tok"]),
        lo=int(lo), hi=int(hi), V=int(cfg["vocab_size"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        factor=float(rs["factor"]),
        orig=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all=float(rs["mscale_all_dim"]),
    )


def _get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn(m: Dict) -> Dict:
    """YaRN on the rope dimensions: the correction range ``[low, high]``,
    the inverse frequencies, the cos/sin factor and the softmax scale."""
    dim, base = m["rope"], m["theta"]

    def correction_dim(rotations):
        return (dim * math.log(m["orig"] / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(m["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(m["beta_slow"])), dim - 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (m["factor"]
                                                         * pos_freqs)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv_freq = interpolation * ramp + extrapolation * (1.0 - ramp)
    all_dim = _get_mscale(m["factor"], m["mscale_all"])
    return dict(
        low=low, high=high, inv_freq=inv_freq.astype(np.float32),
        cos_sin=_get_mscale(m["factor"], m["mscale"]) / all_dim,
        softmax_scale=(m["nope"] + m["rope"]) ** -0.5 * all_dim ** 2,
    )


def seed_key(seed: int):
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def weight_maker(cfg: Dict):
    """``make(key)``: the leading dense layers and the expert layers,
    each stacked, and the embedding, ``lm_head`` and final norm, in the
    served dtype, drawn as the family initialises them: every matrix
    normal with standard deviation ``initializer_range``, every norm scale
    1.  Only the held experts are drawn."""
    m = dims(cfg)
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    std = float(cfg["initializer_range"])
    d, H, r = m["d"], m["H"], m["r"]
    eh = m["hi"] - m["lo"]

    def normal(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def attention(ks, n):
        return {
            "ln1": ones(n, d), "ln2": ones(n, d),
            "wq": normal(ks[0], (n, d, H, m["nope"] + m["rope"])),
            "wkv_a": normal(ks[1], (n, d, r + m["rope"])),
            "kv_norm": ones(n, r),
            "wkv_b": normal(ks[2], (n, r, H, m["nope"] + m["v"])),
            "wo": normal(ks[3], (n, H, m["v"], d)),
        }

    def make(key):
        ks = jax.random.split(key, 4)
        out = {"embed": normal(ks[0], (m["V"], d)),
               "lm_head": normal(ks[1], (m["V"], d)), "norm": ones(d)}
        n = m["lead"]
        if n:
            k = jax.random.split(ks[2], 7)
            out["lead"] = {
                **attention(k, n),
                "mlp_gate": normal(k[4], (n, d, m["dff"])),
                "mlp_up": normal(k[5], (n, d, m["dff"])),
                "mlp_down": normal(k[6], (n, m["dff"], d)),
            }
        n = m["L"] - m["lead"]
        k = jax.random.split(ks[3], 11)
        out["moe"] = {
            **attention(k, n),
            "router": normal(k[4], (n, d, m["E"])),
            "w_gate": normal(k[5], (n, eh, d, m["ff"])),
            "w_up": normal(k[6], (n, eh, d, m["ff"])),
            "w_down": normal(k[7], (n, eh, m["ff"], d)),
            "sh_gate": normal(k[8], (n, d, m["sff"])),
            "sh_up": normal(k[9], (n, d, m["sff"])),
            "sh_down": normal(k[10], (n, m["sff"], d)),
        }
        return out

    return make


def init_weights(cfg: Dict, seed: int) -> Dict:
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


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _swiglu(h, gate, up, down, quant):
    a = _silu(_mm("bsd,df->bsf", h, gate, quant, 0))
    return _mm("bsf,fd->bsd", a * _mm("bsd,df->bsf", h, up, quant, 0), down,
               quant, 0)


def _rotate(x, pos, m):
    """RoPE on interleaved pairs: the pair (2i, 2i+1) of x [B, S, ..., p],
    read as the complex number x[2i] + j x[2i+1], times
    ``cos_sin * exp(j * pos * inv_freq[i])``."""
    y = yarn(m)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(y["inv_freq"])
    ang = ang.reshape((1, ang.shape[0]) + (1,) * (x.ndim - 3)
                      + ang.shape[1:])
    cos, sin = jnp.cos(ang) * y["cos_sin"], jnp.sin(ang) * y["cos_sin"]
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    re, im = pairs[..., 0], pairs[..., 1]
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     axis=-1).reshape(x.shape)


#: Query rows per block of the attention: [B, 16, 512, S] float32 scores
#: at S = 12288 are 0.4 GB per request.
Q_ROWS = 512


def attention(h, lw, m, quant: Optional[str] = None):
    """Causal latent attention in its expanded form, [B, S, d] -> [B, S, d]."""
    b, s, _ = h.shape
    nope, r = m["nope"], m["r"]
    q = _mm("bsd,dhk->bshk", h, lw["wq"], quant, 0)
    kv = _mm("bsd,dc->bsc", h, lw["wkv_a"], quant, 0)
    c = _rms(kv[..., :r], lw["kv_norm"], m["eps"])
    kv_b = _mm("bsr,rhk->bshk", c, lw["wkv_b"], quant, 0)
    pos = jnp.arange(s)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], pos, m)], -1)
    k_pe = _rotate(kv[..., r:], pos, m)  # [B,S,rope], one for every head
    k = jnp.concatenate(
        [kv_b[..., :nope],
         jnp.broadcast_to(k_pe[:, :, None, :], (b, s, m["H"], m["rope"]))],
        -1)
    v = kv_b[..., nope:]
    scale = yarn(m)["softmax_scale"]
    rows = min(Q_ROWS, s)

    def block(r0):
        qb = jax.lax.dynamic_slice_in_dim(q, r0, rows, axis=1)
        sc = jnp.einsum("bqhk,bthk->bhqt", qb, k,
                        precision=jax.lax.Precision.HIGHEST) * scale
        causal = pos[None, :] <= (r0 + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqt,bthv->bqhv", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    a = jax.lax.map(block, jnp.arange(0, s, rows))  # [nb,B,rows,H,v]
    a = jnp.moveaxis(a, 0, 1).reshape(b, s, m["H"], m["v"])
    return _mm("bshv,hvd->bsd", a, lw["wo"], quant, (0, 1))


def experts(h, lw, m, quant: Optional[str] = None):
    """The expert layer's part from the held experts ``[lo, hi)``: a
    softmax over all ``E`` router logits, the ``k`` largest probabilities
    as gates, and each held expert's SwiGLU weighted by its gate (0 for a
    token not routed to it)."""
    logits = _mm("bsd,de->bse", h, lw["router"], quant, 0)
    top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m["k"])
    out = jnp.zeros_like(h)
    for j in range(m["hi"] - m["lo"]):
        w = jnp.sum(jnp.where(idx == m["lo"] + j, top, 0.0), axis=-1)
        out = out + w[..., None] * _swiglu(h, lw["w_gate"][j], lw["w_up"][j],
                                           lw["w_down"][j], quant)
    return out


def shared_experts(h, lw, quant: Optional[str] = None):
    return _swiglu(h, lw["sh_gate"], lw["sh_up"], lw["sh_down"], quant)


def _pick(w, i):
    return {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
            .astype(jnp.float32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("hm", "quant", "dense"))
def _layer(x, w, i, hm, quant, dense):
    m = dict(hm)
    lw = _pick(w, i)
    x = x + attention(_rms(x, lw["ln1"], m["eps"]), lw, m, quant)
    h = _rms(x, lw["ln2"], m["eps"])
    if dense:
        return x + _swiglu(h, lw["mlp_gate"], lw["mlp_up"], lw["mlp_down"],
                           quant)
    return x + experts(h, lw, m, quant) + shared_experts(h, lw, quant)


@jax.jit
def _embed(w, tokens):
    return jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("hm", "quant"))
def _logits(w, x, rows, hm, quant):
    """Logits of the final-normed hidden rows ``x[rows]``."""
    m = dict(hm)
    h = _rms(x[rows], w["norm"].astype(jnp.float32), m["eps"])
    return _mm("rd,vd->rv", h, w["lm_head"].astype(jnp.float32), quant, 1)


def hidden(w, tokens: np.ndarray, hm, quant: Optional[str] = None):
    """Last-layer hidden states ``[B, S, d]`` of padded token rows (every
    position's state depends only on those before it, so padding at the
    end changes nothing)."""
    m = dict(hm)
    x = _embed(w, jnp.asarray(tokens, jnp.int32))
    for i in range(m["lead"]):
        x = _layer(x, w["lead"], jnp.int32(i), hm, quant, True)
    for i in range(m["L"] - m["lead"]):
        x = _layer(x, w["moe"], jnp.int32(i), hm, quant, False)
    return x


def logits(w, tokens: np.ndarray, hm, quant: Optional[str] = None):
    """Every position's logits ``[B, S, V]`` (small sizes: the tests)."""
    x = hidden(w, tokens, hm, quant)
    b, s = x.shape[:2]
    at = (jnp.repeat(jnp.arange(b), s), jnp.tile(jnp.arange(s), b))
    return _logits(w, x, at, hm, quant).reshape(b, s, -1)


def hm_of(cfg: Dict):
    return tuple(sorted(dims(cfg).items()))


#: Positions that go through the layers in one pass: one request of
#: 12288, or three of 4096, keep a pass's float32 activations near 3 GB
#: beside the 6.22 GB of bf16 weights.
PASS_ROWS = 12288
#: Logit rows per product: [512, 102400] float32 is 0.2 GB.
LOGIT_ROWS = 512


def served_logits(cfg: Dict, seed: int,
                  seqs: Sequence[Tuple[List[int], List[int]]], *,
                  lengths: Sequence[int], rows: int, control: bool = False):
    """For each request (prompt, served tokens), at each position that
    produced a served token: the reference's best logit (``best``) and its
    logit of the token served (``served``).  With ``control``, also the
    fp8 control's top logit (``ctl_top``) and the reference's logit of the
    token the control ranks first (``ctl_pick``).

    Each request is padded to the shortest of ``lengths`` that holds it,
    and requests of one padded length go through the layers together,
    ``PASS_ROWS // length`` at a time; ``rows`` is at least the most
    tokens a request serves.  Logits are taken ``LOGIT_ROWS`` positions
    at a time."""
    hm = hm_of(cfg)
    w = init_weights(cfg, seed)
    res: List[Optional[Dict]] = [None] * len(seqs)
    groups: Dict[int, List[int]] = {}
    for i, (prompt, out) in enumerate(seqs):
        need = len(prompt) + len(out) - 1
        fit = [n for n in sorted(lengths) if n >= need]
        if not fit:
            raise ValueError(f"{need} positions, more than {max(lengths)}")
        groups.setdefault(fit[0], []).append(i)
    for length, idx in sorted(groups.items()):
        per = max(1, PASS_ROWS // length)
        for b0 in range(0, len(idx), per):
            part = idx[b0:b0 + per]
            toks = np.zeros((per, length), np.int32)
            for r, i in enumerate(part):
                prompt, out = seqs[i]
                s = prompt + out[:-1]
                toks[r, : len(s)] = s
            with jax.default_matmul_precision("highest"):
                xs = {None: hidden(w, toks, hm)}
                if control:
                    xs["fp8"] = hidden(w, toks, hm, quant="fp8")
            for r, i in enumerate(part):
                prompt, out = seqs[i]
                n = len(out)
                if n > rows:
                    raise ValueError(f"{n} served tokens, more than "
                                     f"rows={rows}")
                padded = -(-n // LOGIT_ROWS) * LOGIT_ROWS
                pos = np.zeros(padded, np.int32)
                pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
                served = np.pad(np.asarray(out, np.int32), (0, padded - n))
                one: Dict[str, list] = {}
                for c0 in range(0, padded, LOGIT_ROWS):
                    at = (jnp.full(LOGIT_ROWS, r, jnp.int32),
                          jnp.asarray(pos[c0:c0 + LOGIT_ROWS]))
                    sv = jnp.asarray(served[c0:c0 + LOGIT_ROWS])[:, None]
                    lg = _logits(w, xs[None], at, hm, None)
                    chunk = {"best": lg.max(axis=1),
                             "served": jnp.take_along_axis(lg, sv, 1)[:, 0]}
                    if control:
                        lc = _logits(w, xs["fp8"], at, hm, "fp8")
                        pick = jnp.argmax(lc, axis=1)[:, None]
                        chunk["ctl_top"] = lc.max(axis=1)
                        chunk["ctl_pick"] = jnp.take_along_axis(lg, pick,
                                                                1)[:, 0]
                    for k, v in chunk.items():
                        one.setdefault(k, []).append(np.asarray(v,
                                                                np.float64))
                res[i] = {k: np.concatenate(v)[:n] for k, v in one.items()}
            del xs
    return res
