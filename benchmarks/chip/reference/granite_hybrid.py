"""Plain float32 reference of a Granite 4.0-H decoder (``granitemoehybrid``).

The published architecture (Hugging Face ``modeling_granitemoehybrid``),
written out in ``jax.numpy``: the token embedding times
``embedding_multiplier``; then each layer, of the kind ``layer_types``
gives it:

* Mamba-2: RMSNorm; ``in_proj`` to [z, xBC, dt]; a causal depthwise conv
  with a bias over xBC and SiLU; x, B, C split from it; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``
  stepped one position at a time; the gated RMSNorm ``norm(y * silu(z))``;
  ``out_proj``;
* attention: RMSNorm; grouped-query attention with no positional encoding
  and scores scaled by ``attention_multiplier``; the output projection;

the mixer's output added to the residual times ``residual_multiplier``;
RMSNorm; the expert layer (router logits over every expert, the
``num_experts_per_tok`` largest, a softmax over those alone, each expert a
SwiGLU) plus the shared SwiGLU MLP, added times ``residual_multiplier``;
a final RMSNorm and logits from the tied embedding over
``logits_scaling``.

No cache, no kernels, no chunked scan: the whole sequence goes through
each layer at once, one layer at a time, float32 with ``highest`` matmul
precision (attention in blocks of query rows, which is exact).  It imports
nothing of the program under test.

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
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn

KINDS = ("mamba", "attention")


def dims(cfg: Dict) -> Dict:
    d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    lo, hi = cfg["held_experts"]
    di = int(cfg["mamba_expand"]) * d
    g, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    return dict(
        types=tuple(cfg["layer_types"]), d=d, hq=hq,
        hkv=int(cfg["num_key_value_heads"]), dh=d // hq,
        ff=int(cfg["intermediate_size"]),
        sff=int(cfg["shared_intermediate_size"]),
        E=int(cfg["router_experts"]), k=int(cfg["num_experts_per_tok"]),
        lo=int(lo), hi=int(hi), V=int(cfg["vocab_size"]),
        eps=float(cfg["rms_norm_eps"]), di=di, H=int(cfg["mamba_n_heads"]),
        P=int(cfg["mamba_d_head"]), N=n, G=g, K=int(cfg["mamba_d_conv"]),
        C=di + 2 * g * n,
        emb=float(cfg["embedding_multiplier"]),
        res=float(cfg["residual_multiplier"]),
        att=float(cfg["attention_multiplier"]),
        logit=float(cfg["logits_scaling"]),
    )


def seed_key(seed: int):
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def weight_maker(cfg: Dict):
    """``make(key)``: per-kind stacked weights and the tied embedding in
    the served dtype, drawn as the family initialises them: every matrix,
    the conv kernels and the embedding normal with standard deviation
    ``initializer_range``; conv biases 0; ``dt_bias`` 1; ``A_log`` the log
    of 1..heads; ``D`` 1; every norm scale 1.  Only the held experts are
    drawn."""
    m = dims(cfg)
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    std = float(cfg["initializer_range"])
    d, eh = m["d"], m["hi"] - m["lo"]
    n_of = {k: sum(t == k for t in m["types"]) for k in KINDS}

    def normal(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def ffn(ks, L):
        return {
            "ln1": ones(L, d), "ln2": ones(L, d),
            "router": normal(ks[0], (L, d, m["E"])),
            "w_gate": normal(ks[1], (L, eh, d, m["ff"])),
            "w_up": normal(ks[2], (L, eh, d, m["ff"])),
            "w_down": normal(ks[3], (L, eh, m["ff"], d)),
            "sh_gate": normal(ks[4], (L, d, m["sff"])),
            "sh_up": normal(ks[5], (L, d, m["sff"])),
            "sh_down": normal(ks[6], (L, m["sff"], d)),
        }

    def make(key):
        ks = jax.random.split(key, 3)
        out = {"embed": normal(ks[0], (m["V"], d)), "norm": ones(d)}
        L = n_of["mamba"]
        if L:
            k = jax.random.split(ks[1], 10)
            H = m["H"]
            out["mamba"] = {
                **ffn(k[3:], L),
                "in_proj": normal(k[0], (L, d, 2 * m["di"] + 2 * m["G"]
                                         * m["N"] + H)),
                "conv_w": normal(k[1], (L, m["K"], m["C"])),
                "conv_b": jnp.zeros((L, m["C"]), dtype),
                "dt_bias": ones(L, H),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                    (L, H)).astype(dtype),
                "D": ones(L, H),
                "gnorm": ones(L, m["di"]),
                "out_proj": normal(k[2], (L, m["di"], d)),
            }
        L = n_of["attention"]
        if L:
            k = jax.random.split(ks[2], 11)
            out["attention"] = {
                **ffn(k[4:], L),
                "wq": normal(k[0], (L, d, m["hq"], m["dh"])),
                "wk": normal(k[1], (L, d, m["hkv"], m["dh"])),
                "wv": normal(k[2], (L, d, m["hkv"], m["dh"])),
                "wo": normal(k[3], (L, m["hq"], m["dh"], d)),
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


def experts(h, lw, m, quant: Optional[str] = None):
    """The expert layer's part from the held experts ``[lo, hi)``: each
    token's ``k`` best router logits over all ``E`` experts, a softmax over
    those, and each held expert's SwiGLU weighted by its gate (0 for a
    token not routed to it)."""
    logits = _mm("bsd,de->bse", h, lw["router"], quant, 0)
    top, idx = jax.lax.top_k(logits, m["k"])
    gates = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(h)
    for j in range(m["hi"] - m["lo"]):
        w = jnp.sum(jnp.where(idx == m["lo"] + j, gates, 0.0), axis=-1)
        out = out + w[..., None] * _swiglu(h, lw["w_gate"][j], lw["w_up"][j],
                                           lw["w_down"][j], quant)
    return out


def shared_mlp(h, lw, quant: Optional[str] = None):
    return _swiglu(h, lw["sh_gate"], lw["sh_up"], lw["sh_down"], quant)


def _ffn(x, lw, m, quant):
    h = _rms(x, lw["ln2"], m["eps"])
    return x + m["res"] * (experts(h, lw, m, quant) + shared_mlp(h, lw, quant))


def _pick(w, i):
    return {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
            .astype(jnp.float32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("hm", "quant"))
def _mamba_layer(x, w, i, hm, quant):
    m = dict(hm)
    lw = _pick(w, i)
    b, s, _ = x.shape
    di, G, N, H, P, K = (m[k] for k in ("di", "G", "N", "H", "P", "K"))
    h = _rms(x, lw["ln1"], m["eps"])
    zxd = _mm("bsd,dk->bsk", h, lw["in_proj"], quant, 0)
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + m["C"]], zxd[..., di + m["C"]:]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(lw["conv_w"][j] * pad[:, j:j + s] for j in range(K))
    xbc = _silu(conv + lw["conv_b"])
    xs = xbc[..., :di].reshape(b, s, H, P)
    rep = H // G  # heads per group of B and C
    bm = xbc[..., di:di + G * N].reshape(b, s, G, N)
    cm = xbc[..., di + G * N:].reshape(b, s, G, N)
    dt = jax.nn.softplus(dt + lw["dt_bias"])  # [B,S,H]
    a = -jnp.exp(lw["A_log"])  # [H]

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp  # [B,H,P], [B,G,N], [B,G,N], [B,H]
        b_t, c_t = jnp.repeat(b_t, rep, axis=1), jnp.repeat(c_t, rep, axis=1)
        state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=jax.lax.Precision.HIGHEST)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt))
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), jnp.float32), seq)
    y = jnp.moveaxis(y, 0, 1) + lw["D"][:, None] * xs  # [B,S,H,P]
    y = _rms(y.reshape(b, s, di) * _silu(z), lw["gnorm"], m["eps"])
    x = x + m["res"] * _mm("bsi,id->bsd", y, lw["out_proj"], quant, 0)
    return _ffn(x, lw, m, quant)


#: Query rows per block in the attention layers: [B, 32, 512, S] float32
#: scores at S = 8192 are 0.5 GB per request.
Q_ROWS = 512


@functools.partial(jax.jit, static_argnames=("hm", "quant"))
def _attention_layer(x, w, i, hm, quant):
    m = dict(hm)
    lw = _pick(w, i)
    b, s, _ = x.shape
    g = m["hq"] // m["hkv"]
    h = _rms(x, lw["ln1"], m["eps"])
    q = _mm("bsd,dhk->bshk", h, lw["wq"], quant, 0) * m["att"]
    k = _mm("bsd,dhk->bshk", h, lw["wk"], quant, 0)
    v = _mm("bsd,dhk->bshk", h, lw["wv"], quant, 0)
    q = q.reshape(b, s, m["hkv"], g, m["dh"])
    rows = min(Q_ROWS, s)
    pos = jnp.arange(s)

    def block(r0):
        qb = jax.lax.dynamic_slice_in_dim(q, r0, rows, axis=1)
        sc = jnp.einsum("bskgd,btkd->bkgst", qb, k,
                        precision=jax.lax.Precision.HIGHEST)
        causal = pos[None, :] <= (r0 + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bkgst,btkd->bskgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    a = jax.lax.map(block, jnp.arange(0, s, rows))  # [nb,B,rows,kv,g,dh]
    a = jnp.moveaxis(a, 0, 1).reshape(b, s, m["hq"], m["dh"])
    x = x + m["res"] * _mm("bshk,hkd->bsd", a, lw["wo"], quant, (0, 1))
    return _ffn(x, lw, m, quant)


@functools.partial(jax.jit, static_argnames=("emb",))
def _embed(w, tokens, emb):
    return jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32) * emb


@functools.partial(jax.jit, static_argnames=("hm", "quant"))
def _logits(w, x, rows, hm, quant):
    """Logits of the final-normed hidden rows ``x[rows]``."""
    m = dict(hm)
    h = _rms(x[rows], w["norm"].astype(jnp.float32), m["eps"])
    e = w["embed"].astype(jnp.float32)
    return _mm("rd,vd->rv", h, e, quant, 1) / m["logit"]


def hidden(w, tokens: np.ndarray, hm, quant: Optional[str] = None):
    """Last-layer hidden states ``[B, S, d]`` of padded token rows (every
    position's state depends only on those before it, so padding at the
    end changes nothing)."""
    m = dict(hm)
    x = _embed(w, jnp.asarray(tokens, jnp.int32), m["emb"])
    seen = {k: 0 for k in KINDS}
    for kind in m["types"]:
        layer = _mamba_layer if kind == "mamba" else _attention_layer
        x = layer(x, w[kind], jnp.int32(seen[kind]), hm, quant)
        seen[kind] += 1
    return x


def logits(w, tokens: np.ndarray, hm, quant: Optional[str] = None):
    """Every position's logits ``[B, S, V]`` (small sizes: the tests)."""
    x = hidden(w, tokens, hm, quant)
    b, s = x.shape[:2]
    at = (jnp.repeat(jnp.arange(b), s), jnp.tile(jnp.arange(s), b))
    return _logits(w, x, at, hm, quant).reshape(b, s, -1)


def hm_of(cfg: Dict):
    return tuple(sorted(dims(cfg).items()))


#: Positions that go through the layers in one pass: one document of
#: 8192, or three chat requests of 2560, keep a pass's float32 activations
#: under 2 GB beside the 8.84 GB of bf16 weights.
PASS_ROWS = 8192


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
    tokens a request serves."""
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
                pos = np.zeros(rows, np.int32)
                pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
                at = (jnp.full(rows, r, jnp.int32), jnp.asarray(pos))
                served = jnp.asarray(np.pad(np.asarray(out, np.int32),
                                            (0, rows - n)))[:, None]
                lg = _logits(w, xs[None], at, hm, None)
                one = {"best": lg.max(axis=1),
                       "served": jnp.take_along_axis(lg, served, 1)[:, 0]}
                if control:
                    lc = _logits(w, xs["fp8"], at, hm, "fp8")
                    pick = jnp.argmax(lc, axis=1)[:, None]
                    one["ctl_top"] = lc.max(axis=1)
                    one["ctl_pick"] = jnp.take_along_axis(lg, pick, 1)[:, 0]
                res[i] = {k: np.asarray(v, np.float64)[:n]
                          for k, v in one.items()}
            del xs
    return res
