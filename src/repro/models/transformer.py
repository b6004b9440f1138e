"""The unified scan-over-layers transformer covering every assigned family.

``ModelConfig`` declares the family (dense / moe / ssm / hybrid, optionally
encoder-decoder); :class:`TransformerLM` builds stacked-layer params, a
training ``forward`` (last-token or loss-ready hidden states), ``prefill``
and a one-token ``decode_step`` with explicit :class:`DecodeState`.

Layer stacking + ``lax.scan`` keeps the HLO program size O(1) in depth: a
46-layer gemma2 or 64-layer mamba2 compiles in roughly the time of one
layer — essential for 512-device dry-run compiles.  Heterogeneous layer
patterns (gemma2 local/global alternation, hymba's three full-attention
layers) are expressed as *per-layer scanned scalars* (attention window
sizes), keeping the scanned computation uniform.

An ``interleaved`` stack (granite-4.0-h) has layers of different kinds in
sequence, read from ``layer_types``: Mamba-2 layers and attention layers,
each followed by an expert layer and a shared MLP.  Its params are stacked
per kind, and it scans over whole periods of the pattern (an inner scan
per run of one kind), so its program is O(1) in depth too.  Its decode
state holds K/V only for the attention layers and SSM state only for the
Mamba layers, both carried through the scan and updated in place.

A third kind, ``mla`` (DeepSeek-V2), is latent attention: its state is the
latent cache (``c_kv`` and ``k_pe``) of every MLA layer.  Leading dense
layers (``n_dense_lead``: the mixer, then a dense SwiGLU of ``d_ff_dense``)
run once before the scan and fill the first layers of the same stacked
state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.autosharding import constrain
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    Axes,
    Params,
    Yarn,
    embed_init,
    embed_lookup,
    layernorm,
    mlp_apply,
    mlp_init,
    rmsnorm,
    softcap,
    unembed,
    yarn_frequencies,
    yarn_mscale,
)

FULL_WINDOW = 1 << 30  # "window" larger than any sequence = dense attention
#: Query rows per block in an interleaved stack's prefill attention: its
#: prompts are multiples of the Mamba chunk (256), and 512-row blocks keep
#: a 7680-token prompt's float32 scores at 0.5 GB.
IL_Q_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_q_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    block: str = "dense"  # dense | moe | ssm | hybrid | interleaved
    # attention flavour
    rope_theta: Optional[float] = 10_000.0
    qkv_bias: bool = False
    qk_norm: bool = False
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    query_scale: Optional[float] = None
    sliding_window: Optional[int] = None  # default window for SWA layers
    #: per-layer window pattern: "full" | "swa" | "gemma2" (alternate
    #: local/global) | "hymba" (full at first/middle/last, SWA elsewhere)
    window_pattern: str = "full"
    # norms / activations / embeddings
    norm: str = "rms"  # rms | layernorm
    activation: str = "silu"  # silu | gelu
    tied_embeddings: bool = False
    embed_scale: bool = False  # gemma-style sqrt(d_model) scaling
    use_post_norms: bool = False  # gemma2 post-attn/post-ffn norms
    # MoE
    n_experts: int = 0
    top_k: int = 0
    shared_expert_ff: int = 0
    capacity_factor: float = 1.25
    #: 1 = every layer MoE (dbrx); 2 = alternating dense/MoE pairs (llama4
    #: maverick: 24 dense + 24 MoE layers — this is what reconciles the
    #: 400B-total / 17B-active name with 128 experts).  Pair-scanned.
    moe_every: int = 1
    d_ff_dense: int = 0  # dense sub-layer FFN width when moe_every == 2
    # SSM
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_expand: int = 2
    ssm_chunk: int = 128
    # interleaved stacks (granite-4.0-h, deepseek-v2): each layer's kind,
    # "mamba", "attention" or "mla", in order; after the leading dense
    # layers the pattern repeats with some period
    layer_types: Optional[Tuple[str, ...]] = None
    #: leading layers of an interleaved stack whose FFN is a dense SwiGLU
    #: of d_ff_dense (DeepSeek's first_k_dense_replace); all of one kind
    n_dense_lead: int = 0
    #: the expert-parallel share this chip holds: experts [lo, hi) of
    #: n_experts (None: all); the router still routes over all of them
    experts_held: Optional[Tuple[int, int]] = None
    #: the held-expert layer's router (``moe.held_moe_apply``):
    #: "topk_softmax" (Granite) or "softmax_topk" (DeepSeek-V2)
    router: str = "topk_softmax"
    # latent attention (MLA): the cached latent's width, each head's
    # query/key width without and with RoPE, its value width, and YaRN
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    yarn: Optional[Yarn] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0  # logits are divided by it
    norm_eps: float = 1e-6  # interleaved stacks' RMSNorms
    # encoder-decoder (whisper)
    n_encoder_layers: int = 0
    encoder_seq: int = 1500  # whisper: 30 s of 10 ms frames after conv stub
    # frontend stub: number of precomputed embedding positions prepended
    frontend: Optional[str] = None  # None | "vision" | "audio"
    frontend_seq: int = 0  # e.g. 256 vision patch embeddings
    dtype: Any = jnp.bfloat16

    @property
    def uses_attention(self) -> bool:
        return self.block in ("dense", "moe", "hybrid")

    @property
    def uses_ssm(self) -> bool:
        return self.block in ("ssm", "hybrid")

    @property
    def ssm_dims(self) -> Dict[str, int]:
        return ssm_lib.ssm_dims(
            self.d_model,
            expand=self.ssm_expand,
            head_dim=self.ssm_head_dim,
            d_state=self.ssm_state,
            n_groups=self.ssm_groups,
        )

    @property
    def interleaved(self) -> bool:
        return self.block == "interleaved"

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def scanned_types(self) -> Tuple[str, ...]:
        """``layer_types`` after the leading dense layers."""
        return self.layer_types[self.n_dense_lead:]

    @property
    def period(self) -> int:
        """Layers in one repeat of ``scanned_types``."""
        t = self.scanned_types
        for p in range(1, len(t) + 1):
            if len(t) % p == 0 and t == t[:p] * (len(t) // p):
                return p
        raise ValueError(t)

    def kind_count(self, kind: str) -> int:
        return sum(k == kind for k in self.layer_types)

    @property
    def kv_kind(self) -> str:
        """The kind whose state ``DecodeState.kv`` holds."""
        return "mla" if "mla" in self.layer_types else "attention"

    def mla_args(self) -> Dict[str, Any]:
        """Constants of the MLA layers, as DeepSeek's code sets them:
        YaRN's inverse frequencies, cos and sin times ``mscale(factor,
        mscale) / mscale(factor, mscale_all_dim)``, and the softmax scale
        ``(nope + rope)^-0.5`` times ``mscale(factor, mscale_all_dim)^2``."""
        y, rope = self.yarn, self.qk_rope_head_dim
        spread = yarn_mscale(y.factor, y.mscale_all_dim)
        return dict(
            inv_freq=yarn_frequencies(rope, self.rope_theta, y),
            rope_scale=yarn_mscale(y.factor, y.mscale) / spread,
            scale=(self.qk_nope_head_dim + rope) ** -0.5 * spread ** 2,
            eps=self.norm_eps)

    def segments(self) -> Tuple[Tuple[str, int, int, int], ...]:
        """Runs of one kind in a period: (kind, index of the run's first
        layer among the period's layers of that kind, run length, its
        position in the period)."""
        out, seen = [], {}
        kinds = self.scanned_types[:self.period]
        start = 0
        for i in range(1, len(kinds) + 1):
            if i == len(kinds) or kinds[i] != kinds[start]:
                k = kinds[start]
                out.append((k, seen.get(k, 0), i - start, start))
                seen[k] = seen.get(k, 0) + i - start
                start = i
        return tuple(out)

    @property
    def paired(self) -> bool:
        return self.block == "moe" and self.moe_every == 2

    @property
    def n_scan(self) -> int:
        """Scanned steps (pairs count as one step)."""
        return self.n_layers // 2 if self.paired else self.n_layers

    def window_sizes(self) -> jnp.ndarray:
        """Per-layer attention windows (scanned).  Shape [n_scan] or
        [n_scan, 2] for paired stacks."""
        w = self.sliding_window or FULL_WINDOW
        if self.window_pattern == "full":
            out = [FULL_WINDOW] * self.n_layers
        elif self.window_pattern == "swa":
            out = [w] * self.n_layers
        elif self.window_pattern == "gemma2":
            # local (SWA) on even layers, global on odd (gemma2 ordering).
            out = [w if i % 2 == 0 else FULL_WINDOW for i in range(self.n_layers)]
        elif self.window_pattern == "hymba":
            full_at = {0, self.n_layers // 2, self.n_layers - 1}
            out = [FULL_WINDOW if i in full_at else w for i in range(self.n_layers)]
        else:
            raise ValueError(self.window_pattern)
        arr = jnp.asarray(out, dtype=jnp.int32)
        return arr.reshape(self.n_scan, 2) if self.paired else arr

    def param_count(self) -> int:
        """Analytic parameter count (for 6·N·D roofline bookkeeping)."""
        if self.interleaved:  # every parameter, from the shapes
            specs = TransformerLM(self).param_specs()
            return sum(int(x.size) for x in jax.tree.leaves(specs))
        d, f, L = self.d_model, self.d_ff, self.n_layers
        n = self.vocab * d  # embed
        if not self.tied_embeddings:
            n += self.vocab * d
        attn_per = d * self.head_dim * (self.n_q_heads * 2 + self.n_kv_heads * 2)
        per_layer = 0
        if self.uses_attention:
            per_layer += attn_per
        if self.block == "moe":
            n_moe_layers = L // 2 if self.paired else L
            n_dense_layers = L - n_moe_layers
            n += n_moe_layers * (
                attn_per
                + d * self.n_experts
                + 3 * d * f * self.n_experts
                + (3 * d * self.shared_expert_ff if self.shared_expert_ff else 0)
            )
            dense_ff = self.d_ff_dense or 2 * f
            n += n_dense_layers * (attn_per + 3 * d * dense_ff)
            per_layer = 0  # fully accounted above
            L = 0
        elif self.block in ("dense", "hybrid") and f > 0:
            per_layer += 3 * d * f
        if self.uses_ssm:
            dims = self.ssm_dims
            per_layer += d * dims["d_in_proj"] + dims["d_inner"] * d
            per_layer += dims["d_conv"] * dims["conv_dim"]
        n += L * per_layer
        if self.n_encoder_layers:
            enc_per = attn_per + 3 * d * f
            n += self.n_encoder_layers * enc_per
            n += self.n_layers * attn_per  # decoder cross-attention
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of n_experts)."""
        if self.block != "moe":
            return self.param_count()
        d, f = self.d_model, self.d_ff
        n_moe_layers = self.n_layers // 2 if self.paired else self.n_layers
        total = self.param_count()
        moe_all = n_moe_layers * 3 * d * f * self.n_experts
        moe_active = n_moe_layers * 3 * d * f * self.top_k
        return total - moe_all + moe_active


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecodeState:
    """Per-request decoding state (stacked over layers for scanning)."""

    #: k/v: [L, B, S_max, Hkv, Dh]; MLA stacks: c_kv [L, B, S_max, r] and
    #: k_pe [L, B, S_max, rope]
    kv: Optional[Dict[str, jax.Array]]
    ssm: Optional[Dict[str, jax.Array]]  # h: [L,B,H,P,N]; conv: [L,B,K-1,C]
    cross_kv: Optional[Dict[str, jax.Array]]  # whisper: [L,B,T_enc,Hkv,Dh]
    length: jax.Array  # [] int32: tokens already decoded
    #: interleaved stacks: per scanned layer, the held experts the last
    #: step's tokens were routed to ([L] int32).  Interleaved stacks also
    #: keep K/V (or the latent cache) of their attention (or MLA) layers
    #: alone and SSM state of their Mamba layers alone.
    experts_touched: Optional[jax.Array] = None


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


class TransformerLM:
    """Unified scan-over-layers LM for every assigned family.

    ``remat``: activation-checkpointing policy applied to the scanned layer
    body under differentiation — "none" | "full" (save only carries) |
    "dots" (save matmul outputs; XLA's checkpoint_dots policy).
    """

    def __init__(self, cfg: ModelConfig, *, remat: str = "none"):
        self.cfg = cfg
        self.remat = remat

    def _maybe_remat(self, body):
        if self.remat == "none":
            return body
        if self.remat == "full":
            return jax.checkpoint(body, prevent_cse=False)
        if self.remat == "dots":
            return jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.checkpoint_dots,
                prevent_cse=False,
            )
        raise ValueError(self.remat)

    # ------------------------------------------------------------------ init
    def _sublayer_init(self, key, stacked: int, *, ffn: Optional[str],
                       d_ff: int, cross: bool = False,
                       with_attn: Optional[bool] = None,
                       with_ssm: Optional[bool] = None) -> Tuple[Params, Axes]:
        """One layer kind: attention/ssm mixing + the chosen FFN."""
        cfg = self.cfg
        keys = jax.random.split(key, 6)
        params: Params = {}
        axes: Axes = {}
        norm_ax = ("layers", "embed")
        zeros = lambda: jnp.zeros((stacked, cfg.d_model), cfg.dtype)  # noqa: E731
        use_attn = cfg.uses_attention if with_attn is None else with_attn
        use_ssm = cfg.uses_ssm if with_ssm is None else with_ssm
        if use_attn:
            params["attn"], axes["attn"] = attn.attention_init(
                keys[0], cfg.d_model, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
                cfg.dtype, stacked=stacked, qkv_bias=cfg.qkv_bias,
                qk_norm=cfg.qk_norm,
            )
            params["pre_attn_norm"] = zeros()
            axes["pre_attn_norm"] = norm_ax
            if cfg.use_post_norms:
                params["post_attn_norm"] = zeros()
                axes["post_attn_norm"] = norm_ax
        if cross:
            params["cross"], axes["cross"] = attn.attention_init(
                keys[1], cfg.d_model, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
                cfg.dtype, stacked=stacked,
            )
            params["pre_cross_norm"] = zeros()
            axes["pre_cross_norm"] = norm_ax
        if use_ssm:
            params["ssm"], axes["ssm"] = ssm_lib.ssm_init(
                keys[2], cfg.d_model, cfg.ssm_dims, cfg.dtype, stacked=stacked
            )
            if not use_attn:
                params["pre_ssm_norm"] = zeros()
                axes["pre_ssm_norm"] = norm_ax
        if ffn == "moe":
            params["moe"], axes["moe"] = moe_lib.moe_init(
                keys[3], cfg.d_model, d_ff, cfg.n_experts, cfg.dtype,
                stacked=stacked, shared_expert_ff=cfg.shared_expert_ff,
            )
            params["pre_mlp_norm"] = zeros()
            axes["pre_mlp_norm"] = norm_ax
        elif ffn == "mlp":
            from repro.models.layers import mlp_init

            params["mlp"], axes["mlp"] = mlp_init(
                keys[4], cfg.d_model, d_ff, cfg.dtype, stacked=stacked
            )
            params["pre_mlp_norm"] = zeros()
            axes["pre_mlp_norm"] = norm_ax
            if cfg.use_post_norms:
                params["post_mlp_norm"] = zeros()
                axes["post_mlp_norm"] = norm_ax
        return params, axes

    def _layer_init(self, key, cross: bool = False) -> Tuple[Params, Axes]:
        cfg = self.cfg
        if cfg.paired:
            kd, km = jax.random.split(key)
            dense_ff = cfg.d_ff_dense or 2 * cfg.d_ff
            pd, ad = self._sublayer_init(kd, cfg.n_scan, ffn="mlp",
                                         d_ff=dense_ff, cross=cross)
            pm, am = self._sublayer_init(km, cfg.n_scan, ffn="moe",
                                         d_ff=cfg.d_ff, cross=False)
            return {"dense": pd, "moe": pm}, {"dense": ad, "moe": am}
        ffn = {"dense": "mlp", "hybrid": "mlp", "moe": "moe", "ssm": None}[cfg.block]
        if cfg.block in ("dense", "hybrid") and cfg.d_ff == 0:
            ffn = None
        return self._sublayer_init(key, cfg.n_scan, ffn=ffn, d_ff=cfg.d_ff,
                                   cross=cross)

    def _kind_init(self, key, kind: str, count: int,
                   dense: bool = False) -> Tuple[Params, Axes]:
        """``count`` stacked layers of one kind of an interleaved stack:
        the mixer (Mamba-2, attention or MLA), then the expert layer with
        its shared MLP (``dense``: a SwiGLU of ``d_ff_dense``), each after
        an RMSNorm."""
        cfg = self.cfg
        km, ke = jax.random.split(key)
        norm = lambda: jnp.zeros((count, cfg.d_model), cfg.dtype)  # noqa: E731
        norm_ax = ("layers", "embed")
        params: Params = {"pre_norm": norm(), "post_norm": norm()}
        axes: Axes = {"pre_norm": norm_ax, "post_norm": norm_ax}
        if kind == "mamba":
            params["ssm"], axes["ssm"] = ssm_lib.ssm_init(
                km, cfg.d_model, cfg.ssm_dims, cfg.dtype, stacked=count)
        elif kind == "mla":
            params["mla"], axes["mla"] = attn.mla_init(
                km, cfg.d_model, cfg.n_q_heads,
                kv_lora_rank=cfg.kv_lora_rank,
                nope_dim=cfg.qk_nope_head_dim, rope_dim=cfg.qk_rope_head_dim,
                v_dim=cfg.v_head_dim, dtype=cfg.dtype, stacked=count)
        else:
            params["attn"], axes["attn"] = attn.attention_init(
                km, cfg.d_model, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
                cfg.dtype, stacked=count, qkv_bias=cfg.qkv_bias)
        if dense:
            params["mlp"], axes["mlp"] = mlp_init(
                ke, cfg.d_model, cfg.d_ff_dense, cfg.dtype, stacked=count)
        else:
            params["moe"], axes["moe"] = moe_lib.held_moe_init(
                ke, cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.held, cfg.dtype,
                stacked=count, shared_expert_ff=cfg.shared_expert_ff)
        return params, axes

    def init(self, key) -> Tuple[Params, Axes]:
        cfg = self.cfg
        k_embed, k_layers, k_enc, k_head = jax.random.split(key, 4)
        params: Params = {}
        axes: Axes = {}
        params["embed"] = embed_init(k_embed, (cfg.vocab, cfg.d_model), cfg.dtype)
        axes["embed"] = ("vocab", "embed")
        if cfg.interleaved:
            params["layers"], axes["layers"] = {}, {}
            for i, kind in enumerate(("mamba", "attention", "mla")):
                n = sum(k == kind for k in cfg.scanned_types)
                if n:
                    params["layers"][kind], axes["layers"][kind] = \
                        self._kind_init(jax.random.fold_in(k_layers, i),
                                        kind, n)
            if cfg.n_dense_lead:
                params["lead"], axes["lead"] = self._kind_init(
                    jax.random.fold_in(k_layers, 3), cfg.layer_types[0],
                    cfg.n_dense_lead, dense=True)
        else:
            params["layers"], axes["layers"] = self._layer_init(
                k_layers, cross=cfg.n_encoder_layers > 0
            )
        if cfg.n_encoder_layers:
            params["enc_layers"], axes["enc_layers"] = self._sublayer_init(
                k_enc, cfg.n_encoder_layers, ffn="mlp", d_ff=cfg.d_ff,
                with_attn=True, with_ssm=False,
            )
            params["enc_final_norm"] = jnp.zeros((cfg.d_model,), cfg.dtype)
            axes["enc_final_norm"] = ("embed",)
        params["final_norm"] = jnp.zeros((cfg.d_model,), cfg.dtype)
        axes["final_norm"] = ("embed",)
        if not cfg.tied_embeddings:
            params["lm_head"] = embed_init(k_head, (cfg.vocab, cfg.d_model), cfg.dtype)
            axes["lm_head"] = ("vocab", "embed")
        return params, axes

    def param_axes(self) -> Axes:
        _, axes = self.init_shapes()
        return axes

    def param_specs(self) -> Params:
        specs, _ = self.init_shapes()
        return specs

    def init_shapes(self) -> Tuple[Params, Axes]:
        """(ShapeDtypeStruct tree, axes tree) without allocating anything."""
        specs = jax.eval_shape(lambda k: self.init(k)[0], jax.random.PRNGKey(0))
        return specs, _axes_of(self)

    # ----------------------------------------------------------------- norms
    def _norm(self, x, scale):
        if self.cfg.norm == "rms":
            return rmsnorm(x, scale)
        return layernorm(x, scale)

    # ------------------------------------------------------- full-seq blocks
    def _ffn_apply(self, layer: Params, x: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        if "moe" in layer:
            h = self._norm(x, layer["pre_mlp_norm"])
            m, aux = moe_lib.moe_apply(
                layer["moe"], h, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, activation=cfg.activation,
            )
            x = x + m
        elif "mlp" in layer:
            from repro.models.layers import mlp_apply

            h = self._norm(x, layer["pre_mlp_norm"])
            m = mlp_apply(layer["mlp"], h, activation=cfg.activation)
            if cfg.use_post_norms:
                m = self._norm(m, layer["post_mlp_norm"])
            x = x + m
        return x, aux

    def _ssm_forward_branch(self, layer: Params, h: jax.Array,
                            eps: float = 1e-6
                            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Full-sequence SSM branch; returns (out, final ssm state pieces)."""
        cfg = self.cfg
        dims = cfg.ssm_dims
        b, s = h.shape[0], h.shape[1]
        z, xbc, dt_raw = ssm_lib._split_proj(layer["ssm"], h, dims)
        xbc_c = jax.nn.silu(
            ssm_lib._causal_depthwise_conv(
                xbc, layer["ssm"]["conv_w"], layer["ssm"]["conv_b"]
            )
        )
        xs_, bm, cm, dt, a_ = ssm_lib._prep_inputs(layer["ssm"], xbc_c, dt_raw, dims)
        y, hfinal = ssm_lib.ssd_chunked(xs_, bm, cm, dt, a_, chunk=cfg.ssm_chunk)
        y = y.reshape(b, s, dims["d_inner"])
        y = y + (layer["ssm"]["D"].repeat(dims["head_dim"])
                 * xs_.reshape(b, s, -1).astype(jnp.float32)).astype(h.dtype)
        y = rmsnorm(y * jax.nn.silu(z), layer["ssm"]["norm"], eps)
        out = jnp.einsum("bsi,id->bsd", y, layer["ssm"]["out_proj"])
        state = {"h": hfinal, "conv": xbc[:, -(dims["d_conv"] - 1):, :]}
        return out, state

    def _sub_block(self, layer: Params, x: jax.Array, positions: jax.Array,
                   window: jax.Array, memory_kv=None
                   ) -> Tuple[jax.Array, jax.Array]:
        """One (sub-)layer, full-sequence.  Returns (x, aux)."""
        cfg = self.cfg
        x = constrain(x, ("batch", "seq", "embed_act"))
        if "attn" not in layer:  # pure SSM block
            h = self._norm(x, layer["pre_ssm_norm"])
            out, _ = self._ssm_forward_branch(layer, h)
            x = x + out
            return self._ffn_apply(layer, x)
        h = self._norm(x, layer["pre_attn_norm"])
        a = attn.attend_full(
            layer["attn"], h, positions, rope_theta=cfg.rope_theta,
            window=window, softcap_value=cfg.attn_softcap, causal=True,
            query_scale=cfg.query_scale,
        )
        if "ssm" in layer:  # hybrid: parallel heads, mean-fused
            s_out, _ = self._ssm_forward_branch(layer, h)
            a = 0.5 * (a + s_out)
        if cfg.use_post_norms:
            a = self._norm(a, layer["post_attn_norm"])
        x = x + a
        if memory_kv is not None and "cross" in layer:
            h = self._norm(x, layer["pre_cross_norm"])
            x = x + attn.attend_cross(layer["cross"], h, memory_kv["k"],
                                      memory_kv["v"])
        return self._ffn_apply(layer, x)

    def _block_body(self, layer: Params, x: jax.Array, positions: jax.Array,
                    window: jax.Array, memory_kv=None
                    ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        if cfg.paired:
            x, aux0 = self._sub_block(layer["dense"], x, positions, window[0],
                                      memory_kv)
            x, aux1 = self._sub_block(layer["moe"], x, positions, window[1], None)
            return x, aux0 + aux1
        return self._sub_block(layer, x, positions, window, memory_kv)

    def _run_stack(self, layers: Params, x: jax.Array, positions: jax.Array,
                   windows: jax.Array, memory_kv=None
                   ) -> Tuple[jax.Array, jax.Array]:
        aux0 = jnp.zeros((), jnp.float32)

        if memory_kv is None:
            def body(carry, inp):
                x1, acc = carry
                layer, window = inp
                x2, aux = self._block_body(layer, x1, positions, window)
                return (x2, acc + aux), None

            (x, aux), _ = jax.lax.scan(
                self._maybe_remat(body), (x, aux0), (layers, windows)
            )
        else:
            def body(carry, inp):
                x1, acc = carry
                layer, window, mem_k, mem_v = inp
                x2, aux = self._block_body(
                    layer, x1, positions, window,
                    memory_kv={"k": mem_k, "v": mem_v},
                )
                return (x2, acc + aux), None

            (x, aux), _ = jax.lax.scan(
                self._maybe_remat(body), (x, aux0),
                (layers, windows, memory_kv["k"], memory_kv["v"]),
            )
        return x, aux

    # ------------------------------------------------------------- embedding
    def _embed_inputs(self, params: Params, tokens: jax.Array,
                      frontend_embeds: Optional[jax.Array]) -> jax.Array:
        cfg = self.cfg
        x = embed_lookup(params["embed"], tokens)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        if frontend_embeds is not None and cfg.frontend == "vision":
            # VLM early fusion: precomputed patch embeddings (stubbed
            # InternViT output) replace the first frontend_seq positions.
            x = jnp.concatenate(
                [frontend_embeds.astype(x.dtype), x[:, frontend_embeds.shape[1]:]],
                axis=1,
            )
        return constrain(x, ("batch", "seq", "embed_act"))

    def encode(self, params: Params, frames: jax.Array) -> jax.Array:
        """Whisper-style encoder over precomputed (stubbed conv) frames."""
        cfg = self.cfg
        b, t = frames.shape[0], frames.shape[1]
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        windows = jnp.full((cfg.n_encoder_layers,), FULL_WINDOW, jnp.int32)
        x = frames.astype(cfg.dtype)

        def body(carry, inp):
            layer, window = inp
            h = self._norm(carry, layer["pre_attn_norm"])
            a = attn.attend_full(
                layer["attn"], h, positions, rope_theta=None, window=window,
                softcap_value=None, causal=False,
            )
            x2 = carry + a
            x2, _ = self._ffn_apply(layer, x2)
            return x2, None

        x, _ = jax.lax.scan(body, x, (params["enc_layers"], windows))
        return self._norm(x, params["enc_final_norm"])

    def _cross_memory(self, params: Params, frontend_embeds: jax.Array):
        enc = self.encode(params, frontend_embeds)
        layers = params["layers"]["dense"] if self.cfg.paired else params["layers"]
        ks, vs = jax.vmap(lambda c: attn.project_memory_kv(c, enc))(layers["cross"])
        return {"k": ks, "v": vs}

    # ------------------------------------------------------- train / prefill
    def forward(
        self,
        params: Params,
        tokens: jax.Array,  # [B, S]
        *,
        frontend_embeds: Optional[jax.Array] = None,
        last_only: bool = False,
    ) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence forward.  Returns (hidden [B,S,D] or last-logits
        [B,1,V], moe aux loss).  The training loss computes chunked logits
        itself — [B,S,V] is never materialized here."""
        cfg = self.cfg
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        x = self._embed_inputs(params, tokens, frontend_embeds)
        memory_kv = None
        if cfg.n_encoder_layers:
            assert frontend_embeds is not None, "enc-dec needs frontend frames"
            memory_kv = self._cross_memory(params, frontend_embeds)
        if cfg.interleaved:
            x, _, _ = self._il_full(params, x, positions, None)
            aux = jnp.zeros((), jnp.float32)
        else:
            x, aux = self._run_stack(params["layers"], x, positions,
                                     cfg.window_sizes(), memory_kv=memory_kv)
        x = self._final_norm(params, x)
        if last_only:
            return self._logits(params, x[:, -1:, :]), aux
        return x, aux

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        table = params["embed"] if cfg.tied_embeddings else params["lm_head"]
        logits = unembed(x, table)
        if cfg.final_softcap:
            logits = softcap(logits, cfg.final_softcap)
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        return logits

    def logits(self, params: Params, hidden: jax.Array) -> jax.Array:
        return self._logits(params, hidden)

    # ---------------------------------------------------------------- serving
    def init_decode_state(self, batch: int, max_len: int) -> DecodeState:
        cfg = self.cfg
        if cfg.interleaved:
            return self._il_init_state(batch, max_len)
        kv = ssm_state = cross_kv = None
        if cfg.uses_attention:
            shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            kv = {"k": jnp.zeros(shape, cfg.dtype),
                  "v": jnp.zeros(shape, cfg.dtype)}
        if cfg.uses_ssm:
            dims = cfg.ssm_dims
            ssm_state = {
                "h": jnp.zeros((cfg.n_layers, batch, dims["n_heads"],
                                dims["head_dim"], dims["d_state"]), jnp.float32),
                "conv": jnp.zeros((cfg.n_layers, batch, dims["d_conv"] - 1,
                                   dims["conv_dim"]), cfg.dtype),
            }
        if cfg.n_encoder_layers:
            shape = (cfg.n_layers, batch, cfg.encoder_seq, cfg.n_kv_heads,
                     cfg.head_dim)
            cross_kv = {"k": jnp.zeros(shape, cfg.dtype),
                        "v": jnp.zeros(shape, cfg.dtype)}
        return DecodeState(kv=kv, ssm=ssm_state, cross_kv=cross_kv,
                           length=jnp.zeros((batch,), jnp.int32))

    def decode_state_axes(self) -> DecodeState:
        cfg = self.cfg
        kv_ax = {"k": ("layers", "batch", "kv_seq", "cache_heads", "cache_dim"),
                 "v": ("layers", "batch", "kv_seq", "cache_heads", "cache_dim")}
        ssm_ax = {
            "h": ("layers", "batch", "ssm_heads", "ssm_head_dim", "ssm_state"),
            "conv": ("layers", "batch", "conv", "ssm_conv_dim"),
        }
        if cfg.interleaved:
            if cfg.kv_kind == "mla":
                kv_ax = {"c_kv": ("layers", "batch", "kv_seq", "cache_dim"),
                         "k_pe": ("layers", "batch", "kv_seq", "cache_dim")}
            return DecodeState(
                kv=kv_ax if cfg.kind_count(cfg.kv_kind) else None,
                ssm=ssm_ax if cfg.kind_count("mamba") else None,
                cross_kv=None, length=("batch",),
                experts_touched=("layers",),
            )
        return DecodeState(
            kv=kv_ax if cfg.uses_attention else None,
            ssm=ssm_ax if cfg.uses_ssm else None,
            cross_kv=kv_ax if cfg.n_encoder_layers else None,
            length=("batch",),
        )

    def _pair_view(self, tree):
        """[L, ...] -> [L/2, 2, ...] for a pair-scanned prefill."""
        if tree is None:
            return None
        ns = self.cfg.n_scan
        return jax.tree.map(lambda x: x.reshape((ns, 2) + x.shape[1:]), tree)

    def _pair_unview(self, tree):
        if tree is None:
            return None
        return jax.tree.map(
            lambda x: x.reshape((x.shape[0] * 2,) + x.shape[2:]), tree
        )

    def _ssm_decode(self, layer: Params, h: jax.Array, st, i,
                    eps: float = 1e-6):
        """One-token SSM step of layer ``i`` of the stacked SSM state
        ``st``, which it updates in place.  Returns (out, st)."""
        mine = {k: jax.lax.dynamic_index_in_dim(st[k], i, keepdims=False)
                for k in st}
        out, new = ssm_lib.ssm_step(layer["ssm"], h, mine, self.cfg.ssm_dims,
                                    eps)
        return out, {k: jax.lax.dynamic_update_index_in_dim(
            st[k], new[k].astype(st[k].dtype), i, 0) for k in st}

    def _sub_decode(self, layer: Params, x: jax.Array, kv, ssm_state, cross,
                    window, length, i):
        """One (sub-)layer, one-token decode: layer ``i`` of the stacked
        K/V and SSM state, both updated in place.  Returns (x, kv, ssm)."""
        cfg = self.cfg
        x = constrain(x, ("batch", "seq", "embed_act"))
        if "attn" not in layer:
            h = self._norm(x, layer["pre_ssm_norm"])
            y, ssm_state = self._ssm_decode(layer, h, ssm_state, i)
            x = x + y
            x, _ = self._ffn_apply(layer, x)
            return x, kv, ssm_state
        h = self._norm(x, layer["pre_attn_norm"])
        a, kv = attn.attend_cached_stacked(
            layer["attn"], h, kv, i, length, rope_theta=cfg.rope_theta,
            window=window, softcap_value=cfg.attn_softcap,
            query_scale=cfg.query_scale,
        )
        if "ssm" in layer:
            s2, ssm_state = self._ssm_decode(layer, h, ssm_state, i)
            a = 0.5 * (a + s2)
        if cfg.use_post_norms:
            a = self._norm(a, layer["post_attn_norm"])
        x = x + a
        if cross is not None and "cross" in layer:
            h = self._norm(x, layer["pre_cross_norm"])
            x = x + attn.attend_cross(layer["cross"], h, cross["k"], cross["v"])
        x, _ = self._ffn_apply(layer, x)
        return x, kv, ssm_state

    def decode_step(
        self,
        params: Params,
        state: DecodeState,
        token: jax.Array,  # [B] int32
    ) -> Tuple[jax.Array, DecodeState]:
        """One decode step: (logits [B, V], new state).  The stacked K/V
        and SSM state ride the layer scan's carry, and each layer writes
        its one new row in place, so a donated state is updated where it
        lies; cross-attention K/V is read only, scanned per layer."""
        cfg = self.cfg
        if cfg.interleaved:
            return self._il_decode_step(params, state, token)
        x = embed_lookup(params["embed"], token[:, None])  # [B,1,D]
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        length = state.length
        xs: Dict[str, Any] = {"layer": params["layers"],
                              "window": cfg.window_sizes(),
                              "i": jnp.arange(cfg.n_scan)}
        if state.cross_kv is not None:
            xs["cross"] = state.cross_kv

        def body(carry, inp):
            x1, kv, ssm = carry
            layer, window, i = inp["layer"], inp["window"], inp["i"]
            cross = inp.get("cross")
            if cfg.paired:  # layers 2i (dense) and 2i+1 (MoE) of one stack
                x1, kv, _ = self._sub_decode(layer["dense"], x1, kv, None,
                                             cross, window[0], length, 2 * i)
                x1, kv, _ = self._sub_decode(layer["moe"], x1, kv, None, None,
                                             window[1], length, 2 * i + 1)
            else:
                x1, kv, ssm = self._sub_decode(layer, x1, kv, ssm, cross,
                                               window, length, i)
            return (x1, kv, ssm), None

        (x, kv, ssm), _ = jax.lax.scan(body, (x, state.kv, state.ssm), xs)
        x = self._norm(x, params["final_norm"])
        logits = self._logits(params, x)[:, 0, :]
        return logits, DecodeState(kv=kv, ssm=ssm, cross_kv=state.cross_kv,
                                   length=length + 1)

    def _sub_prefill(self, layer: Params, x: jax.Array, positions, window,
                     kv, cross):
        """One (sub-)layer full-prompt prefill writing the KV prefix.
        Returns (x, new_kv, new_ssm)."""
        cfg = self.cfg
        x = constrain(x, ("batch", "seq", "embed_act"))
        b, s = x.shape[0], x.shape[1]
        new_kv = new_ssm = None
        if "attn" not in layer:
            h = self._norm(x, layer["pre_ssm_norm"])
            out, new_ssm = self._ssm_forward_branch(layer, h)
            x = x + out
            x, _ = self._ffn_apply(layer, x)
            return x, new_kv, new_ssm
        h = self._norm(x, layer["pre_attn_norm"])
        q, k, v = attn.project_qkv(layer["attn"], h, positions,
                                   rope_theta=cfg.rope_theta)
        kbuf = jax.lax.dynamic_update_slice_in_dim(
            kv["k"], k.astype(kv["k"].dtype), 0, axis=1)
        vbuf = jax.lax.dynamic_update_slice_in_dim(
            kv["v"], v.astype(kv["v"].dtype), 0, axis=1)
        new_kv = {"k": kbuf, "v": vbuf}
        a = attn.attend_full(
            layer["attn"], h, positions, rope_theta=cfg.rope_theta,
            window=window, softcap_value=cfg.attn_softcap,
            query_scale=cfg.query_scale,
        )
        if "ssm" in layer:
            s_out, new_ssm = self._ssm_forward_branch(layer, h)
            a = 0.5 * (a + s_out)
        if cfg.use_post_norms:
            a = self._norm(a, layer["post_attn_norm"])
        x = x + a
        if cross is not None and "cross" in layer:
            h = self._norm(x, layer["pre_cross_norm"])
            x = x + attn.attend_cross(layer["cross"], h, cross["k"], cross["v"])
        x, _ = self._ffn_apply(layer, x)
        return x, new_kv, new_ssm

    def prefill(
        self,
        params: Params,
        tokens: jax.Array,
        state: DecodeState,
        *,
        frontend_embeds: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, DecodeState]:
        """Prefill the caches with a prompt; returns (last logits [B,V], state)."""
        cfg = self.cfg
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        x = self._embed_inputs(params, tokens, frontend_embeds)
        if cfg.interleaved:
            x, st, touched = self._il_full(
                params, x, positions, {cfg.kv_kind: state.kv,
                                       "mamba": state.ssm})
            x = self._final_norm(params, x)
            logits = self._logits(params, x[:, -1:, :])[:, 0, :]
            return logits, DecodeState(
                kv=st[cfg.kv_kind], ssm=st["mamba"], cross_kv=None,
                length=jnp.full((b,), s, jnp.int32), experts_touched=touched)
        windows = cfg.window_sizes()
        memory_kv = None
        if cfg.n_encoder_layers:
            assert frontend_embeds is not None
            memory_kv = self._cross_memory(params, frontend_embeds)

        inp: Dict[str, Any] = {"layer": params["layers"], "window": windows}
        if state.kv is not None:
            inp["kv"] = self._pair_view(state.kv) if cfg.paired else state.kv
        if state.ssm is not None:
            inp["ssm"] = state.ssm
        if memory_kv is not None:
            inp["cross"] = memory_kv

        def body(carry, inp1):
            x1 = carry
            layer, window = inp1["layer"], inp1["window"]
            outs: Dict[str, Any] = {}
            if cfg.paired:
                kv = inp1["kv"]
                x1, k0, _ = self._sub_prefill(
                    layer["dense"], x1, positions, window[0],
                    jax.tree.map(lambda t: t[0], kv), inp1.get("cross"),
                )
                x1, k1, _ = self._sub_prefill(
                    layer["moe"], x1, positions, window[1],
                    jax.tree.map(lambda t: t[1], kv), None,
                )
                outs["kv"] = jax.tree.map(lambda p, q2: jnp.stack([p, q2]), k0, k1)
            else:
                x1, new_kv, new_ssm = self._sub_prefill(
                    layer, x1, positions, window, inp1.get("kv"),
                    inp1.get("cross"),
                )
                if new_kv is not None:
                    outs["kv"] = new_kv
                if new_ssm is not None:
                    outs["ssm"] = new_ssm
            return x1, outs

        x, outs = jax.lax.scan(body, x, inp)
        x = self._norm(x, params["final_norm"])
        logits = self._logits(params, x[:, -1:, :])[:, 0, :]
        new_kv = outs.get("kv")
        if new_kv is not None and cfg.paired:
            new_kv = self._pair_unview(new_kv)
        new_state = DecodeState(
            kv=new_kv if new_kv is not None else state.kv,
            ssm=outs.get("ssm", state.ssm),
            cross_kv=memory_kv if memory_kv is not None else state.cross_kv,
            length=jnp.full((b,), s, jnp.int32),
        )
        return logits, new_state

    # ------------------------------------------------- interleaved stacks
    def _final_norm(self, params: Params, x: jax.Array) -> jax.Array:
        if self.cfg.interleaved:
            return rmsnorm(x, params["final_norm"], self.cfg.norm_eps)
        return self._norm(x, params["final_norm"])

    def _il_init_state(self, batch: int, max_len: int) -> DecodeState:
        cfg = self.cfg
        n_attn, n_mamba = cfg.kind_count("attention"), cfg.kind_count("mamba")
        n_mla = cfg.kind_count("mla")
        kv = ssm_state = None
        if n_attn:
            shape = (n_attn, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            kv = {"k": jnp.zeros(shape, cfg.dtype),
                  "v": jnp.zeros(shape, cfg.dtype)}
        if n_mla:
            kv = {"c_kv": jnp.zeros((n_mla, batch, max_len, cfg.kv_lora_rank),
                                    cfg.dtype),
                  "k_pe": jnp.zeros((n_mla, batch, max_len,
                                     cfg.qk_rope_head_dim), cfg.dtype)}
        if n_mamba:
            dims = cfg.ssm_dims
            ssm_state = {
                "h": jnp.zeros((n_mamba, batch, dims["n_heads"],
                                dims["head_dim"], dims["d_state"]), jnp.float32),
                "conv": jnp.zeros((n_mamba, batch, dims["d_conv"] - 1,
                                   dims["conv_dim"]), cfg.dtype),
            }
        return DecodeState(kv=kv, ssm=ssm_state, cross_kv=None,
                           length=jnp.zeros((batch,), jnp.int32),
                           experts_touched=jnp.zeros(
                               (len(cfg.scanned_types),), jnp.int32))

    def _il_stack(self, params: Params, x: jax.Array, state: Dict[str, Any],
                  mixer):
        """Run an interleaved stack: its leading dense layers, then a scan
        over its periods, and in each an inner scan per run of one kind.
        Each layer is an RMSNorm, its mixer ``mixer(kind, layer_params, h,
        kind_state, index_in_kind) -> (out, kind_state)`` added to the
        residual times ``residual_multiplier``, then the expert layer (a
        leading layer's dense SwiGLU).  ``state`` maps each kind to its
        stacked state (or None), carried and updated in place.  Returns
        (x, state, held experts touched [scanned layers])."""
        cfg = self.cfg
        layers = params["layers"]
        kinds = cfg.scanned_types
        per_kind = {k: sum(t == k for t in kinds[:cfg.period])
                    for k in set(kinds)}
        touched0 = jnp.zeros((len(kinds),), jnp.int32)
        res = cfg.residual_multiplier
        # a kind's state index, counted past its leading dense layers
        past = {k: cfg.layer_types[:cfg.n_dense_lead].count(k)
                for k in per_kind}
        for j, kind in enumerate(cfg.layer_types[:cfg.n_dense_lead]):
            lp = jax.tree.map(lambda a, j=j: a[j], params["lead"])
            h = rmsnorm(x, lp["pre_norm"], cfg.norm_eps)
            out, st_k = mixer(kind, lp, h, state[kind], j)
            x = x + out * jnp.asarray(res, x.dtype)
            h = rmsnorm(x, lp["post_norm"], cfg.norm_eps)
            m = mlp_apply(lp["mlp"], h, activation=cfg.activation)
            x = x + m * jnp.asarray(res, x.dtype)
            state = {**state, kind: st_k}

        def period_body(carry, p):
            for kind, first, count, at in cfg.segments():
                def layer_body(c, j, kind=kind, first=first, at=at):
                    x1, st, touched = c
                    i = p * per_kind[kind] + first + j
                    lp = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, i, keepdims=False), layers[kind])
                    x1 = constrain(x1, ("batch", "seq", "embed_act"))
                    h = rmsnorm(x1, lp["pre_norm"], cfg.norm_eps)
                    out, st_k = mixer(kind, lp, h, st[kind], i + past[kind])
                    x1 = x1 + out * jnp.asarray(res, x1.dtype)
                    x1, t = self._il_ffn(lp, x1)
                    touched = touched.at[p * cfg.period + at + j].set(t)
                    return (x1, {**st, kind: st_k}, touched), None

                carry, _ = jax.lax.scan(self._maybe_remat(layer_body), carry,
                                        jnp.arange(count))
            return carry, None

        (x, state, touched), _ = jax.lax.scan(
            period_body, (x, state, touched0),
            jnp.arange(len(kinds) // cfg.period))
        return x, state, touched

    def _il_ffn(self, lp: Params, x: jax.Array):
        cfg = self.cfg
        h = rmsnorm(x, lp["post_norm"], cfg.norm_eps)
        with jax.named_scope("moe"):
            m, touched = moe_lib.held_moe_apply(
                lp["moe"], h, top_k=cfg.top_k, held=cfg.held,
                activation=cfg.activation, router=cfg.router)
        return x + m * jnp.asarray(cfg.residual_multiplier, x.dtype), touched

    def _il_full(self, params: Params, x: jax.Array, positions: jax.Array,
                 state: Optional[Dict[str, Any]]):
        """Whole-sequence pass (training forward, or prefill when ``state``
        holds the stacked K/V (or latent) and SSM state to fill)."""
        cfg = self.cfg
        mla = cfg.mla_args() if "mla" in cfg.layer_types else {}

        def mixer(kind, lp, h, st, i):
            if kind == "mamba":
                with jax.named_scope("ssm"):
                    out, new = self._ssm_forward_branch(lp, h,
                                                        eps=cfg.norm_eps)
                if st is not None:
                    st = {k: jax.lax.dynamic_update_index_in_dim(
                        st[k], new[k].astype(st[k].dtype), i, 0) for k in st}
            elif kind == "mla":
                with jax.named_scope("attn"):
                    out, st = attn.mla_full(lp["mla"], h, positions, st, i,
                                            q_block=IL_Q_BLOCK, **mla)
            else:
                with jax.named_scope("attn"):
                    if st is not None:
                        _, k, v = attn.project_qkv(lp["attn"], h, positions,
                                                   rope_theta=cfg.rope_theta)
                        st = {n: jax.lax.dynamic_update_slice(
                            st[n], t.astype(st[n].dtype)[None],
                            (i, 0, 0, 0, 0)) for n, t in (("k", k), ("v", v))}
                    out = attn.attend_full(
                        lp["attn"], h, positions, rope_theta=cfg.rope_theta,
                        window=jnp.int32(FULL_WINDOW),
                        query_scale=cfg.query_scale, q_block=IL_Q_BLOCK)
            return out, st

        st0 = state or {"mamba": None, cfg.kv_kind: None}
        x, st, touched = self._il_stack(params, x, st0, mixer)
        return x, (st if state is not None else None), touched

    def _il_decode_step(self, params: Params, state: DecodeState,
                        token: jax.Array) -> Tuple[jax.Array, DecodeState]:
        cfg = self.cfg
        length = state.length
        x = self._embed_inputs(params, token[:, None], None)  # [B,1,D]
        mla = cfg.mla_args() if "mla" in cfg.layer_types else {}

        def mixer(kind, lp, h, st, i):
            if kind == "mamba":
                with jax.named_scope("ssm"):
                    out, st = self._ssm_decode(lp, h, st, i, cfg.norm_eps)
            elif kind == "mla":
                with jax.named_scope("attn"):
                    out, st = attn.mla_cached(lp["mla"], h, st, i, length,
                                              **mla)
            else:
                with jax.named_scope("attn"):
                    out, st = attn.attend_cached_stacked(
                        lp["attn"], h, st, i, length,
                        rope_theta=cfg.rope_theta, query_scale=cfg.query_scale)
            return out, st

        x, st, touched = self._il_stack(
            params, x, {cfg.kv_kind: state.kv, "mamba": state.ssm}, mixer)
        x = self._final_norm(params, x)
        logits = self._logits(params, x)[:, 0, :]
        return logits, DecodeState(kv=st[cfg.kv_kind], ssm=st["mamba"],
                                   cross_kv=None, length=length + 1,
                                   experts_touched=touched)


def _axes_of(model: "TransformerLM") -> Axes:
    """Build the axes tree without touching device memory: run init under
    eval_shape and capture the (shape-independent) axes side through a
    holder."""
    holder = {}

    def capture(k):
        p, a = model.init(k)
        holder["axes"] = a
        return p

    jax.eval_shape(capture, jax.random.PRNGKey(0))
    return holder["axes"]
