"""Operation and byte counts of a DeepSeek-V2 share (latent attention in
every layer, a leading dense layer, then expert layers with shared
experts), from the widths of its configuration file (Hugging Face
``config.json`` keys, plus ``router_experts`` and ``held_experts``).

Counts are what the algorithm needs, not what a program happens to do:

* bytes: a decode step reads every weight outside the held experts once,
  the weights of the held experts its tokens were routed to (counted by
  the program: ``moe.experts_touched``), and each slot's cached latent
  and rope key at its live positions in every layer, writing one new row
  per slot;
* operations: two per multiply-add of each matrix product a token goes
  through.  A token's expert work is its routed pairs with held experts,
  counted at their expected number, ``k * held / E`` per expert layer (the
  router's choices are not recorded).  Attention is counted in the form
  each phase computes it: a prefill expands per-head keys and values and
  attends over its causal pairs with ``nope + rope`` wide scores and
  ``v`` wide values; a decode token absorbs them, scoring each cached
  position against ``r + rope`` latent and rope values and summing ``r``
  latent values per head.
"""

from __future__ import annotations

from typing import Dict, Iterable, List

#: Bytes of one element of each served dtype.
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: Dict, published: bool = False) -> Dict:
    """Widths and counts; ``published``: the whole model, every expert
    held."""
    lo, hi = (0, int(cfg["router_experts"])) if published else \
        cfg["held_experts"]
    ff = int(cfg["moe_intermediate_size"])
    layers = int(cfg["num_hidden_layers"])
    lead = int(cfg["first_k_dense_replace"])
    return {
        "layers": layers, "lead": lead, "moe": layers - lead,
        "d": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "r": int(cfg["kv_lora_rank"]), "ff": ff,
        "dff": int(cfg["intermediate_size"]),
        "sff": int(cfg["n_shared_experts"]) * ff,
        "E": int(cfg["router_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "held": int(hi) - int(lo), "vocab": int(cfg["vocab_size"]),
        "elem": DTYPE_BYTES[cfg.get("torch_dtype", "bfloat16")],
    }


def _mla_matmuls(m: Dict) -> int:
    """Weights of one layer's latent attention that a token multiplies:
    ``q_proj``, ``kv_a_proj_with_mqa``, ``kv_b_proj`` and ``o_proj``."""
    d, H = m["d"], m["H"]
    return (d * H * (m["nope"] + m["rope"]) + d * (m["r"] + m["rope"])
            + m["r"] * H * (m["nope"] + m["v"]) + H * m["v"] * d)


def mla_params(m: Dict) -> int:
    """Parameters of one layer's latent attention (``kv_a_layernorm``'s
    scale included)."""
    return _mla_matmuls(m) + m["r"]


def expert_params(m: Dict) -> int:
    return 3 * m["d"] * m["ff"]


def _moe_outside_experts(m: Dict) -> int:
    """The router and the shared experts of one expert layer."""
    return m["d"] * m["E"] + 3 * m["d"] * m["sff"]


def param_count(cfg: Dict, published: bool = False) -> int:
    """Every parameter of the share (or, ``published``, of the model)."""
    m = dims(cfg, published)
    d = m["d"]
    n = 2 * m["vocab"] * d + d  # embedding, lm_head, final norm
    n += m["lead"] * (mla_params(m) + 3 * d * m["dff"] + 2 * d)
    n += m["moe"] * (mla_params(m) + _moe_outside_experts(m)
                     + m["held"] * expert_params(m) + 2 * d)
    return n


def _bytes(cfg: Dict, n: int) -> int:
    return n * dims(cfg)["elem"]


def weight_bytes(cfg: Dict) -> int:
    return _bytes(cfg, param_count(cfg))


def expert_bytes(cfg: Dict) -> int:
    """One expert's weights."""
    return _bytes(cfg, expert_params(dims(cfg)))


def latent_bytes_per_token(cfg: Dict) -> int:
    """The cached latent and rope key of one position in every layer."""
    m = dims(cfg)
    return m["layers"] * (m["r"] + m["rope"]) * m["elem"]


def mla_bytes(cfg: Dict, steps: Iterable[List[int]]) -> int:
    """The ``attn`` scope over decode steps given by their active slots'
    lengths: each step's latent attention weights in every layer, and
    each slot's live latent rows read and its new row written."""
    m = dims(cfg)
    weights = _bytes(cfg, m["layers"] * mla_params(m))
    row = latent_bytes_per_token(cfg)
    return sum(weights + sum((int(n) + 1) * row for n in lengths)
               for lengths in steps)


def moe_bytes(cfg: Dict, steps: int, experts_touched: int) -> int:
    """The ``moe`` scope's weights over ``steps`` decode steps that
    touched ``experts_touched`` held experts in all: each step's routers
    and shared experts, and each touched expert once."""
    m = dims(cfg)
    outside = m["moe"] * _moe_outside_experts(m)
    return steps * _bytes(cfg, outside) + experts_touched * expert_bytes(cfg)


def decode_bytes(cfg: Dict, steps: Iterable[List[int]],
                 experts_touched: int) -> int:
    """HBM bytes of decode steps (each the lengths of its active slots)
    that touched ``experts_touched`` held experts in all."""
    m = dims(cfg)
    dense = weight_bytes(cfg) - m["moe"] * m["held"] * expert_bytes(cfg)
    row = latent_bytes_per_token(cfg)
    total = experts_touched * expert_bytes(cfg)
    for lengths in steps:
        total += dense + sum((int(n) + 1) * row for n in lengths)
    return total


def _token_matmuls(m: Dict) -> float:
    """Multiply-adds of one token's weight products over the stack, with
    its expected routed pairs with held experts."""
    routed = m["k"] * m["held"] / m["E"] * expert_params(m)
    lead = _mla_matmuls(m) + 3 * m["d"] * m["dff"]
    moe = _mla_matmuls(m) + _moe_outside_experts(m) + routed
    return m["lead"] * lead + m["moe"] * moe


def token_flops(cfg: Dict, context: int, logits: bool) -> float:
    """Decode operations of one token that attends to ``context``
    positions (itself included) in the absorbed form, with its output
    logits or without."""
    m = dims(cfg)
    f = 2 * _token_matmuls(m)
    per_position = m["H"] * ((m["r"] + m["rope"]) + m["r"])
    f += 2 * m["layers"] * per_position * int(context)
    if logits:
        f += 2 * m["d"] * m["vocab"]
    return f


def prefill_flops(cfg: Dict, prompt: int) -> float:
    """A causal prefill of ``prompt`` tokens in the expanded form, logits
    of the last only."""
    m = dims(cfg)
    p = int(prompt)
    f = p * 2 * _token_matmuls(m)
    per_pair = m["H"] * ((m["nope"] + m["rope"]) + m["v"])
    f += 2 * m["layers"] * per_pair * (p * (p + 1) // 2)
    return f + 2 * m["d"] * m["vocab"]


def decode_flops(cfg: Dict, lengths: Iterable[int]) -> float:
    """One decode step: each active slot's token sees its ``length`` live
    positions and itself, and gets its logits."""
    return sum(token_flops(cfg, int(n) + 1, True) for n in lengths)
