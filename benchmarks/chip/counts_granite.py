"""Operation and byte counts of a Granite 4.0-H share (Mamba-2 and
attention layers in one stack, each with an expert layer and a shared
MLP), from the widths of its configuration file (Hugging Face
``config.json`` keys, plus ``router_experts`` and ``held_experts``).

Counts are what the algorithm needs, not what a program happens to do:

* bytes: a decode step reads every weight outside the held experts once,
  the weights of the held experts its tokens were routed to (counted by
  the program: ``moe.experts_touched``), each active slot's SSM and conv
  state (read and written), and the K and V rows of each slot's live
  positions in the attention layers, writing one new row per slot;
* operations: two per multiply-add of each matrix product a token goes
  through.  A token's expert work is its routed pairs with held experts,
  counted at their expected number, ``k * held / E`` per layer (the
  router's choices are not recorded); attention over the positions it
  sees; the Mamba recurrence, per token in decode (``h`` updated and read
  out) and as the chunked algorithm's causal blocks in prefill.
"""

from __future__ import annotations

from typing import Dict, Iterable, List

#: Bytes of one element of each served dtype.
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: Dict, published: bool = False) -> Dict:
    """Widths and counts; ``published``: the whole model, every expert
    held, from the file's ``published`` block."""
    src = {**cfg, **cfg["published"]} if published else cfg
    types = list(src["layer_types"])
    d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    lo, hi = (0, int(cfg["router_experts"])) if published else \
        cfg["held_experts"]
    di = int(cfg["mamba_expand"]) * d
    g, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    m = {
        "mamba": types.count("mamba"), "attention": types.count("attention"),
        "d": d, "hq": hq, "hkv": int(cfg["num_key_value_heads"]),
        "dh": d // hq, "ff": int(cfg["intermediate_size"]),
        "sff": int(cfg["shared_intermediate_size"]),
        "E": int(cfg["router_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "held": int(hi) - int(lo), "vocab": int(cfg["vocab_size"]),
        "di": di, "H": int(cfg["mamba_n_heads"]), "P": int(cfg["mamba_d_head"]),
        "N": n, "G": g, "K": int(cfg["mamba_d_conv"]), "conv": di + 2 * g * n,
        "chunk": int(cfg["mamba_chunk_size"]),
        "elem": DTYPE_BYTES[cfg.get("torch_dtype", "bfloat16")],
    }
    m["layers"] = m["mamba"] + m["attention"]
    return m


def _mixer(m: Dict, kind: str) -> int:
    """Parameters of one layer's mixer."""
    d = m["d"]
    if kind == "attention":
        return 2 * d * m["hq"] * m["dh"] + 2 * d * m["hkv"] * m["dh"]
    in_proj = d * (m["di"] + m["conv"] + m["H"])
    conv = m["K"] * m["conv"] + m["conv"]
    return in_proj + conv + 3 * m["H"] + m["di"] + m["di"] * d


def _ffn_outside_experts(m: Dict) -> int:
    """Router, shared MLP and the layer's two RMSNorm scales."""
    d = m["d"]
    return d * m["E"] + 3 * d * m["sff"] + 2 * d


def expert_params(m: Dict) -> int:
    return 3 * m["d"] * m["ff"]


def param_count(cfg: Dict, published: bool = False) -> int:
    """Every parameter of the share (or, ``published``, of the model)."""
    m = dims(cfg, published)
    n = m["vocab"] * m["d"] + m["d"]  # tied embedding, final norm
    for kind in ("mamba", "attention"):
        n += m[kind] * (_mixer(m, kind) + _ffn_outside_experts(m)
                        + m["held"] * expert_params(m))
    return n


def _bytes(cfg: Dict, n: int) -> int:
    return n * dims(cfg)["elem"]


def weight_bytes(cfg: Dict) -> int:
    return _bytes(cfg, param_count(cfg))


def expert_bytes(cfg: Dict) -> int:
    """One expert's weights."""
    return _bytes(cfg, expert_params(dims(cfg)))


def state_bytes_per_slot(cfg: Dict) -> int:
    """One slot's recurrent state: float32 ``h`` and the conv window of
    every Mamba layer."""
    m = dims(cfg)
    h = m["H"] * m["P"] * m["N"] * 4
    conv = (m["K"] - 1) * m["conv"] * m["elem"]
    return m["mamba"] * (h + conv)


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one position in every attention layer."""
    m = dims(cfg)
    return 2 * m["attention"] * m["hkv"] * m["dh"] * m["elem"]


def moe_bytes(cfg: Dict, steps: int, experts_touched: int) -> int:
    """The ``moe`` scope's weights over ``steps`` decode steps that
    touched ``experts_touched`` held experts in all: each step's routers
    and shared MLPs, and each touched expert once."""
    m = dims(cfg)
    outside = m["layers"] * (m["d"] * m["E"] + 3 * m["d"] * m["sff"])
    return steps * _bytes(cfg, outside) + experts_touched * expert_bytes(cfg)


def ssm_bytes(cfg: Dict, steps: Iterable[List[int]]) -> int:
    """The ``ssm`` scope over decode steps given by their active slots'
    lengths: each step's Mamba mixer weights, and each active slot's state
    read and written."""
    m = dims(cfg)
    mixers = _bytes(cfg, m["mamba"] * _mixer(m, "mamba"))
    state = state_bytes_per_slot(cfg)
    return sum(mixers + 2 * state * len(lengths) for lengths in steps)


def decode_bytes(cfg: Dict, steps: Iterable[List[int]],
                 experts_touched: int) -> int:
    """HBM bytes of decode steps (each the lengths of its active slots)
    that touched ``experts_touched`` held experts in all."""
    m = dims(cfg)
    dense = weight_bytes(cfg) - m["layers"] * m["held"] * expert_bytes(cfg)
    kv, state = kv_bytes_per_token(cfg), state_bytes_per_slot(cfg)
    total = experts_touched * expert_bytes(cfg)
    for lengths in steps:
        total += dense + sum(2 * state + (int(n) + 1) * kv for n in lengths)
    return total


def _layer_matmuls(m: Dict) -> float:
    """Multiply-adds of one token's matrix products over the stack, with
    its expected routed pairs with held experts."""
    routed = m["k"] * m["held"] / m["E"] * expert_params(m)
    ffn = m["d"] * m["E"] + 3 * m["d"] * m["sff"] + routed
    return sum(m[kind] * (_mixer(m, kind) + ffn)
               for kind in ("mamba", "attention"))


def token_flops(cfg: Dict, context: int, logits: bool) -> float:
    """Decode operations of one token that attends to ``context``
    positions (itself included), with its output logits or without."""
    m = dims(cfg)
    f = 2 * _layer_matmuls(m)
    f += 4 * m["attention"] * m["hq"] * m["dh"] * int(context)
    f += 4 * m["mamba"] * m["H"] * m["P"] * m["N"]  # update h, read it out
    if logits:
        f += 2 * m["d"] * m["vocab"]
    return f


def prefill_flops(cfg: Dict, prompt: int) -> float:
    """A causal prefill of ``prompt`` tokens, logits of the last only.
    The recurrence is the chunked algorithm's: within each chunk of Q
    positions, C B^T and its product with x over the causal pairs; across
    chunks, each position's contribution to the chunk state and its read
    of the carried state."""
    m = dims(cfg)
    p = int(prompt)
    f = p * 2 * _layer_matmuls(m)
    f += 4 * m["attention"] * m["hq"] * m["dh"] * (p * (p + 1) // 2)
    q = m["chunk"]
    full, rest = divmod(p, q)
    pairs = full * q * (q + 1) // 2 + rest * (rest + 1) // 2
    per_layer = (pairs * (m["G"] * m["N"] + m["H"] * m["P"])
                 + 2 * p * m["H"] * m["P"] * m["N"])
    f += 2 * m["mamba"] * per_layer
    return f + 2 * m["d"] * m["vocab"]


def decode_flops(cfg: Dict, lengths: Iterable[int]) -> float:
    """One decode step: each active slot's token sees its ``length`` live
    positions and itself, and gets its logits."""
    return sum(token_flops(cfg, int(n) + 1, True) for n in lengths)

