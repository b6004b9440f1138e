"""Operation and byte counts of a dense GQA decoder, from the widths of its
configuration file (Hugging Face ``config.json`` keys).

Counts are what the algorithm needs, not what a program happens to do:
a decode step reads every weight once and the K and V rows of each active
slot's live positions, and writes one new row per slot; a token's forward
pass costs two operations per multiply-add of its matrix products and of
its attention over the positions it sees.
"""

from __future__ import annotations

from typing import Dict, Iterable

#: Bytes of one element of each served dtype.
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: Dict) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    hq = int(cfg["num_attention_heads"])
    return {
        "layers": int(cfg["num_hidden_layers"]),
        "d": d,
        "hq": hq,
        "hkv": int(cfg["num_key_value_heads"]),
        "dh": int(cfg.get("head_dim") or d // hq),
        "ff": int(cfg["intermediate_size"]),
        "vocab": int(cfg["vocab_size"]),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
        "bias": bool(cfg.get("qkv_bias", False)),
        "elem": DTYPE_BYTES[cfg.get("torch_dtype", "bfloat16")],
    }


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    m = dims(cfg)
    d, q, kv = m["d"], m["hq"] * m["dh"], m["hkv"] * m["dh"]
    return d * q + 2 * d * kv + q * d + 3 * d * m["ff"]


def param_count(cfg: Dict) -> int:
    m = dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * m["d"]  # two RMSNorm scales
    if m["bias"]:
        per_layer += (m["hq"] + 2 * m["hkv"]) * m["dh"]
    n = m["layers"] * per_layer + m["vocab"] * m["d"] + m["d"]
    if not m["tied"]:
        n += m["vocab"] * m["d"]
    return n


def weight_bytes(cfg: Dict) -> int:
    return param_count(cfg) * dims(cfg)["elem"]


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one position in every layer."""
    m = dims(cfg)
    return 2 * m["layers"] * m["hkv"] * m["dh"] * m["elem"]


def decode_bytes(cfg: Dict, lengths: Iterable[int]) -> int:
    """HBM bytes one decode step needs: every weight once, plus for each
    active slot the K/V of its ``length`` live positions and its new row."""
    kv = kv_bytes_per_token(cfg)
    return weight_bytes(cfg) + sum((int(n) + 1) * kv for n in lengths)


def token_flops(cfg: Dict, context: int, logits: bool) -> int:
    """Forward operations of one token that attends to ``context``
    positions (itself included), with its output logits or without."""
    m = dims(cfg)
    f = 2 * m["layers"] * layer_matmul_params(cfg)
    f += 4 * m["layers"] * m["hq"] * m["dh"] * int(context)  # QK^T and PV
    if logits:
        f += 2 * m["d"] * m["vocab"]
    return f


def prefill_flops(cfg: Dict, prompt: int) -> int:
    """A causal prefill of ``prompt`` tokens: logits of the last only."""
    m = dims(cfg)
    p = int(prompt)
    f = p * 2 * m["layers"] * layer_matmul_params(cfg)
    f += 4 * m["layers"] * m["hq"] * m["dh"] * (p * (p + 1) // 2)
    return f + 2 * m["d"] * m["vocab"]


def decode_flops(cfg: Dict, lengths: Iterable[int]) -> int:
    """One decode step: each active slot's token sees its ``length`` live
    positions and itself, and gets its logits."""
    return sum(token_flops(cfg, int(n) + 1, True) for n in lengths)
