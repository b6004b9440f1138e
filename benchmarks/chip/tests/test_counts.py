"""Operation and byte counts, and the table of peaks."""

import json
import os

import pytest

import counts
import peaks
from conftest import BENCH


def qwen():
    with open(os.path.join(BENCH, "configs", "qwen2.5-3b.json")) as f:
        return json.load(f)


def test_qwen25_3b_sizes():
    cfg = qwen()
    # Qwen2.5-3B: 3.09 B parameters with the tied embedding.
    assert counts.param_count(cfg) == 3_085_938_688
    assert counts.weight_bytes(cfg) == 6_171_877_376
    # 36 layers x 2 KV heads x 128 x (K and V) x 2 bytes.
    assert counts.kv_bytes_per_token(cfg) == 36_864


def test_decode_bytes_and_flops():
    cfg = qwen()
    w = counts.weight_bytes(cfg)
    assert counts.decode_bytes(cfg, []) == w
    assert counts.decode_bytes(cfg, [9, 0]) == w + (10 + 1) * 36_864
    per = 2 * 36 * counts.layer_matmul_params(cfg)
    attn = 4 * 36 * 16 * 128
    logits = 2 * 2048 * 151_936
    assert counts.token_flops(cfg, 5, True) == per + 5 * attn + logits
    assert counts.decode_flops(cfg, [4]) == counts.token_flops(cfg, 5, True)


@pytest.mark.parametrize("prompt", [1, 2, 128, 1024])
def test_prefill_is_its_tokens(prompt):
    """A prefill counts each prompt token at its causal context, and the
    logits of the last alone."""
    cfg = qwen()
    want = sum(counts.token_flops(cfg, i + 1, False) for i in range(prompt))
    want += 2 * 2048 * 151_936
    assert counts.prefill_flops(cfg, prompt) == want


def test_peaks_by_device_kind():
    v5e = peaks.of({"kind": "TPU v5 lite"})
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.of({"kind": "cpu"})
