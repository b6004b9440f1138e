"""The deepseek-v2-lite cell's check and counts, on the CPU at small sizes.

The check is driven through the whole harness on a cell of the tests'
own (the cell's closed loop and check at smoke widths): it passes the
program as it is, and fails it with a served token altered where it is
sampled, or with a decode step that hands back the state it got (a stale
latent cache).
"""

import json
import os

import pytest

import cell as cell_lib
import counts_deepseek
from conftest import BENCH
from test_checks import _decode_fault, harness


def deepseek():
    with open(os.path.join(BENCH, "configs", "deepseek-v2-lite.json")) as f:
        return json.load(f)


def deepseek_cell():
    cfg = deepseek()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=32, moe_intermediate_size=32,
               intermediate_size=96, router_experts=16, held_experts=[0, 2],
               n_routed_experts=2, num_experts_per_tok=4,
               num_hidden_layers=5, vocab_size=256, initializer_range=0.2)
    cfg["rope_scaling"] = {**cfg["rope_scaling"], "factor": 4,
                           "original_max_position_embeddings": 32}
    # Limits for this size, set as a cell's are: above the program's
    # readings here (gap <= 0.145, logit error <= 0.228 on the tests' seed
    # and seeds 1-3: weights of std 0.2 at width 64 give logits of order 8,
    # and bfloat16 keeps three digits of them) and below what an altered
    # token (gap >= 8.11) or a stale latent cache (gap >= 6.96, error
    # >= 6.51) reads on seeds 1-3.
    cfg["limits"] = {"max_logit_gap": 1.0, "max_logit_err": 1.0}
    traffic = {
        "engines": [{"name": "hbm", "placement": "device", "clients": 4}],
        "miku": False, "max_len": 160, "stream_chunks": 64,
        "prompt_lengths": [16, 32, 64, 128], "prompt_counts": [3, 3, 1, 1],
        "output_min": 4, "output_max": 32, "post_window_s": 60,
        "trace_seconds": 1,
        "check": {"requests": 6, "documents": 2, "document_min_prompt": 64,
                  "reference_lengths": [96, 160], "host_requests": 0,
                  "min_tokens": 40},
    }
    metrics = [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
               {"name": "setup_s", "unit": "s"}]
    return cell_lib.Cell(name="serve.deepseek.test", config_name=cfg["name"],
                         config=cfg, traffic_name="test", traffic=traffic,
                         chips=1, end_to_end=metrics, per_layer=[])


def deepseek_harness(monkeypatch):
    monkeypatch.setattr(cell_lib, "resolve",
                        lambda workload, root=None: deepseek_cell())
    return harness("serve.deepseek.test", None)


def test_deepseek_as_it_is(monkeypatch):
    line = deepseek_harness(monkeypatch)
    assert line["correct"], line["checks"]
    assert line["checks"]["documents_compared"]["value"] >= 2
    assert line["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("kind", ["altered", "unchanged"],
                         ids=["altered_token", "stale_state"])
def test_deepseek_fault_fails(kind, monkeypatch):
    from repro.models.transformer import TransformerLM
    from repro.serving import sampler

    if kind == "altered":
        greedy = sampler.greedy

        def shifted(logits, key=None):
            return (greedy(logits) + 1) % logits.shape[-1]

        monkeypatch.setattr(sampler, "greedy", shifted)
    else:
        monkeypatch.setattr(TransformerLM, "decode_step",
                            _decode_fault(kind, TransformerLM.decode_step))
    line = deepseek_harness(monkeypatch)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for k, c in line["checks"].items()
               if k.startswith("max_logit"))


def test_deepseek_sizes():
    cfg = deepseek()
    # The published model, and the share one chip holds (PERF.md).
    assert counts_deepseek.param_count(cfg, published=True) == 15_706_484_224
    assert counts_deepseek.param_count(cfg) == 3_110_989_312
    assert counts_deepseek.weight_bytes(cfg) == 6_221_978_624
    # 27 layers x (512 latent + 64 rope key) x 2 bytes.
    assert counts_deepseek.latent_bytes_per_token(cfg) == 31_104


def test_deepseek_decode_bytes_and_flops():
    cfg = deepseek()
    e = counts_deepseek.expert_bytes(cfg)
    assert e == 3 * 2048 * 1408 * 2
    dense = counts_deepseek.weight_bytes(cfg) - 26 * 8 * e
    assert counts_deepseek.decode_bytes(cfg, [[9, 0]], 5) == (
        dense + 5 * e + (10 + 1) * 31_104)
    assert counts_deepseek.moe_bytes(cfg, 1, 0) == 2 * 26 * (
        2048 * 64 + 3 * 2048 * 2816)
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256 \
        + 16 * 128 * 2048
    assert mla == 13_763_072
    assert counts_deepseek.mla_bytes(cfg, [[9, 0]]) == (
        2 * 27 * mla + (10 + 1) * 31_104)
    assert counts_deepseek.decode_flops(cfg, [4]) == \
        counts_deepseek.token_flops(cfg, 5, True)
    # A one-token prefill attends to itself in the expanded form: 192-wide
    # scores and 128-wide values per head, where a decode token's absorbed
    # form has 576 and 512.
    one = counts_deepseek.prefill_flops(cfg, 1)
    assert one == counts_deepseek.token_flops(cfg, 1, True) \
        - 2 * 27 * 16 * (576 + 512) + 2 * 27 * 16 * (192 + 128)


@pytest.mark.parametrize("metric", [
    "serve.dsv2.decode_hbm_roofline", "serve.dsv2.mla_roofline",
    "serve.dsv2.moe_roofline", "serve.dsv2.mfu"])
def test_deepseek_metrics_read_nothing_without_a_trace(metric):
    run = cell_lib.Run(cell=None, seed=1, device={"kind": "TPU v5 lite"},
                       setup_s=1.0, window_s=1.0, data={"cfg": deepseek()})
    assert cell_lib.reader(metric)(run) is None
