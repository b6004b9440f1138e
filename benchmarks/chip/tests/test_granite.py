"""The granite cell's check and counts, on the CPU at small sizes.

The check is driven through the whole harness on a cell of the tests'
own (the granite cell's closed loop and check at smoke widths): it passes
the program as it is, and fails it with a served token altered where it
is sampled, or with a decode step that hands back the state it got
(stale K/V and SSM state).
"""

import json
import os

import pytest

import cell as cell_lib
import counts_granite
from conftest import BENCH
from test_checks import _decode_fault, harness

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def granite():
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-small.json")) as f:
        return json.load(f)


def granite_cell():
    cfg = granite()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=32, shared_intermediate_size=64,
               router_experts=16, held_experts=[0, 2], num_local_experts=2,
               num_experts_per_tok=4, mamba_n_heads=8, mamba_d_head=16,
               mamba_d_state=16, mamba_chunk_size=16, layer_types=PERIOD,
               num_hidden_layers=10, vocab_size=256,
               attention_multiplier=1 / 16, initializer_range=0.2)
    # Limits for this size, set as a cell's are: above the program's
    # readings here (gap 0, logit error <= 0.0043 on seeds 1-3) and below
    # what an altered token (gap 0.83) or a stale state (error 0.079)
    # reads on seed 1.
    cfg["limits"] = {"max_logit_gap": 0.05, "max_logit_err": 0.02}
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
    return cell_lib.Cell(name="serve.granite.test", config_name=cfg["name"],
                         config=cfg, traffic_name="test", traffic=traffic,
                         chips=1, end_to_end=metrics, per_layer=[])


def granite_harness(monkeypatch):
    monkeypatch.setattr(cell_lib, "resolve",
                        lambda workload, root=None: granite_cell())
    return harness("serve.granite.test", None)


def test_granite_as_it_is(monkeypatch):
    line = granite_harness(monkeypatch)
    assert line["correct"], line["checks"]
    assert line["checks"]["documents_compared"]["value"] >= 2
    assert line["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("kind", ["altered", "unchanged"],
                         ids=["altered_token", "stale_state"])
def test_granite_fault_fails(kind, monkeypatch):
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
    line = granite_harness(monkeypatch)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for k, c in line["checks"].items()
               if k.startswith("max_logit"))


def test_granite_sizes():
    cfg = granite()
    # The published model, and the share one chip holds (PERF.md).
    assert counts_granite.param_count(cfg, published=True) == 32_207_337_984
    assert counts_granite.param_count(cfg) == 4_418_340_096
    assert counts_granite.weight_bytes(cfg) == 8_836_680_192
    # 18 Mamba layers x (128 x 64 x 128 float32 + 3 x 8448 bf16).
    assert counts_granite.state_bytes_per_slot(cfg) == 18 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2)
    # 2 attention layers x 8 KV heads x 128 x (K and V) x 2 bytes.
    assert counts_granite.kv_bytes_per_token(cfg) == 8192


def test_granite_decode_bytes_and_flops():
    cfg = granite()
    e = counts_granite.expert_bytes(cfg)
    dense = counts_granite.weight_bytes(cfg) - 20 * 9 * e
    state = counts_granite.state_bytes_per_slot(cfg)
    assert counts_granite.decode_bytes(cfg, [[9, 0]], 5) == (
        dense + 5 * e + 2 * 2 * state + (10 + 1) * 8192)
    assert counts_granite.moe_bytes(cfg, 1, 0) == 2 * 20 * (
        4096 * 72 + 3 * 4096 * 1536)
    assert counts_granite.decode_flops(cfg, [4]) == \
        counts_granite.token_flops(cfg, 5, True)
    # A prefill of one chunk or less: the SSD's causal pairs within it.
    one = counts_granite.prefill_flops(cfg, 1)
    assert one == counts_granite.token_flops(cfg, 1, False) \
        - 4 * 18 * 128 * 64 * 128 + 2 * 18 * (
            (128 + 128 * 64) + 2 * 128 * 64 * 128) \
        + 2 * 4096 * 100352


def test_scopes_from_hlo_and_trace():
    """Ops are mapped to the named scope in their ``op_name`` metadata, and
    their device time summed over the events of the program asked for,
    inside the traced window."""
    import jax
    import jax.numpy as jnp

    import scopes
    import tracing

    def f(x):
        with jax.named_scope("ssm"):
            y = jnp.sin(x) @ x
        with jax.named_scope("moe"):
            return jnp.cos(y) @ y

    hlo = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    scope_of = scopes.op_scopes(hlo)
    assert {"ssm", "moe"} <= set(scope_of.values())
    dev = "/device:TPU:0"
    events = [
        ("/host:CPU", "python", tracing.WINDOW_SPAN, 0.0, 1000.0),
        (dev, tracing.MODULES_LINE, "jit_decode_step(1)", 100.0, 300.0),
        (dev, tracing.MODULES_LINE, "jit_fn(2)", 500.0, 300.0),
        (dev, tracing.OPS_LINE, "%a = fusion(...)", 110.0, 50.0),
        (dev, tracing.OPS_LINE, "%b", 200.0, 30.0),
        (dev, tracing.OPS_LINE, "%while.1", 100.0, 290.0),
        (dev, tracing.OPS_LINE, "%a", 510.0, 70.0),  # another program
        (dev, tracing.OPS_LINE, "%c", 300.0, 10.0),  # no scope
    ]
    got = scopes.scope_seconds(tracing.collect(events), "jit_decode_step",
                               {"a": "moe", "b": "ssm", "while.1": "ssm"})
    assert got == pytest.approx({"moe": 50e-9, "ssm": 30e-9})


@pytest.mark.parametrize("metric", [
    "serve.granite.decode_hbm_roofline", "serve.granite.moe_roofline",
    "serve.granite.ssm_roofline", "serve.granite.mfu"])
def test_granite_metrics_read_nothing_without_a_trace(metric):
    run = cell_lib.Run(cell=None, seed=1, device={"kind": "TPU v5 lite"},
                       setup_s=1.0, window_s=1.0, data={"cfg": granite()})
    assert cell_lib.reader(metric)(run) is None
