"""Driver for a DeepSeek-V2 share served by one ``ServingEngine``: the
serving driver's closed loop, instrumentation and logit check
(``drivers/serve.py``), with the expert-layer cell's set-up and window
(``drivers/serve_granite.py``: the decode program's named scopes and the
held experts its steps touch), and this family's configuration, weights
and reference.

Configuration: the model's Hugging Face ``config.json`` keys with
``n_routed_experts`` cut to the share this chip holds,
``router_experts`` (the router's width) and ``held_experts``
(``[lo, hi)``), ``limits``.  Traffic: the serving driver's keys; its
``check`` adds ``documents`` (how many of the compared requests have
prompts of at least ``document_min_prompt`` tokens) and
``reference_lengths`` (the padded lengths the reference runs at).
"""

from __future__ import annotations

import gc
import math
import os
import sys
from typing import Any, Dict, List

import numpy as np

import cell as cell_lib

granite = cell_lib.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "serve_granite.py"),
    "chipbench_serve_granite_for_deepseek")
base = granite.base

setup = granite.setup


def window(state, seconds: float, capture) -> Dict[str, Any]:
    """The expert-layer cell's window (``serve_granite.window``); the
    prefills admitted inside it, and the host seconds their admissions
    held the tick loop, are printed to stderr.  Decode steps wait while a
    prompt is prefilled, so these say how far the window's tokens/s falls
    below what its decode steps alone would give."""
    import time

    admits: List[tuple] = []
    for eng in state.engines.values():
        def admit_w(now_ns, _f=eng.admit):
            t = time.perf_counter()
            out = _f(now_ns)
            if out:
                admits.append((t, time.perf_counter() - t,
                               [len(r.prompt) for r, _ in out]))
            return out

        eng.admit = admit_w
    data = granite.window(state, seconds, capture)
    inside = [a for a in admits if data["t0"] <= a[0] < data["t1"]]
    held = sum(a[1] for a in inside)
    lens = [n for a in inside for n in a[2]]
    by_len = {n: lens.count(n) for n in sorted(set(lens))}
    print(f"[window] {len(lens)} prefills {by_len} held the loop {held:.4f} s "
          f"of {data['window_s']:.4f} s; {data['tokens']} tokens, "
          f"{data['tokens'] / max(data['window_s'] - held, 1e-9):.2f} "
          f"tokens/s outside the prefills", file=sys.stderr)
    return data


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a DeepSeek-V2 config file."""
    import jax.numpy as jnp

    from repro.models.layers import Yarn
    from repro.models.transformer import ModelConfig

    rs = cfg["rope_scaling"]
    if (cfg["q_lora_rank"] is not None or cfg["attention_bias"]
            or cfg["tie_word_embeddings"] or rs["type"] != "yarn"
            or cfg["topk_method"] != "greedy"
            or cfg["scoring_func"] != "softmax"
            or int(cfg["moe_layer_freq"]) != 1
            or cfg["hidden_act"] != "silu" or cfg["norm_topk_prob"]
            or float(cfg["routed_scaling_factor"]) != 1):
        raise ValueError("a configuration this driver does not serve")
    lo, hi = cfg["held_experts"]
    if hi - lo != int(cfg["n_routed_experts"]):
        raise ValueError("held_experts disagrees with n_routed_experts")
    n = int(cfg["num_hidden_layers"])
    ff = int(cfg["moe_intermediate_size"])
    return ModelConfig(
        name=cfg["name"], n_layers=n, d_model=int(cfg["hidden_size"]),
        n_q_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["v_head_dim"]), d_ff=ff,
        d_ff_dense=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]), block="interleaved",
        layer_types=("mla",) * n,
        n_dense_lead=int(cfg["first_k_dense_replace"]),
        rope_theta=float(cfg["rope_theta"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        yarn=Yarn(factor=float(rs["factor"]),
                  original_max_position_embeddings=int(
                      rs["original_max_position_embeddings"]),
                  beta_fast=float(rs["beta_fast"]),
                  beta_slow=float(rs["beta_slow"]),
                  mscale=float(rs["mscale"]),
                  mscale_all_dim=float(rs["mscale_all_dim"])),
        n_experts=int(cfg["router_experts"]),
        top_k=int(cfg["num_experts_per_tok"]), experts_held=(lo, hi),
        shared_expert_ff=int(cfg["n_shared_experts"]) * ff,
        router="softmax_topk",
        tied_embeddings=False, activation="silu",
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype=getattr(jnp, cfg.get("torch_dtype", "bfloat16")),
    )


def to_program(w: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's weights (``deepseek_v2.weight_maker``) in the
    program's layout.  The program scales its RMSNorms by ``1 + w``, so a
    scale of 1 is stored as 0; ``kv_b_proj`` is split into its key and
    value halves; the experts' gate and up weights are [E, F, D]."""
    import jax.numpy as jnp

    def mla(k):
        nope = k["wkv_b"].shape[-1] - k["wo"].shape[-2]
        return {"wq": k["wq"], "wkv_a": k["wkv_a"],
                "kv_norm": k["kv_norm"] - 1,
                "w_uk": k["wkv_b"][..., :nope],
                "w_uv": k["wkv_b"][..., nope:], "wo": k["wo"]}

    k = w["moe"]
    out = {
        "embed": w["embed"], "lm_head": w["lm_head"],
        "final_norm": w["norm"] - 1,
        "layers": {"mla": {
            "pre_norm": k["ln1"] - 1, "post_norm": k["ln2"] - 1,
            "mla": mla(k),
            "moe": {"router": k["router"],
                    "w_gate": jnp.swapaxes(k["w_gate"], -2, -1),
                    "w_up": jnp.swapaxes(k["w_up"], -2, -1),
                    "w_down": k["w_down"],
                    "shared": {"w_gate": k["sh_gate"], "w_up": k["sh_up"],
                               "w_down": k["sh_down"]}}}},
    }
    if "lead" in w:
        k = w["lead"]
        out["lead"] = {
            "pre_norm": k["ln1"] - 1, "post_norm": k["ln2"] - 1,
            "mla": mla(k),
            "mlp": {"w_gate": k["mlp_gate"], "w_up": k["mlp_up"],
                    "w_down": k["mlp_down"]}}
    return out


def program_params(cfg: Dict[str, Any], seed: int, model):
    """The seed's weights in the program's layout, made on the device in
    one jitted call."""
    import jax

    from reference import deepseek_v2

    make = deepseek_v2.weight_maker(cfg)

    def tree(key):
        return to_program(make(key))

    want = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    got = jax.eval_shape(tree, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the weights' layout differs from the program's")
    params = jax.jit(tree)(deepseek_v2.seed_key(seed))
    return jax.block_until_ready(params)


def readings(state, run, control: bool = False) -> Dict[str, Any]:
    """The serving driver's readings (``drivers/serve.py``) against this
    family's reference, with the documents compared counted."""
    import jax

    from reference import deepseek_v2

    chk = state.traffic["check"]
    sample = granite._sample(state, run)
    n_docs = sum(len(r.request.prompt) >= chk["document_min_prompt"]
                 for r in sample)
    tops = base._program_tops(state, sample)
    seqs = [(list(r.request.prompt), list(r.request.output)) for r in sample]
    prog = [tops[id(r.request)] for r in sample]
    state.cluster = state.engines = state.clients = None
    state.live = {}
    state.reqs = []
    state.probes = []
    run.data.pop("fast", None)
    gc.collect()
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    print(f"[check] {len(seqs)} requests ({n_docs} documents), "
          f"{sum(len(o) for _, o in seqs)} served tokens; device bytes in "
          f"use before the reference: {in_use}", file=sys.stderr)
    ref = deepseek_v2.served_logits(
        state.cfg, state.seed, seqs, lengths=chk["reference_lengths"],
        rows=state.traffic["output_max"], control=control)

    def widest(xs):
        xs = np.concatenate(xs) if xs else np.array([math.inf])
        return float(np.max(np.where(np.isnan(xs), math.inf, xs)))

    out = {
        "served_tokens_compared": sum(len(o) for _, o in seqs),
        "documents_compared": n_docs,
        "max_logit_gap": widest([r["best"] - r["served"] for r in ref]),
        "max_logit_err": widest([np.abs(p - r["served"])
                                 for p, r in zip(prog, ref)]),
    }
    if control:
        out["control_max_logit_gap"] = widest(
            [r["best"] - r["ctl_pick"] for r in ref])
        out["control_max_logit_err"] = widest(
            [np.abs(r["ctl_top"] - r["ctl_pick"]) for r in ref])
    return out


def check(state, run) -> List[Dict[str, Any]]:
    r = readings(state, run)
    lim = state.cfg["limits"]
    chk = state.traffic["check"]
    out = [{"name": k, "value": r[k], "limit": lim[k], "ok": r[k] <= lim[k]}
           for k in ("max_logit_gap", "max_logit_err")]
    for name, need in (("served_tokens_compared", chk["min_tokens"]),
                       ("documents_compared", chk["documents"])):
        out.append({"name": name, "value": r[name], "limit": need,
                    "ok": r[name] >= need})
    return out


base.model_config = model_config
base.program_params = program_params
