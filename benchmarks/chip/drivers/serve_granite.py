"""Driver for a Granite 4.0-H share served by one ``ServingEngine``: the
serving driver's closed loop, instrumentation and logit check
(``drivers/serve.py``), with this family's configuration, weights and
reference.

Configuration: the model's Hugging Face ``config.json`` keys with
``num_hidden_layers``, ``layer_types`` and ``num_local_experts`` cut to
the share this chip holds, ``router_experts`` (the router's width) and
``held_experts`` (``[lo, hi)``), ``limits``.  Traffic: the serving
driver's keys; its ``check`` adds ``documents`` (how many of the compared
requests have prompts of at least ``document_min_prompt`` tokens) and
``reference_lengths`` (the padded lengths the reference runs at).

A traced window also reads the device time of the decode program's
``ssm``, ``moe`` and ``attn`` scopes (``scopes.py``) and the held experts
its steps touched (the engine's ``moe.experts_touched`` counter).
"""

from __future__ import annotations

import gc
import math
import os
import sys
from typing import Any, Dict, List

import numpy as np

import cell as cell_lib
import scopes
import tracing

base = cell_lib.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve.py"),
    "chipbench_serve_for_granite")

DECODE_PROGRAM = "jit_decode_step"
COUNTER = "moe.experts_touched"


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a Granite 4.0-H config file."""
    import jax.numpy as jnp

    from repro.models.transformer import ModelConfig

    d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    types = tuple(cfg["layer_types"])
    expand, head = int(cfg["mamba_expand"]), int(cfg["mamba_d_head"])
    if (len(types) != int(cfg["num_hidden_layers"])
            or expand * d // head != int(cfg["mamba_n_heads"])
            or cfg["position_embedding_type"] != "nope"
            or cfg["attention_bias"] or cfg["mamba_proj_bias"]
            or not cfg["mamba_conv_bias"]):
        raise ValueError("a configuration this driver does not serve")
    lo, hi = cfg["held_experts"]
    if hi - lo != int(cfg["num_local_experts"]):
        raise ValueError("held_experts disagrees with num_local_experts")
    return ModelConfig(
        name=cfg["name"], n_layers=len(types), d_model=d, n_q_heads=hq,
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=d // hq,
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        block="interleaved", layer_types=types, rope_theta=None,
        query_scale=float(cfg["attention_multiplier"]),
        n_experts=int(cfg["router_experts"]),
        top_k=int(cfg["num_experts_per_tok"]), experts_held=(lo, hi),
        shared_expert_ff=int(cfg["shared_intermediate_size"]),
        ssm_state=int(cfg["mamba_d_state"]), ssm_head_dim=head,
        ssm_groups=int(cfg["mamba_n_groups"]), ssm_expand=expand,
        ssm_chunk=int(cfg["mamba_chunk_size"]),
        tied_embeddings=bool(cfg["tie_word_embeddings"]), activation="silu",
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype=getattr(jnp, cfg.get("torch_dtype", "bfloat16")),
    )


def to_program(w: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's weights (``granite_hybrid.weight_maker``) in the
    program's layout.  The program scales its RMSNorms by ``1 + w``, so a
    scale of 1 is stored as 0; ``A_log``, ``D`` and ``dt_bias`` are float32
    there (widened exactly); its experts' gate and up weights are [E, F, D]."""
    import jax.numpy as jnp

    def ffn(k):
        return {
            "pre_norm": k["ln1"] - 1, "post_norm": k["ln2"] - 1,
            "moe": {"router": k["router"],
                    "w_gate": jnp.swapaxes(k["w_gate"], -2, -1),
                    "w_up": jnp.swapaxes(k["w_up"], -2, -1),
                    "w_down": k["w_down"],
                    "shared": {"w_gate": k["sh_gate"], "w_up": k["sh_up"],
                               "w_down": k["sh_down"]}},
        }

    layers = {}
    if "mamba" in w:
        k = w["mamba"]
        layers["mamba"] = {**ffn(k), "ssm": {
            "in_proj": k["in_proj"], "conv_w": k["conv_w"],
            "conv_b": k["conv_b"],
            **{n: k[n].astype(jnp.float32) for n in ("A_log", "D",
                                                     "dt_bias")},
            "norm": k["gnorm"] - 1, "out_proj": k["out_proj"]}}
    if "attention" in w:
        k = w["attention"]
        layers["attention"] = {**ffn(k), "attn": {
            n: k[n] for n in ("wq", "wk", "wv", "wo")}}
    return {"embed": w["embed"], "layers": layers,
            "final_norm": w["norm"] - 1}


def program_params(cfg: Dict[str, Any], seed: int, model):
    """The seed's weights in the program's layout, made on the device in
    one jitted call."""
    import jax

    from reference import granite_hybrid

    make = granite_hybrid.weight_maker(cfg)

    def tree(key):
        return to_program(make(key))

    want = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    got = jax.eval_shape(tree, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the weights' layout differs from the program's")
    params = jax.jit(tree)(granite_hybrid.seed_key(seed))
    return jax.block_until_ready(params)


def _counter() -> float:
    from repro.obs.metrics import default_registry

    return default_registry().counter(COUNTER).value


def setup(cell, seed: int):
    """The serving driver's set-up, then the decode program's map from
    op to named scope (its compiled HLO; the program is compiled by now,
    so this loads it again)."""
    state = base.setup(cell, seed)
    eng = next(iter(state.engines.values()))
    hlo = eng._decode.lower(eng.params, eng.state,
                            eng._tokens).compile().as_text()
    state.scope_of = scopes.op_scopes(hlo)
    print(f"[setup] {len(state.scope_of)} decode ops in the "
          f"ssm, moe and attn scopes", file=sys.stderr)
    return state


def window(state, seconds: float, capture) -> Dict[str, Any]:
    """The serving driver's window; a traced one also records the held
    experts its decode steps touched and, once the trace is read, the
    device seconds of each scope of the decode program (printed to stderr
    with the programs' device seconds)."""
    scope_of = getattr(state, "scope_of", {})
    touched: Dict[str, float] = {}
    if capture is not None:
        start, stop, events = capture.start, capture.stop, capture.events

        def start_w():
            touched["start"] = _counter()
            start()

        def stop_w():
            stop()
            touched["stop"] = _counter()

        capture.start, capture.stop = start_w, stop_w
    data = base.window(state, seconds, capture)
    if capture is not None:
        data["traced"]["experts_touched"] = (touched["stop"]
                                             - touched["start"])

        def events_w():
            trace = events()
            data["scope_s"] = scopes.scope_seconds(trace, DECODE_PROGRAM,
                                                   scope_of)
            steps = len(data["traced"]["decode"])
            summary = tracing.summarize(trace)
            if summary is not None:
                print("[trace] device s (calls) per program: " + ", ".join(
                    f"{k} {v:.4f} ({summary.program_n[k]})" for k, v in
                    sorted(summary.program_s.items(), key=lambda kv: -kv[1])
                    [:4]), file=sys.stderr)
            print(f"[trace] {steps} decode steps, "
                  f"{data['traced']['experts_touched']:.0f} held experts "
                  f"touched; device s per scope of {DECODE_PROGRAM}: "
                  + ", ".join(f"{k} {v:.4f}" for k, v in
                              sorted(data["scope_s"].items())),
                  file=sys.stderr)
            return trace

        capture.events = events_w
    return data


def _sample(state, run) -> List[Any]:
    """Requests to compare: ``documents`` of them with document prompts
    that served tokens in the window (finished in it or in flight at its
    close), the longest first, then a draw from the seed; the rest from
    the chat requests sent and finished in the window, the longest first,
    then a draw."""
    chk = state.traffic["check"]
    t0, t1 = run.data["t0"], run.data["t1"]
    rng = np.random.default_rng([state.seed, 2])
    doc = chk["document_min_prompt"]

    def draw(pool, k):
        if not pool or k <= 0:
            return []
        longest = max(pool, key=lambda r: len(r.request.prompt)
                      + len(r.request.output))
        rest = [r for r in pool if r is not longest]
        idx = rng.permutation(len(rest))[:k - 1]
        return [longest] + [rest[i] for i in sorted(idx)]

    docs = [r for r in state.reqs if len(r.request.prompt) >= doc
            and r.request.output and (r.t_done is None or r.t_done > t0)]
    chat = [r for r in state.reqs[run.data["first_req"]:]
            if len(r.request.prompt) < doc
            and r.t_done is not None and r.t_done <= t1]
    picked = draw(docs, chk["documents"])
    return picked + draw(chat, chk["requests"] - len(picked))


def readings(state, run, control: bool = False) -> Dict[str, Any]:
    """The serving driver's readings (``drivers/serve.py``) against this
    family's reference, with the documents compared counted."""
    import jax

    from reference import granite_hybrid

    chk = state.traffic["check"]
    sample = _sample(state, run)
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
    ref = granite_hybrid.served_logits(
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
