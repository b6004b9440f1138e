"""Driver for tiered serving: ``TieredServingCluster`` ticked from the
harness with a closed loop of clients, one per engine slot.

Configuration: a decoder's Hugging Face ``config.json`` keys (``counts.py``
reads the same), ``qkv_bias`` and ``limits``.  Traffic keys:

* ``engines``: ``[{"name", "placement", "clients"}]``, one engine each,
  with as many slots as clients; ``miku`` puts the MIKU controller on the
  cluster's transfer queue, as ``repro.launch.serve.build_cluster`` does;
* ``max_len``, ``stream_chunks``;
* ``prompt_lengths`` with ``prompt_counts`` and ``output_min``/``output_max``:
  one block of ``sum(prompt_counts)`` request sizes (the prompt lengths in
  those counts, the outputs at evenly spaced quantiles of the log-uniform
  law), which every client walks in its own order drawn from the seed;
* ``check``: how many requests the check compares (``requests``, of them
  ``host_requests`` from host-placed engines; see ``_sample``) and the
  fewest served tokens they must hold (``min_tokens``);
* ``post_window_s``: how long requests sent in the window may wait for
  their first token after it closes; ``trace_seconds``: how much of a
  traced window the profiler records.

A client sends its next request as soon as its previous one finishes.  The
harness stamps each request on the host clock: submitted, first token
seen, done, after each tick (``cluster.run(max_ticks=1)``).  Set-up fills
every slot, the first requests of each engine covering every prompt
length, so the prefill of each length, the decode step of each engine and
every slot's insert compile before the window.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

import counts
import tracing


@dataclasses.dataclass
class Req:
    engine: str
    request: Any
    t_sub: float
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    counted: int = 0


@dataclasses.dataclass
class Client:
    engine: Any
    index: int
    order: np.ndarray
    sent: int = 0
    pending: Optional[Req] = None


@dataclasses.dataclass
class State:
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    sizes: List[tuple]
    cluster: Any
    engines: Dict[str, Any]
    clients: List[Client]
    live: Dict[str, List[Req]]
    reqs: List[Req]
    record: Dict[str, list]
    steps: Dict[str, Dict[str, int]] = dataclasses.field(default_factory=dict)
    probes: List[tuple] = dataclasses.field(default_factory=list)
    next_rid: int = 0


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a Qwen2-style config file."""
    import jax.numpy as jnp

    from repro.models.transformer import ModelConfig

    m = counts.dims(cfg)
    return ModelConfig(
        name=cfg["name"], n_layers=m["layers"], d_model=m["d"],
        n_q_heads=m["hq"], n_kv_heads=m["hkv"], head_dim=m["dh"],
        d_ff=m["ff"], vocab=m["vocab"], block="dense",
        rope_theta=float(cfg["rope_theta"]), qkv_bias=m["bias"],
        tied_embeddings=m["tied"], activation="silu", norm="rms",
        dtype=getattr(jnp, cfg.get("torch_dtype", "bfloat16")),
    )


def program_params(cfg: Dict[str, Any], seed: int, model):
    """The seed's weights (``reference.qwen2.weight_maker``) in the
    program's layout, made on the device in one jitted call.  The program
    scales its RMSNorms by ``1 + w``, so a scale of 1 is stored as 0."""
    import jax

    from reference import qwen2

    make = qwen2.weight_maker(cfg)

    def tree(key):
        w = make(key)
        attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")}
        return {
            "embed": w["embed"],
            "layers": {
                "attn": attn,
                "pre_attn_norm": w["ln1"] - 1,
                "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
                "pre_mlp_norm": w["ln2"] - 1,
            },
            "final_norm": w["norm"] - 1,
        }

    want = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    got = jax.eval_shape(tree, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the weights' layout differs from the program's")
    params = jax.jit(tree)(qwen2.seed_key(seed))
    return jax.block_until_ready(params)


def request_sizes(tr: Dict[str, Any]) -> List[tuple]:
    """The block of (prompt, output) sizes every client walks."""
    prompts = [p for p, n in zip(tr["prompt_lengths"], tr["prompt_counts"])
               for _ in range(n)]
    n = len(prompts)
    lo, hi = math.log(tr["output_min"]), math.log(tr["output_max"])
    outs = [int(round(math.exp(lo + (hi - lo) * (i + 0.5) / n)))
            for i in range(n)]
    return list(zip(prompts, outs))


def _controller(host_engine, stream_chunks: int):
    """MIKU on the transfer queue, as ``launch.serve.build_cluster`` sets
    it: rungs 1-2-4-8, the slow-read threshold at 8 chunk services."""
    from repro.core.controller import MikuConfig, MikuController
    from repro.core.littles_law import EstimatorConfig

    chunk_service = host_engine.param_bytes / stream_chunks / 16.0
    return MikuController(
        MikuConfig(levels=(1, 2, 4, 8)),
        EstimatorConfig(t_fast=1.2e3, slow_read_threshold=8 * chunk_service,
                        min_window_inserts=4, min_slow_inserts=1),
    )


def _send(state: State, client: Client, now: float, prompt_len=None,
          n_out=None) -> None:
    from repro.serving.engine import Request

    block = state.sizes
    plen, n = block[int(client.order[client.sent % len(block)])]
    plen = prompt_len or plen
    n_out = n_out or n
    rng = np.random.default_rng([state.seed, client.index, client.sent])
    prompt = rng.integers(0, int(state.cfg["vocab_size"]), plen).tolist()
    req = Request(rid=state.next_rid, prompt=prompt, max_new_tokens=n_out)
    state.next_rid += 1
    client.sent += 1
    client.engine.submit(req)
    r = Req(engine=client.engine.cfg.name, request=req, t_sub=now)
    state.live[r.engine].append(r)
    state.reqs.append(r)
    client.pending = r


def _instrument(state: State) -> None:
    """Spans around each engine's admit, fetch and decode; a record of
    each admission's prompt length and each decode step's live lengths
    (for the counts); and
    the largest logit of each row the engine samples a token from, kept
    on the device with the request and output index it served."""
    import jax.numpy as jnp

    rec = state.record
    for eng in state.engines.values():
        name = eng.cfg.name
        admit, decode, fetch = eng.admit, eng.decode_once, eng.step_params
        sample = eng._sample
        state.steps[name] = {"decode": 0, "admit_after_decode": 0}
        admitting: List[Any] = []

        def sample_w(logits, _f=sample, _e=eng, _a=admitting):
            tok = _f(logits)
            if _a:  # a prefill: the next queued request's first token
                rows = [(_a.pop(0), 0, 0)]
            else:
                rows = [(r, len(r.output), s)
                        for s, r in enumerate(_e.slot_req) if r is not None]
            state.probes.append((jnp.max(logits, axis=-1), rows))
            return tok

        def admit_w(now_ns, _f=admit, _e=eng, _n=name, _a=admitting):
            _a[:] = list(_e.queue)
            with tracing.span("admit." + _n):
                out = _f(now_ns)
            _a.clear()
            rec["prefill"].extend(len(r.prompt) for r, _ in out)
            if out and state.steps[_n]["decode"]:
                state.steps[_n]["admit_after_decode"] += 1
            return out

        def decode_w(now_ns, _f=decode, _e=eng, _n=name):
            lengths = [len(r.prompt) + len(r.output) - 1
                       for r in _e.slot_req if r is not None]
            if lengths:
                rec["decode"].append(lengths)
                state.steps[_n]["decode"] += 1
            with tracing.span("decode." + _n):
                return _f(now_ns)

        def fetch_w(_f=fetch, _n=name):
            with tracing.span("fetch." + _n):
                return _f()

        eng.admit, eng.decode_once, eng.step_params = admit_w, decode_w, \
            fetch_w
        eng._sample = sample_w


def setup(cell, seed: int) -> State:
    from repro.models.transformer import TransformerLM
    from repro.serving.engine import (
        EngineConfig,
        ServingEngine,
        TieredServingCluster,
    )

    cfg, tr = cell.config, cell.traffic
    mcfg = model_config(cfg)
    params = program_params(cfg, seed, TransformerLM(mcfg))
    engines = {}
    for e in tr["engines"]:
        engines[e["name"]] = ServingEngine(
            EngineConfig(name=e["name"], model=mcfg, max_slots=e["clients"],
                         max_len=tr["max_len"], placement=e["placement"],
                         stream_chunks=tr["stream_chunks"]),
            params,
        )
    del params
    host = [e for e in engines.values() if e.cfg.placement == "host"]
    controller = (_controller(host[0], tr["stream_chunks"])
                  if tr.get("miku") and host else None)
    cluster = TieredServingCluster(list(engines.values()),
                                   controller=controller, window_ns=3e4)
    rng = np.random.default_rng([seed, 0])
    sizes = request_sizes(tr)
    clients = []
    for e in tr["engines"]:
        for _ in range(e["clients"]):
            clients.append(Client(engine=engines[e["name"]],
                                  index=len(clients),
                                  order=rng.permutation(len(sizes))))
    state = State(cfg=cfg, traffic=tr, seed=seed, sizes=sizes,
                  cluster=cluster, engines=engines, clients=clients,
                  live={n: [] for n in engines}, reqs=[],
                  record={"prefill": [], "decode": []})
    _instrument(state)
    # Fill every slot; each engine's first requests cover every length.
    # Its first client's first request stops after two tokens, so that an
    # admission follows the engine's first decode step: a decode step's
    # outputs can carry another placement than fresh arrays, and the
    # programs that take them then compile anew (host-placed engines).
    lengths = tr["prompt_lengths"]
    per_engine: Dict[str, int] = {}
    now = time.perf_counter()
    for c in clients:
        k = per_engine.get(c.engine.cfg.name, 0)
        per_engine[c.engine.cfg.name] = k + 1
        _send(state, c, now, prompt_len=lengths[k % len(lengths)],
              n_out=2 if k == 0 else None)
    while not all(v["decode"] >= 2 and v["admit_after_decode"]
                  for v in state.steps.values()):
        cluster.run(max_ticks=1)
        _harvest(state, time.perf_counter(), resend=True)
    for v in state.record.values():
        v.clear()
    return state


def _harvest(state: State, now: float, resend: bool) -> Dict[str, int]:
    """Stamp what the last tick produced; return new tokens per engine."""
    new: Dict[str, int] = {}
    for name, live in state.live.items():
        keep, n = [], 0
        for r in live:
            out = len(r.request.output)
            n += out - r.counted
            r.counted = out
            if out and r.t_first is None:
                r.t_first = now
            if r.request.t_done is not None:
                r.t_done = now
            else:
                keep.append(r)
        state.live[name] = keep
        new[name] = n
    if resend:
        for c in state.clients:
            if c.pending.t_done is not None:
                _send(state, c, now)
    return new


def _traced(state: State, tokens: Dict[str, int], host: List[str],
            seconds: float) -> Dict[str, Any]:
    """The counts of the traced part of a window."""
    return {"seconds": seconds,
            "host_tokens": sum(tokens[n] for n in host),
            **{k: list(v) for k, v in state.record.items()}}


def window(state: State, seconds: float, capture) -> Dict[str, Any]:
    """Tick for ``seconds``; a ``capture`` traces the first
    ``trace_seconds`` of it, and the counts of that part are kept apart
    (stopping the profiler holds the loop while it writes the trace)."""
    cluster = state.cluster
    n_before = len(state.reqs)
    tokens = {name: 0 for name in state.engines}
    traced = None
    trace_s = state.traffic.get("trace_seconds", seconds)
    host = sorted(n for n, e in state.engines.items()
                  if e.cfg.placement == "host")
    if capture is not None:
        capture.start()
    t0 = time.perf_counter()
    while True:
        with tracing.span("tick"):
            cluster.run(max_ticks=1)
        now = time.perf_counter()
        for name, n in _harvest(state, now, resend=True).items():
            tokens[name] += n
        if capture is not None and traced is None and now - t0 >= trace_s:
            capture.stop()
            traced = _traced(state, tokens, host, now - t0)
        if now - t0 >= seconds:
            break
    t1 = time.perf_counter()
    if capture is not None and traced is None:
        capture.stop()
        traced = _traced(state, tokens, host, t1 - t0)
    window_reqs = state.reqs[n_before:]
    # Requests sent in the window wait for their first token past its close.
    fast = [r for r in window_reqs if r.engine not in host]
    deadline = t1 + state.traffic["post_window_s"]
    while (any(r.t_first is None for r in fast)
           and time.perf_counter() < deadline):
        cluster.run(max_ticks=1)
        _harvest(state, time.perf_counter(), resend=False)
    return {
        "window_s": t1 - t0,
        "t0": t0,
        "t1": t1,
        "first_req": n_before,
        "tokens": sum(tokens.values()),
        "host_engines": host,
        "attempted": len(window_reqs),
        "failed": sum(r.t_first is None for r in fast),
        "fast": fast,
        "cfg": state.cfg,
        "traced": traced,
    }


def _sample(state: State, run) -> List[Req]:
    """Requests to compare, each the longest of its pool and then a draw
    from the seed: ``host_requests`` from host-placed engines, of those
    that served tokens in the window (finished in it or in flight at its
    close: a host-placed step fetches every weight, so few finish), and
    the rest from requests sent and finished in the window."""
    chk = state.traffic["check"]
    host_names = {n for n, e in state.engines.items()
                  if e.cfg.placement == "host"}
    t0, t1 = run.data["t0"], run.data["t1"]
    rng = np.random.default_rng([state.seed, 2])

    def draw(pool, k):
        if not pool or k <= 0:
            return []
        longest = max(pool, key=lambda r: len(r.request.prompt)
                      + len(r.request.output))
        rest = [r for r in pool if r is not longest]
        idx = rng.permutation(len(rest))[:k - 1]
        return [longest] + [rest[i] for i in sorted(idx)]

    host = [r for r in state.reqs if r.engine in host_names
            and r.request.output and (r.t_done is None or r.t_done > t0)]
    fast = [r for r in state.reqs[run.data["first_req"]:]
            if r.engine not in host_names
            and r.t_done is not None and r.t_done <= t1]
    picked = draw(host, chk["host_requests"])
    return draw(fast, chk["requests"] - len(picked)) + picked


def _program_tops(state: State, reqs) -> Dict[int, np.ndarray]:
    """The program's top logit at each served token of ``reqs``."""
    want = {id(r.request): np.full(len(r.request.output), np.nan)
            for r in reqs}
    for peak, rows in state.probes:
        hits = [(id(q), i, row) for q, i, row in rows if id(q) in want]
        if hits:
            host = np.asarray(peak, np.float64)
            for key, i, row in hits:
                if i < len(want[key]):
                    want[key][i] = host[row]
    return want


def readings(state: State, run, control: bool = False) -> Dict[str, Any]:
    """The numbers compared, for the program and (``control``) for the
    fp8 reference at the same positions: the widest gap by which a served
    token's reference logit lies below the reference's best, and the
    widest distance between the program's top logit (that of the token it
    served) and the reference's logit of that token.  Frees the program's
    state first: the reference needs the chip's memory."""
    import jax

    from reference import qwen2

    sample = _sample(state, run)
    host_names = {n for n, e in state.engines.items()
                  if e.cfg.placement == "host"}
    n_host = sum(r.engine in host_names for r in sample)
    tops = _program_tops(state, sample)
    seqs = [(list(r.request.prompt), list(r.request.output)) for r in sample]
    prog = [tops[id(r.request)] for r in sample]
    state.cluster = state.engines = state.clients = None
    state.live = {}
    state.reqs = []
    state.probes = []
    run.data.pop("fast", None)
    gc.collect()
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    print(f"[check] {len(seqs)} requests, "
          f"{sum(len(o) for _, o in seqs)} served tokens; device bytes in "
          f"use before the reference: {in_use}", file=sys.stderr)
    ref = qwen2.served_logits(state.cfg, state.seed, seqs,
                              max_len=state.traffic["max_len"],
                              control=control)

    def widest(xs):
        xs = np.concatenate(xs) if xs else np.array([math.inf])
        return float(np.max(np.where(np.isnan(xs), math.inf, xs)))

    out = {
        "served_tokens_compared": sum(len(o) for _, o in seqs),
        "host_requests_compared": n_host,
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


def check(state: State, run) -> List[Dict[str, Any]]:
    r = readings(state, run)
    lim = state.cfg["limits"]
    need = state.traffic["check"]["min_tokens"]
    out = [{"name": k, "value": r[k], "limit": lim[k], "ok": r[k] <= lim[k]}
           for k in ("max_logit_gap", "max_logit_err")]
    out.append({"name": "served_tokens_compared",
                "value": r["served_tokens_compared"], "limit": need,
                "ok": r["served_tokens_compared"] >= need})
    n_host = state.traffic["check"]["host_requests"]
    if n_host:
        out.append({"name": "host_requests_compared",
                    "value": r["host_requests_compared"], "limit": n_host,
                    "ok": r["host_requests_compared"] >= n_host})
    return out
