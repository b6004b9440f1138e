"""Batched LLM serving engine with tiered placement and MIKU admission
control — the TPU deployment of the paper's §6 case study.

Architecture
------------
* :class:`ServingEngine` — one model instance: continuous batching over a
  fixed slot array, real jitted prefill/decode steps, per-slot lengths.
  The engine keeps each slot's length on the host too (a mirror of the
  state's ``length``), so a decode step makes one device read: the sampled
  tokens, with the state's experts-touched counts where it carries them.
  The instance's *placement* decides which memory tier its weights live
  on: ``device`` (HBM — the DDR analogue) or ``host`` (pinned host memory
  over PCIe — the CXL analogue).  Host-placed weights are put on the
  device's ``pinned_host`` memory space; a device without one is refused.

* :class:`TieredServingCluster` — co-locates several engines on one chip's
  shared transfer path (:class:`repro.core.offload.TransferQueue`).  Every
  decode step charges the queue its tier traffic: HBM-resident steps
  account fast-tier bytes (weights + KV read once per token — the
  memory-bound decode reality); host-resident steps *submit* their weight/KV
  stream as slow-tier transfers on the queue's "slow" link.  A MIKU
  controller attached to the queue watches the same per-tier Little's-Law
  counters (the :class:`~repro.core.littles_law.TierWindow` vector
  contract) as on the x86 platforms and throttles each slow link's
  concurrency via tier-addressed decisions — reproducing Figure 11/12's
  DataRacing -> MIKU recovery end to end with real model math and modeled
  PCIe timing (ARCHITECTURE.md).

The cluster's clock, and every ``wall_ns`` and ``tokens_per_s`` it reports,
is the simulated transfer-queue clock, not the device's; model outputs
(tokens) are real.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import MikuController
from repro.core.littles_law import OpClass
from repro.core.offload import TransferQueue
from repro.core.tiers import HBM_TIER, HOST_TIER, require_host_offload
from repro.models.transformer import DecodeState, ModelConfig, TransformerLM
from repro.obs.metrics import default_registry
from repro.serving import sampler as sampler_lib


def _read(x: Any) -> Any:
    """One device -> host read, counted by ``serving.host_reads``."""
    default_registry().counter("serving.host_reads").inc()
    return jax.device_get(x)


def kv_bytes_per_token(state: DecodeState) -> int:
    """Bytes of one slot's per-position cache at one position, summed over
    the state's ``kv`` leaves ([L, B, S_max, ...]): K and V of the
    attention layers, or MLA's latent and rope key."""
    return sum(x.size // (x.shape[1] * x.shape[2]) * x.dtype.itemsize
               for x in jax.tree.leaves(state.kv))


def insert_slot(state: DecodeState, state1: DecodeState, slot: jax.Array,
                plen: jax.Array) -> DecodeState:
    """``state`` with slot ``slot`` set to the one-slot prefill state
    ``state1`` of a ``plen``-token prompt.  Jitted with ``state`` donated,
    it writes that slot where the state lies, and every other slot is left
    as it was."""
    def put(dst, src):
        return jax.lax.dynamic_update_slice_in_dim(dst, src.astype(dst.dtype),
                                                   slot, axis=1)

    kv, ssm = state.kv, state.ssm
    if kv is not None:
        kv = {k: put(kv[k], state1.kv[k]) for k in kv}
    if ssm is not None:
        ssm = {k: put(ssm[k], state1.ssm[k]) for k in ssm}
    return DecodeState(kv=kv, ssm=ssm, cross_kv=state.cross_kv,
                       length=state.length.at[slot].set(plen),
                       experts_touched=state.experts_touched)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    arrival_ns: float = 0.0
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    name: str
    model: ModelConfig
    max_slots: int = 8
    max_len: int = 1024
    placement: str = "device"  # "device" | "host" (weights+KV tier)
    sampler: str = "greedy"
    #: serve_step bytes model: fraction of weight bytes actually streamed
    #: per decode step (1.0 = classic memory-bound decode).
    weight_stream_fraction: float = 1.0
    #: host-tier transfer chunks per decode step (None => 2 x n_layers:
    #: one weight + one KV chunk per layer).
    stream_chunks: Optional[int] = None


class ServingEngine:
    """One model instance with continuous batching.

    ``kv_pagemap`` (optional) hands KV-cache offload placement to the
    tiering subsystem: a :class:`repro.tiering.pagemap.PageMap` carrying a
    region named after this engine.  Instead of the all-or-nothing
    ``placement`` split, each decode step's KV bytes divide between the HBM
    path and the host link by the region's *live* access-weighted tier
    fractions — so promoting hot KV pages genuinely moves their stream off
    the slow link mid-run.  The engine feeds the region one access sample
    per decoded token (station accounting, same contract as the DES hook).
    """

    def __init__(self, cfg: EngineConfig, params: Any, *,
                 rng: Optional[jax.Array] = None, kv_pagemap: Any = None):
        self.cfg = cfg
        self.kv_pagemap = kv_pagemap
        self.model = TransformerLM(cfg.model)
        self.params = params
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._place_state()
        self.state = self.model.init_decode_state(cfg.max_slots, cfg.max_len)
        self.slot_req: List[Optional[Request]] = [None] * cfg.max_slots
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self._tokens = jnp.zeros((cfg.max_slots,), jnp.int32)
        self._active = np.zeros((cfg.max_slots,), bool)
        #: host mirror of ``state.length``: set at admission, +1 per step
        self._lengths = np.zeros((cfg.max_slots,), np.int32)
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))
        self._insert = jax.jit(insert_slot, donate_argnums=(0,))
        self._prefill_cache: Dict[int, Callable] = {}

        # Tier accounting constants, from the state's own leaves: the rows
        # of the per-position cache (K/V of attention layers, or MLA's
        # latent and rope key) per token, and the fixed-size recurrent
        # state (SSM ``h`` and conv window) of the Mamba layers per slot.
        self.param_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(self.params)
        )
        self.kv_bytes_per_token = kv_bytes_per_token(self.state)
        self.ssm_bytes_per_slot = sum(
            x.size // x.shape[1] * x.dtype.itemsize
            for x in jax.tree.leaves(self.state.ssm)
        )

    def _place_state(self) -> None:
        if self.cfg.placement == "host":
            dev = jax.devices()[0]
            require_host_offload(dev)
            host_sh = jax.sharding.SingleDeviceSharding(
                dev, memory_kind=HOST_TIER.memory_kind
            )
            self.params = jax.device_put(self.params, host_sh)
            self._device_sh = jax.sharding.SingleDeviceSharding(
                dev, memory_kind=HBM_TIER.memory_kind
            )

    def step_params(self) -> Any:
        """Working copy of the weights for one step.  Host-placed
        instances FETCH them device-ward — the PCIe stream the transfer
        queue charges (a TPU build would pipeline this per-layer inside the
        step; the aggregate bytes are identical)."""
        if self.cfg.placement == "host":
            return jax.device_put(self.params, self._device_sh)
        return self.params

    # -- request lifecycle ---------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _prefill_fn(self, plen: int) -> Callable:
        if plen not in self._prefill_cache:
            model = self.model

            def fn(params, tokens):
                state1 = model.init_decode_state(1, self.cfg.max_len)
                return model.prefill(params, tokens, state1)

            self._prefill_cache[plen] = jax.jit(fn)
        return self._prefill_cache[plen]

    def _insert_state(self, slot: int, state1: DecodeState,
                      plen: int) -> None:
        self.state = self._insert(self.state, state1, jnp.int32(slot),
                                  jnp.int32(plen))
        self._lengths[slot] = plen

    def admit(self, now_ns: float) -> List[Tuple[Request, int]]:
        """Prefill queued requests into free slots.  Returns admissions
        (request, prompt_bytes_touched) for tier accounting."""
        admitted = []
        for slot in self._free_slots():
            if not self.queue:
                break
            req = self.queue.pop(0)
            plen = len(req.prompt)
            tokens = jnp.asarray(req.prompt, jnp.int32)[None, :]
            logits, state1 = self._prefill_fn(plen)(self.step_params(), tokens)
            first = self._sample(logits)
            req.output.append(int(_read(first)[0]))
            req.t_first_token = now_ns
            self._insert_state(slot, state1, plen)
            self._tokens = self._tokens.at[slot].set(first[0])
            self.slot_req[slot] = req
            self._active[slot] = True
            admitted.append((req, plen * self.kv_bytes_per_token
                             + self.ssm_bytes_per_slot))
        return admitted

    def _sample(self, logits: jax.Array) -> jax.Array:
        if self.cfg.sampler == "greedy":
            return sampler_lib.greedy(logits)
        self.rng, sub = jax.random.split(self.rng)
        return sampler_lib.temperature(logits, sub)

    @property
    def n_active(self) -> int:
        return int(self._active.sum())

    def step_bytes(self) -> Tuple[int, int]:
        """(weight_bytes, kv_bytes) one decode step streams: the K/V bytes
        include each active slot's recurrent state, read and written.
        Lengths come from the host mirror: no device read."""
        wb = int(self.param_bytes * self.cfg.weight_stream_fraction)
        live = int(self._lengths[self._active].sum(dtype=np.int64))
        kvb = (live * self.kv_bytes_per_token
               + 2 * self.ssm_bytes_per_slot * self.n_active)
        return wb, kvb

    def kv_tier_bytes(self, kv_bytes: int) -> Tuple[int, int]:
        """Split one step's KV stream into (fast_bytes, slow_bytes).

        Without a PageMap the split follows the static placement (the
        pre-tiering behavior, bit-for-bit).  With one, the engine's KV
        region decides: its access-weighted fast fraction stays on HBM and
        only the slow remainder crosses the host link."""
        if self.kv_pagemap is None or self.cfg.name not in getattr(
            self.kv_pagemap, "regions", {}
        ):
            if self.cfg.placement == "host":
                return 0, kv_bytes
            return kv_bytes, 0
        self.kv_pagemap.record_window(self.cfg.name, float(self.n_active))
        fast = self.kv_pagemap.fast_fraction(self.cfg.name)
        fast_bytes = int(kv_bytes * fast)
        return fast_bytes, kv_bytes - fast_bytes

    def decode_once(self, now_ns: float) -> int:
        """One real decode step for all active slots.  Returns #tokens.

        One device read: the sampled tokens, and the step's per-layer count
        of held experts its tokens were routed to where the state carries
        it (the ``moe.experts_touched`` counter)."""
        if self.n_active == 0:
            return 0
        logits, self.state = self._decode(self.step_params(), self.state,
                                          self._tokens)
        nxt = self._sample(logits)
        self._tokens = nxt
        self._lengths += 1  # decode_step's length + 1, on every slot
        touched = self.state.experts_touched
        if touched is None:
            toks = _read(nxt)
        else:
            toks, touched = _read((nxt, touched))
            default_registry().counter("moe.experts_touched").inc(
                float(np.sum(touched)))
        produced = 0
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            req.output.append(int(toks[slot]))
            produced += 1
            done = len(req.output) >= req.max_new_tokens
            overflow = self._lengths[slot] >= self.cfg.max_len - 1
            if done or overflow:
                req.t_done = now_ns
                self.done.append(req)
                self.slot_req[slot] = None
                self._active[slot] = False
                # Slot length is reset on next admit's insert.
        return produced

    @property
    def finished(self) -> bool:
        return not self.queue and self.n_active == 0


class TieredServingCluster:
    """Co-located engines sharing one chip's transfer path + MIKU control.

    ``run`` drives all engines until completion: per simulated tick every
    engine that is *admissible* takes one decode step; host-placed engines
    must first get their weight/KV stream admitted by the transfer queue —
    whose in-flight cap and rate are MIKU's decision.  Step durations come
    from the tier bandwidth model (decode is bandwidth-bound, paper §6).
    """

    def __init__(
        self,
        engines: List[ServingEngine],
        *,
        controller: Optional[MikuController] = None,
        window_ns: float = 2e6,
        hbm_bw: float = HBM_TIER.bandwidth_gbps,  # B/ns per chip
        trace: int = 0,
    ):
        self.engines = engines
        self.queue = TransferQueue(
            controller=controller, window_ns=window_ns, trace=trace
        )
        #: The cluster's control plane is the transfer queue's ControlLoop —
        #: same substrate interface as the DES and the launcher.
        self.control = self.queue.control
        self.hbm_bw = hbm_bw
        self.timeline: List[Dict[str, float]] = []
        self._host_busy_until: Dict[str, float] = {
            e.cfg.name: 0.0 for e in engines
        }

    def run(self, max_ticks: int = 10_000) -> Dict[str, Dict[str, float]]:
        q = self.queue
        tick = 0
        produced: Dict[str, int] = {e.cfg.name: 0 for e in self.engines}
        started: Dict[str, Optional[float]] = {
            e.cfg.name: None for e in self.engines
        }
        finished_at: Dict[str, float] = {e.cfg.name: 0.0 for e in self.engines}
        while tick < max_ticks and not all(e.finished for e in self.engines):
            tick += 1
            fast_time = 0.0
            for eng in self.engines:
                eng.admit(q.now)
                if eng.n_active == 0:
                    continue
                name = eng.cfg.name
                if started[name] is None:
                    started[name] = q.now
                wb, kvb = eng.step_bytes()
                if eng.cfg.placement == "host":
                    # One decode step = one weight/KV stream over the slow
                    # tier, submitted as per-layer chunks.  Uncapped, the
                    # chunk backlog floods the shared descriptor pool (the
                    # unfair-queuing mechanism); a MIKU cap bounds it at no
                    # throughput cost (chunks still saturate the link).
                    if q.now < self._host_busy_until[name]:
                        continue
                    n_chunks = (eng.cfg.stream_chunks
                                or 2 * eng.cfg.model.n_layers)
                    # A KV PageMap routes the hot share of the KV stream
                    # over HBM; only the slow remainder crosses the link.
                    # The HBM share costs exactly what it would cost an
                    # hbm-placed engine (fast_penalty included) and the
                    # step completes only when both paths have.
                    kv_fast, kv_slow = eng.kv_tier_bytes(kvb)
                    fast_dur = 0.0
                    if kv_fast:
                        fast_dur = kv_fast / self.hbm_bw * q.fast_penalty()
                        q.account_fast(kv_fast, fast_dur, OpClass.LOAD)
                        fast_time += fast_dur
                    done_t = q.submit_slow_stream(wb + kv_slow, n_chunks,
                                                  OpClass.LOAD, tier="slow")
                    done_t = max(done_t, q.now + fast_dur)
                    self._host_busy_until[name] = done_t
                    n = eng.decode_once(done_t)
                    finished_at[name] = done_t
                else:
                    dur = (wb + kvb) / self.hbm_bw * q.fast_penalty()
                    q.account_fast(wb + kvb, dur, OpClass.LOAD)
                    fast_time += dur
                    n = eng.decode_once(q.now + dur)
                    finished_at[name] = q.now + dur
                produced[name] += n
            # Advance the clock by the fast-tier step time (engines on HBM
            # run back-to-back; host engines progress via queue completions).
            # With no HBM step this tick, jump to the next host engine's
            # completion: a full-width host step spans ~10^5 idle 1 µs ticks.
            step = fast_time or min(
                (t for t in self._host_busy_until.values() if t > q.now),
                default=q.now,
            ) - q.now
            q.advance(max(step, 1e3))
            self.timeline.append(
                {"t_ns": q.now,
                 "slow_backlog": float(q.slow_backlog()),
                 **{f"tok_{k}": float(v) for k, v in produced.items()}}
            )
        out: Dict[str, Dict[str, float]] = {}
        reg = default_registry()
        for eng in self.engines:
            name = eng.cfg.name
            toks = sum(len(r.output) for r in eng.done)
            t0 = started[name] or 0.0
            span = max(finished_at[name] - t0, 1.0)
            out[name] = {
                "tokens": float(toks),
                "wall_ns": span,
                "tokens_per_s": toks / span * 1e9,
                "requests": float(len(eng.done)),
            }
            reg.counter("serving.tokens").inc(float(toks))
            reg.counter("serving.requests").inc(float(len(eng.done)))
        return out
