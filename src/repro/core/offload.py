"""Host-offload runtime: real JAX tier transfers + the simulated transfer clock.

Two cooperating pieces:

* :class:`HostOffloader` — executes *real* JAX device<->host transfers
  (``jax.device_put`` with memory-kind shardings) with double-buffered
  prefetch.  JAX's async dispatch gives natural overlap; ``block()`` fences.
  A backend without a pinned_host space is refused at construction.

* :class:`TransferQueue` — the *timing* model of the shared transfer path
  (per-chip DMA descriptors): a simulated clock charging each transfer its
  tier service time, with bounded in-flight slots.  This is the structure
  MIKU instruments (per-tier TierCounters) and throttles (per-tier max
  in-flight + byte-rate), exactly like the DES's ToR — but driven by the
  serving engine's actual request stream instead of synthetic cores.  The
  queue speaks the vector control-plane contract: ``counters_delta()``
  returns the per-tier :class:`~repro.core.littles_law.TierWindow` (fast
  tier first) and ``apply`` accepts tier-addressed
  :class:`~repro.core.controller.TierDecisions`, so each slow link (the
  default pinned-host path, plus any ``extra_slow`` tiers) gets its own
  in-flight cap and byte-rate.  On real TPU hardware this class would be
  replaced by reading transfer-completion timestamps from the runtime; the
  control law is unchanged (ARCHITECTURE.md).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.controller import Decision, MikuController, TierDecisions
from repro.core.device_model import UnknownTierError
from repro.core.invariants import sanitize_enabled
from repro.core.littles_law import OpClass, TierCounters, TierWindow
from repro.core.substrate import ControlLoop, TierSetWindowedCounters
from repro.core.tiers import (
    HBM_TIER,
    HOST_TIER,
    TierSpec,
    require_host_offload,
)


class HostOffloader:
    """Real JAX transfers between the device tier and the host tier."""

    def __init__(self, sharding: Optional[jax.sharding.Sharding] = None):
        if sharding is None:
            sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        require_host_offload(next(iter(sharding.device_set)))
        self._host_sharding = sharding.with_memory_kind(HOST_TIER.memory_kind)
        self._device_sharding = sharding.with_memory_kind(HBM_TIER.memory_kind)

    def to_host(self, tree: Any) -> Any:
        """Offload a pytree to the host tier (async)."""
        return jax.device_put(tree, self._host_sharding)

    def to_device(self, tree: Any) -> Any:
        """Fetch a pytree back into HBM (async)."""
        return jax.device_put(tree, self._device_sharding)

    @staticmethod
    def block(tree: Any) -> Any:
        return jax.block_until_ready(tree)


@dataclasses.dataclass
class _InFlight:
    nbytes: int
    op: OpClass
    tier: str
    t_enqueue: float
    t_complete: float


class TransferQueue:
    """Simulated shared transfer path with MIKU instrumentation + control.

    ``submit`` charges a transfer; the clock is advanced by the engine
    (``advance``).  Fast-tier traffic (HBM reads/writes of the step itself)
    is reported via ``account_fast`` so the controller sees the same two-tier
    picture as on the x86 platforms.

    The queue is a :class:`~repro.core.substrate.MemorySubstrate`: a
    :class:`~repro.core.substrate.ControlLoop` owns window scheduling,
    counter deltas, and the decision history; ``advance`` merely interleaves
    the loop's window boundaries with transfer completions in time order.
    """

    def __init__(
        self,
        fast: TierSpec = HBM_TIER,
        slow: TierSpec = HOST_TIER,
        controller: Optional[MikuController] = None,
        window_ns: float = 1_000_000.0,
        extra_slow: Sequence[TierSpec] = (),
        sanitize=None,
        trace: int = 0,
    ):
        self.fast = fast
        self.slow = slow
        #: Ordered slow links by label: the canonical pinned-host path keeps
        #: its legacy "slow" label; extra tiers are addressed by TierSpec
        #: name (e.g. a second host pool or a disaggregated tier).
        self.slow_tiers: Dict[str, TierSpec] = {"slow": slow}
        for spec in extra_slow:
            if spec.name in self.slow_tiers or spec.name == "fast":
                raise ValueError(f"duplicate slow tier label {spec.name!r}")
            self.slow_tiers[spec.name] = spec
        self.controller = controller
        self.now = 0.0
        self._counters = TierSetWindowedCounters(
            names=("fast", *self.slow_tiers)
        )
        self.counters: Dict[str, TierCounters] = {
            name: tc
            for name, tc in zip(self._counters.names, self._counters.tiers)
        }
        self._inflight: List[_InFlight] = []
        self._pending: List[Tuple[int, OpClass]] = []
        self._decision = Decision(
            max_concurrency=None, rate_factor=1.0, phase=None  # type: ignore[arg-type]
        )
        # record=False: nothing consumes per-window telemetry records here,
        # and a long-lived queue fires windows forever.
        self.control = ControlLoop(
            self, controller, window_ns=window_ns, record=False
        )
        # Runtime sanitizer (repro.analysis): per-link transfer/byte
        # conservation after every ``advance``.  None consults
        # REPRO_SANITIZE, mirroring the DES.
        if sanitize is None:
            sanitize = sanitize_enabled()
        if sanitize:
            from repro.analysis.sanitizer import QueueSanitizer

            mode = sanitize if isinstance(sanitize, str) else "raise"
            self._san: Optional[QueueSanitizer] = QueueSanitizer(mode=mode)
            self._counters.attach_sanitizer(self._san.check_counter_deltas)
        else:
            self._san = None
        # Sampled transfer tracing (repro.obs.trace): every Nth chunk's
        # enqueue→service→complete span, request-shaped for to_chrome.
        if trace:
            from repro.obs.trace import TransferTracer

            self._tracer: Optional[TransferTracer] = TransferTracer(
                sample_every=int(trace)
            )
        else:
            self._tracer = None
        # Process-wide observability counters (repro.obs.metrics).
        from repro.obs.metrics import default_registry

        reg = default_registry()
        self._m_transfers = reg.counter("offload.transfers")
        self._m_bytes = reg.counter("offload.bytes")

    # -- substrate protocol -------------------------------------------------
    @property
    def clock_ns(self) -> float:
        return self.now

    def counters_delta(self) -> TierWindow:
        return self._counters.delta()

    def apply(self, decision) -> None:
        self._decision = decision

    def _check_tier(self, tier: str) -> None:
        """Unknown slow-link names are a loud error (the DES already does
        this at construction; the queue used to fall back silently).  The
        message names the *link* namespace — this queue's links, not the
        platform's tiers — and lists every known link name."""
        if tier not in self.slow_tiers:
            raise UnknownTierError(
                tier, ("fast", *self.slow_tiers),
                kind="transfer link",
                known_desc="this queue's links",
            )

    def decision_for(self, tier: str = "slow") -> Decision:
        """The decision governing one slow link: its own tier-addressed
        entry, or the broadcast legacy decision."""
        self._check_tier(tier)
        d = self._decision
        if isinstance(d, TierDecisions) and tier in d.tiers:
            return d.for_tier(tier)
        return d

    @property
    def window_ns(self) -> float:
        return self.control.window_ns

    @property
    def decisions(self) -> List[Decision]:
        return self.control.decisions

    # -- instrumentation ----------------------------------------------------
    def account_fast(self, nbytes: int, duration_ns: float, op: OpClass) -> None:
        self.counters["fast"].record(op, duration_ns)
        del nbytes

    def _service_ns(self, nbytes: int, tier: TierSpec, op: OpClass) -> float:
        t = nbytes / tier.bandwidth_gbps  # B / (B/ns)
        if op is not OpClass.LOAD:
            t *= 2.0 if op is OpClass.NT_STORE else 1.5
        return t

    # -- submission / progress ------------------------------------------------
    def slow_inflight(self, tier: str = "slow") -> int:
        """One slow link's transfers holding descriptors *now* (enqueued,
        incomplete)."""
        self._check_tier(tier)
        return sum(
            1 for f in self._inflight
            if f.tier == tier and f.t_enqueue <= self.now
        )

    def submit_slow(self, nbytes: int, op: OpClass = OpClass.LOAD) -> float:
        return self.submit_slow_stream(int(nbytes), 1, op)

    def submit_slow_stream(
        self,
        total_bytes: int,
        n_chunks: int,
        op: OpClass = OpClass.LOAD,
        tier: str = "slow",
    ) -> float:
        """Submit one logical stream as ``n_chunks`` transfers (per-layer
        weight/KV chunks) over one bandwidth-bound slow link.

        The link serializes chunks, so total duration is ~bytes/bw however
        they are queued — which is exactly why a MIKU in-flight cap is
        work-conserving: it bounds how many *descriptors* the stream holds
        (chunk i enqueues only when chunk i-cap completes) without slowing
        the stream.  Uncapped, every chunk enqueues immediately — the deep
        backlog that starves fast-tier request slots.  rate_factor < 1
        additionally stretches per-chunk service (the MBA/quota analogue).
        Cap and rate are this link's own (tier-addressed decision), so two
        co-resident slow links can run different ladders.  Returns the
        stream's completion time.
        """
        self._check_tier(tier)
        spec = self.slow_tiers[tier]
        decision = self.decision_for(tier)
        cap = decision.max_concurrency
        rate = max(decision.rate_factor, 1e-3)
        chunk = max(1, int(total_bytes) // max(1, n_chunks))
        service = self._service_ns(chunk, spec, op) / rate
        link_free = max(
            [f.t_complete for f in self._inflight if f.tier == tier],
            default=self.now,
        )
        done = max(self.now, link_free)
        dones: List[float] = []
        san = self._san
        tr = self._tracer
        for i in range(n_chunks):
            done = done + service
            if cap is None or i < cap:
                enq = self.now
            else:
                enq = dones[i - cap]
            self._inflight.append(_InFlight(chunk, op, tier, enq, done))
            if san is not None:
                san.on_submit(tier, chunk)
            if tr is not None:
                tr.on_chunk(tier, enq, done, service)
            dones.append(done)
        self._m_transfers.inc(float(n_chunks))
        self._m_bytes.inc(float(chunk * n_chunks))
        return done

    def slow_backlog(self, tier: Optional[str] = None) -> int:
        """In-flight slow transfers beyond the tier's parallel slots —
        the descriptor backlog that blocks fast-tier request slots (the
        IRQ/ToR unfairness, TPU rendition).  ``tier=None`` sums every slow
        link's backlog."""
        if tier is not None:
            self._check_tier(tier)
        tiers = self.slow_tiers if tier is None else (tier,)
        return sum(
            max(0, self.slow_inflight(t) - self.slow_tiers[t].parallelism)
            for t in tiers
        )

    def fast_penalty(self, pool: int = 56, c: float = 0.45) -> float:
        """Service-time multiplier for fast-tier steps while slow-tier
        backlog occupies shared descriptors.  Calibrated so full racing
        (pool exhausted) degrades the fast tier to ~70% (paper Fig. 12) and
        a backlog-free slow stream costs ~nothing."""
        return 1.0 + c * min(1.0, self.slow_backlog() / pool)

    def advance(self, dt_ns: float) -> None:
        """Move the simulated clock; retire completed transfers; fire MIKU
        windows (via the control loop) on schedule, in time order."""
        target = self.now + dt_ns
        while True:
            next_evt = min(
                [f.t_complete for f in self._inflight if f.t_complete <= target],
                default=None,
            )
            nw = self.control.next_window_ns
            boundary = nw if nw <= target else None
            if next_evt is None and boundary is None:
                break
            if boundary is not None and (next_evt is None or boundary <= next_evt):
                self.now = boundary
                self.control.fire()
            else:
                self.now = next_evt  # type: ignore[assignment]
                done = [f for f in self._inflight if f.t_complete <= self.now]
                self._inflight = [
                    f for f in self._inflight if f.t_complete > self.now
                ]
                for f in done:
                    self.counters[f.tier].record(f.op, f.t_complete - f.t_enqueue)
                    if self._san is not None:
                        self._san.on_complete(f.tier, f.nbytes)
        self.now = target
        if self._san is not None:
            self._san.check(self)

    @property
    def decision(self) -> Decision:
        return self._decision

    @property
    def trace_records(self) -> List[dict]:
        """Sampled transfer spans (empty when tracing is off); the
        request-shaped records :func:`repro.obs.trace.to_chrome` accepts."""
        return [] if self._tracer is None else self._tracer.records
