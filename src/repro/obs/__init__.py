"""repro.obs — the observability layer: tracing, histograms, metrics.

Three cooperating pieces, all opt-in and all zero-cost when disabled
(the hot paths pay one integer/pointer compare per request transition,
and the tracing-off DES stays bit-identical to every pinned golden):

* :mod:`repro.obs.trace` — sampled request-lifecycle tracing: a 1-in-N
  deterministic sampler (keyed on ToR insert order, no RNG draws) records
  each traced request's span chain — issue → ToR entry → per-hop port
  queue/service → device queue/service → return flight — from the DES
  and the serving :class:`~repro.core.offload.TransferQueue`, exportable
  as Chrome trace-event JSON (``benchmarks/run.py --perfetto NAME``).
* :mod:`repro.obs.histogram` — mergeable log-bucketed latency histograms
  (HDR-style: 16 sub-buckets per power-of-two octave, globally fixed
  boundaries) as a first-class metric type alongside the bounded
  reservoir: per workload, per tier, per window, with *exact* merge
  across windows, cells, and process-pool shards.
* :mod:`repro.obs.metrics` — a small named-metric registry (counters /
  gauges / histograms registered by the DES, TransferQueue, serving
  engine, ControlLoop, and sweep pool) plus a wall-clock
  :class:`~repro.obs.metrics.PhaseProfiler` for sim setup / event-loop /
  window-pass self-profiling, and :func:`~repro.obs.metrics.span`, the
  program's named host phases on the ``jax.profiler`` trace's clock
  (``repro.plan``, ``repro.lane.window``, ...), charged to the current
  ``PhaseProfiler``.

See ``docs/observability.md`` for the span schema, bucket layout, merge
semantics, and CLI surface.
"""

from repro.obs.histogram import LatencyHistogram
from repro.obs.metrics import (
    MetricsRegistry,
    PhaseProfiler,
    default_registry,
    span,
)
from repro.obs.trace import (
    RequestTracer,
    TraceConfig,
    TransferTracer,
    to_chrome,
)

__all__ = [
    "LatencyHistogram",
    "MetricsRegistry",
    "PhaseProfiler",
    "RequestTracer",
    "TraceConfig",
    "TransferTracer",
    "default_registry",
    "span",
    "to_chrome",
]
