"""Named-metric registry and wall-clock self-profiler.

A deliberately small, stdlib-only metrics facility.  Components
(:class:`~repro.core.des.TieredMemorySim`, the serving
``TransferQueue``/``ServingEngine``, ``ControlLoop``, the sweep pool)
register named counters/gauges/histograms against the *process-default*
registry; ``run_scenario(..., profile=True)`` snapshots it into
``ResultTable.meta["metrics"]``.  Registries are per-process: sweep
shards running in a process pool each accumulate their own registry,
so pool-run metrics reflect only the parent process (documented in
``docs/observability.md``).

:class:`PhaseProfiler` is the one place in the repo allowed to touch
``time.perf_counter`` for simulation work — sim packages are screened
for wall-clock calls by the repo lint pass, so the DES and planner call
``profiler.clock()`` / ``profiler.add()`` instead and stay deterministic
when no profiler is attached.

:class:`span` marks a host phase of the program: a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (a TraceMe on the
profiler's host plane, the clock the device events are stamped on) that
also charges its seconds to the :class:`PhaseProfiler` made current by
:meth:`PhaseProfiler.activate`, if any.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Dict, Iterator, Optional

from repro.obs.histogram import LatencyHistogram

__all__ = [
    "Counter",
    "Gauge",
    "MetricsRegistry",
    "PhaseProfiler",
    "default_registry",
    "span",
]


class Counter:
    """Monotonic named counter."""

    __slots__ = ("name", "value")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n

    def __repr__(self) -> str:
        return f"Counter({self.name}={self.value:g})"


class Gauge:
    """Last-write-wins named gauge."""

    __slots__ = ("name", "value")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = v

    def __repr__(self) -> str:
        return f"Gauge({self.name}={self.value:g})"


class MetricsRegistry:
    """Accessor-on-first-use registry of named metrics."""

    def __init__(self) -> None:
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, LatencyHistogram] = {}

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            c = self._counters[name] = Counter(name)
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            g = self._gauges[name] = Gauge(name)
        return g

    def histogram(self, name: str) -> LatencyHistogram:
        h = self._histograms.get(name)
        if h is None:
            h = self._histograms[name] = LatencyHistogram()
        return h

    def snapshot(self) -> dict:
        """JSON-able view: counters/gauges verbatim, histograms summarized."""
        return {
            "counters": {k: c.value for k, c in sorted(self._counters.items())},
            "gauges": {k: g.value for k, g in sorted(self._gauges.items())},
            "histograms": {
                k: {
                    "n": h.n,
                    "mean": h.mean(),
                    "p50": h.percentile(0.5),
                    "p95": h.percentile(0.95),
                    "p99": h.percentile(0.99),
                }
                for k, h in sorted(self._histograms.items())
            },
        }

    def reset(self) -> None:
        self._counters.clear()
        self._gauges.clear()
        self._histograms.clear()


_DEFAULT = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """The process-wide registry components register against."""
    return _DEFAULT


class PhaseProfiler:
    """Wall-clock phase accounting for sim self-profiling.

    Phases are additive: ``add("window_pass", dt)`` accumulates across
    windows; ``window_pass`` time is a subset of ``event_loop`` time.
    The profiler is attached explicitly (``SimJob.profile=True``) so an
    unprofiled simulation performs no clock reads at all.
    """

    __slots__ = ("seconds", "calls", "clock")

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}
        self.clock = time.perf_counter

    def add(self, phase: str, dt: float) -> None:
        self.seconds[phase] = self.seconds.get(phase, 0.0) + dt
        self.calls[phase] = self.calls.get(phase, 0) + 1

    @contextmanager
    def activate(self) -> Iterator["PhaseProfiler"]:
        """Make this the profiler that :class:`span` charges inside the
        ``with`` block; the one current before is restored on exit."""
        token = _CURRENT.set(self)
        try:
            yield self
        finally:
            _CURRENT.reset(token)

    @contextmanager
    def phase(self, name: str) -> Iterator[None]:
        t0 = self.clock()
        try:
            yield
        finally:
            self.add(name, self.clock() - t0)

    def snapshot(self) -> dict:
        return {
            "phases": {
                k: {"seconds": round(v, 6), "calls": self.calls.get(k, 0)}
                for k, v in sorted(self.seconds.items())
            }
        }


_CURRENT: ContextVar[Optional[PhaseProfiler]] = ContextVar(
    "repro_phase_profiler", default=None
)
#: ``jax.profiler.TraceAnnotation``, imported by the first span.
_annotation = None
#: ``name`` -> ``"repro." + name``, built once per name.
_FULL_NAMES: Dict[str, str] = {}


class span:
    """A named host phase: ``with span("lane.window"): ...``.

    Opens the profiler annotation ``repro.<name>`` and, when a
    :class:`PhaseProfiler` is current (:meth:`PhaseProfiler.activate`),
    adds the phase's seconds and one call to it under ``name``.  With no
    trace running and no profiler current it costs one annotation's
    construction (about a microsecond); spans mark phases, never cells.
    """

    __slots__ = ("name", "_annotation", "_prof", "_t0")

    def __init__(self, name: str) -> None:
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        full = _FULL_NAMES.get(name)
        if full is None:
            full = _FULL_NAMES[name] = "repro." + name
        self.name = name
        self._annotation = _annotation(full)

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        prof = self._prof = _CURRENT.get()
        if prof is not None:
            self._t0 = prof.clock()
        return self

    def __exit__(self, *exc) -> None:
        prof = self._prof
        if prof is not None:
            prof.add(self.name, prof.clock() - self._t0)
        self._annotation.__exit__(*exc)
