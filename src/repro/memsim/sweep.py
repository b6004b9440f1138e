"""Parallel sweep runner: many independent DES configs, one process pool.

Every figure in the paper is a *matrix* of independent simulations (ops x
tiers x thread counts x platforms).  :class:`SimJob` is the picklable
description of one cell; :func:`run_sweep` executes a batch — serially in
process for small batches, or fanned out over a spawned process pool
(:func:`host_pool`) for figure matrices (``processes`` argument, or the
``REPRO_SWEEP_PROCS`` environment variable for the benchmark harness).
Results come back in job order regardless of scheduling, and each job is
deterministic given its seed, so serial and parallel execution are
bit-identical.

``run_sweep(jobs, lane="batched")`` (or ``REPRO_SWEEP_LANE=batched``)
routes the whole batch through the vectorized sweep-scale lane
(:mod:`repro.memsim.batched`) instead: the grid advances as one stacked
window-lockstep computation — tiering hooks and per-window telemetry
included — with automatic per-job fallback to the scalar DES only for the
rare job the lane genuinely cannot stack.

MIKU controllers are *constructed inside the worker* (``miku=True``) rather
than shipped across the pool: the controller is stateful, and a fresh,
platform-calibrated instance per job is exactly what the figure runners
want anyway.
"""

from __future__ import annotations

import dataclasses
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, List, Optional, Sequence

from repro.core.des import (
    SimResult,
    TieredMemorySim,
    WorkloadSpec,
    validate_workloads,
)
from repro.core.device_model import PlatformModel


@dataclasses.dataclass
class SimJob:
    """One independent simulation: everything a worker needs, picklable."""

    platform: PlatformModel
    workloads: List[WorkloadSpec]
    sim_ns: float
    seed: int = 0
    granularity: int = 4
    window_ns: float = 10_000.0
    #: Build a platform-calibrated MIKU controller in the worker.
    miku: bool = False
    miku_overrides: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: Which decision law ``miku=True`` builds: "pertier" (the per-slow-tier
    #: ensemble, default), "merged" (the explicit MergedSlowPolicy
    #: baseline — one CXL-calibrated ladder over the folded slow deltas),
    #: or "peredge" (the fabric generalization: one ladder per control
    #: edge — device edges plus port-bearing link edges — driving the sim
    #: with ``control_scope="edge"``; identical to "pertier" on
    #: fabric-less platforms).
    miku_law: str = "pertier"
    #: Record per-window control telemetry into SimResult.window_records
    #: (the ``benchmarks/run.py --trace`` payload).
    record_windows: bool = False
    #: Optional :class:`repro.tiering.TieringSpec` — the worker builds a
    #: fresh hook per sim (stateful, like MIKU controllers).
    tiering: Optional[object] = None
    #: Runtime sanitizer (:mod:`repro.analysis.sanitizer`): True/"raise"
    #: checks invariants every window and raises on violation, "record"
    #: accumulates into ``SimResult.sanitizer``; None (default) consults
    #: the ``REPRO_SANITIZE`` environment switch.  Sanitized jobs always
    #: run on the scalar DES (the batched lane cannot be instrumented).
    sanitize: Optional[object] = None
    #: Sampled request-lifecycle tracing (:mod:`repro.obs.trace`): trace
    #: every Nth ToR admission's span chain into ``SimResult.trace``
    #: (0 = off).  Traced jobs always run on the scalar DES — the span
    #: chain is an event-level lens the closed-form lanes cannot produce.
    trace: int = 0
    #: Collect mergeable log-bucketed latency histograms
    #: (:mod:`repro.obs.histogram`) per workload and per tier — and per
    #: window when combined with ``record_windows``.  Supported on every
    #: lane: the exact lane buckets its full latency vector, the fluid
    #: lane synthesizes analytic histograms from station waits.
    latency_hist: bool = False
    #: Record a wall-clock phase profile (setup / event loop / window
    #: passes) into ``SimResult.profile`` via
    #: :class:`repro.obs.metrics.PhaseProfiler` (scalar lane only).
    profile: bool = False

    def __post_init__(self):
        # Fail at job construction (with the platform's tier list) rather
        # than deep inside a pool worker: unknown tier names raise
        # UnknownTierError here.
        validate_workloads(self.platform, self.workloads)
        if self.miku_law not in ("pertier", "merged", "peredge"):
            raise ValueError(
                f"unknown miku_law {self.miku_law!r}; "
                "expected 'pertier', 'merged' or 'peredge'"
            )


def run_job(job: SimJob) -> SimResult:
    """Execute one job (the worker entry point; also the serial path)."""
    controller = None
    if job.miku:
        if job.miku_law == "peredge":
            from repro.fabric import peredge_miku

            controller = peredge_miku(
                job.platform, job.granularity, **job.miku_overrides
            )
        else:
            from repro.memsim.calibration import default_miku, merged_miku

            build = merged_miku if job.miku_law == "merged" else default_miku
            controller = build(
                job.platform, job.granularity, **job.miku_overrides
            )
    prof = None
    if job.profile:
        from repro.obs.metrics import PhaseProfiler

        prof = PhaseProfiler()
        _t0 = prof.clock()
    sim = TieredMemorySim(
        job.platform,
        job.workloads,
        seed=job.seed,
        granularity=job.granularity,
        controller=controller,
        window_ns=job.window_ns,
        record_windows=job.record_windows,
        tiering=job.tiering.build() if job.tiering is not None else None,
        control_scope="edge" if job.miku and job.miku_law == "peredge"
        else "tier",
        sanitize=job.sanitize,
        latency_hist=job.latency_hist,
        trace=job.trace,
        profiler=prof,
    )
    if prof is not None:
        prof.add("setup", prof.clock() - _t0)
    return sim.run(job.sim_ns)


def default_processes() -> int:
    """Worker count from ``REPRO_SWEEP_PROCS`` (0/1 = serial)."""
    try:
        return int(os.environ.get("REPRO_SWEEP_PROCS", "0"))
    except ValueError:
        return 0


def default_lane() -> str:
    """Execution lane from ``REPRO_SWEEP_LANE`` (scalar | batched)."""
    return os.environ.get("REPRO_SWEEP_LANE", "scalar").strip().lower() \
        or "scalar"


def run_sweep(
    jobs: Sequence[SimJob],
    processes: Optional[int] = None,
    lane: Optional[str] = None,
) -> List[SimResult]:
    """Run ``jobs``, returning results in job order.

    ``processes=None`` consults ``REPRO_SWEEP_PROCS``; <=1 runs serially in
    process (no pool overhead — the right default under pytest and for
    single-job calls).

    ``lane`` selects the execution engine (``REPRO_SWEEP_LANE`` when None):

    * ``"scalar"`` (default) — one event-driven DES per job, bit-identical
      to the pinned goldens, fanned over the process pool.
    * ``"batched"`` — the vectorized sweep-scale lane
      (:mod:`repro.memsim.batched`): the whole grid advances as one stacked
      window-lockstep computation, tiering hooks and ``record_windows``
      telemetry included; only jobs the lane genuinely cannot stack (e.g.
      an unregistered tiering policy) fall back to the scalar DES.
    """
    if lane is None:
        lane = default_lane()
    if lane not in ("scalar", "batched"):
        raise ValueError(
            f"unknown sweep lane {lane!r}; expected 'scalar' or 'batched'"
        )
    jobs = list(jobs)
    # Pool metrics on the parent-process registry (worker registries are
    # per-process and not folded back; see docs/observability.md).
    from repro.obs.metrics import default_registry

    reg = default_registry()
    reg.counter("sweep.jobs").inc(float(len(jobs)))
    reg.counter(f"sweep.lane.{lane}").inc(float(len(jobs)))
    if lane == "batched":
        from repro.memsim.batched import run_sweep_batched

        return run_sweep_batched(jobs, processes)
    if processes is None:
        processes = default_processes()
    if processes <= 1 or len(jobs) <= 1:
        return [run_job(j) for j in jobs]
    workers = min(processes, len(jobs), os.cpu_count() or 1)
    with host_pool(workers) as pool:
        return list(pool.map(run_job, jobs))


def host_pool(workers: int) -> ProcessPoolExecutor:
    """A pool for host-only work (the scalar DES), started with ``spawn``.

    A forked child would inherit a parent that may already hold the
    accelerator (the batched lane's solver, a serving engine); spawned
    workers import afresh and never touch a device."""
    return ProcessPoolExecutor(
        max_workers=workers, mp_context=multiprocessing.get_context("spawn")
    )
