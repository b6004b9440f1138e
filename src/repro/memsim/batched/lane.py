"""Lane entry points: partition a job list, run it batched, fall back scalar.

``run_sweep(jobs, lane="batched")`` lands here.  The lane is *total* over
the job grid: single-workload cells take the exact closed form, everything
else — tiering hooks and ``record_windows`` telemetry included — stacks
into the window-lockstep fluid engine, one group per (window cadence,
ladder rung table) pair so heterogeneous-rung grids still run batched.
Groups are further chunked into blocks of at most ``REPRO_BATCH_BLOCK``
cells (default 1024) to cap the stacked arrays' memory footprint on
10k+-cell grids.

Fallbacks are the exception, not the rule: only a job whose *plan or
stack* is genuinely inexpressible (heterogeneous per-tier rung tables in
one cell, an unregistered tiering policy the vector twin can't replicate)
reruns on the scalar DES — a failing group is re-stacked cell by cell so
an unstackable cell never drags its group-mates to the scalar pool — and
every one of them is recorded as an
``(index, reason)`` pair, whether it fell at the static planning screen or
at dynamic group stacking, so :func:`repro.scenarios.planner.run_scenario`
can report the split in result metadata.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Sequence, Tuple

from repro.core.des import SimResult
from repro.core.invariants import require, sanitize_enabled
from repro.memsim.batched.stacking import BatchGroup, CellPlan, plan_cell
from repro.obs.metrics import default_registry, span

#: (plans aligned with the job list — None where the job fell back,
#:  [(job_index, reason), ...] for the fallbacks)
Partition = Tuple[List[Optional[CellPlan]], List[Tuple[int, str]]]

#: Cells per stacked fluid group — chunked execution caps peak memory
#: (arrays scale with cells x workloads x stations, plus cells x regions x
#: pages when tiering is stacked).
_DEFAULT_BLOCK = 1024


def batch_block() -> int:
    """The configured chunk size (``REPRO_BATCH_BLOCK``, default 1024)."""
    try:
        return max(1, int(os.environ.get("REPRO_BATCH_BLOCK",
                                         _DEFAULT_BLOCK)))
    except ValueError:
        return _DEFAULT_BLOCK


def can_batch(job) -> Optional[str]:
    """Static screen: the fallback reason, or None when the lane applies.

    The lane is total over the *flat-station* SimJob surface — tiering
    and telemetry jobs run batched too.  Fabric jobs are the exception:
    a platform whose topology puts port-bearing links on some route (and
    likewise the ``peredge`` control law built for such routes) needs the
    multi-hop/backpressure scalar DES, so those jobs fall back with the
    explicit ``"fabric_topology"`` reason — surfaced in
    ``fallback_reason_counts`` and the stderr per-reason summary, never
    silently.  Degenerate all-transparent topologies have no hops and
    batch normally.  The dynamic screen (plan construction,
    ladder/tiering stacking) happens in :func:`partition_jobs` and
    :func:`run_sweep_batched`.
    """
    fabric = getattr(job.platform, "fabric", None)
    if fabric is not None and fabric.has_hops:
        return "fabric_topology"
    if getattr(job, "miku", False) and \
            getattr(job, "miku_law", None) == "peredge":
        return "fabric_topology"
    # Sanitized jobs need the instrumented scalar DES: the fluid/exact
    # engines have no event stream or per-window queue state to check.
    # job.sanitize=None defers to the process-wide REPRO_SANITIZE switch;
    # an explicit False opts the job back into the batched lane.
    san = getattr(job, "sanitize", None)
    if san is None:
        san = sanitize_enabled()
    if san:
        return "sanitize"
    # Open-loop arrival workloads are event-driven by construction: each
    # generated request enters a backlog and gates issue — queue growth
    # and shed accounting have no fluid/closed-form counterpart yet.
    if any(getattr(w, "arrival", None) is not None for w in job.workloads):
        return "arrival"
    # Traced jobs record per-request span chains — an event-level lens the
    # closed-form/fluid engines cannot produce.  (``latency_hist`` jobs DO
    # run batched: the exact lane buckets its full latency vector and the
    # fluid lane synthesizes analytic histograms from station waits.)
    if getattr(job, "trace", 0):
        return "trace"
    return None


def partition_jobs(jobs: Sequence) -> Partition:
    """Split ``jobs`` into batchable cell plans and scalar fallbacks."""
    plans: List[Optional[CellPlan]] = []
    fallbacks: List[Tuple[int, str]] = []
    with span("lane.partition"):
        for i, job in enumerate(jobs):
            reason = can_batch(job)
            if reason is None:
                try:
                    plans.append(plan_cell(job))
                    continue
                except ValueError as ex:  # e.g. an invalid tiering region
                    reason = str(ex)
            plans.append(None)
            fallbacks.append((i, reason))
    return plans, fallbacks


def run_sweep_batched(
    jobs: Sequence,
    processes: Optional[int] = None,
    partition: Optional[Partition] = None,
    profile: bool = False,
) -> List[SimResult]:
    """Run ``jobs`` through the batched lane, results in job order.

    Single-workload cells take the exact closed form
    (:mod:`~repro.memsim.batched.exact`); the rest stack into window-lockstep
    fluid groups (:mod:`~repro.memsim.batched.fluid`, one group per control
    cadence, chunked at :func:`batch_block` cells).  Fallback jobs run on
    the scalar lane — through the process pool when ``processes`` says so —
    and dynamic stacking failures are appended to the partition's fallback
    list so callers holding it see the *complete* accounting.
    ``profile=True`` marks the fallback jobs ``SimJob.profile`` (the
    exact and fluid paths read no per-job profile).
    """
    from repro.memsim.batched import exact as exact_mod
    from repro.memsim.batched import fluid as fluid_mod
    from repro.memsim.batched import tiering as tiering_mod
    from repro.memsim.sweep import run_sweep

    jobs = list(jobs)
    plans, fallbacks = partition if partition is not None else (
        partition_jobs(jobs)
    )
    results: List[Optional[SimResult]] = [None] * len(jobs)

    fluid_cells: List[Tuple[int, CellPlan]] = []
    n_exact = 0
    with span("lane.exact"):
        for i, plan in enumerate(plans):
            if plan is None:
                continue
            if exact_mod.exact_regime(plan) is not None:
                results[i] = exact_mod.run_exact(plan)
                n_exact += 1
            else:
                fluid_cells.append((i, plan))
    registry = default_registry()
    registry.counter("lane.cells_exact").inc(n_exact)
    registry.counter("lane.cells_fluid").inc(len(fluid_cells))

    # Group by window cadence (lockstep needs one shared cadence) AND by
    # ladder rung sequence (the vector ladder stacks one rung table per
    # group — cells with different MikuConfig.levels go to separate
    # groups and still run batched), then chunk each group to cap memory.
    by_key: dict = {}
    scalar_idxs: List[int] = []
    for i, plan in fluid_cells:
        levels = tuple(plan.units[0].config.levels) if plan.units else ()
        key = (float(plan.export["window_ns"]), levels)
        by_key.setdefault(key, []).append((i, plan))
    def _stack(cells_):
        # Stacking (array layout + vector ladder/tiering build) is the
        # part that can legitimately reject a group (e.g. a cell whose
        # per-tier units mix rung tables, or a tiering policy outside the
        # vectorized registry).  Keep the net that narrow: a failure
        # *running* the fluid engine is a bug and must surface, not
        # silently rerun scalar.
        with span("lane.stack"):
            group = BatchGroup(cells_)
            ladder = fluid_mod.build_ladder(group)
            tiering = tiering_mod.build_tiering(group)
        return group, ladder, tiering

    block = batch_block()
    for _, cells in sorted(by_key.items()):
        for lo in range(0, len(cells), block):
            chunk = cells[lo:lo + block]
            try:
                stacks = [_stack(chunk)]
            except ValueError:
                # One unstackable cell must not drag its group-mates to
                # the scalar pool: re-stack each cell alone and fall back
                # only the ones that genuinely cannot stack.
                stacks = []
                for cell in chunk:
                    try:
                        stacks.append(_stack([cell]))
                    except ValueError as ex:
                        scalar_idxs.append(cell[0])
                        fallbacks.append(
                            (cell[0], f"group stacking failed: {ex}")
                        )
            for group, ladder, tiering in stacks:
                with span("lane.group"):
                    group_results = fluid_mod.run_fluid(group, ladder,
                                                        tiering)
                for idx, res in zip(group.indices, group_results):
                    results[idx] = res

    # Partition-time fallbacks (plan is None); dynamic stacking fallbacks
    # were appended to ``scalar_idxs`` (and ``fallbacks``) above.
    scalar_idxs.extend(i for i, plan in enumerate(plans) if plan is None)
    if scalar_idxs:
        scalar_jobs = [jobs[i] for i in scalar_idxs]
        if profile:
            scalar_jobs = [dataclasses.replace(j, profile=True)
                           for j in scalar_jobs]
        with span("lane.scalar"):
            scalar_results = run_sweep(scalar_jobs, processes, lane="scalar")
        for idx, res in zip(scalar_idxs, scalar_results):
            results[idx] = res
    require(
        all(r is not None for r in results),
        "lane-total",
        "batched lane dropped jobs: every job must land a result via the "
        "exact, fluid, or scalar-fallback path",
        missing=[i for i, r in enumerate(results) if r is None],
    )
    return results  # type: ignore[return-value]
