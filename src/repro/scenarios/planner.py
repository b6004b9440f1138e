"""The scenario planner: axis grid → cells → SimJob batches → result table.

``plan()`` expands a grid scenario into ``(cell, platform, jobs)`` triples
without running anything — the unit the equivalence tests pin against the
legacy imperative runners.  ``run_scenario()`` executes: every cell's jobs
go through one :func:`~repro.memsim.sweep.run_sweep` batch (so figure-wide
matrices fan out over the process pool exactly like the legacy runners),
then each cell's ``reduce`` collects rows into a :class:`ResultTable`.

``run_scenario(..., trace=True)`` additionally records every job's
ControlLoop per-window decision telemetry (per-tier counter deltas +
tier-addressed decisions) and attaches it as ``ResultTable.traces`` —
the payload ``benchmarks/run.py --trace`` dumps as JSON next to the
scenario's CSV.
"""

from __future__ import annotations

import dataclasses
import itertools
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from repro.core.device_model import PLATFORMS, PlatformModel
from repro.memsim.sweep import SimJob, run_sweep
from repro.obs.metrics import PhaseProfiler, default_registry, span
from repro.scenarios import registry
from repro.scenarios.spec import ResultTable, Scenario

ScenarioRef = Union[str, Scenario]


def _scenario(ref: ScenarioRef) -> Scenario:
    return registry.get(ref) if isinstance(ref, str) else ref


def resolve_platform(value: Any) -> Tuple[str, PlatformModel]:
    """(label, model) for a platform axis value (name or model instance)."""
    if isinstance(value, PlatformModel):
        return value.name, value
    if value in PLATFORMS:
        return value, PLATFORMS[value]
    raise KeyError(
        f"unknown platform {value!r}; known platforms: "
        f"{', '.join(PLATFORMS)}"
    )


def resolve_axes(
    scenario: ScenarioRef, overrides: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    """Axis values for a run: defaults overlaid with ``overrides``.

    String overrides are parsed via the axis (the ``--set`` path);
    non-string overrides pass through.  A scalar override on a grid axis
    becomes a one-point grid.
    """
    sc = _scenario(scenario)
    values: Dict[str, Any] = {a.name: a.default for a in sc.axes}
    for k, v in (overrides or {}).items():
        axis = sc.axis(k)  # raises with the axis list on unknown names
        if isinstance(v, str):
            v = axis.parse_text(v)
        if axis.is_grid and not isinstance(v, (tuple, list)):
            v = (v,)
        elif axis.is_grid:
            v = tuple(v)
        values[k] = v
    return values


def expand_cells(
    scenario: ScenarioRef, values: Dict[str, Any]
) -> List[Dict[str, Any]]:
    """Cartesian product of the grid axes (declaration order, row-major),
    with scalar axes constant in every cell."""
    sc = _scenario(scenario)
    grid = [a for a in sc.axes if a.is_grid]
    scalars = {a.name: values[a.name] for a in sc.axes if not a.is_grid}
    cells = []
    for combo in itertools.product(*[values[a.name] for a in grid]):
        cell = dict(scalars)
        cell.update({a.name: v for a, v in zip(grid, combo)})
        cells.append(cell)
    return cells


def _resolved_cells(
    sc: Scenario, values: Dict[str, Any]
) -> List[Tuple[Dict[str, Any], Optional[PlatformModel]]]:
    out = []
    for cell in expand_cells(sc, values):
        pm: Optional[PlatformModel] = None
        if "platform" in cell:
            label, pm = resolve_platform(cell["platform"])
            cell = {**cell, "platform": label}
        out.append((cell, pm))
    return out


def plan(
    scenario: ScenarioRef, overrides: Optional[Dict[str, Any]] = None
) -> List[Tuple[Dict[str, Any], Optional[PlatformModel], List[SimJob]]]:
    """Expand a grid scenario into (cell, platform, jobs) without running."""
    sc = _scenario(scenario)
    if sc.build is None:
        raise ValueError(
            f"scenario {sc.name!r} is multi-stage (run_cell); it has no "
            "static job plan"
        )
    values = resolve_axes(sc, overrides)
    return [
        (cell, pm, sc.build(pm, cell))
        for cell, pm in _resolved_cells(sc, values)
    ]


def run_scenario(
    scenario: ScenarioRef,
    overrides: Optional[Dict[str, Any]] = None,
    processes: Optional[int] = None,
    *,
    trace: bool = False,
    lane: Optional[str] = None,
    perfetto: bool = False,
    profile: bool = False,
) -> ResultTable:
    """Execute a scenario and collect its uniform result table.

    ``trace=True`` (grid scenarios only) turns on per-window control-plane
    telemetry recording in every job and attaches the per-cell window
    records as ``ResultTable.traces``.

    ``perfetto=True`` (grid scenarios only) turns on sampled
    request-lifecycle tracing (:mod:`repro.obs.trace`, every 16th ToR
    admission) in every job and attaches the per-cell span payloads as
    ``ResultTable.request_traces`` — the records ``benchmarks/run.py
    --perfetto`` exports as Chrome trace-event JSON.  Traced jobs always
    run on the scalar DES.

    ``profile=True`` records a wall-clock phase profile into
    ``ResultTable.meta["profile"]``: the planner's plan / sweep / reduce
    and every :func:`~repro.obs.span` opened inside the call (the batched
    lane's ``lane.*`` phases), plus the setup / event-loop / window split
    of each job that runs on the scalar DES; it snapshots the process-wide
    observability counters into ``meta["metrics"]``.  The phases are the
    ``repro.*`` annotations a ``jax.profiler`` trace shows, whether or not
    the call profiles.

    ``lane="batched"`` routes the whole grid through the vectorized sweep
    lane (:mod:`repro.memsim.batched`); jobs it cannot express fall back to
    the scalar DES, and ``ResultTable.meta`` records the split (lane name,
    batched vs fallback job counts, fallback reasons).  Multi-stage
    (``run_cell``) scenarios always run scalar; the meta notes it.
    """
    from repro.memsim.sweep import default_lane

    sc = _scenario(scenario)
    values = resolve_axes(sc, overrides)
    rows: List[Dict[str, Any]] = []
    results: list = []
    traces: Optional[List[Dict[str, Any]]] = [] if trace else None
    req_traces: Optional[List[Dict[str, Any]]] = [] if perfetto else None
    prof = PhaseProfiler() if profile else None
    # Resolve the effective lane up front so meta reports what actually ran
    # (lane=None defers to REPRO_SWEEP_LANE, exactly like run_sweep).
    lane = lane or default_lane()
    meta: Dict[str, Any] = {"lane": lane}
    if sc.run_cell is not None:
        if trace:
            raise ValueError(
                f"scenario {sc.name!r} is multi-stage (run_cell); per-window "
                "decision tracing supports grid scenarios only"
            )
        if perfetto:
            raise ValueError(
                f"scenario {sc.name!r} is multi-stage (run_cell); request-"
                "lifecycle tracing supports grid scenarios only"
            )
        if lane == "batched":
            meta = {"lane": "scalar",
                    "note": "multi-stage (run_cell) scenario; the batched "
                            "lane applies to grid scenarios only"}
    with prof.activate() if prof is not None else nullcontext():
        if sc.run_cell is not None:
            with span("run_cell"):
                for cell, pm in _resolved_cells(sc, values):
                    rows.extend(sc.run_cell(pm, cell, processes))
        else:
            rows, results = _run_grid(sc, values, processes, lane, meta,
                                      traces, req_traces, profile)
    if prof is not None:
        meta["profile"] = prof.snapshot()
        meta["profile"]["jobs"] = [r.profile for r in results if r.profile]
        meta["metrics"] = default_registry().snapshot()
    return ResultTable(scenario=sc.name, rows=rows, params=values,
                       traces=traces, meta=meta,
                       request_traces=req_traces)


def _run_grid(sc, values, processes, lane, meta, traces, req_traces,
              profile):
    """Plan, sweep and reduce a grid scenario: ``(rows, results)``.

    The batched lane's results land in ``meta``'s job split; ``traces``
    and ``req_traces`` (when lists) collect each cell's window records and
    request-trace payloads."""
    with span("plan"):
        planned = [
            (cell, pm, sc.build(pm, cell))
            for cell, pm in _resolved_cells(sc, values)
        ]
        if traces is not None:
            planned = [
                (cell, pm,
                 [dataclasses.replace(j, record_windows=True) for j in jobs])
                for cell, pm, jobs in planned
            ]
        if req_traces is not None:
            # Every 16th ToR admission: dense enough that even a short CI
            # cell lands spans, sparse enough to keep the export small.
            planned = [
                (cell, pm,
                 [dataclasses.replace(j, trace=16) for j in jobs])
                for cell, pm, jobs in planned
            ]
        if profile and lane != "batched":
            # Only the scalar DES reads SimJob.profile; the batched lane
            # marks the jobs it falls back itself.
            planned = [
                (cell, pm,
                 [dataclasses.replace(j, profile=True) for j in jobs])
                for cell, pm, jobs in planned
            ]
    with span("sweep"):
        all_jobs: List[SimJob] = [j for _, _, jobs in planned for j in jobs]
        if lane == "batched":
            from repro.memsim.batched import partition_jobs, run_sweep_batched

            partition = partition_jobs(all_jobs)
            results = run_sweep_batched(all_jobs, processes,
                                        partition=partition, profile=profile)
            # Account fallbacks *after* the run: run_sweep_batched appends
            # dynamic stacking failures to the partition's fallback list.
            _, fallbacks = partition
            reason_counts: Dict[str, int] = {}
            for _, r in fallbacks:
                reason_counts[r] = reason_counts.get(r, 0) + 1
            meta.update(
                batched_jobs=len(all_jobs) - len(fallbacks),
                scalar_fallback_jobs=len(fallbacks),
                fallback_reasons=sorted(reason_counts),
                fallback_reason_counts=dict(sorted(reason_counts.items())),
            )
        else:
            results = run_sweep(all_jobs, processes, lane=lane)
    rows: List[Dict[str, Any]] = []
    with span("reduce"):
        i = 0
        for cell, pm, jobs in planned:
            chunk = results[i: i + len(jobs)]
            i += len(jobs)
            rows.extend(sc.reduce(pm, cell, jobs, chunk))
            if traces is not None:
                traces.append({
                    "cell": {k: getattr(v, "value", v)
                             for k, v in cell.items()},
                    "jobs": [
                        {
                            "job": j,
                            "workloads": [w.name for w in job.workloads],
                            "windows": res.window_records,
                        }
                        for j, (job, res) in enumerate(zip(jobs, chunk))
                    ],
                })
            if req_traces is not None:
                req_traces.append({
                    "cell": {k: getattr(v, "value", v)
                             for k, v in cell.items()},
                    "jobs": [
                        {
                            "job": j,
                            "workloads": [w.name for w in job.workloads],
                            "trace": res.trace,
                        }
                        for j, (job, res) in enumerate(zip(jobs, chunk))
                    ],
                })
    return rows, results


def parse_set_args(
    scenario: ScenarioRef, pairs: Sequence[str]
) -> Dict[str, Any]:
    """``--set axis=value`` tokens → an overrides dict (parsed per axis)."""
    sc = _scenario(scenario)
    overrides: Dict[str, Any] = {}
    for pair in pairs:
        if "=" not in pair:
            raise ValueError(f"--set expects axis=value, got {pair!r}")
        k, v = pair.split("=", 1)
        overrides[k.strip()] = sc.axis(k.strip()).parse_text(v)
    return overrides
