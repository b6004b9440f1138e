"""repro.memsim.batched — the vectorized sweep-scale execution lane.

Every paper figure is a *grid* of independent simulations, and the scalar
DES pays the full event-loop cost per cell.  This package runs an entire
:class:`~repro.memsim.sweep.SimJob` grid as one stacked, window-lockstep
station-service computation instead:

* :mod:`~repro.memsim.batched.stacking` — builds one (un-run)
  :class:`~repro.core.des.TieredMemorySim` per job, exports its static
  state (:meth:`~repro.core.des.TieredMemorySim.export_state`), and stacks
  the cells into ``(n_jobs, n_workloads, n_stations)`` numpy arrays,
  grouped by control-window cadence.
* :mod:`~repro.memsim.batched.fluid` — advances all cells window-by-window
  in lockstep: each window solves a closed-network fluid equilibrium (fair
  per-core admission, station capacities, the shared ToR population bound)
  and feeds the per-tier counters to the vectorized MIKU ladder
  (:class:`repro.core.controller.VectorMikuLadder`), whose decisions
  throttle the next window — the same feedback loop as the scalar DES, at
  window granularity.
* :mod:`~repro.memsim.batched.exact` — the closed-form fast path for
  single-workload cells (bw-test / lat-test shapes): event counts are
  reproduced exactly, including the DES's float-accumulated event times,
  so bandwidth and completed counts are **bit-identical** to the scalar
  lane.
* :mod:`~repro.memsim.batched.kernel` — the per-window equilibrium solver,
  picked by the platform (:func:`~repro.memsim.batched.kernel.
  uses_fused_solver`): on the TPU one jit dispatch per window whose
  global-λ bisection is a compiled Pallas kernel, elsewhere a numpy
  bisection.

Entry point: :func:`run_sweep_batched`, normally reached through
``run_sweep(jobs, lane="batched")`` / ``benchmarks/run.py --lane batched``.
Jobs the lane cannot express (tiering hooks, ``record_windows`` traces)
fall back to the scalar DES automatically — :func:`partition_jobs`
reports who fell back and why — and cells with different ladder rung
tables simply stack into separate lockstep groups.

Fidelity contract (see ``docs/decision-laws.md``): single-workload cells
are exact; multi-workload cells are fluid approximations — bandwidths
track the scalar DES to within a few percent on the pinned equivalence
scenarios (``tests/test_batched.py``), latency *percentiles* and
per-request reservoirs are not reproduced.
"""

from repro.memsim.batched.lane import (
    can_batch,
    partition_jobs,
    run_sweep_batched,
)

__all__ = [
    "can_batch",
    "partition_jobs",
    "run_sweep_batched",
]
