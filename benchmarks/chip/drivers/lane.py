"""Driver for the batched sweep lane: whole grids of a registry scenario,
run back to back through ``run_scenario(..., lane="batched")`` with the
fused jit/Pallas window solver.

Configuration keys: ``scenario``, ``backend`` (the program's
``REPRO_BATCH_BACKEND``), ``axes`` (overrides every grid shares), ``grid``
(the registry scenario's axes, which the reference expands), the
deployment's constants that ``reference/fluid_corun.py`` reads, and
``limits``.  Traffic keys: ``registry_seed`` (the seed that runs the
registry grid as it stands), ``draw`` (``axis: [count, lo, hi]``: each
grid draws ``count`` distinct whole values in ``[lo, hi]`` for that axis,
from the seed and the grid's index, so every grid has the same shape) and
``sample`` (how many of the window's cells the check compares).

Correctness: once the window has closed, a sample of the cells of every
grid it ran, drawn from the seed, is run again by the plain reference of
the whole co-run (``reference/fluid_corun.py``: window solves, counters,
MIKU ladder and the scenario's row), and each cell's row is compared with
the program's: whether MIKU restricted the same number of windows, and the
relative gap of the DDR and CXL bandwidths where it did.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List

import numpy as np

import tracing


@dataclasses.dataclass
class State:
    scenario: str
    config: Dict[str, Any]
    axes: Dict[str, Any]
    draw: Dict[str, list]
    registry: bool
    seed: int
    limits: Dict[str, float]
    n_sample: int
    trace_seconds: float
    grid_index: int = 0
    #: (axis overrides, rows) of each grid the window ran
    grids: List[tuple] = dataclasses.field(default_factory=list)


def _spans(kernel) -> None:
    """A span around each call of the program's per-window solver."""
    orig = kernel.fused_window_solve

    def solve(*args):
        with tracing.span("solve"):
            return orig(*args)

    solve.spanned = True
    kernel.fused_window_solve = solve


def grid(state: State, index: int) -> Dict[str, Any]:
    """Axis overrides of grid ``index`` of this run."""
    over = dict(state.axes)
    if state.registry:
        return over
    rng = np.random.default_rng([state.seed, index])
    for axis, (count, lo, hi) in sorted(state.draw.items()):
        vals = rng.choice(np.arange(lo, hi + 1), size=count, replace=False)
        over[axis] = tuple(int(v) for v in sorted(vals))
    return over


def _run_grid(state: State, profile: bool = False):
    from repro.scenarios import run_scenario

    over = grid(state, state.grid_index)
    state.grid_index += 1
    with tracing.span("grid"):
        return over, run_scenario(state.scenario, over, lane="batched",
                                  profile=profile)


def setup(cell, seed: int) -> State:
    cfg, tr = cell.config, cell.traffic
    os.environ["REPRO_BATCH_BACKEND"] = cfg["backend"]
    from repro.memsim.batched import kernel

    if not getattr(kernel.fused_window_solve, "spanned", False):
        _spans(kernel)
    state = State(
        scenario=cfg["scenario"],
        config=cfg,
        axes=dict(cfg.get("axes", {})),
        draw={k: list(v) for k, v in tr.get("draw", {}).items()},
        registry=seed == tr.get("registry_seed"),
        seed=seed,
        limits=dict(cfg["limits"]),
        n_sample=int(tr["sample"]),
        trace_seconds=float(tr["trace_seconds"]),
    )
    # Warm-up: one grid of the window's shape compiles the solver.
    _run_grid(state)
    return state


def window(state: State, seconds: float, capture) -> Dict[str, Any]:
    """Grids back to back for ``seconds``; a ``capture`` traces those
    that start in its first ``trace_seconds``."""
    cells = cell_us = fallback = 0
    grid_s: List[float] = []
    plan_s = 0.0
    tracing_on = capture is not None
    if tracing_on:
        capture.start()
    t0 = time.perf_counter()
    while True:
        t_g = time.perf_counter()
        over, table = _run_grid(state, profile=capture is not None)
        now = time.perf_counter()
        grid_s.append(now - t_g)
        state.grids.append((over, table.rows))
        cells += len(table.rows)
        cell_us += len(table.rows) * float(table.params["sim_ns"]) * 1e-3
        fallback += int(table.meta.get("scalar_fallback_jobs", 0))
        prof = table.meta.get("profile")
        if prof:
            plan_s += prof["phases"].get("plan", {}).get("seconds", 0.0)
        if tracing_on and now - t0 >= state.trace_seconds:
            capture.stop()
            tracing_on = False
        if now - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if tracing_on:
        capture.stop()
    return {
        "window_s": window_s,
        "attempted": cells,
        "failed": fallback,
        "cell_us": cell_us,
        "grid_wall_s": sum(grid_s),
        "plan_s": plan_s,
    }


def _sample(state: State) -> tuple:
    """The cells compared, drawn from the seed over every grid of the
    window, and the program's row of each (``None`` where it has none)."""
    from reference import fluid_corun

    cells, rows, pool = [], [], []
    for over, got in state.grids:
        spec = {**state.config["grid"],
                **{k: list(v) for k, v in over.items()}}
        expect = fluid_corun.grid_cells(spec)
        by_key = {fluid_corun.key(r): r for r in got}
        whole = len(got) == len(expect)  # else none of the grid's rows holds
        pool.extend((c, by_key.get(fluid_corun.key(c)) if whole else None)
                    for c in expect)
    rng = np.random.default_rng([state.seed, 1])
    for i in sorted(rng.permutation(len(pool))[:state.n_sample]):
        cells.append(pool[i][0])
        rows.append(pool[i][1])
    return cells, rows


#: A compared cell is off when MIKU restricted another number of windows
#: than in the reference, or when its worse bandwidth misses the
#: reference's by more than this share.
OFF = 1e-3


def row_errors(cells, rows, ref) -> Dict[str, Any]:
    """The numbers compared: the share of cells that are off (or have no
    row), the share whose MIKU decisions differ, and over the cells whose
    decisions agree the relative gap of the worse of the DDR and CXL
    bandwidths, widest and median."""
    from reference import fluid_corun

    errs, flips = [], 0
    for c, r in zip(cells, rows):
        q = ref[fluid_corun.key(c)]
        if r is None or r["restricted_windows"] != q["restricted_windows"]:
            flips += 1
            continue
        errs.append(max(abs(r[k] - q[k]) / max(abs(q[k]), 1e-9)
                        for k in ("ddr_gbps", "cxl_gbps")))
    errs = np.asarray(errs) if errs else np.full(1, np.inf)
    n = max(len(cells), 1)
    return {"row_off_share": (flips + int(np.sum(errs > OFF))) / n,
            "row_flip_share": flips / n,
            "row_gbps_rel_err": float(errs.max()),
            "row_gbps_rel_err_median": float(np.median(errs))}


def readings(state: State, run, control: bool = False) -> Dict[str, float]:
    """The numbers compared, for the program and (``control``) for the
    reference with its window solves in bfloat16, put in the program's
    place at the same cells."""
    import ml_dtypes

    from reference import fluid_corun

    cells, rows = _sample(state)
    state.grids = []
    ref = fluid_corun.run(cells, state.config)
    out = {"rows_compared": len(cells), **row_errors(cells, rows, ref)}
    if control:
        ctl = fluid_corun.run(cells, state.config, dtype=ml_dtypes.bfloat16)
        ctl_rows = [ctl[fluid_corun.key(c)] for c in cells]
        out.update({"control_" + k: v for k, v in
                    row_errors(cells, ctl_rows, ref).items()})
    return out


def check(state: State, run) -> List[Dict[str, Any]]:
    r = readings(state, run)
    out = [{"name": k, "value": r[k], "limit": state.limits[k],
            "ok": r[k] <= state.limits[k]}
           for k in sorted(state.limits)]
    out.append({"name": "rows_compared", "value": r["rows_compared"],
                "limit": state.n_sample,
                "ok": r["rows_compared"] == state.n_sample})
    return out
