"""Batched sweep lane: ladder equivalence, exact fast path, fluid tolerance.

Three layers of cross-lane guarantees, strongest first:

1. The vectorized MIKU ladder is *decision-identical* to per-cell
   ``SlowTierMiku`` ensembles on arbitrary counter traces (same state
   machine, different arithmetic substrate).
2. Single-workload cells (bw-test / lat-test shapes) are *bit-identical*
   on completed counts, bytes and bandwidth, and ≤1e-9 relative on
   occupancy/latency integrals (float-summation order is the only
   difference).
3. Co-run cells are fluid approximations: bandwidths within pinned
   tolerances on the two equivalence scenarios (fig5-style co-run grid and
   ``corun3_pertier``), with the fast-tier error much tighter than the
   throttled-slow-tier error.  Tolerances were measured on the scalar
   baselines and pinned with ~2x margin (see docs/decision-laws.md).
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.controller import VectorMikuLadder
from repro.core.device_model import PLATFORMS, platform_a
from repro.core.littles_law import OpClass, TierCounters
from repro.memsim.batched import can_batch, partition_jobs
from repro.memsim.batched.exact import exact_regime
from repro.memsim.batched.lane import run_sweep_batched
from repro.memsim.calibration import default_miku
from repro.memsim.sweep import SimJob, run_sweep
from repro.memsim.workloads import bw_test, lat_test

_OPS = tuple(OpClass)


def _counters(rng, scale=1.0) -> TierCounters:
    tc = TierCounters()
    tc.inserts = int(rng.integers(0, 400) * scale)
    tc.occupancy_time = tc.inserts * float(rng.uniform(100.0, 3000.0))
    if tc.inserts:
        split = rng.multinomial(tc.inserts, [0.5, 0.3, 0.15, 0.05])
        tc.class_counts = {op: int(n) for op, n in zip(_OPS, split)}
    return tc


def _cls_array(tc: TierCounters) -> np.ndarray:
    return np.asarray([tc.class_counts.get(op, 0) for op in _OPS], float)


# ---------------------------------------------------------------------------
# 1. Vectorized ladder == scalar ladder, decision for decision.
# ---------------------------------------------------------------------------


def test_vector_ladder_matches_scalar_ensembles():
    rng = np.random.default_rng(7)
    platform = PLATFORMS["A-switch"]
    n_cells, n_units, n_windows = 5, 2, 60
    scalar_units = []
    for _ in range(n_cells):
        ctl = default_miku(platform, 4)
        ctl._ensure_units(n_units, ["cxl", "cxl_sw"])
        scalar_units.append(ctl.units[:n_units])
    vec = VectorMikuLadder.from_units(scalar_units)

    for w in range(n_windows):
        # Mix regimes: calm, backlogged, idle-fast, and starved windows.
        fast = [_counters(rng, scale=rng.choice([0.0, 0.2, 1.0]))
                for _ in range(n_cells)]
        slows = [[_counters(rng, scale=rng.choice([0.0, 1.0, 3.0]))
                  for _ in range(n_units)] for _ in range(n_cells)]
        out = vec.window(
            np.asarray([f.inserts for f in fast], float),
            np.asarray([f.occupancy_time for f in fast]),
            np.stack([_cls_array(f) for f in fast]),
            np.asarray([[s.inserts for s in row] for row in slows], float),
            np.asarray([[s.occupancy_time for s in row] for row in slows]),
            np.stack([np.stack([_cls_array(s) for s in row])
                      for row in slows]),
        )
        for ci in range(n_cells):
            for ui in range(n_units):
                d = scalar_units[ci][ui].window(fast[ci], slows[ci][ui])
                cap = np.inf if d.max_concurrency is None \
                    else d.max_concurrency
                assert out["restricted"][ci, ui] == d.restricted, (w, ci, ui)
                assert out["cap"][ci, ui] == cap, (w, ci, ui)
                assert out["rate"][ci, ui] == pytest.approx(d.rate_factor)
                est = d.estimate
                assert out["valid"][ci, ui] == est.valid
                assert out["backlogged"][ci, ui] == est.backlogged
                assert out["t_slow_raw"][ci, ui] == pytest.approx(
                    est.t_slow_raw, abs=1e-9)
                assert out["threshold"][ci, ui] == pytest.approx(
                    est.threshold)


# ---------------------------------------------------------------------------
# 2. Exact fast path: bit-identical single-workload cells.
# ---------------------------------------------------------------------------


def _exact_jobs():
    p = platform_a()
    jobs = []
    for op in _OPS[:3]:
        for tier in ("ddr", "cxl"):
            jobs.append(SimJob(platform=p, workloads=[bw_test(tier, op, 16)],
                               sim_ns=120_000.0))
    jobs.append(SimJob(platform=p, workloads=[bw_test("ddr", OpClass.LOAD, 1)],
                       sim_ns=120_000.0))
    jobs.append(SimJob(platform=p,
                       workloads=[lat_test("ddr", OpClass.LOAD, 1)],
                       sim_ns=200_000.0, granularity=1))
    jobs.append(SimJob(platform=p,
                       workloads=[lat_test("cxl", OpClass.LOAD, 8)],
                       sim_ns=200_000.0, granularity=1))
    return jobs


def test_exact_path_bit_identical_to_scalar():
    jobs = _exact_jobs()
    plans, fallbacks = partition_jobs(jobs)
    assert not fallbacks
    regimes = [exact_regime(p) for p in plans]
    assert all(r in ("noqueue", "saturated") for r in regimes), regimes
    scalar = run_sweep(jobs)
    batched = run_sweep_batched(jobs)
    for job, s, b in zip(jobs, scalar, batched):
        name = job.workloads[0].name
        ss, bs = s.stats[name], b.stats[name]
        assert bs.completed == ss.completed
        assert bs.bytes == ss.bytes  # bit-identical bandwidth
        assert b.bandwidth(name) == s.bandwidth(name)
        assert bs.timeline == ss.timeline
        assert b.tor_inserts == s.tor_inserts
        assert b.tor_peak == s.tor_peak
        assert b.tor_occupancy_integral == pytest.approx(
            s.tor_occupancy_integral, rel=1e-9)
        assert bs.latency_sum == pytest.approx(ss.latency_sum, rel=1e-9)
        tier = job.workloads[0].tier
        assert b.tier_counters[tier].inserts == s.tier_counters[tier].inserts
        assert b.tier_counters[tier].occupancy_time == pytest.approx(
            s.tier_counters[tier].occupancy_time, rel=1e-9)


def test_middle_regime_falls_to_fluid_and_stays_close():
    # 1 thread on CXL: outstanding (40) sits between the device's 28 slots
    # and the saturated-cohort bound — no closed form, fluid instead.
    p = platform_a()
    job = SimJob(platform=p, workloads=[bw_test("cxl", OpClass.LOAD, 1)],
                 sim_ns=120_000.0)
    (plan,), _ = partition_jobs([job])
    assert exact_regime(plan) is None
    (s,), (b,) = run_sweep([job]), run_sweep_batched([job])
    name = job.workloads[0].name
    assert b.bandwidth(name) == pytest.approx(s.bandwidth(name), rel=0.02)


# ---------------------------------------------------------------------------
# 3. Fluid tolerance on co-run cells (the unfair-queuing collapse + MIKU).
# ---------------------------------------------------------------------------


def _corun_job(platform, op, miku, sim_ns=300_000.0, threads=16):
    wls = [bw_test("ddr", op, threads, name="ddr", miku_managed=False),
           bw_test("cxl", op, threads, name="cxl")]
    return SimJob(platform=platform, workloads=wls, sim_ns=sim_ns, miku=miku)


def test_corun_racing_equivalence():
    p = platform_a()
    jobs = [_corun_job(p, op, miku=False) for op in _OPS[:3]]
    scalar = run_sweep(jobs)
    batched = run_sweep_batched(jobs)
    for s, b in zip(scalar, batched):
        # Racing collapse: measured ≤2.2% across the full grid; pinned 5%.
        assert b.bandwidth("ddr") == pytest.approx(s.bandwidth("ddr"),
                                                   rel=0.05)
        assert b.bandwidth("cxl") == pytest.approx(s.bandwidth("cxl"),
                                                   rel=0.05)
        # The collapse mechanism itself: loaded slow-tier ToR residency.
        assert (b.tier_counters["cxl"].mean_service_time
                == pytest.approx(s.tier_counters["cxl"].mean_service_time,
                                 rel=0.1))


def test_corun_miku_equivalence():
    p = platform_a()
    jobs = [_corun_job(p, OpClass.LOAD, miku=True),
            _corun_job(p, OpClass.STORE, miku=True)]
    scalar = run_sweep(jobs)
    batched = run_sweep_batched(jobs)
    for s, b in zip(scalar, batched):
        # Fast-tier recovery: measured ≤0.7%; pinned 5%.  Throttled slow
        # tier: measured ≤4.2%; pinned 10%.
        assert b.bandwidth("ddr") == pytest.approx(s.bandwidth("ddr"),
                                                   rel=0.05)
        assert b.bandwidth("cxl") == pytest.approx(s.bandwidth("cxl"),
                                                   rel=0.10)
        rs = sum(1 for d in s.decisions if d.restricted)
        rb = sum(1 for d in b.decisions if d.restricted)
        assert len(b.decisions) == len(s.decisions)
        assert abs(rs - rb) <= 3


@pytest.mark.slow
def test_corun_sweep_grid_equivalence_full():
    from repro.scenarios import plan

    jobs = [j for _, _, js in plan("corun_sweep") for j in js]
    scalar = run_sweep(jobs)
    batched = run_sweep_batched(jobs)
    errs = []
    for s, b in zip(scalar, batched):
        for w in ("ddr", "cxl"):
            errs.append(abs(b.bandwidth(w) - s.bandwidth(w))
                        / max(s.bandwidth(w), 1e-9))
    # Full 96-cell grid: measured worst ~8%, mean ~0.7%; pinned 15% / 3%.
    assert max(errs) < 0.15
    assert sum(errs) / len(errs) < 0.03


def test_corun3_pertier_equivalence_one_cell():
    from repro.scenarios import run_scenario

    overrides = {"law": ("pertier",), "sim_ns": 300_000.0}
    ts = run_scenario("corun3_pertier", overrides)
    tb = run_scenario("corun3_pertier", overrides, lane="batched")
    assert tb.meta["lane"] == "batched"
    assert tb.meta["scalar_fallback_jobs"] == 0
    (rs,), (rb,) = ts.rows, tb.rows
    # The per-tier signature must survive the lane change: the switch tier
    # is capped harder than local CXL, and DDR recovers.
    assert rb["cxl_sw_mean_cap"] < rb["cxl_mean_cap"]
    assert rb["ddr_pct_of_opt"] == pytest.approx(rs["ddr_pct_of_opt"], abs=8)
    for col in ("cxl_mean_cap", "cxl_sw_mean_cap"):
        assert rb[col] == pytest.approx(rs[col], rel=0.25)
    for col in ("cxl_corun_gbps", "cxl_sw_corun_gbps"):
        assert rb[col] == pytest.approx(rs[col], rel=0.12)


@pytest.mark.slow
def test_corun3_pertier_equivalence_full_grid():
    from repro.scenarios import run_scenario

    ts = run_scenario("corun3_pertier", {})
    tb = run_scenario("corun3_pertier", {}, lane="batched")
    for rs, rb in zip(ts.rows, tb.rows):
        assert rb["law"] == rs["law"]
        assert rb["ddr_pct_of_opt"] == pytest.approx(rs["ddr_pct_of_opt"],
                                                     abs=8)
    by_law = {r["law"]: r for r in tb.rows}
    # Merged broadcasts one cap; per-tier throttles the switch tier harder.
    assert by_law["merged"]["cxl_mean_cap"] == pytest.approx(
        by_law["merged"]["cxl_sw_mean_cap"])
    assert by_law["pertier"]["cxl_sw_mean_cap"] \
        < by_law["pertier"]["cxl_mean_cap"]


# ---------------------------------------------------------------------------
# Edge cases: fallback routing, single-cell grids, mixed MIKU grids.
# ---------------------------------------------------------------------------


def test_lane_is_total_over_tiering_and_telemetry():
    # The lane no longer screens out tiering or record_windows jobs: every
    # SimJob passes the static screen and runs batched.
    p = platform_a()
    traced = SimJob(platform=p, workloads=[bw_test("cxl", OpClass.LOAD, 4)],
                    sim_ns=60_000.0, record_windows=True, miku=True)
    assert can_batch(traced) is None
    from repro.tiering import HotSetPattern, RegionSpec, TieringSpec

    spec = TieringSpec(
        regions=(RegionSpec(workload="cxl", n_pages=128,
                            placement={"cxl": 1.0},
                            pattern=HotSetPattern()),),
        policy="static",
    )
    tiering = SimJob(platform=p,
                     workloads=[bw_test("cxl", OpClass.LOAD, 4, name="cxl")],
                     sim_ns=60_000.0, tiering=spec)
    assert can_batch(tiering) is None
    clean = SimJob(platform=p, workloads=[bw_test("cxl", OpClass.LOAD, 4)],
                   sim_ns=60_000.0)
    assert can_batch(clean) is None

    jobs = [clean, traced, tiering]
    plans, fallbacks = partition_jobs(jobs)
    assert not fallbacks
    assert all(pl is not None for pl in plans)
    batched = run_sweep_batched(jobs, partition=(plans, fallbacks))
    assert not fallbacks  # no dynamic stacking failures either
    scalar = run_sweep(jobs)
    for i in (1, 2):
        name = jobs[i].workloads[0].name
        assert batched[i].bandwidth(name) == pytest.approx(
            scalar[i].bandwidth(name), rel=0.05)
    assert batched[1].window_records  # vectorized telemetry
    assert batched[2].tiering is not None  # vectorized tiering summary


def test_dynamic_stacking_failure_is_recorded_and_runs_scalar():
    # A tiering policy outside the vectorized registry plans fine (the
    # scalar hook can run it) but can't stack — the group must fall back
    # AND the partition's fallback list must say so.
    from repro.tiering import HotSetPattern, RegionSpec, TieringSpec
    from repro.tiering.policies import POLICIES

    class FrozenPolicy:  # deliberately outside the vectorizable hierarchy
        name = "frozen_test_policy"

        def decide(self, pagemap, ctx):
            del pagemap, ctx
            return []

    POLICIES[FrozenPolicy.name] = FrozenPolicy
    try:
        p = platform_a()
        spec = TieringSpec(
            regions=(RegionSpec(workload="cxl", n_pages=128,
                                placement={"cxl": 1.0},
                                pattern=HotSetPattern()),),
            policy=FrozenPolicy.name,
        )
        job = SimJob(
            platform=p,
            workloads=[bw_test("cxl", OpClass.LOAD, 4, name="cxl")],
            sim_ns=60_000.0, tiering=spec,
        )
        plans, fallbacks = partition_jobs([job])
        assert not fallbacks  # the plan itself is fine
        (b,) = run_sweep_batched([job], partition=(plans, fallbacks))
        assert [i for i, _ in fallbacks] == [0]
        assert "frozen_test_policy" in fallbacks[0][1]
        (s,) = run_sweep([job])
        # The fallback reran the scalar DES — identical, not approximate.
        assert b.bandwidth("cxl") == s.bandwidth("cxl")
        assert b.tiering == s.tiering
    finally:
        POLICIES.pop(FrozenPolicy.name, None)


def test_zero_fallbacks_surface_in_result_table_meta():
    from repro.scenarios import run_scenario

    # migrate_interference builds tiering jobs: the now-total batched lane
    # runs all of them stacked and reports a clean split.
    table = run_scenario(
        "migrate_interference", {"sim_ns": 60_000.0}, lane="batched"
    )
    assert table.meta["lane"] == "batched"
    assert table.meta["scalar_fallback_jobs"] == 0
    assert table.meta["batched_jobs"] == 3
    assert table.meta["fallback_reasons"] == []
    assert table.meta["fallback_reason_counts"] == {}


def test_single_cell_grid_batched():
    from repro.scenarios import run_scenario

    overrides = {"platform": ("A",), "op": (OpClass.LOAD,), "threads": (16,),
                 "miku": (True,), "mlp": (160,), "sim_ns": 150_000.0}
    table = run_scenario("corun_sweep", overrides, lane="batched")
    assert len(table.rows) == 1
    assert table.meta["batched_jobs"] == 1
    assert table.rows[0]["restricted_windows"] > 0


def test_mixed_miku_grid_batched():
    from repro.scenarios import run_scenario

    overrides = {"platform": ("A",), "op": (OpClass.LOAD,), "threads": (16,),
                 "miku": (False, True), "mlp": (160,), "sim_ns": 150_000.0}
    table = run_scenario("corun_sweep", overrides, lane="batched")
    off, on = table.rows
    assert off["restricted_windows"] == 0
    assert on["restricted_windows"] > 0
    assert on["ddr_gbps"] > 2.0 * off["ddr_gbps"]  # MIKU recovers DDR


def test_multistage_scenario_notes_scalar_lane(monkeypatch):
    from repro.scenarios import run_scenario

    table = run_scenario(
        "fig2_tiering", {"op": OpClass.LOAD}, lane="batched"
    )
    assert table.meta["lane"] == "scalar"
    assert "multi-stage" in table.meta["note"]
    # REPRO_SWEEP_LANE must not leak into run_cell bodies' internal
    # run_sweep calls: the rows must be the scalar lane's, bit for bit.
    monkeypatch.setenv("REPRO_SWEEP_LANE", "batched")
    enved = run_scenario("fig2_tiering", {"op": OpClass.LOAD})
    assert enved.meta["note"].startswith("multi-stage")
    assert enved.rows == table.rows


def test_tiny_tor_disqualifies_noqueue_regime():
    """tor_capacity < outstanding < slots: admissions stagger even though
    servers are idle — not the no-queue closed form (it would double-count;
    the cell must take the fluid path and stay close to the scalar DES)."""
    import dataclasses as dc

    p = dc.replace(platform_a(), tor_entries=64)  # 16 macro entries
    job = SimJob(platform=p,
                 workloads=[bw_test("ddr", OpClass.LOAD, 1, mlp=128)],
                 sim_ns=120_000.0)
    (plan,), _ = partition_jobs([job])
    assert exact_regime(plan) is None
    (s,), (b,) = run_sweep([job]), run_sweep_batched([job])
    name = job.workloads[0].name
    assert b.stats[name].completed == pytest.approx(
        s.stats[name].completed, rel=0.02)


def test_mixed_workload_counts_in_one_fluid_group():
    """A 1-workload middle-regime cell and a 2-workload co-run cell share
    one fluid window group: padded workload slots must stay inert (no NaN
    from the unused-station +inf fair shares)."""
    p = platform_a()
    single = SimJob(platform=p, workloads=[bw_test("cxl", OpClass.LOAD, 1)],
                    sim_ns=100_000.0)
    corun = _corun_job(p, OpClass.LOAD, miku=True, sim_ns=100_000.0)
    batched = run_sweep_batched([single, corun])
    scalar = run_sweep([single, corun])
    name = single.workloads[0].name
    assert batched[0].bandwidth(name) == pytest.approx(
        scalar[0].bandwidth(name), rel=0.03)
    assert batched[1].bandwidth("ddr") == pytest.approx(
        scalar[1].bandwidth("ddr"), rel=0.05)


def test_env_lane_is_reported_in_meta(monkeypatch):
    from repro.scenarios import run_scenario

    monkeypatch.setenv("REPRO_SWEEP_LANE", "batched")
    overrides = {"platform": ("A",), "op": (OpClass.LOAD,), "threads": (8,),
                 "miku": (False,), "mlp": (160,), "sim_ns": 60_000.0}
    table = run_scenario("corun_sweep", overrides)
    assert table.meta["lane"] == "batched"
    assert table.meta["batched_jobs"] == 1


# ---------------------------------------------------------------------------
# Solver backends.
# ---------------------------------------------------------------------------


def test_fused_window_solver_matches_numpy_loop(monkeypatch):
    """With the fused solver picked, the whole per-window relaxation runs
    through kernel.fused_window_solve (one jit dispatch per window); the
    results must match the numpy loop, and the loud scalar-loop fallback
    must NOT fire (warnings are errors here)."""
    pytest.importorskip("jax")
    import warnings

    from repro.memsim.batched import kernel

    p = platform_a()
    jobs = [_corun_job(p, op, miku=m, sim_ns=150_000.0)
            for op in _OPS[:2] for m in (False, True)]
    monkeypatch.setattr(kernel, "uses_fused_solver", lambda: False)
    base = run_sweep_batched(jobs)
    monkeypatch.setattr(kernel, "uses_fused_solver", lambda: True)
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        fused = run_sweep_batched(jobs)
    for s, b in zip(base, fused):
        for w in ("ddr", "cxl"):
            assert b.bandwidth(w) == pytest.approx(s.bandwidth(w), rel=1e-4)
        rs = sum(1 for d in s.decisions if d.restricted)
        rb = sum(1 for d in b.decisions if d.restricted)
        assert rs == rb


def _window_inputs(C, W, S, seed):
    """The ten inputs of one window solve: some cells uncapped (``+inf``
    rate caps), some whose ToR never fills (infinite λ)."""
    rng = np.random.default_rng(seed)
    A = rng.integers(0, 17, (C, W)).astype(float)
    y_rate = np.where(rng.random((C, W)) < 0.5, np.inf,
                      rng.uniform(0.01, 2.0, (C, W)))
    route = rng.dirichlet(np.ones(S), (C, W))
    svc = rng.uniform(20.0, 400.0, (C, W, S))
    o_eff = A * rng.uniform(8.0, 64.0, (C, W))
    tor = np.where(np.arange(C) % 3 == 0, 1e9, rng.uniform(32.0, 256.0, C))
    irq = np.full(C, 64.0)
    slots = rng.integers(1, 8, (C, S)).astype(float)
    Wq = rng.uniform(0.0, 50.0, (C, S))
    return (A, y_rate, o_eff, route, route * svc, svc + 5.0, slots, tor,
            irq, Wq)


def _f32(arrays):
    """Each input as the device sees it: clipped to 1e30, float32, with
    the ToR and IRQ capacities as (C, 1) columns."""
    out = [np.minimum(x, 1e30).astype(np.float32) for x in arrays]
    out[7], out[8] = out[7][:, None], out[8][:, None]
    return out


def test_window_pack_round_trip_is_exact():
    from repro.memsim.batched import kernel

    C, W, S = 6, 3, 4
    arrays = _window_inputs(C, W, S, 1)
    packed = kernel.pack_window(*arrays)
    assert packed.dtype == np.float32 and packed.flags.c_contiguous
    assert packed.shape == (C, 3 * W + 3 * W * S + 2 * S + 2)
    back = kernel.unpack_window(packed, W, S)
    assert len(back) == len(arrays) == 10
    for got, want in zip(back, _f32(arrays)):
        assert got.shape == want.shape
        assert np.array_equal(got, want)


@pytest.mark.parametrize("C,W,S", [(6, 3, 4), (512, 2, 3)])
def test_packed_window_solve_is_bit_identical(C, W, S):
    """One packed copy each way gives exactly what the relaxation gives on
    the ten float32 arrays sent one by one."""
    jax = pytest.importorskip("jax")
    from repro.memsim.batched import fluid, kernel

    arrays = _window_inputs(C, W, S, 7)
    relax = jax.jit(kernel.build_relaxation(fluid._N_OUTER, fluid._DAMP,
                                            interpret=True))
    want = [np.asarray(x, dtype=np.float64) for x in relax(*_f32(arrays))]
    got = kernel.fused_window_solve(*arrays, fluid._N_OUTER, fluid._DAMP)
    assert np.isinf(got[2]).any() and np.isfinite(got[2]).any()
    for g, w in zip(got, want):
        assert g.dtype == np.float64 and g.shape == w.shape
        assert np.array_equal(g, w, equal_nan=True)


def test_window_solve_makes_one_copy_each_way(caplog):
    """Two host-device copies per solve, and no device program besides the
    unpacking and the solver (no per-array float32 conversions)."""
    jax = pytest.importorskip("jax")
    import logging

    from repro.memsim.batched import fluid, kernel
    from repro.obs.metrics import default_registry

    transfers = default_registry().counter("lane.solve_transfers")
    arrays = _window_inputs(11, 2, 3, 3)  # a shape no other test compiles
    before = transfers.value
    with caplog.at_level(logging.WARNING), jax.log_compiles(True):
        kernel.fused_window_solve(*arrays, fluid._N_OUTER, fluid._DAMP)
    assert transfers.value - before == 2
    compiled = [r.getMessage().split()[1] for r in caplog.records
                if r.getMessage().startswith("Compiling ")]
    assert sorted(compiled) == ["jit(solve)", "jit(unpack_window)"]
    kernel.fused_window_solve(*arrays, fluid._N_OUTER, fluid._DAMP)
    assert transfers.value - before == 4
