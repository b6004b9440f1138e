"""DES microbenchmark: fast-path rewrite vs the seed DES, interleaved A/B.

Measures the fig5 co-run config (LOAD, 16+16 threads, 300 us simulated) on
both the current DES and the pinned seed snapshot
(``benchmarks/_seed_des_baseline.py``), alternating reps so container CPU
throttling hits both sides equally, and verifies the Fig. 3/5 bandwidths
against the recorded seed goldens (they are bit-identical by construction;
1% is the gate).  Also runs the sweep-scale lane A/B: the 96-cell
``corun_sweep`` grid on the scalar process pool vs the batched lane
(``repro.memsim.batched``; ≥5x is the acceptance bar, with the cross-lane
bandwidth deviation recorded alongside), and the kilo-cell A/B/C: the
1024-cell ``corun_sweep_1k`` grid on the scalar pool vs the batched lane
under both solver backends (numpy and the fused jit/Pallas window solver,
``REPRO_BATCH_BACKEND=pallas``; the gate bounds control-decision flips
and the decision-aligned p95 bandwidth deviation — see
``_SWEEP1K_MAX_FLIPS``).  Emits ``BENCH_des.json`` at the repo root.

Usage:  PYTHONPATH=src python benchmarks/bench_des.py [--reps N] [--out PATH]
        PYTHONPATH=src python benchmarks/bench_des.py --sweep-1k   # CI slow
        PYTHONPATH=src python benchmarks/bench_des.py --smoke      # CI fast
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_HERE)
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from repro.core.des import run_bw_test, run_corun
from repro.core.device_model import platform_a
from repro.core.littles_law import OpClass

from benchmarks import _seed_des_baseline as seed_des

_GOLDENS = os.path.join(_REPO_ROOT, "tests", "data", "seed_fig_goldens.json")

#: Every time below is host wall seconds: the DES is host Python, and the
#: Pallas solver runs interpreted unless JAX's platform is the TPU.
_CLOCK = "host CPU wall seconds; Pallas interpreted off-TPU"


def _time(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def bench_ab(reps: int) -> dict:
    p = platform_a()
    kw = dict(op=OpClass.LOAD, n_threads=16, sim_ns=300_000)
    seed_t, new_t = [], []
    completed = 0
    for _ in range(reps):
        seed_t.append(_time(lambda: seed_des.run_corun(p, **kw)))
        t0 = time.perf_counter()
        res = run_corun(p, **kw)
        new_t.append(time.perf_counter() - t0)
        completed = sum(s.completed for s in res.stats.values())
    return {
        "config": "fig5_corun_load_16t_300us",
        "seed_wall_s": {"best": round(min(seed_t), 4),
                        "median": round(statistics.median(seed_t), 4)},
        "corun_wall_s": {"best": round(min(new_t), 4),
                         "median": round(statistics.median(new_t), 4)},
        "speedup_vs_seed": round(min(seed_t) / min(new_t), 2),
        "speedup_vs_seed_median": round(
            statistics.median(seed_t) / statistics.median(new_t), 2),
        "events_per_s": int(completed / min(new_t)),
        "completed_requests": completed,
    }


def check_goldens() -> dict:
    p = platform_a()
    with open(_GOLDENS) as f:
        gold = json.load(f)
    worst = 0.0
    for row in gold["fig3"]:
        op = OpClass(row["op"])
        r = run_bw_test(p, op=op, tier=row["tier"], n_threads=16,
                        sim_ns=120_000)
        bw = r.bandwidth(f"bw-{row['tier']}-{op.value}")
        worst = max(worst, abs(bw - row["bandwidth_gbps"])
                    / max(row["bandwidth_gbps"], 1e-9))
    for opv, g in gold["fig5"].items():
        both = run_corun(p, op=OpClass(opv), n_threads=16, sim_ns=300_000)
        worst = max(worst, abs(both.bandwidth("ddr") - g["ddr_gbps"])
                    / max(g["ddr_gbps"], 1e-9))
        worst = max(worst, abs(both.bandwidth("cxl") - g["cxl_gbps"])
                    / max(g["cxl_gbps"], 1e-9))
    return {
        "goldens_within_1pct": worst < 0.01,
        "goldens_max_rel_err": worst,
    }


def bench_sweep_lanes() -> dict:
    """Sweep-scale lane A/B: the 96-cell ``corun_sweep`` grid, scalar
    process pool vs the batched lane (``repro.memsim.batched``).

    The batched side runs twice and keeps the warm time (first call pays
    numpy/ladder setup); the scalar side runs once through the pool the
    ``--jobs`` path would use.  Also records the worst per-cell bandwidth
    deviation between the lanes — the speedup is only meaningful while the
    lanes agree."""
    import os as _os

    from repro.memsim.sweep import run_sweep
    from repro.scenarios import plan

    jobs = [j for _, _, js in plan("corun_sweep") for j in js]
    procs = max(2, min(8, _os.cpu_count() or 1))
    t0 = time.perf_counter()
    batched = run_sweep(jobs, lane="batched")
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_sweep(jobs, lane="batched")
    t_batched = min(t_cold, time.perf_counter() - t0)
    t0 = time.perf_counter()
    scalar = run_sweep(jobs, processes=procs, lane="scalar")
    t_scalar = time.perf_counter() - t0
    errs = []
    for s, b in zip(scalar, batched):
        for w in ("ddr", "cxl"):
            errs.append(abs(b.bandwidth(w) - s.bandwidth(w))
                        / max(s.bandwidth(w), 1e-9))
    return {
        "sweep_scenario": "corun_sweep",
        "sweep_cells": len(jobs),
        "scalar_pool_procs": procs,
        "scalar_pool_wall_s": round(t_scalar, 3),
        "batched_wall_s": round(t_batched, 3),
        "batched_speedup": round(t_scalar / max(t_batched, 1e-9), 1),
        "batched_speedup_ge_5x": t_scalar / max(t_batched, 1e-9) >= 5.0,
        "lane_worst_rel_err": round(max(errs), 4),
        "lane_mean_rel_err": round(sum(errs) / len(errs), 4),
    }


#: Kilo-grid lane gate.  A dense MLP × thread sweep necessarily contains
#: knife-edge cells where the MIKU restriction threshold sits between the
#: two lanes' bandwidth estimates — the lanes then take *different control
#: decisions* and the bandwidth gap is the (real, large) gap between the
#: restricted and unrestricted operating points, not a fluid-model error.
#: The gate therefore (a) bounds how many cells may flip decisions, and
#: (b) bounds the p95 bandwidth error over the decision-aligned cells.
#: Measured on the seed machine: 4/1024 flips, aligned p95 6.5%.
_SWEEP1K_MAX_FLIPS = 12
_SWEEP1K_P95_BOUND = 0.08


def lane_gate(ref, cand) -> dict:
    """The kilo-grid gate of ``cand`` against ``ref``, two runs of one grid.

    Each is a per-cell sequence of ``(restricted, {workload: GB/s})``:
    whether MIKU restricted the cell, and its bandwidths.  Cells whose
    decision differs count as flips; the rest give the aligned worst-of-
    workloads relative bandwidth deviations."""
    errs, flips = [], 0
    for (r_ref, bw_ref), (r, bw) in zip(ref, cand):
        if r_ref != r:
            flips += 1
            continue
        errs.append(max(abs(bw[w] - bw_ref[w]) / max(bw_ref[w], 1e-9)
                        for w in bw_ref))
    errs.sort()
    p95 = errs[int(0.95 * (len(errs) - 1))] if errs else 0.0
    return {
        "decision_flip_cells": flips,
        "aligned_p95_rel_err": p95,
        "aligned_worst_rel_err": errs[-1] if errs else 0.0,
        "within_gate": (flips <= _SWEEP1K_MAX_FLIPS
                        and p95 <= _SWEEP1K_P95_BOUND),
    }


def bench_sweep_1k() -> dict:
    """Kilo-cell lane A/B/C: the 1024-cell ``corun_sweep_1k`` grid on the
    scalar pool, the batched numpy lane, and the batched lane with the
    fused jit/Pallas window solver (``REPRO_BATCH_BACKEND=pallas``).

    Each batched side runs twice and keeps the warm time (the first pallas
    call pays jit tracing).  Gates each backend against the scalar DES on
    decision flips + aligned-cell p95 error (see ``_SWEEP1K_MAX_FLIPS``),
    recording the worst aligned/overall deviations for transparency."""
    import os as _os

    from repro.core.controller import Phase
    from repro.memsim.sweep import run_sweep
    from repro.scenarios import plan

    jobs = [j for _, _, js in plan("corun_sweep_1k") for j in js]
    procs = max(2, min(8, _os.cpu_count() or 1))

    def timed_batched():
        t0 = time.perf_counter()
        res = run_sweep(jobs, lane="batched")
        t_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_sweep(jobs, lane="batched")
        return res, min(t_cold, time.perf_counter() - t0)

    numpy_res, t_numpy = timed_batched()
    prev = _os.environ.get("REPRO_BATCH_BACKEND")
    _os.environ["REPRO_BATCH_BACKEND"] = "pallas"
    try:
        pallas_res, t_pallas = timed_batched()
    finally:
        if prev is None:
            _os.environ.pop("REPRO_BATCH_BACKEND", None)
        else:
            _os.environ["REPRO_BATCH_BACKEND"] = prev
    t0 = time.perf_counter()
    scalar = run_sweep(jobs, processes=procs, lane="scalar")
    t_scalar = time.perf_counter() - t0

    def cells(results):
        return [(any(d.phase == Phase.RESTRICTED for d in r.decisions),
                 {w: r.bandwidth(w) for w in ("ddr", "cxl")})
                for r in results]

    st_np = lane_gate(cells(scalar), cells(numpy_res))
    st_pl = lane_gate(cells(scalar), cells(pallas_res))
    return {
        "sweep_scenario": "corun_sweep_1k",
        "sweep_cells": len(jobs),
        "scalar_pool_procs": procs,
        "scalar_pool_wall_s": round(t_scalar, 3),
        "batched_wall_s": round(t_numpy, 3),
        "batched_speedup": round(t_scalar / max(t_numpy, 1e-9), 1),
        "batched_speedup_ge_5x": t_scalar / max(t_numpy, 1e-9) >= 5.0,
        "pallas_wall_s": round(t_pallas, 3),
        "pallas_speedup": round(t_scalar / max(t_pallas, 1e-9), 1),
        "numpy_lane": st_np,
        "pallas_lane": st_pl,
        "max_decision_flips": _SWEEP1K_MAX_FLIPS,
        "aligned_p95_bound": _SWEEP1K_P95_BOUND,
        "lanes_within_gate": st_np["within_gate"] and st_pl["within_gate"],
    }


def bench_obs(reps: int = 4) -> dict:
    """Observability overhead A/B: the fig5 co-run config with tracing +
    histograms + profiling off vs on, interleaved reps.

    Two gates: (a) the instrumented run must cost < 10% wall time over the
    plain run; (b) the instrumented run's simulation outcome (bandwidth,
    latency sums, ToR inserts) must be *bit-identical* — the deterministic
    sampler draws no random numbers, so observability must never perturb
    the simulation."""
    import dataclasses

    from repro.memsim.sweep import SimJob, run_job
    from repro.memsim.workloads import bw_test

    p = platform_a()
    wls = [
        bw_test("ddr", OpClass.LOAD, 16, name="ddr", miku_managed=False),
        bw_test("cxl", OpClass.LOAD, 16, name="cxl"),
    ]
    base = SimJob(platform=p, workloads=wls, sim_ns=300_000.0, miku=True)
    obs = dataclasses.replace(
        base, trace=64, latency_hist=True, profile=True
    )
    off_t, on_t = [], []
    r_off = r_on = None
    for _ in range(reps):
        t0 = time.perf_counter()
        r_off = run_job(base)
        off_t.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        r_on = run_job(obs)
        on_t.append(time.perf_counter() - t0)
    identical = all(
        r_off.stats[w].bytes == r_on.stats[w].bytes
        and r_off.stats[w].latency_sum == r_on.stats[w].latency_sum
        and r_off.stats[w].completed == r_on.stats[w].completed
        for w in ("ddr", "cxl")
    ) and r_off.tor_inserts == r_on.tor_inserts
    overhead = (min(on_t) / max(min(off_t), 1e-9) - 1.0) * 100.0
    return {
        "config": "fig5_corun_load_16t_300us",
        "plain_wall_s": round(min(off_t), 4),
        "instrumented_wall_s": round(min(on_t), 4),
        "obs_overhead_pct": round(overhead, 2),
        "obs_within_10pct": overhead < 10.0,
        "obs_bit_identical": identical,
        "traced_requests": r_on.trace["n_traced"],
        "phase_profile": r_on.profile,
    }


def check_fast_path_overhead(out: dict, snapshot_path: str) -> dict:
    """Two-tier fast-path overhead gate for the per-tier contract.

    Compares this run's interleaved A/B speedup-vs-seed against the
    committed BENCH_des.json snapshot's.  The speedup ratio is
    machine-robust (both sides of each A/B pair ran on the same box), so a
    drop > 5% means the control-plane change itself slowed the two-tier
    hot path."""
    try:
        with open(snapshot_path) as f:
            snap = json.load(f)
        snap_speedup = float(snap["speedup_vs_seed"])
    except (OSError, KeyError, ValueError):
        return {"fast_path_overhead_pct": None, "fast_path_within_5pct": True}
    overhead = (snap_speedup / max(out["speedup_vs_seed"], 1e-9) - 1.0) * 100.0
    return {
        "snapshot_speedup_vs_seed": snap_speedup,
        "fast_path_overhead_pct": round(overhead, 2),
        "fast_path_within_5pct": overhead < 5.0,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--out", default=os.path.join(_REPO_ROOT, "BENCH_des.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="quick 2-rep timing print (no file write) — the CI "
                         "gating-lane smoke")
    ap.add_argument("--sweep-1k", action="store_true",
                    help="run only the 1024-cell grid A/B/C (numpy + pallas "
                         "batched vs scalar pool; no file write) and gate on "
                         "the <=8%% cross-lane bound — the CI slow-lane job")
    ap.add_argument("--obs", action="store_true",
                    help="run only the observability overhead A/B (tracing/"
                         "histograms/profiler on vs off; no file write) and "
                         "gate on <10%% overhead + bit-identical outcomes — "
                         "the CI gating-lane obs smoke")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    snapshot = os.path.join(_REPO_ROOT, "BENCH_des.json")
    if args.smoke:
        out = {"bench": "des_fast_path_smoke", **bench_ab(2)}
        out.update(check_fast_path_overhead(out, snapshot))
        print(json.dumps(out, indent=2))
        return
    if args.obs:
        out = {"bench": "des_obs_overhead", **bench_obs(max(args.reps, 3))}
        print(json.dumps(out, indent=2))
        assert out["obs_bit_identical"], (
            "observability instrumentation perturbed the simulation "
            "(bandwidth/latency/ToR counters differ with tracing on)"
        )
        assert out["obs_within_10pct"], (
            f"observability instrumentation added {out['obs_overhead_pct']}% "
            "wall time on the co-run config (>10% budget)"
        )
        return
    if args.sweep_1k:
        out = {"bench": "des_sweep_1k", "clock": _CLOCK, **bench_sweep_1k()}
        print(json.dumps(out, indent=2))
        assert out["lanes_within_gate"], (
            f"batched lanes off the scalar DES on the 1024-cell grid "
            f"(numpy {out['numpy_lane']}, pallas {out['pallas_lane']})"
        )
        if not out["batched_speedup_ge_5x"]:
            print("WARNING: batched lane below the 5x acceptance bar on "
                  "the 1024-cell grid (noisy machine, or a regression)")
        return
    out = {"bench": "des_fast_path", "clock": _CLOCK,
           **bench_ab(args.reps), **check_goldens()}
    out.update(check_fast_path_overhead(out, snapshot))
    out["sweep_lanes"] = bench_sweep_lanes()
    out["sweep_1k"] = bench_sweep_1k()
    out["observability"] = bench_obs(args.reps)
    print(json.dumps(out, indent=2))
    if out["speedup_vs_seed"] < 2.0:
        print("WARNING: speedup below the 2x acceptance bar "
              "(noisy machine, or a fast-path regression)")
    if not out["sweep_lanes"]["batched_speedup_ge_5x"]:
        print("WARNING: batched lane below the 5x acceptance bar vs the "
              "scalar pool (noisy machine, or a batched-lane regression)")
    if not out["sweep_1k"]["batched_speedup_ge_5x"]:
        print("WARNING: batched lane below the 5x acceptance bar on the "
              "1024-cell grid (noisy machine, or a batched-lane regression)")
    assert out["sweep_1k"]["lanes_within_gate"], (
        "batched lanes off the scalar DES on the 1024-cell grid "
        "(decision flips or aligned-p95 out of bounds); snapshot left "
        "untouched"
    )
    assert out["observability"]["obs_bit_identical"], (
        "observability instrumentation perturbed the simulation; "
        "snapshot left untouched"
    )
    assert out["observability"]["obs_within_10pct"], (
        f"observability added {out['observability']['obs_overhead_pct']}% "
        "on the co-run config (>10% budget); snapshot left untouched"
    )
    # Gate BEFORE writing: a failing run must not replace the snapshot it
    # was compared against (the baseline would self-ratchet downward).
    assert out["fast_path_within_5pct"], (
        f"per-tier contract added {out['fast_path_overhead_pct']}% on the "
        "two-tier fast path vs the BENCH_des.json snapshot (>5% budget); "
        "snapshot left untouched"
    )
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")


if __name__ == "__main__":
    main()
