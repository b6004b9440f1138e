"""Plain reference of the batched lane's whole co-run, MIKU ladder included.

The fluid model of a ``corun_sweep`` cell written out straight in numpy: a
DDR-bound and a CXL-bound bandwidth test share one socket's request queue
for ``sim_ns``; each control window solves the closed-network equilibrium
(``fluid_window.solve``), adds the window's inserts, occupancy and bytes,
and feeds the slow tier's counters to the MIKU ladder (the paper's Eq. 1
estimator, detection, hierarchical throttling, draining hysteresis and
work-conserving promotion), whose cap and rate throttle the CXL test in the
next window.  It imports nothing of the program: the deployment's
constants (stations, service times, queue bounds, MIKU calibration) come
from the configuration file, and it gives each cell's row as the scenario
reports it: ``ddr_gbps``, ``cxl_gbps`` and ``restricted_windows``.

``dtype`` is the precision of the window solve: float64 for the reference,
``ml_dtypes.bfloat16`` for the control one step below the float32 that the
configuration states.  Everything around the solve is float64.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Tuple

import numpy as np

from reference import fluid_window

#: A row's key: the axes that tell the grid's cells apart.
KEY = ("platform", "op", "threads", "mlp", "miku")


def grid_cells(grid: Dict[str, list]) -> List[dict]:
    """Every cell of ``grid`` (axis name -> values, in axis order)."""
    names = list(grid)
    vals = [v if isinstance(v, (list, tuple)) else [v] for v in grid.values()]
    return [dict(zip(names, combo)) for combo in itertools.product(*vals)]


def key(row: dict) -> Tuple:
    return tuple(row[k] for k in KEY)


def _ladder(cfg: dict, C: int) -> dict:
    m = cfg["miku"]
    return {"level": np.full(C, len(m["levels"]) - 1), "rate": np.ones(C),
            "calm": np.zeros(C, np.int64), "restricted": np.zeros(C, bool),
            "prev_raw": np.zeros(C), "has_prev": np.zeros(C, bool),
            "t_slow": np.zeros(C), "has_ewma": np.zeros(C, bool)}


def _ladder_window(st: dict, m: dict, t_fast0, scale, class_cap, rd, wr,
                   f_ins, f_occ, s_ins, s_occ) -> None:
    """One estimation window of every cell's slow-tier ladder, in place."""
    levels = np.asarray(m["levels"], float)
    n_lv = len(levels)
    tot_ins, tot_occ = f_ins + s_ins, f_occ + s_occ
    tot_rw = s_ins * (rd + wr)
    rf = np.where(tot_rw > 0, s_ins * rd / np.maximum(tot_rw, 1e-300), 1.0)
    wf = np.where(tot_rw > 0, s_ins * wr / np.maximum(tot_rw, 1e-300), 0.0)
    threshold = m["slow_read_threshold"] * (rf + wf * m["write_threshold_scale"])
    # The fast tier's service time, weighted by its class mix.
    t_fast = np.where(f_ins > 0,
                      t_fast0 * f_ins * scale / np.maximum(f_ins, 1.0), t_fast0)
    valid = (tot_ins >= m["min_window_inserts"]) & (
        s_ins >= m["min_slow_inserts"])
    t_avg = np.where(tot_ins > 0, tot_occ / np.maximum(tot_ins, 1e-300), 0.0)
    alpha = np.where(valid, f_ins / np.maximum(tot_ins, 1e-300),
                     np.where(s_ins == 0, 1.0, 0.0))
    slow_mean = np.where(s_ins > 0, s_occ / np.maximum(s_ins, 1e-300), 0.0)
    eq1 = (t_avg - alpha * t_fast) / np.maximum(1.0 - alpha, 1e-12)
    raw = np.maximum(np.where(alpha > m["alpha_calm"], slow_mean, eq1), 0.0)
    raw = np.where(valid, raw, 0.0)
    a = m["ewma"]
    ewma = np.where(st["has_ewma"], a * raw + (1 - a) * st["t_slow"], raw)
    st["t_slow"] = np.where(valid, ewma, st["t_slow"])
    st["has_ewma"] = st["has_ewma"] | valid
    backlogged = valid & (st["t_slow"] > threshold)

    was = st["restricted"]
    level, rate = st["level"], st["rate"]
    start = ~was & backlogged
    fast_idle = (~valid & (f_ins == 0)) | (valid & (alpha < m["fast_idle_alpha"]))
    release = was & fast_idle
    over = was & ~fast_idle & valid & (raw > threshold)
    draining = over & st["has_prev"] & (raw < st["prev_raw"] * m["drain_factor"])
    deeper = over & ~draining & (level > 0)
    back_off = over & ~draining & (level == 0)
    under = (was & ~fast_idle & ~over & valid
             & (raw < m["target_margin"] * threshold))
    hold = was & ~fast_idle & ~over & ~under
    calm = np.where(over | hold, 0, st["calm"])
    calm = np.where(under, calm + 1, calm)
    step_up = under & (calm >= m["promote_patience"])
    calm = np.where(step_up | release | start, 0, calm)
    recover = step_up & (rate < 1.0)
    promote = step_up & (rate >= 1.0)
    cap_here = np.where(s_ins > 0, class_cap, levels[-1])
    nxt = level + 1
    can = (nxt < n_lv) & (levels[np.minimum(nxt, n_lv - 1)]
                          <= np.maximum(cap_here, levels[0]))
    new_level = np.where(start | deeper, 0, level)
    new_level = np.where(release, n_lv - 1, new_level)
    new_level = np.where(promote & can, level + 1, new_level)
    new_rate = np.where(start | release, 1.0, rate)
    new_rate = np.where(back_off, np.maximum(m["min_rate"],
                                             rate * m["rate_backoff"]), new_rate)
    new_rate = np.where(recover, np.minimum(1.0, rate * m["rate_recover"]),
                        new_rate)
    st["level"], st["rate"], st["calm"] = new_level, new_rate, calm
    st["restricted"] = (was | start) & ~release
    st["prev_raw"] = np.where(valid, raw, st["prev_raw"])
    st["has_prev"] = st["has_prev"] | valid


def run(cells: List[dict], cfg: dict, dtype=np.float64) -> Dict[Tuple, dict]:
    """``{key: row}`` for ``cells`` (dicts over ``KEY`` and ``sim_ns``)."""
    plat = [cfg["platforms"][c["platform"]] for c in cells]
    ops = [cfg["ops"][c["op"]] for c in cells]
    m = cfg["miku"]
    C, g = len(cells), cfg["granularity"]
    win = float(cfg["window_ns"])
    sim_ns = np.array([float(c["sim_ns"]) for c in cells])

    # Stations: DDR, CXL, LLC.  Workload 0 reads DDR, workload 1 CXL.
    slots = np.array([p["slots"] for p in plat], float)
    pipe = np.array([list(p["pipe"]) + [0.0] for p in plat], float)
    svc_t = np.array([o["svc"] for o in ops], float)  # (C, tiers)
    svc = np.empty((C, 2, 3))
    svc[:, :, :2] = svc_t[:, None, :]
    svc[:, :, 2] = np.array([p["llc_svc"] for p in plat], float)[:, None]
    svc_pipe = svc + pipe[:, None, :]
    route = np.zeros((C, 2, 3))
    route[:, 0, 0] = route[:, 1, 1] = 1.0
    route_svc = route * svc
    tor = np.array([p["tor_capacity"] for p in plat], float)
    irq = np.array([p["irq_capacity"] for p in plat], float)
    cores = np.array([c["threads"] for c in cells], float)
    effmlp = np.array([max(1, int(c["mlp"]) // g) for c in cells], float)
    line = float(cfg["line_bytes"] * g)
    miku = np.array([bool(c["miku"]) for c in cells])

    t_fast0 = np.array([p["t_fast"] for p in plat], float)
    scale = np.array([m["t_fast_class_scale"][c["op"]] for c in cells])
    class_cap = np.array([m["class_caps"][c["op"]] for c in cells], float)
    rd = np.array([m["access_mix"][c["op"]][0] for c in cells], float)
    wr = np.array([m["access_mix"][c["op"]][1] for c in cells], float)
    levels = np.asarray(m["levels"], float)
    st = _ladder(cfg, C)

    cap = np.full(C, np.inf)  # the CXL test's core cap and issue rate
    rate = np.ones(C)
    Wq = np.zeros((C, 3))
    nbytes = np.zeros((C, 2))
    restricted = np.zeros(C, np.int64)
    n_win = sim_ns / win
    if np.any(n_win != np.round(n_win)) or np.any(n_win != n_win[0]):
        raise ValueError("cells must share one whole number of windows")
    for _ in range(int(n_win[0])):
        A = np.stack([cores, np.minimum(cores, cap)], axis=1)
        y_rate = np.stack(
            [np.full(C, np.inf),
             np.where(rate >= 1.0 - 1e-12, np.inf,
                      rate / np.maximum(svc_t[:, 1], 1e-9))], axis=1)
        y, Wq, _ = fluid_window.solve(
            A, y_rate, A * effmlp[:, None], route, route_svc, svc_pipe,
            slots, tor, irq, Wq, cfg["solver"]["n_outer"],
            cfg["solver"]["damp"], dtype=dtype)
        ins = y * win
        nbytes += ins * line
        f_ins, s_ins = ins[:, 0], ins[:, 1]
        f_occ = f_ins * (Wq[:, 0] + svc_pipe[:, 0, 0])
        s_occ = s_ins * (Wq[:, 1] + svc_pipe[:, 1, 1])
        _ladder_window(st, m, t_fast0, scale, class_cap, rd, wr,
                       f_ins, f_occ, s_ins, s_occ)
        # Only cells with MIKU on apply the ladder's decision.
        cap = np.where(miku & st["restricted"], levels[st["level"]], np.inf)
        rate = np.where(miku & st["restricted"], st["rate"], 1.0)
        restricted += miku & st["restricted"]
    gbps = nbytes / sim_ns[:, None]  # B/ns is GB/s
    return {key(c): {"ddr_gbps": float(gbps[i, 0]),
                     "cxl_gbps": float(gbps[i, 1]),
                     "restricted_windows": int(restricted[i])}
            for i, c in enumerate(cells)}
