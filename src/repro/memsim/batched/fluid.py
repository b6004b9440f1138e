"""The window-lockstep fluid engine: one numpy step advances every cell.

Each control window is modeled as a closed-network equilibrium of the same
structures the DES simulates event-by-event (§4.2): cores with bounded MLP
issuing round-robin, the FIFO IRQ/ToR admission path, per-tier device
stations, the optional LLC station, and the shared ToR population bound.
Two regimes per cell per window, matching the scalar dynamics:

* **uncoupled** — the ToR has room: each workload runs at its own issue
  cap (MLP / token rate), clamped to the fair share of any saturated
  station it routes traffic through.
* **coupled** — the combined queue appetite exceeds the ToR: every
  admission is a fair per-core share (FIFO arbitration), so one λ governs
  all workloads and a saturated slow station collapses the fast tier's
  inserts — the paper's unfair-queuing mechanism in fluid form.

Station waits relax to put the queued population where the saturated
stations are (Little's law both ways), the per-tier window counters feed
the vectorized MIKU ladders (:class:`repro.core.controller.
VectorMikuLadder`), and the resulting tier-addressed caps/rates throttle
the next window — the same sample → estimate → decide → apply loop as
:class:`repro.core.substrate.ControlLoop`, evaluated across all cells at
once.
"""

from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

from repro.core.controller import (
    Decision,
    Phase,
    TierDecisions,
    VectorMikuLadder,
)
from repro.core.des import SimResult, WorkloadStats
from repro.core.littles_law import OpClass, TierCounters, TierEstimate
from repro.core.substrate import _decision_jsonable
from repro.memsim.batched import kernel
from repro.memsim.batched.stacking import BatchGroup
from repro.memsim.batched.tiering import VectorTiering, build_tiering
from repro.obs.metrics import default_registry, span

_OPS = tuple(OpClass)
_N_OUTER = 30  # wait-relaxation iterations per window
_DAMP = 0.5


def build_ladder(group: BatchGroup) -> Optional[VectorMikuLadder]:
    """The group's stacked vector ladder (None when no cell has MIKU).

    Raises ``ValueError`` for unstackable ladder configurations — the lane
    catches that during planning and falls the group back to the scalar
    DES."""
    grid = [
        p.units if p.units else []
        for p in group.plans
    ]
    if not any(grid):
        return None
    return VectorMikuLadder.from_units(grid)


def _relax_numpy(A, y_rate, o_eff, route, route_svc, svc_pipe, slots,
                 tor_cap, irq_cap, Wq):
    """The window's wait relaxation and water-filling in numpy: the
    :func:`~repro.memsim.batched.kernel.fused_window_solve` loop, one
    einsum at a time.  Returns ``(y, Wq, coupled)``."""
    y = np.zeros(A.shape)
    coupled = np.zeros(A.shape[0], bool)
    used = route_svc > 1e-12
    for _ in range(_N_OUTER):
        r_sta = Wq[:, None, :] + svc_pipe
        R_tor = (route * r_sta).sum(axis=2)
        R_base = (route * svc_pipe).sum(axis=2)
        # Issue-side caps: token-bucket rate and the MLP population
        # (waits included — a backlogged tier slows its own
        # issuers).
        cap = np.minimum(y_rate, o_eff / np.maximum(R_tor, 1e-9))
        cap = np.where(A > 0, cap, 0.0)
        lam_s = kernel.station_lambdas(
            A, cap, route_svc, slots
        )
        lam_min = np.where(
            used, lam_s[:, None, :], np.inf
        ).min(axis=2)
        # Inactive (padded) workload slots have no used station:
        # their lam_min is +inf and A is 0 — clamp before
        # multiplying so the product is 0, not NaN.
        y_sta = np.where(np.isfinite(lam_min), lam_min, 1e30) \
            * np.maximum(A, 0.0)
        lam = kernel.global_lambda(
            A, cap, y_sta, o_eff, R_tor, tor_cap,
            irq_cap,
        )
        coupled = np.isfinite(lam)
        lam_b = np.where(np.isfinite(lam), lam, 1e30)[:, None]
        y_free = np.minimum(lam_b * A, cap)
        y = np.minimum(y_free, y_sta)
        # Queue-builders: held at their station share while their
        # admission allowance (λ·A) and issue caps still have
        # headroom — their queue soaks up permits up to the MLP
        # population (minus the IRQ-staged share), which is what
        # fills the ToR at the feasibility boundary.
        qb = (y_sta <= lam_b * A * (1.0 + 1e-9)) & (
            y_sta < cap * (1.0 - 1e-9)
        )
        unc_pop = np.minimum(o_eff, y * R_tor)
        share = y / np.maximum(y.sum(axis=1, keepdims=True), 1e-12)
        pop_w = np.where(
            qb,
            np.maximum(
                o_eff - irq_cap[:, None] * share, unc_pop
            ),
            unc_pop,
        )

        # Wait relaxation: the queued population (ToR holdings
        # beyond service + flight) sits at the saturated stations
        # of the station-clamped workloads; Little's law converts
        # queue depth to wait.
        d_s = np.einsum("cw,cws->cs", y, route_svc)
        inflow_s = np.einsum("cw,cws->cs", y, route)
        util = d_s / np.maximum(slots, 1e-9)
        sat = (util >= 0.98) & (slots > 0)
        n_pop = np.minimum(pop_w.sum(axis=1), tor_cap)
        base_pop = (y * R_base).sum(axis=1)
        q_total = np.maximum(n_pop - base_pop, 0.0)
        q_max = np.where(
            qb, np.maximum(pop_w - y * R_base, 0.0), 0.0
        )
        q_sum = q_max.sum(axis=1)
        scale = np.where(
            q_sum > 1e-12, np.minimum(1.0, q_total / np.maximum(
                q_sum, 1e-12)), 0.0
        )
        q_w = q_max * scale[:, None]
        w_st = np.where(sat[:, None, :], route_svc, 0.0)
        w_norm = w_st.sum(axis=2, keepdims=True)
        w_st = np.where(
            w_norm > 1e-12, w_st / np.maximum(w_norm, 1e-12), 0.0
        )
        q_s = np.einsum("cw,cws->cs", q_w, w_st)
        mean_svc = d_s / np.maximum(inflow_s, 1e-12)
        w_new = q_s * mean_svc / np.maximum(slots, 1e-9)
        w_new = np.where(sat, w_new, 0.0)
        Wq = _DAMP * Wq + (1.0 - _DAMP) * w_new
    return y, Wq, coupled


def run_fluid(
    group: BatchGroup,
    ladder: Optional[VectorMikuLadder] = None,
    tiering: Optional[VectorTiering] = None,
) -> List[SimResult]:
    """Run one stacked cell group to its horizons; SimResults in group order.

    ``ladder``/``tiering`` are the group's pre-built :func:`build_ladder` /
    :func:`~repro.memsim.batched.tiering.build_tiering` results (built here
    when omitted)."""
    C, W, S, T = (len(group.plans), group.n_wl, group.n_st, group.n_tiers)
    llc = group.llc
    win = group.window_ns
    n_ops = len(_OPS)
    merged = np.array([p.merged for p in group.plans])
    has_ctl = np.array([bool(p.units) for p in group.plans])
    n_slow_cell = group.n_tiers_cell - 1
    U = max(1, T - 1)

    if ladder is None:
        ladder = build_ladder(group)
    if tiering is None:
        tiering = build_tiering(group)
    vt = tiering
    record_mask = np.array(
        [bool(p.job.record_windows) for p in group.plans]
    )
    # Analytic latency histograms (repro.obs): fluid cells have no per-
    # request events, so each window contributes one weighted entry — the
    # window's mean station latency at the window's completion count —
    # per workload (and per tier from the device-station split).  Same
    # mergeable bucket layout as the scalar lane; parity is toleranced,
    # not exact (documented in docs/observability.md).
    hist_mask = np.array(
        [bool(getattr(p.job, "latency_hist", False)) for p in group.plans]
    )
    hist_on = bool(hist_mask.any())
    LatencyHistogram = None
    hist_w: Optional[list] = None
    hist_t: Optional[list] = None
    if hist_on:
        from repro.obs.histogram import LatencyHistogram

        hist_w = [
            [LatencyHistogram() for _ in range(W)] if hist_mask[ci] else None
            for ci in range(C)
        ]
        hist_t = [
            [LatencyHistogram() for _ in range(T)] if hist_mask[ci] else None
            for ci in range(C)
        ]

    # Station-shaped constants: device service per (c, w, s) with the LLC
    # column; pipeline per station (LLC has none).
    pipe_st = np.zeros((C, W, S))
    pipe_st[:, :, :T] = group.pipe[:, None, :T]
    svc = group.svc  # (C, W, S): tiers then llc
    svc_pipe = svc + pipe_st  # per-insert station residency sans queueing
    op_onehot = np.zeros((C, W, n_ops))
    for o in range(n_ops):
        op_onehot[:, :, o] = group.op == o
    has_phases = any(
        seq is not None for row in group.phases for seq in row
    )

    # Throttle state written by the ladder (tier-addressed, like apply()).
    tier_cap = np.full((C, max(1, T - 1)), np.inf)
    tier_rate = np.ones((C, max(1, T - 1)))
    Wq = np.zeros((C, S))  # station waits, warm-started across windows
    # Live issue tables written by the tiering twin: routing vectors
    # (placement re-resolution) and effective MLP (migration issue gating),
    # the fluid image of the scalar hook's ``_apply_placements`` /
    # ``_w_effmlp`` writes.  Without tiering they never change.
    tier_frac_live = group.tier_frac.copy()
    effmlp_live = group.effmlp.copy()
    # On the TPU the whole relaxation loop runs as one jit dispatch per
    # window (kernel.fused_window_solve); a failure raises.
    use_fused = kernel.uses_fused_solver()

    # Accumulators.
    bytes_w = np.zeros((C, W))
    completed_w = np.zeros((C, W))
    latsum_w = np.zeros((C, W))
    ins_t = np.zeros((C, T))
    occ_t = np.zeros((C, T))
    cls_t = np.zeros((C, T, n_ops))
    occ_int_t = np.zeros((C, T))
    tor_inserts = np.zeros(C)
    tor_occ = np.zeros(C)
    tor_peak = np.zeros(C)
    decisions: List[list] = [[] for _ in range(C)]
    timelines: List[List[np.ndarray]] = [[] for _ in range(C)]
    records: List[List[dict]] = [[] for _ in range(C)]
    fired_count = np.zeros(C, np.int64)

    n_seg = int(np.max(np.ceil(group.sim_ns / win - 1e-9))) if C else 0
    windows = default_registry().counter("lane.windows")
    for k in range(n_seg):
        t0 = np.full(C, k * win)
        t1 = np.minimum(t0 + win, group.sim_ns)
        seg_len = np.maximum(t1 - t0, 0.0)
        active = seg_len > 1e-12
        if not active.any():
            break
        windows.inc()
        with span("lane.window"):
            fire = active & (t1 >= t0 + win - 1e-9)

            # -- routing & throttles for this window --------------------------
            with span("lane.route"):
                frac = (
                    group.window_fracs(t0, t1, base=tier_frac_live)
                    if has_phases else tier_frac_live
                )  # (C, W, T)
                p = group.p_llc
                route = np.zeros((C, W, S))
                lottery = (p >= 0.0) & (p <= 1.0)
                p_llc = np.where(p == 2.0, 1.0, np.where(lottery, p, 0.0))
                route[:, :, :T] = frac * (1.0 - p_llc)[:, :, None]
                route[:, :, llc] = p_llc
                touched = group.managed[:, :, None] & (frac[:, :, 1:] > 1e-12)
                cap_full = np.where(touched, tier_cap[:, None, :T - 1], np.inf)
                w_cap = cap_full.min(axis=2) if T > 1 else np.full((C, W), np.inf)
                rate_full = np.where(touched, tier_rate[:, None, :T - 1], 1.0)
                w_rate = rate_full.min(axis=2) if T > 1 else np.ones((C, W))
                A = np.minimum(group.cores, w_cap)
                A = np.where(group.active_w, np.maximum(A, 0.0), 0.0)
                e_cost = (frac * svc[:, :, :T]).sum(axis=2)
                y_rate = np.where(
                    w_rate >= 1.0 - 1e-12, np.inf,
                    w_rate / np.maximum(e_cost, 1e-9),
                )
                o_eff = A * effmlp_live
                route_svc = route * svc

            # -- equilibrium solve (wait relaxation + water-filling) ----------
            with span("lane.solve"):
                if use_fused:
                    y, Wq, lam = kernel.fused_window_solve(
                        A, y_rate, o_eff, route, route_svc, svc_pipe,
                        group.slots, group.tor_cap, group.irq_cap, Wq,
                        _N_OUTER, _DAMP,
                    )
                    coupled = np.isfinite(lam)
                else:
                    y, Wq, coupled = _relax_numpy(
                        A, y_rate, o_eff, route, route_svc, svc_pipe,
                        group.slots, group.tor_cap, group.irq_cap, Wq,
                    )

            # -- accumulate window counters -----------------------------------
            with span("lane.accumulate"):
                dt = np.where(active, seg_len, 0.0)
                ins_w = y * dt[:, None]
                r_sta = Wq[:, None, :] + svc_pipe
                R_tor = (route * r_sta).sum(axis=2)
                y_tot = y.sum(axis=1)
                w_irq = np.where(
                    coupled, group.irq_cap / np.maximum(y_tot, 1e-9), 0.0
                )
                route_dev = route[:, :, :T]
                ins_dev = np.einsum("cw,cwt->cwt", ins_w, route_dev)
                ins_t += ins_dev.sum(axis=1)
                occ_dev = ins_dev * r_sta[:, :, :T]
                occ_t += occ_dev.sum(axis=1)
                cls_w = np.einsum("cwt,cwo->cto", ins_dev, op_onehot)
                cls_t += cls_w
                bytes_win = ins_w * (frac * group.bytes_t).sum(axis=2)
                bytes_w += bytes_win
                completed_w += ins_w
                lat_mean = R_tor + w_irq[:, None]  # (C, W) analytic mean latency
                latsum_w += ins_w * lat_mean
                if hist_on:
                    lat_dev = r_sta[:, :, :T] + w_irq[:, None, None]
                    for ci in np.flatnonzero(hist_mask & active):
                        hw = hist_w[ci]
                        for wi in range(W):
                            cnt = float(ins_w[ci, wi])
                            if cnt > 0.0:
                                hw[wi].record_weighted(
                                    float(lat_mean[ci, wi]), cnt)
                        ht = hist_t[ci]
                        for ti in range(T):
                            cnt = float(ins_dev[ci, :, ti].sum())
                            if cnt > 0.0:
                                mean_t = float(
                                    (ins_dev[ci, :, ti]
                                     * lat_dev[ci, :, ti]).sum() / cnt
                                )
                                ht[ti].record_weighted(mean_t, cnt)
                tor_inserts += ins_w.sum(axis=1)
                pop = np.minimum((y * R_tor).sum(axis=1), group.tor_cap)
                tor_occ += pop * dt
                tor_peak = np.maximum(tor_peak, pop)
                llc_res = route[:, :, llc] * r_sta[:, :, llc]
                occ_int_t += (
                    occ_dev + np.einsum("cw,cwt->cwt", ins_w * llc_res, frac)
                ).sum(axis=1)
                for ci in np.flatnonzero(fire):
                    timelines[ci].append(((k + 1) * win, bytes_win[ci].copy()))

            # -- fire the control window (decisions apply to the next one) ----
            if not fire.any():
                continue
            out = None
            if ladder is not None:
                with span("lane.ladder"):
                    f_ins = ins_dev[:, :, 0].sum(axis=1)
                    f_occ = occ_dev[:, :, 0].sum(axis=1)
                    f_cls = cls_w[:, 0]
                    s_ins = np.zeros((C, U))
                    s_occ = np.zeros((C, U))
                    s_cls = np.zeros((C, U, n_ops))
                    slow_ins_t = ins_dev.sum(axis=1)[:, 1:]  # (C, T-1)
                    slow_occ_t = occ_dev.sum(axis=1)[:, 1:]
                    slow_cls_t = cls_w[:, 1:]
                    per_tier = ~merged
                    n_avail = min(U, T - 1)
                    s_ins[per_tier, :n_avail] = slow_ins_t[per_tier, :n_avail]
                    s_occ[per_tier, :n_avail] = slow_occ_t[per_tier, :n_avail]
                    s_cls[per_tier, :n_avail] = slow_cls_t[per_tier, :n_avail]
                    s_ins[merged, 0] = slow_ins_t[merged].sum(axis=1)
                    s_occ[merged, 0] = slow_occ_t[merged].sum(axis=1)
                    s_cls[merged, 0] = slow_cls_t[merged].sum(axis=1)
                    out = ladder.window(f_ins, f_occ, f_cls, s_ins, s_occ, s_cls)

            # Tier-addressed apply: per-tier caps/rates for the next window.
            # (has_ctl implies the ladder exists, so ``out`` is never None
            # here.)
            with span("lane.apply"):
                for ci in np.flatnonzero(fire & has_ctl):
                    ns = int(n_slow_cell[ci])
                    names = group.plans[ci].export["tier_names"][1:]
                    ds = []
                    for u in range(ns):
                        uu = 0 if merged[ci] else u
                        if merged[ci] and u > 0:
                            ds.append(ds[0])
                            tier_cap[ci, u] = tier_cap[ci, 0]
                            tier_rate[ci, u] = tier_rate[ci, 0]
                            continue
                        cap_v = out["cap"][ci, uu]
                        rate_v = out["rate"][ci, uu]
                        tier_cap[ci, u] = cap_v
                        tier_rate[ci, u] = rate_v
                        est = TierEstimate(
                            t_avg=float(out["t_avg"][ci, uu]),
                            alpha=float(out["alpha"][ci, uu]),
                            t_slow=float(out["t_slow"][ci, uu]),
                            t_slow_raw=float(out["t_slow_raw"][ci, uu]),
                            threshold=float(out["threshold"][ci, uu]),
                            backlogged=bool(out["backlogged"][ci, uu]),
                            valid=bool(out["valid"][ci, uu]),
                        )
                        restricted = bool(out["restricted"][ci, uu])
                        ds.append(Decision(
                            max_concurrency=(
                                None if not restricted or math.isinf(cap_v)
                                else int(cap_v)
                            ),
                            rate_factor=float(rate_v),
                            phase=(Phase.RESTRICTED if restricted
                                   else Phase.UNRESTRICTED),
                            estimate=est,
                        ))
                    decisions[ci].append(
                        TierDecisions(tiers=tuple(names), decisions=tuple(ds))
                    )

            # -- tiering pass: migrations, hotness, placements (post-fire) ----
            if vt is not None:
                with span("lane.tiering"):
                    if out is not None:
                        budgets = ladder.migration_budgets()
                        restr = np.asarray(out["restricted"], bool).copy()
                        if merged.any():
                            # The merged law broadcasts its single decision
                            # to every slow tier — same for its restricted
                            # bit.
                            restr[merged] = restr[merged][:, :1]
                        has_budgets = has_ctl & ~merged
                        has_decisions = has_ctl
                    else:
                        budgets = restr = None
                        has_budgets = np.zeros(C, bool)
                        has_decisions = np.zeros(C, bool)
                    vt.step(
                        fire, ins_w, budgets, restr, has_budgets, has_decisions,
                        (k + 1) * win, tier_frac_live, effmlp_live,
                    )

            # -- vectorized telemetry: window_record_jsonable-shaped dicts ----
            # straight from the stacked per-window arrays (scalar schema: the
            # ControlLoop record, with the tiering hook's block merged in).
            with span("lane.telemetry"):
                fired_count += fire
                for ci in np.flatnonzero(fire & record_mask):
                    has_t = vt is not None and vt.cell_act[ci]
                    has_h = bool(hist_on and hist_mask[ci])
                    if not has_ctl[ci] and not has_t and not has_h:
                        continue  # scalar ControlLoop records nothing either
                    rec: dict = {
                        "window": int(fired_count[ci]),
                        "t_ns": float((k + 1) * win),
                    }
                    if has_ctl[ci]:
                        nt = int(group.n_tiers_cell[ci])
                        names = group.plans[ci].export["tier_names"]
                        rec["tiers"] = {
                            names[t]: {
                                "inserts": int(round(ins_dev[ci, :, t].sum())),
                                "occupancy_time": float(occ_dev[ci, :, t].sum()),
                                "class_counts": {
                                    op.value: int(round(cls_w[ci, t, o]))
                                    for o, op in enumerate(_OPS)
                                },
                            }
                            for t in range(nt)
                        }
                        rec["decision"] = {
                            t: _decision_jsonable(td)
                            for t, td in decisions[ci][-1].items()
                        }
                    if has_t:
                        entry = vt.window_log[ci][-1]
                        rec["tiering"] = {
                            key: v for key, v in entry.items()
                            if key not in ("window", "t_ns")
                        }
                    if has_h:
                        # One weighted entry per workload — the window's
                        # analytic contribution, same shape as the scalar
                        # per-window blocks.
                        lh = {}
                        w_names = group.plans[ci].export["w_names"]
                        for wi, nm in enumerate(w_names):
                            h = LatencyHistogram()
                            cnt = float(ins_w[ci, wi])
                            if cnt > 0.0:
                                h.record_weighted(float(lat_mean[ci, wi]), cnt)
                            lh[nm] = h.to_jsonable()
                        rec["latency_hist"] = lh
                    records[ci].append(rec)

    # -- materialize SimResults -------------------------------------------
    with span("lane.materialize"):
        results: List[SimResult] = []
        for ci, plan in enumerate(group.plans):
            e = plan.export
            nt = e["n_tiers"]
            names = e["tier_names"]
            stats = {}
            for wi, name in enumerate(e["w_names"]):
                st = WorkloadStats()
                st.completed = int(round(completed_w[ci, wi]))
                st.bytes = float(bytes_w[ci, wi])
                st.latency_sum = float(latsum_w[ci, wi])
                st.latency_count = st.completed
                mean = st.latency_sum / max(1, st.latency_count)
                # The fluid lane has no per-request reservoir; percentiles
                # degenerate to the mean (documented in docs/decision-laws.md).
                st.latency_samples = [mean] if st.completed else []
                st.timeline = [
                    (t, float(b[wi])) for t, b in timelines[ci]
                ]
                if hist_on and hist_mask[ci]:
                    st.latency_hist = hist_w[ci][wi]
                stats[name] = st
            tcs = {}
            for t in range(nt):
                tc = TierCounters()
                tc.inserts = int(round(ins_t[ci, t]))
                tc.occupancy_time = float(occ_t[ci, t])
                tc.class_counts = {
                    op: int(round(cls_t[ci, t, o]))
                    for o, op in enumerate(_OPS)
                }
                tcs[names[t]] = tc
            results.append(SimResult(
                sim_ns=float(group.sim_ns[ci]),
                stats=stats,
                tier_counters=tcs,
                tor_peak=int(math.ceil(tor_peak[ci])),
                tor_occupancy_integral=float(tor_occ[ci]),
                tor_inserts=int(round(tor_inserts[ci])),
                decisions=decisions[ci],
                per_tier_occupancy_integral={
                    names[t]: float(occ_int_t[ci, t]) for t in range(nt)
                },
                window_records=records[ci] if plan.job.record_windows else [],
                tiering=vt.summary(ci) if vt is not None else None,
                tier_latency_hist=(
                    {names[t]: hist_t[ci][t] for t in range(nt)}
                    if hist_on and hist_mask[ci] else None
                ),
            ))
    return results
