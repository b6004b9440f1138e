"""Plain reference of the batched lane's per-window equilibrium.

One control window of the fluid co-run model: damped wait relaxation
around two water-filling bisections over the common per-core admission
rate (per station, then under the shared ToR population bound), written
out straight in numpy.  It imports nothing of the program; given the same
per-window inputs it gives the per-(cell, workload) insert rates ``y``,
the station waits ``Wq`` and the global rate ``lam`` (``+inf`` where the
ToR never fills).

``dtype`` is the precision of every array and every intermediate:
float64 for the reference, ``ml_dtypes.bfloat16`` for the control one
step below the float32 that the configuration states.
"""

from __future__ import annotations

import numpy as np

BISECT_ITERS = 48
EPS = 1e-9


def _station_lambdas(A, cap, route_svc, slots, f):
    C, W = A.shape
    S = slots.shape[1]
    hi0 = f(f(cap / np.maximum(A, f(1e-12))).max(axis=1) + f(1e-6))
    hi = f(np.broadcast_to(hi0[:, None], (C, S)).copy())
    lo = f(np.zeros((C, S)))

    def demand(lam):
        y = f(np.minimum(f(lam[:, None, :] * A[:, :, None]), cap[:, :, None]))
        return f(f(y * route_svc).sum(axis=1))

    feasible_at_cap = demand(hi) <= slots + f(EPS)
    for _ in range(BISECT_ITERS):
        mid = f(f(0.5) * f(lo + hi))
        ok = demand(mid) <= slots + f(EPS)
        lo = np.where(ok, mid, lo)
        hi = np.where(ok, hi, mid)
    return np.where(feasible_at_cap, f(np.inf), lo)


def _global_lambda(A, cap, y_sta, o_eff, R_tor, tor_cap, irq_cap, f):
    hi0 = f(f(cap / np.maximum(A, f(1e-12))).max(axis=1) + f(1e-6))
    lo = f(np.zeros(A.shape[0]))
    hi = hi0.copy()

    def feasible(lam):
        y_free = f(np.minimum(f(lam[:, None] * A), cap))
        y = np.minimum(y_free, y_sta)
        clamped = y_sta < f(y_free * f(1.0 - 1e-9))
        unc = f(np.minimum(o_eff, f(y * R_tor)))
        share = f(y / np.maximum(f(y.sum(axis=1, keepdims=True)), f(1e-12)))
        qb = f(np.maximum(f(o_eff - f(irq_cap[:, None] * share)), unc))
        pop = np.where(clamped, qb, unc)
        return f(pop.sum(axis=1)) <= tor_cap + f(EPS)

    at_cap = feasible(hi0)
    for _ in range(BISECT_ITERS):
        mid = f(f(0.5) * f(lo + hi))
        ok = feasible(mid)
        lo = np.where(ok, mid, lo)
        hi = np.where(ok, hi, mid)
    return np.where(at_cap, f(np.inf), lo)


def solve(A, y_rate, o_eff, route, route_svc, svc_pipe, slots, tor_cap,
          irq_cap, Wq, n_outer, damp, dtype=np.float64):
    """``(y, Wq, lam)`` after ``n_outer`` damped relaxation passes."""

    def f(x):
        return np.asarray(x, dtype=dtype)

    A, y_rate, o_eff, route, route_svc, svc_pipe, slots, tor_cap, irq_cap, \
        Wq = map(f, (A, y_rate, o_eff, route, route_svc, svc_pipe, slots,
                     tor_cap, irq_cap, Wq))
    big = f(1e30)
    used = route_svc > 1e-12
    R_base = f(f(route * svc_pipe).sum(axis=2))
    y = f(np.zeros(A.shape))
    lam = f(np.full(A.shape[0], np.inf))
    for _ in range(n_outer):
        r_sta = f(Wq[:, None, :] + svc_pipe)
        R_tor = f(f(route * r_sta).sum(axis=2))
        cap = f(np.minimum(y_rate, f(o_eff / np.maximum(R_tor, f(1e-9)))))
        cap = np.where(A > 0, cap, f(0.0))
        lam_s = _station_lambdas(A, cap, route_svc, slots, f)
        lam_min = np.where(used, lam_s[:, None, :], f(np.inf)).min(axis=2)
        y_sta = f(np.where(np.isfinite(lam_min), lam_min, big)
                  * np.maximum(A, f(0.0)))
        lam = _global_lambda(A, cap, y_sta, o_eff, R_tor, tor_cap, irq_cap, f)
        lam_b = np.where(np.isfinite(lam), lam, big)[:, None]
        y_free = f(np.minimum(f(lam_b * A), cap))
        y = np.minimum(y_free, y_sta)
        qb = (y_sta <= f(f(lam_b * A) * f(1.0 + 1e-9))) & (
            y_sta < f(cap * f(1.0 - 1e-9)))
        unc_pop = f(np.minimum(o_eff, f(y * R_tor)))
        share = f(y / np.maximum(f(y.sum(axis=1, keepdims=True)), f(1e-12)))
        pop_w = np.where(
            qb, f(np.maximum(f(o_eff - f(irq_cap[:, None] * share)), unc_pop)),
            unc_pop)
        d_s = f(f(y[:, :, None] * route_svc).sum(axis=1))
        inflow_s = f(f(y[:, :, None] * route).sum(axis=1))
        util = f(d_s / np.maximum(slots, f(1e-9)))
        sat = (util >= 0.98) & (slots > 0)
        n_pop = f(np.minimum(f(pop_w.sum(axis=1)), tor_cap))
        base_pop = f(f(y * R_base).sum(axis=1))
        q_total = f(np.maximum(f(n_pop - base_pop), f(0.0)))
        q_max = np.where(qb, f(np.maximum(f(pop_w - f(y * R_base)), f(0.0))),
                         f(0.0))
        q_sum = f(q_max.sum(axis=1))
        scale = np.where(
            q_sum > 1e-12,
            f(np.minimum(f(1.0), f(q_total / np.maximum(q_sum, f(1e-12))))),
            f(0.0))
        q_w = f(q_max * scale[:, None])
        w_st = np.where(sat[:, None, :], route_svc, f(0.0))
        w_norm = f(w_st.sum(axis=2, keepdims=True))
        w_st = np.where(w_norm > 1e-12,
                        f(w_st / np.maximum(w_norm, f(1e-12))), f(0.0))
        q_s = f(f(q_w[:, :, None] * w_st).sum(axis=1))
        mean_svc = f(d_s / np.maximum(inflow_s, f(1e-12)))
        w_new = f(f(q_s * mean_svc) / np.maximum(slots, f(1e-9)))
        w_new = np.where(sat, w_new, f(0.0))
        Wq = f(f(f(damp) * Wq) + f(f(1.0 - damp) * w_new))
    as64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    return as64(y), as64(Wq), as64(lam)
