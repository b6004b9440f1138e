"""Per-window equilibrium solvers for the batched lane.

The fluid engine reduces each control window to two water-filling
questions, both answered by bisection over the common per-core admission
rate λ (the fluid image of the DES's round-robin core arbitration):

* :func:`station_lambdas` — per-station fair rates: the largest λ each
  station can serve among its users (``+inf`` where unconstrained).  A
  workload held below its fair inflow by a saturated station queues — up
  to its MLP population — instead of inserting faster.
* :func:`global_lambda` — one λ per cell under the shared-ToR *population*
  constraint: each workload's ToR holding is ``min(O, y·R_tor)``, jumping
  to its full MLP population ``O`` once a saturated station clamps it
  below its fair share (its queue then soaks up every permit it has).
  When the summed holdings exceed the ToR, λ shrinks until they fit —
  FIFO admission ties every hungry workload to the same per-core share,
  which is the paper's unfair-queuing collapse in fluid form.

``global_lambda`` has two backends: numpy (default) and a Pallas kernel
(``REPRO_BATCH_BACKEND=pallas``) that runs the whole bisection on-device
(``jax.lax.fori_loop`` inside one ``pl.pallas_call``; compiled for the TPU,
interpreted on the CPU backend, refused anywhere else).  Both produce the
same fixed point to float tolerance — ``tests/test_batched.py`` pins
backend parity.  A failing Pallas backend raises: nothing falls back to
numpy behind the caller's back.

:func:`fused_window_solve` goes further: under the pallas backend the
fluid engine hands the *entire* per-window wait-relaxation loop (station
scaling, global-λ Pallas bisection, queue-builder population
accounting, Little's-law wait update — everything between routing setup
and the control-window fire) to one jit-compiled function, so a window
costs one device dispatch instead of ``n_outer`` python iterations of
einsums.  That is what scales 1k+-cell grids: the python overhead per
window becomes O(1) in cell count.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

_BISECT_ITERS = 48
_EPS = 1e-9


def backend() -> str:
    """Solver backend from ``REPRO_BATCH_BACKEND`` (numpy | pallas)."""
    return os.environ.get("REPRO_BATCH_BACKEND", "numpy").strip().lower()


def station_lambdas(
    A: np.ndarray, cap: np.ndarray, route_svc: np.ndarray, slots: np.ndarray
) -> np.ndarray:
    """Per-(cell, station) fair per-core rate.

    ``A``/``cap``: ``(C, W)`` active cores and per-workload issue-rate caps;
    ``route_svc``: ``(C, W, S)`` expected service seconds each inserted
    request demands from station ``s``; ``slots``: ``(C, S)`` server counts
    (0 = padding).  Returns ``(C, S)`` λ, ``+inf`` where the station can
    serve every user at their cap."""
    C, W = A.shape
    S = slots.shape[1]
    hi0 = (cap / np.maximum(A, 1e-12)).max(axis=1) + 1e-6  # y saturates here
    hi = np.broadcast_to(hi0[:, None], (C, S)).copy()
    lo = np.zeros((C, S))

    def demand(lam):
        y = np.minimum(lam[:, None, :] * A[:, :, None], cap[:, :, None])
        return (y * route_svc).sum(axis=1)

    feasible_at_cap = demand(hi) <= slots + _EPS
    for _ in range(_BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        ok = demand(mid) <= slots + _EPS
        lo = np.where(ok, mid, lo)
        hi = np.where(ok, hi, mid)
    return np.where(feasible_at_cap, np.inf, lo)


def _population(lam, A, cap, y_sta, o_eff, R_tor, irq_cap):
    """Per-workload ToR holdings at per-core rate ``lam`` (see module doc).

    A queue-builder's holdings are its MLP population minus its share of
    the (full, at the boundary) IRQ — staged requests count against MLP
    but hold no ToR entry."""
    y_free = np.minimum(lam[:, None] * A, cap)
    y = np.minimum(y_free, y_sta)
    clamped = y_sta < y_free * (1.0 - 1e-9)
    unclamped_pop = np.minimum(o_eff, y * R_tor)
    share = y / np.maximum(y.sum(axis=1, keepdims=True), 1e-12)
    qb_pop = np.maximum(o_eff - irq_cap[:, None] * share, unclamped_pop)
    return y, np.where(clamped, qb_pop, unclamped_pop)


def _global_lambda_numpy(A, cap, y_sta, o_eff, R_tor, tor_cap, irq_cap):
    C = A.shape[0]
    hi0 = (cap / np.maximum(A, 1e-12)).max(axis=1) + 1e-6
    lo = np.zeros(C)
    hi = hi0.copy()

    def feasible(lam):
        _, pop = _population(lam, A, cap, y_sta, o_eff, R_tor, irq_cap)
        return pop.sum(axis=1) <= tor_cap + _EPS

    feasible_at_cap = feasible(hi0)
    for _ in range(_BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        ok = feasible(mid)
        lo = np.where(ok, mid, lo)
        hi = np.where(ok, hi, mid)
    return np.where(feasible_at_cap, np.inf, lo)


_pallas_solver = None


def interpret_mode(platform: str) -> bool:
    """Whether Pallas kernels run interpreted on ``platform``.

    Only the CPU backend interprets (tests and host rehearsals); the TPU
    compiles the kernel.  Any other platform has no kernel to run."""
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas solver runs on 'tpu' (compiled) or 'cpu' "
        f"(interpreted), not on {platform!r}"
    )


def _default_interpret() -> bool:
    import jax

    return interpret_mode(jax.default_backend())


def _glam_kernel(jax, jnp):
    """The global-λ bisection as a Pallas kernel body (shared by the
    standalone :func:`global_lambda` backend and the fused window solver)."""

    def kernel(a_ref, cap_ref, ysta_ref, oeff_ref, rtor_ref, tor_ref,
               irq_ref, hi_ref, out_ref):
        A = a_ref[:]              # (C, W)
        cap = cap_ref[:]          # (C, W)
        y_sta = ysta_ref[:]       # (C, W)
        o_eff = oeff_ref[:]       # (C, W)
        r_tor = rtor_ref[:]       # (C, W)
        tor = tor_ref[:]          # (C, 1)
        irq = irq_ref[:]          # (C, 1)
        hi0 = hi_ref[:]           # (C, 1)

        def feasible(lam):        # lam (C, 1) -> (C, 1) bool
            y_free = jnp.minimum(lam * A, cap)
            y = jnp.minimum(y_free, y_sta)
            clamped = y_sta < y_free * (1.0 - 1e-9)
            unc = jnp.minimum(o_eff, y * r_tor)
            share = y / jnp.maximum(y.sum(axis=1, keepdims=True), 1e-12)
            pop = jnp.where(
                clamped, jnp.maximum(o_eff - irq * share, unc), unc
            )
            return pop.sum(axis=1, keepdims=True) <= tor + _EPS

        def body(_, lo_hi):
            lo, hi = lo_hi
            mid = 0.5 * (lo + hi)
            ok = feasible(mid)
            return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

        lo = jnp.zeros_like(hi0)
        lo, _ = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi0))
        out_ref[:] = jnp.where(feasible(hi0), jnp.inf, lo)

    return kernel


def _build_pallas_solver(interpret: bool):
    """Compile the bisection as one Pallas kernel."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kernel = _glam_kernel(jax, jnp)

    @jax.jit
    def solve(A, cap, y_sta, o_eff, r_tor, tor, irq, hi0):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(hi0.shape, jnp.float32),
            interpret=interpret,
        )(A, cap, y_sta, o_eff, r_tor, tor, irq, hi0)

    return solve


def _global_lambda_pallas(A, cap, y_sta, o_eff, R_tor, tor_cap, irq_cap):
    global _pallas_solver
    import jax.numpy as jnp

    if _pallas_solver is None:
        _pallas_solver = _build_pallas_solver(_default_interpret())
    big = 1e30  # f32-safe stand-in for +inf inputs
    f32 = lambda x: jnp.asarray(np.minimum(x, big), jnp.float32)  # noqa: E731
    hi0 = (np.minimum(cap, big) / np.maximum(A, 1e-12)).max(axis=1) + 1e-6
    lam = _pallas_solver(
        f32(A), f32(cap), f32(y_sta), f32(o_eff), f32(R_tor),
        f32(tor_cap[:, None]), f32(irq_cap[:, None]), f32(hi0[:, None]),
    )
    return np.asarray(lam, dtype=np.float64)[:, 0]


def global_lambda(
    A: np.ndarray,
    cap: np.ndarray,
    y_sta: np.ndarray,
    o_eff: np.ndarray,
    R_tor: np.ndarray,
    tor_cap: np.ndarray,
    irq_cap: np.ndarray,
    force_backend: Optional[str] = None,
) -> np.ndarray:
    """Max common per-core rate per cell under the ToR population bound.

    ``cap`` is the issue-side cap (token rate and MLP); ``y_sta`` the
    per-workload fair station-capacity share; ``o_eff`` the MLP population
    bound; ``R_tor`` the per-insert ToR residency; ``irq_cap`` the staging
    queue each queue-builder's MLP partly parks in.  Returns ``(C,)`` λ,
    ``+inf`` where the ToR never fills."""
    if (force_backend or backend()) == "pallas":
        return _global_lambda_pallas(
            A, cap, y_sta, o_eff, R_tor, tor_cap, irq_cap
        )
    return _global_lambda_numpy(
        A, cap, y_sta, o_eff, R_tor, tor_cap, irq_cap
    )


_fused_solvers: dict = {}


def fused_solver_args(C: int, W: int, S: int, sharding=None) -> tuple:
    """Shape stand-ins for the fused solver's arguments at ``(C, W, S)``
    cells × workloads × stations, for lowering it without data."""
    import jax
    import jax.numpy as jnp

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    cw, cws = f32(C, W), f32(C, W, S)
    return (cw, cw, cw, cws, cws, cws, f32(C, S), f32(C, 1), f32(C, 1),
            f32(C, S))


def fused_solver(n_outer: int, damp: float):
    """The jitted window solver for this platform, built once per setting."""
    key = (int(n_outer), float(damp))
    solver = _fused_solvers.get(key)
    if solver is None:
        solver = _fused_solvers[key] = build_fused_solver(
            *key, _default_interpret()
        )
    return solver


def build_fused_solver(n_outer: int, damp: float, interpret: bool):
    """Compile the whole wait-relaxation loop as one jit function.

    The outer loop (``n_outer`` damped iterations), the station bisection,
    and the global-λ Pallas bisection all run inside a single ``jax.jit``
    trace, so the fluid engine pays one dispatch per window regardless of
    cell count.  f32 throughout with ``1e30`` standing in for ``+inf``.
    ``interpret`` comes from the caller (:func:`interpret_mode` of the
    platform that will run it)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    glam_kernel = _glam_kernel(jax, jnp)
    big = 1e30

    def glam(A, cap, y_sta, o_eff, r_tor, tor, irq):
        hi0 = (jnp.minimum(cap, big)
               / jnp.maximum(A, 1e-12)).max(axis=1, keepdims=True) + 1e-6
        return pl.pallas_call(
            glam_kernel,
            out_shape=jax.ShapeDtypeStruct(hi0.shape, jnp.float32),
            interpret=interpret,
        )(A, cap, y_sta, o_eff, r_tor, tor, irq, hi0)

    def station_lams(A, cap, route_svc, slots):
        hi0 = (cap / jnp.maximum(A, 1e-12)).max(axis=1) + 1e-6  # (C,)
        hi = jnp.broadcast_to(hi0[:, None], slots.shape)
        lo = jnp.zeros_like(hi)

        def demand(lam):
            y = jnp.minimum(lam[:, None, :] * A[:, :, None], cap[:, :, None])
            return (y * route_svc).sum(axis=1)

        feasible_at_cap = demand(hi) <= slots + _EPS

        def body(_, lo_hi):
            lo, hi = lo_hi
            mid = 0.5 * (lo + hi)
            ok = demand(mid) <= slots + _EPS
            return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

        lo, _ = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
        return jnp.where(feasible_at_cap, big, lo)

    @jax.jit
    def solve(A, y_rate, o_eff, route, route_svc, svc_pipe, slots, tor,
              irq, Wq0):
        # Mirrors the numpy relaxation in fluid.run_fluid line for line;
        # tor/irq arrive as (C, 1) columns for in-kernel broadcasting.
        R_base = (route * svc_pipe).sum(axis=2)
        used = route_svc > 1e-12

        def outer(_, state):
            y, Wq, lam = state
            r_sta = Wq[:, None, :] + svc_pipe
            R_tor = (route * r_sta).sum(axis=2)
            cap = jnp.minimum(y_rate, o_eff / jnp.maximum(R_tor, 1e-9))
            cap = jnp.where(A > 0, cap, 0.0)
            lam_s = station_lams(A, cap, route_svc, slots)
            lam_min = jnp.where(used, lam_s[:, None, :], big).min(axis=2)
            y_sta = jnp.minimum(lam_min, big) * jnp.maximum(A, 0.0)
            lam = glam(A, cap, y_sta, o_eff, R_tor, tor, irq)  # (C, 1)
            lam_b = jnp.minimum(lam, big)
            y_free = jnp.minimum(lam_b * A, cap)
            y = jnp.minimum(y_free, y_sta)
            qb = (y_sta <= lam_b * A * (1.0 + 1e-9)) & (
                y_sta < cap * (1.0 - 1e-9)
            )
            unc_pop = jnp.minimum(o_eff, y * R_tor)
            share = y / jnp.maximum(y.sum(axis=1, keepdims=True), 1e-12)
            pop_w = jnp.where(
                qb, jnp.maximum(o_eff - irq * share, unc_pop), unc_pop
            )
            d_s = jnp.einsum("cw,cws->cs", y, route_svc)
            inflow_s = jnp.einsum("cw,cws->cs", y, route)
            util = d_s / jnp.maximum(slots, 1e-9)
            sat = (util >= 0.98) & (slots > 0)
            n_pop = jnp.minimum(pop_w.sum(axis=1), tor[:, 0])
            base_pop = (y * R_base).sum(axis=1)
            q_total = jnp.maximum(n_pop - base_pop, 0.0)
            q_max = jnp.where(qb, jnp.maximum(pop_w - y * R_base, 0.0), 0.0)
            q_sum = q_max.sum(axis=1)
            scale = jnp.where(
                q_sum > 1e-12,
                jnp.minimum(1.0, q_total / jnp.maximum(q_sum, 1e-12)), 0.0,
            )
            q_w = q_max * scale[:, None]
            w_st = jnp.where(sat[:, None, :], route_svc, 0.0)
            w_norm = w_st.sum(axis=2, keepdims=True)
            w_st = jnp.where(
                w_norm > 1e-12, w_st / jnp.maximum(w_norm, 1e-12), 0.0
            )
            q_s = jnp.einsum("cw,cws->cs", q_w, w_st)
            mean_svc = d_s / jnp.maximum(inflow_s, 1e-12)
            w_new = q_s * mean_svc / jnp.maximum(slots, 1e-9)
            w_new = jnp.where(sat, w_new, 0.0)
            Wq = damp * Wq + (1.0 - damp) * w_new
            return y, Wq, lam

        y0 = jnp.zeros_like(A)
        lam0 = jnp.full((A.shape[0], 1), jnp.inf, jnp.float32)
        y, Wq, lam = jax.lax.fori_loop(
            0, n_outer, outer, (y0, Wq0, lam0)
        )
        return y, Wq, lam[:, 0]

    return solve


def fused_window_solve(
    A: np.ndarray,
    y_rate: np.ndarray,
    o_eff: np.ndarray,
    route: np.ndarray,
    route_svc: np.ndarray,
    svc_pipe: np.ndarray,
    slots: np.ndarray,
    tor_cap: np.ndarray,
    irq_cap: np.ndarray,
    Wq: np.ndarray,
    n_outer: int,
    damp: float,
) -> tuple:
    """One jit dispatch for a window's full wait-relaxation loop.

    Numpy in / numpy out: arrays go to f32 on device (``1e30`` standing in
    for ``+inf`` rate caps) and come back float64.  Returns ``(y, Wq, lam)``
    with ``lam`` the last iteration's global λ — ``+inf`` where the ToR
    never fills, so ``np.isfinite(lam)`` stays the coupling test.  Raises
    on any jax failure.
    """
    import jax.numpy as jnp

    solver = fused_solver(n_outer, damp)
    big = 1e30
    f32 = lambda x: jnp.asarray(np.minimum(x, big), jnp.float32)  # noqa: E731
    y, wq, lam = solver(
        f32(A), f32(y_rate), f32(o_eff), f32(route), f32(route_svc),
        f32(svc_pipe), f32(slots), f32(tor_cap[:, None]),
        f32(irq_cap[:, None]), f32(Wq),
    )
    return (
        np.asarray(y, dtype=np.float64),
        np.asarray(wq, dtype=np.float64),
        np.asarray(lam, dtype=np.float64),
    )
