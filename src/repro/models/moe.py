"""Mixture-of-Experts FFN with sort-based token dispatch.

Covers dbrx (16 experts, top-4, fine-grained) and llama4-maverick (128
experts, top-1, plus a shared expert).  Dispatch is the MaxText-style
sort/gather/scatter pipeline — *not* one-hot dispatch einsums, whose
[tokens x experts x capacity] contractions would add O(T^2) FLOPs at 128
experts and drown the roofline's useful-compute ratio.

:func:`held_moe_apply` is the expert layer of one expert-parallel share
(granite-4.0-h): it routes over every expert, holds the weights of a
contiguous range of them, and computes their part of the result without
dropping a token.

Expert weights are stacked [E, ...] and logically sharded over the
``experts`` axis (expert parallelism); the gather/scatter pair is what GSPMD
turns into the all-to-all (baseline) — the perf pass replaces it with an
explicit shard_map dispatch where profitable.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.autosharding import constrain
from repro.models.layers import Axes, Params, dense_init


def moe_init(
    key,
    d_model: int,
    d_ff: int,
    n_experts: int,
    dtype,
    *,
    stacked: Optional[int] = None,
    shared_expert_ff: int = 0,
) -> Tuple[Params, Axes]:
    kr, kg, ku, kd, ksg, ksu, ksd = jax.random.split(key, 7)
    lead = (stacked,) if stacked else ()
    lead_ax = ("layers",) if stacked else ()
    params: Params = {
        "router": dense_init(kr, d_model, lead + (d_model, n_experts), dtype),
        "w_gate": dense_init(kg, d_model, lead + (n_experts, d_model, d_ff), dtype),
        "w_up": dense_init(ku, d_model, lead + (n_experts, d_model, d_ff), dtype),
        "w_down": dense_init(kd, d_ff, lead + (n_experts, d_ff, d_model), dtype),
    }
    axes: Axes = {
        "router": lead_ax + ("embed", "experts_r"),
        "w_gate": lead_ax + ("experts", "embed", "ffn"),
        "w_up": lead_ax + ("experts", "embed", "ffn"),
        "w_down": lead_ax + ("experts", "ffn", "embed"),
    }
    if shared_expert_ff > 0:
        params["shared"] = {
            "w_gate": dense_init(
                ksg, d_model, lead + (d_model, shared_expert_ff), dtype
            ),
            "w_up": dense_init(
                ksu, d_model, lead + (d_model, shared_expert_ff), dtype
            ),
            "w_down": dense_init(
                ksd, shared_expert_ff, lead + (shared_expert_ff, d_model), dtype
            ),
        }
        axes["shared"] = {
            "w_gate": lead_ax + ("embed", "ffn"),
            "w_up": lead_ax + ("embed", "ffn"),
            "w_down": lead_ax + ("ffn", "embed"),
        }
    return params, axes


def moe_apply(
    params: Params,
    x: jax.Array,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    activation: str = "silu",
) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (out [B, S, D], load-balance aux loss scalar).

    Under an active logical-sharding context with a >1 data axis, the
    dispatch runs shard_map-manual over the batch axes: the token sort,
    capacity ranking and scatter are *local per data shard* (capacity is
    per-shard), so there is no global argsort and — critically — no
    replicated [E, C, D] scatter buffer for GSPMD to all-reduce (tens of
    TB/step on dbrx otherwise).  Expert weights enter through replicated
    in_specs (one FSDP all-gather's worth) while their expert dimension
    stays auto-sharded over ``model`` (EP).
    """
    from repro.distributed.autosharding import _top

    ctx = _top()
    if ctx is not None:
        mesh, _rules = ctx
        data_axes = tuple(
            a for a in ("pod", "data")
            if a in mesh.shape and mesh.shape[a] > 1
        )
        n_shards = 1
        for a in data_axes:
            n_shards *= mesh.shape[a]
        # NOTE: the shard_map dispatch path triggers an XLA CPU crash
        # ("Invalid binary instruction opcode copy") under scan+remat in
        # jax 0.8.2; the pure-GSPMD path below achieves locality with
        # explicit sharding constraints instead.  Flip to re-enable on TPU.
        _SHARD_MAP_DISPATCH = False
        if _SHARD_MAP_DISPATCH and n_shards > 1 and x.shape[0] % n_shards == 0:
            return _moe_apply_sharded(
                params, x, mesh, data_axes,
                top_k=top_k, capacity_factor=capacity_factor,
                activation=activation,
            )
    return _moe_apply_local(
        params, x, top_k=top_k, capacity_factor=capacity_factor,
        activation=activation,
    )


def _moe_apply_sharded(params, x, mesh, data_axes, *, top_k,
                       capacity_factor, activation):
    from jax.sharding import PartitionSpec as P

    dn = data_axes if len(data_axes) > 1 else data_axes[0]

    def body(x_l, router, w_gate, w_up, w_down, shared):
        sub = {"router": router, "w_gate": w_gate, "w_up": w_up,
               "w_down": w_down}
        if shared is not None:
            sub["shared"] = shared
        out_l, aux_l = _moe_apply_local(
            sub, x_l, top_k=top_k, capacity_factor=capacity_factor,
            activation=activation, use_constraints=False,
        )
        return out_l, jax.lax.pmean(aux_l, data_axes)

    shared = params.get("shared")
    in_specs = (
        P(dn), P(), P(), P(), P(),
        (jax.tree.map(lambda _: P(), shared) if shared is not None else None),
    )
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(dn), P()),
        axis_names=set(data_axes),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"], shared)
    return out, aux


def _moe_apply_local(
    params: Params,
    x: jax.Array,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    activation: str = "silu",
    use_constraints: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Shard-major dispatch: tokens are viewed as [NS, T_local, D] with the
    leading dim on the batch mesh axes.  Every sort/rank/scatter is batched
    over that axis (vmap), so under GSPMD each device executes its own
    *local* dispatch — no global argsort, no cross-shard scatter for the
    partitioner to replicate-and-all-reduce.  Capacity is per shard
    (standard per-device capacity semantics).  NS=1 without a mesh context
    (tests, single device) — then this is the plain global algorithm."""
    from repro.distributed.autosharding import _top

    b, s, d = x.shape
    e = params["router"].shape[-1]
    t = b * s

    ns = 1
    ctx = _top()
    if use_constraints and ctx is not None:
        mesh, _ = ctx
        cand = 1
        for a in ("pod", "data"):
            if a in mesh.shape:
                cand *= mesh.shape[a]
        if cand > 1 and b % cand == 0:
            ns = cand
    tl = t // ns

    x3 = x.reshape(ns, tl, d)
    if use_constraints:
        x3 = constrain(x3, ("data_shards", "moe_tok", "embed_act"))

    router_logits = jnp.einsum(
        "ntd,de->nte", x3, params["router"]
    ).astype(jnp.float32)
    gates = jax.nn.softmax(router_logits, axis=-1)  # [NS, TL, E]
    top_vals, top_idx = jax.lax.top_k(gates, top_k)  # [NS, TL, k]
    top_vals = top_vals / jnp.maximum(
        jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9
    )

    # Load-balance auxiliary loss (Switch-style): E * sum_e f_e * p_e.
    me = jnp.mean(gates, axis=(0, 1))  # [E]
    assign_mean = jnp.zeros((e,), jnp.float32).at[top_idx.reshape(-1)].add(
        1.0 / (t * top_k)
    )
    aux_loss = e * jnp.sum(me * assign_mean)

    capacity = int(max(top_k, capacity_factor * tl * top_k / e))
    capacity = min(capacity, tl)

    flat_e = top_idx.reshape(ns, tl * top_k)  # [NS, TL*k]
    sort_idx = jnp.argsort(flat_e, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=1)
    # Rank of each request within its expert's arrival order (per shard).
    group_start = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(e), side="left")
    )(sorted_e)  # [NS, E]
    rank = jnp.arange(tl * top_k)[None, :] - jnp.take_along_axis(
        group_start, sorted_e, axis=1
    )
    keep = rank < capacity
    slot = sorted_e * capacity + rank
    token_of = sort_idx // top_k
    gate_of = jnp.take_along_axis(
        top_vals.reshape(ns, tl * top_k), sort_idx, axis=1
    )

    # Dispatch: per-shard scatter into [E*C, D] (out-of-capacity dropped).
    safe_slot = jnp.where(keep, slot, e * capacity)

    def scatter_one(slot_l, src_l):
        buf = jnp.zeros((e * capacity, d), x.dtype)
        return buf.at[slot_l].set(src_l, mode="drop")

    src = jnp.take_along_axis(x3, token_of[..., None], axis=1)  # [NS,TL*k,D]
    xe = jax.vmap(scatter_one)(safe_slot, src)  # [NS, E*C, D]
    xe = xe.reshape(ns, e, capacity, d)
    if use_constraints:
        xe = constrain(xe, ("data_shards", "experts", "moe_cap_l",
                            "embed_act"))

    # Expert FFNs: E over model (EP), NS over data.  Gather the FSDP weight
    # shards first — otherwise GSPMD partial-sums the contraction and
    # all-reduces [NS, E, C, F] activations.
    if use_constraints:
        wg = constrain(params["w_gate"], ("experts", "gathered", "gathered"))
        wu = constrain(params["w_up"], ("experts", "gathered", "gathered"))
        wd = constrain(params["w_down"], ("experts", "gathered", "gathered"))
    else:
        wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    g = jnp.einsum("necd,edf->necf", xe, wg)
    u = jnp.einsum("necd,edf->necf", xe, wu)
    act = jax.nn.silu(g) if activation == "silu" else jax.nn.gelu(
        g, approximate=True)
    y = jnp.einsum("necf,efd->necd", act * u, wd)

    # Combine: per-shard gather + weighted scatter-add back to tokens.
    y_flat = y.reshape(ns, e * capacity, d)
    gather_slot = jnp.where(keep, slot, 0)
    contrib = jnp.take_along_axis(y_flat, gather_slot[..., None], axis=1)
    contrib = contrib * (keep.astype(x.dtype) * gate_of.astype(x.dtype))[
        ..., None
    ]

    def combine_one(tok_l, con_l):
        return jnp.zeros((tl, d), x.dtype).at[tok_l].add(con_l)

    out = jax.vmap(combine_one)(token_of, contrib)  # [NS, TL, D]
    out = out.reshape(b, s, d)
    if use_constraints:
        out = constrain(out, ("batch", "seq", "embed_act"))

    if "shared" in params:
        sh = params["shared"]
        xf = x.reshape(t, d)
        g2 = jnp.einsum("td,df->tf", xf, sh["w_gate"])
        u2 = jnp.einsum("td,df->tf", xf, sh["w_up"])
        a2 = jax.nn.silu(g2) if activation == "silu" else jax.nn.gelu(
            g2, approximate=True)
        out = out + jnp.einsum("tf,fd->td", a2 * u2, sh["w_down"]).reshape(
            b, s, d)

    return out, aux_loss


# ---------------------------------------------------------------------------
# One expert-parallel share: routed over all experts, computed for the held
# ---------------------------------------------------------------------------


def held_moe_init(
    key,
    d_model: int,
    d_ff: int,
    n_experts: int,
    held: Tuple[int, int],
    dtype,
    *,
    stacked: Optional[int] = None,
    shared_expert_ff: int = 0,
) -> Tuple[Params, Axes]:
    """A router over all ``n_experts`` and the weights of experts
    ``[held[0], held[1])`` only (plus the shared MLP, which every share
    holds whole).  The gate and up weights are laid out [E, F, D], with
    the contracted width minor, as the TPU's long-prompt products want it
    (a [E, D, F] stack is copied whole into that layout for them)."""
    lo, hi = held
    kr, ke = jax.random.split(key)
    params, axes = moe_init(ke, d_model, d_ff, hi - lo, dtype,
                            stacked=stacked,
                            shared_expert_ff=shared_expert_ff)
    lead = (stacked,) if stacked else ()
    lead_ax = ("layers",) if stacked else ()
    params["router"] = dense_init(kr, d_model, lead + (d_model, n_experts),
                                  dtype)
    for name in ("w_gate", "w_up"):
        params[name] = jnp.swapaxes(params[name], -2, -1)
        axes[name] = lead_ax + ("experts", "ffn", "embed")
    return params, axes


def held_moe_apply(
    params: Params,
    x: jax.Array,
    *,
    top_k: int,
    held: Tuple[int, int],
    activation: str = "silu",
) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (this share's output [B, S, D], held experts that
    at least one token was routed to).

    Granite's router: float32 logits over every expert, the ``top_k``
    largest, and a softmax over those ``top_k`` logits alone.  Each held
    expert runs on every token, weighted by that token's gate for it (0
    where the token was routed elsewhere), so no token is dropped and a
    token routed to experts held on other chips adds nothing here.  The
    gate and up projections of all held experts are one matrix product,
    and so are the down projections and their weighted sum."""
    b, s, d = x.shape
    lo, hi = held
    xf = x.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xf, params["router"],
                        preferred_element_type=jnp.float32)
    top_vals, top_idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top_vals, axis=-1)  # [T, k]
    ids = lo + jnp.arange(hi - lo)
    weight = jnp.sum(jnp.where(top_idx[:, :, None] == ids, gates[:, :, None],
                               0.0), axis=1)  # [T, held]
    g = jnp.einsum("td,efd->etf", xf, params["w_gate"])
    u = jnp.einsum("td,efd->etf", xf, params["w_up"])
    act = jax.nn.silu(g) if activation == "silu" else jax.nn.gelu(
        g, approximate=True)
    act = act * u * weight.T[:, :, None].astype(x.dtype)
    out = jnp.einsum("etf,efd->td", act, params["w_down"],
                     preferred_element_type=jnp.float32)
    if "shared" in params:
        sh = params["shared"]
        g2 = jnp.einsum("td,df->tf", xf, sh["w_gate"])
        u2 = jnp.einsum("td,df->tf", xf, sh["w_up"])
        a2 = jax.nn.silu(g2) if activation == "silu" else jax.nn.gelu(
            g2, approximate=True)
        out = out + jnp.einsum("tf,fd->td", a2 * u2, sh["w_down"],
                               preferred_element_type=jnp.float32)
    touched = jnp.sum(jnp.any(weight > 0.0, axis=0).astype(jnp.int32))
    return out.astype(x.dtype).reshape(b, s, d), touched
