"""Mixture-of-Experts FFN: top-k routing with sort-based capacity dispatch.

Dispatch strategy (MaxText/Megablocks-style, einsum-one-hot free): flatten
(token, expert-choice) pairs, sort by expert id, compute each pair's rank
inside its expert run, drop pairs past the per-expert capacity, scatter into
an (experts, capacity, d_model) buffer, run the batched expert FFN as one
einsum over the expert dim, gather back and combine with router probs.

Compute is O(k · T · cf · d · f) — the *active* FLOPs — instead of the
O(T · X · cap) one-hot dispatch tensor which is infeasible at kimi scale
(384 experts × 1M tokens).

Sharding: the (X, C, E) buffer puts experts on "model" (expert parallelism);
tokens enter sharded on ("pod","data"). The scatter across those two
shardings is the EP all-to-all — visible in the dry-run HLO and the dominant
collective for kimi-k2 (see EXPERIMENTS.md §Roofline).

Aux losses: Switch-style load-balance + router z-loss, returned for logging
and added to the train loss with small coefficients.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common, sharding
from .common import ParamDef


def defs(cfg):
    m = cfg.moe
    e = cfg.d_model
    f = m.d_ff or cfg.d_ff
    x = m.num_experts
    d = {
        "router": ParamDef((e, x), ("embed", None), dtype=jnp.float32, scale=0.1),
        "w_gate": ParamDef((x, e, f), ("experts", "embed", None)),
        "w_up": ParamDef((x, e, f), ("experts", "embed", None)),
        "w_down": ParamDef((x, f, e), ("experts", None, "embed")),
    }
    if m.shared_expert:
        d["shared"] = {
            "w_gate": ParamDef((e, f), ("embed", "ffn")),
            "w_up": ParamDef((e, f), ("embed", "ffn")),
            "w_down": ParamDef((f, e), ("ffn", "embed")),
        }
    if m.dense_residual:
        d["residual"] = {
            "w_gate": ParamDef((e, cfg.d_ff), ("embed", "ffn")),
            "w_up": ParamDef((e, cfg.d_ff), ("embed", "ffn")),
            "w_down": ParamDef((cfg.d_ff, e), ("ffn", "embed")),
        }
    return d


def capacity(cfg, n_tokens: int) -> int:
    m = cfg.moe
    cap = int(n_tokens * m.top_k * m.capacity_factor / m.num_experts) + 1
    return max(cap, 4)


def apply(params, x, cfg, mesh=None):
    """Dispatcher: cfg.moe.impl selects the execution strategy.

    The a2a path requires tokens % mesh.size == 0 and experts % model == 0;
    tiny decode batches (one token per sequence) fall back to the scatter
    path, where the dispatch buffer is small enough that GSPMD's
    replicate+reduce fallback is harmless."""
    if (
        cfg.moe.impl == "shard_map_a2a"
        and mesh is not None
        and "model" in mesh.axis_names
        and x.shape[0] % mesh.size == 0
        and cfg.moe.num_experts % mesh.shape["model"] == 0
    ):
        return apply_a2a(params, x, cfg, mesh)
    return apply_scatter(params, x, cfg, mesh)


def _pack_by_owner(owner, n_owners: int, cap: int):
    """Stage-1 capacity packing: stable owner sort + per-owner rank.

    Returns (order, owner_sorted, rank, keep). The SAME routine computes the
    in-shard dispatch inside apply_a2a's local_fn and the drop_fraction
    replay outside it — keep them shared so the reported metric can't drift
    from what the dispatch actually drops.
    """
    order = jnp.argsort(owner)
    own_s = owner[order]
    cnt = jnp.bincount(own_s, length=n_owners)
    start = jnp.cumsum(cnt) - cnt
    rank = jnp.arange(owner.shape[0]) - start[own_s]
    return order, own_s, rank, rank < cap


def apply_a2a(params, x, cfg, mesh):
    """Explicit expert parallelism: two-hop all-to-all under shard_map.

    Stage 0: tokens resharded over EVERY mesh axis (data axes x "model") so
             no routing work is duplicated across TP peers.
    Stage 1: each device sorts its local (token, expert-choice) pairs by the
             expert's OWNER device, packs per-peer capacity buffers, and
             all_to_all's them across "model".
    Stage 2: received candidates are sorted by local expert, capacity-
             truncated, run through the batched expert FFN, scattered back to
             their arrival slots, and all_to_all'd home, where they combine
             into token outputs weighted by router probs.

    Wire volume per device = 2 hops x (T_loc·k·cf·d_model) bytes — the
    irreducible EP exchange — versus the GSPMD-scatter baseline's
    all-reduce of the full (X·C, d_model) buffer per layer (§Perf log).
    """
    m = cfg.moe
    t, e = x.shape
    nx = m.num_experts
    k = m.top_k

    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(mesh.axis_names)  # e.g. ("pod","data","model")
    nm = int(mesh.shape["model"])
    # Tokens sharded over EVERY axis (data x model) for the dispatch.
    x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(axes, None)))

    x_loc_count = t // mesh.size
    cap_send = max(int(x_loc_count * k * m.capacity_factor / nm) + 1, 4)
    x_l = nx // nm  # experts per device
    cap_exp = max(int(nm * cap_send * m.capacity_factor / x_l) + 1, 4)

    def local_fn(xl, router, wg, wu, wd):
        tl = xl.shape[0]
        logits = jnp.einsum("te,ex->tx", xl.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        e_flat = top_e.reshape(-1)
        p_flat = top_p.reshape(-1)
        tok_flat = jnp.repeat(jnp.arange(tl), k)

        # ---- stage 1: pack per-owner send buffers -------------------------
        owner = e_flat // x_l
        order1, own_s, rank1, keep1 = _pack_by_owner(owner, nm, cap_send)
        e_s, tok_s, p_s = e_flat[order1], tok_flat[order1], p_flat[order1]
        dest1 = jnp.where(keep1, own_s * cap_send + rank1, nm * cap_send)

        send_x = jnp.zeros((nm * cap_send + 1, e), xl.dtype).at[dest1].set(xl[tok_s])
        send_le = jnp.full((nm * cap_send + 1,), -1, jnp.int32).at[dest1].set(
            (e_s % x_l).astype(jnp.int32)
        )
        recv_x = jax.lax.all_to_all(
            send_x[:-1].reshape(nm, cap_send, e), "model", 0, 0, tiled=False
        ).reshape(nm * cap_send, e)
        recv_le = jax.lax.all_to_all(
            send_le[:-1].reshape(nm, cap_send), "model", 0, 0, tiled=False
        ).reshape(nm * cap_send)

        # ---- stage 2: sort by local expert, FFN, unsort -------------------
        valid = recv_le >= 0
        key2 = jnp.where(valid, recv_le, x_l)
        order2 = jnp.argsort(key2)
        key2s = key2[order2]
        cnt2 = jnp.bincount(key2s, length=x_l + 1)
        start2 = jnp.cumsum(cnt2) - cnt2
        rank2 = jnp.arange(nm * cap_send) - start2[key2s]
        keep2 = (rank2 < cap_exp) & (key2s < x_l)
        dest2 = jnp.where(keep2, key2s * cap_exp + rank2, x_l * cap_exp)

        buf = jnp.zeros((x_l * cap_exp + 1, e), xl.dtype).at[dest2].set(recv_x[order2])
        buf = buf[:-1].reshape(x_l, cap_exp, e)
        g = common.silu(jnp.einsum("xce,xef->xcf", buf, wg))
        u = jnp.einsum("xce,xef->xcf", buf, wu)
        out = jnp.einsum("xcf,xfe->xce", g * u, wd)
        out_flat = jnp.concatenate([out.reshape(x_l * cap_exp, e), jnp.zeros((1, e), xl.dtype)])

        back = jnp.zeros((nm * cap_send, e), xl.dtype).at[order2].set(
            out_flat[dest2] * keep2[:, None].astype(xl.dtype)
        )
        ret = jax.lax.all_to_all(
            back.reshape(nm, cap_send, e), "model", 0, 0, tiled=False
        ).reshape(nm * cap_send, e)
        ret_flat = jnp.concatenate([ret, jnp.zeros((1, e), xl.dtype)])

        y = jnp.zeros((tl, e), xl.dtype).at[tok_s].add(
            ret_flat[dest1] * (p_s * keep1).astype(xl.dtype)[:, None]
        )
        return y

    y = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axes, None), P(), P("model", None, None), P("model", None, None), P("model", None, None)),
        out_specs=P(axes, None),
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])

    # ---- aux losses, computed OUTSIDE the shard_map ------------------------
    # Two reasons: (1) shard_map transposition on some JAX versions chokes on
    # outputs whose cotangent is a symbolic Zero (any caller that grads
    # through y alone, as the equivalence tests do, hits that path); (2) the
    # global statistic matches apply_scatter's aux definition exactly, where
    # the pmean of per-shard products is a slightly different estimator. The
    # duplicated router pass is a (T, X) einsum — noise next to the expert
    # FFN, and load_balance/router_z keep their gradients for the train loss.
    logits = jnp.einsum("te,ex->tx", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_e = jax.lax.top_k(probs, k)
    e_flat = top_e.reshape(-1)
    frac = jnp.bincount(e_flat, length=nx).astype(jnp.float32) / (t * k)
    lb = nx * jnp.sum(frac * probs.mean(0))
    zl = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)

    # drop_fraction: replay stage-1's per-device capacity packing on the
    # (n_dev, t_loc*k) block view — _pack_by_owner is the same routine
    # local_fn dispatches with, so the metric tracks the real drops.
    n_dev = mesh.size
    owner_blk = (top_e.reshape(n_dev, -1) // x_l).astype(jnp.int32)
    drop = 1.0 - jax.vmap(lambda own: _pack_by_owner(own, nm, cap_send)[3])(owner_blk).mean()
    aux = {"load_balance": lb, "router_z": zl, "drop_fraction": drop}

    if m.shared_expert:
        p = params["shared"]
        y = y + common.swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
    if m.dense_residual:
        p = params["residual"]
        y = y + common.swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
    return y, aux


def apply_scatter(params, x, cfg, mesh=None):
    """x: (T, E) flattened tokens. Returns (y, aux) with aux loss scalars."""
    m = cfg.moe
    t, e = x.shape
    nx = m.num_experts
    k = m.top_k
    cap = capacity(cfg, t)

    logits = jnp.einsum("te,ex->tx", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch ------------------------------------------------
    e_flat = top_e.reshape(-1)  # (T*k,)
    p_flat = top_p.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t), k)

    order = jnp.argsort(e_flat)  # stable
    e_sorted = e_flat[order]
    tok_sorted = tok_flat[order]
    p_sorted = p_flat[order]

    # rank of each pair within its expert's run
    counts = jnp.bincount(e_sorted, length=nx)  # (X,)
    seg_start = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * k) - seg_start[e_sorted]
    keep = rank < cap
    dest = jnp.where(keep, e_sorted * cap + rank, nx * cap)  # overflow slot

    buf = jnp.zeros((nx * cap + 1, e), x.dtype).at[dest].set(x[tok_sorted])
    buf = buf[: nx * cap].reshape(nx, cap, e)
    if mesh is not None:
        buf = sharding.constrain(buf, mesh, "experts", None, None)

    # ---- batched expert FFN (active compute only) ---------------------------
    g = common.silu(jnp.einsum("xce,xef->xcf", buf, params["w_gate"]))
    u = jnp.einsum("xce,xef->xcf", buf, params["w_up"])
    out = jnp.einsum("xcf,xfe->xce", g * u, params["w_down"])
    if mesh is not None:
        out = sharding.constrain(out, mesh, "experts", None, None)

    # ---- combine -------------------------------------------------------------
    out_flat = jnp.concatenate([out.reshape(nx * cap, e), jnp.zeros((1, e), x.dtype)])
    if mesh is not None:
        # Replicate before the combine gather. GSPMD's partitioned gather from
        # a "model"-sharded operand mis-accumulates across a second (data)
        # mesh axis on some JAX versions (each data replica's partial gets
        # summed), doubling every expert output; an explicit all-gather here
        # is what the correct fallback lowers to anyway and keeps the expert
        # FFN itself on the EP layout.
        out_flat = sharding.constrain(out_flat, mesh, None, None)
    contrib = out_flat[dest] * (p_sorted * keep).astype(x.dtype)[:, None]
    y = jnp.zeros((t, e), x.dtype).at[tok_sorted].add(contrib)

    if m.shared_expert:
        p = params["shared"]
        y = y + common.swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
    if m.dense_residual:
        p = params["residual"]
        y = y + common.swiglu(x, p["w_gate"], p["w_up"], p["w_down"])

    # ---- aux losses ----------------------------------------------------------
    # Switch load-balance: X * sum_x( frac_tokens(x) * mean_prob(x) ).
    frac = jnp.bincount(e_flat, length=nx).astype(jnp.float32) / (t * k)
    mean_p = probs.mean(axis=0)
    lb = nx * jnp.sum(frac * mean_p)
    zl = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    drop_frac = 1.0 - keep.mean()
    aux = {"load_balance": lb, "router_z": zl, "drop_fraction": drop_frac}
    return y, aux
