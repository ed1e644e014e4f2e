"""Mixture-of-Experts FFN.

Two implementations share the same math:

* ``moe_ffn_local`` — single-shard dropless MoE: sort tokens by expert,
  ``jax.lax.ragged_dot`` against the stacked expert weights, unsort, combine.
  Used for smoke tests and as the oracle for the distributed path.

* ``moe_ffn_sharded`` — production expert-parallel path under ``shard_map``:
  tokens are bucketed per expert-owning shard (fixed capacity), exchanged
  with ``lax.all_to_all`` along the model axis, computed with the local
  expert slices via sort+ragged_dot, and returned.  Tokens above capacity
  are dropped (counted in metrics) — GShard semantics with a configurable
  capacity factor.

The Morpheus *hot-expert fast path* (``core/passes/branch_inject.py``)
reads the hot experts' weights in place, as static slices of these
stacks, and runs them densely over every row behind an in-graph guard.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.compat import shard_map
from ..distributed.meshctx import get_policy
from .config import MoEConfig, ModelConfig
from .layers import ffn, init_ffn
from .params import Initializer


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_moe(ini: Initializer, cfg: ModelConfig):
    """The router spans every expert; the stacks hold ``moe.held_ids``."""
    moe: MoEConfig = cfg.moe
    d = cfg.d_model
    f = moe.expert_d_ff or cfg.d_ff
    n = len(moe.held_ids)
    p = {
        "w_router": ini.normal((d, moe.num_experts), ("embed", None),
                               dtype=jnp.float32),
        "b_router": ini.zeros((moe.num_experts,), (None,),
                              dtype=jnp.float32),
        "w1": ini.normal((n, d, f), ("experts", "embed", "mlp")),
        "w3": ini.normal((n, d, f), ("experts", "embed", "mlp")),
        "w2": ini.normal((n, f, d), ("experts", "mlp", "embed"), fan_in=f),
    }
    if moe.num_shared:
        p["shared"] = init_ffn(ini, d, moe.num_shared *
                               (moe.shared_d_ff or f))
    return p


# ---------------------------------------------------------------------------
# Routing helpers
# ---------------------------------------------------------------------------

def route(w_router, x2d: jax.Array, top_k: int, bias=None):
    """x2d: (T,D) -> gates (T,K) fp32, ids (T,K) int32, logits (T,E) fp32.
    ``bias``: additive per-expert routing bias (DeepSeek-v3-style)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    gates, ids = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(gates, axis=-1)
    return gates, ids.astype(jnp.int32), logits


def route_grouped(w_router, x2d: jax.Array, moe: MoEConfig, bias=None):
    """DeepSeek-V2 routing: scores are the softmax of the logits over
    every expert; a group's score is its largest; only the
    ``topk_group`` best of ``n_group`` groups stay eligible (the others'
    scores zeroed); the top-k of what is left are the experts, each gated
    by ``routed_scaling`` x its score, not renormalised.  Returns gates
    (T,K) fp32, ids (T,K) int32, logits (T,E) fp32."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    if moe.n_group > 1:
        T, E = scores.shape
        groups = scores.reshape(T, moe.n_group, E // moe.n_group)
        _, best = jax.lax.top_k(groups.max(axis=-1), moe.topk_group)
        keep = jax.nn.one_hot(best, moe.n_group, dtype=jnp.bool_).any(1)
        scores = jnp.where(keep[:, :, None], groups, 0.0).reshape(T, E)
    top, ids = jax.lax.top_k(scores, moe.top_k)
    return top * moe.routed_scaling, ids.astype(jnp.int32), logits


def route_for(params, x2d: jax.Array, moe: MoEConfig):
    """The routing ``moe.scoring`` names, over the router's full width."""
    if moe.scoring == "softmax":
        return route_grouped(params["w_router"], x2d, moe,
                             params.get("b_router"))
    return route(params["w_router"], x2d, moe.top_k,
                 params.get("b_router"))


def held_remap(moe: MoEConfig) -> np.ndarray:
    """Global expert id -> index in the held stacks, -1 where another
    chip holds it (a host constant)."""
    remap = np.full((moe.num_experts,), -1, np.int32)
    remap[list(moe.held_ids)] = np.arange(len(moe.held_ids), dtype=np.int32)
    return remap


def held_slots(ids: jax.Array, moe: MoEConfig) -> jax.Array:
    """How many of the routed slots ``ids`` land on experts this layer
    holds (int32)."""
    if not moe.held:
        return jnp.int32(ids.size)
    return (jnp.asarray(held_remap(moe))[ids] >= 0).sum(dtype=jnp.int32)


def load_balance_loss(logits: jax.Array, ids: jax.Array, n_experts: int):
    """Switch-style auxiliary loss (per-shard; caller averages)."""
    probs = jax.nn.softmax(logits, axis=-1)                  # (T,E)
    density_proxy = probs.mean(axis=0)                       # (E,)
    onehot = jax.nn.one_hot(ids, n_experts, dtype=jnp.float32)
    density = onehot.sum(axis=(0, 1)) / ids.size             # (E,)
    return n_experts * jnp.sum(density * density_proxy)


def _expert_compute(xs: jax.Array, group_sizes: jax.Array, w1, w3, w2,
                    act: str = "silu") -> jax.Array:
    """xs: (N,D) sorted by expert; group_sizes: (E,). Returns (N,D)."""
    h1 = jax.lax.ragged_dot(xs, w1, group_sizes)
    h3 = jax.lax.ragged_dot(xs, w3, group_sizes)
    h = (jax.nn.silu(h1) if act == "silu" else jax.nn.gelu(h1)) * h3
    return jax.lax.ragged_dot(h, w2, group_sizes)


def _expert_compute_blocked(xs: jax.Array, group_sizes: jax.Array, w1, w3,
                            w2, act: str, cap_e: int):
    """Capacity-blocked grouped matmul (megablox-style, §Perf iteration).

    ``jax.lax.ragged_dot``'s default XLA lowering computes DENSE over all
    E groups (measured 8x FLOP waste at E=8) — catastrophic for
    deepseek-v2's 10 local experts/shard.  Here each expert's rows (they
    are contiguous after the sort) are sliced into an (E, cap_e, D) block
    tensor and computed as E batched dense matmuls: FLOPs = E x cap_e x
    6DF ~= capacity_factor x useful, and every matmul is MXU-shaped.
    Rows past ``cap_e`` per expert are dropped (returned for metrics).
    """
    E_l, D = group_sizes.shape[0], xs.shape[1]
    starts = jnp.cumsum(group_sizes) - group_sizes
    idx = starts[:, None] + jnp.arange(cap_e, dtype=jnp.int32)[None, :]
    valid = jnp.arange(cap_e, dtype=jnp.int32)[None, :] < group_sizes[:, None]
    idx_c = jnp.clip(idx, 0, xs.shape[0] - 1)
    blocks = jnp.where(valid[..., None], xs[idx_c], 0)   # (E, cap_e, D)
    h1 = jnp.einsum("ecd,edf->ecf", blocks, w1)
    h3 = jnp.einsum("ecd,edf->ecf", blocks, w3)
    h = (jax.nn.silu(h1) if act == "silu" else jax.nn.gelu(h1)) * h3
    y = jnp.einsum("ecf,efd->ecd", h, w2)
    out = jnp.zeros_like(xs)
    out = out.at[idx_c.reshape(-1)].add(
        jnp.where(valid[..., None], y, 0).reshape(-1, D))
    dropped = jnp.maximum(group_sizes - cap_e, 0).sum().astype(jnp.float32)
    return out, dropped


# ---------------------------------------------------------------------------
# Local (single-shard) dropless path
# ---------------------------------------------------------------------------

def moe_ffn_local(params, x2d: jax.Array, moe: MoEConfig, act: str = "silu"):
    """Dropless MoE over the experts this layer holds.  With
    ``moe.held`` set, slots routed to experts held elsewhere add nothing
    here: they sort after the held groups and their rows are zeroed."""
    T, D = x2d.shape
    E, K = moe.num_experts, moe.top_k
    gates, ids, logits = route_for(params, x2d, moe)
    if moe.held:
        return _moe_ffn_held(params, x2d, moe, act, gates, ids, logits)

    flat_ids = ids.reshape(-1)                                # (T*K,)
    sort_idx = jnp.argsort(flat_ids)
    xs = x2d[sort_idx // K]                                   # (T*K, D)
    group_sizes = jnp.bincount(flat_ids, length=E).astype(jnp.int32)
    ys = _expert_compute(xs, group_sizes, params["w1"], params["w3"],
                         params["w2"], act)
    y = jnp.zeros_like(ys).at[sort_idx].set(ys)               # unsort
    y = (y.reshape(T, K, D) * gates[..., None].astype(y.dtype)).sum(axis=1)
    aux = load_balance_loss(logits, ids, E)
    return y.astype(x2d.dtype), {"aux_loss": aux,
                                 "dropped": jnp.zeros((), jnp.float32),
                                 "expert_counts": group_sizes}


def _moe_ffn_held(params, x2d, moe: MoEConfig, act, gates, ids, logits):
    T, D = x2d.shape
    K, n = moe.top_k, len(moe.held_ids)
    local = jnp.asarray(held_remap(moe))[ids]                 # (T,K)
    flat = jnp.where(local >= 0, local, n).reshape(-1)        # elsewhere last
    sort_idx = jnp.argsort(flat)
    xs = x2d[sort_idx // K]
    group_sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
    ys = _expert_compute(xs, group_sizes, params["w1"], params["w3"],
                         params["w2"], act)
    here = jnp.arange(T * K) < group_sizes.sum()
    ys = jnp.where(here[:, None], ys, 0)
    y = jnp.zeros_like(ys).at[sort_idx].set(ys)               # unsort
    y = (y.reshape(T, K, D) * gates[..., None].astype(y.dtype)).sum(axis=1)
    aux = load_balance_loss(logits, ids, moe.num_experts)
    return y.astype(x2d.dtype), {
        "aux_loss": aux, "dropped": jnp.zeros((), jnp.float32),
        "expert_counts": jnp.bincount(ids.reshape(-1), length=moe.num_experts
                                      ).astype(jnp.int32)}


# ---------------------------------------------------------------------------
# Sharded expert-parallel path (shard_map + all_to_all along the model axis)
# ---------------------------------------------------------------------------

def _moe_shard_body(x2d, w_router, b_router, w1, w3, w2, *,
                    moe: MoEConfig, act: str,
                    model_axis: str, n_model: int, all_axes):
    """Runs per-device.  x2d: (T_l, D) local tokens; w1/w3/w2: local expert
    slices (E_l, ...)."""
    T_l, D = x2d.shape
    E, K = moe.num_experts, moe.top_k
    E_l = E // n_model
    cap = int(max(8, round(T_l * K / n_model * moe.capacity_factor)))
    # round capacity to a lane-friendly multiple
    cap = -(-cap // 8) * 8

    gates, ids, logits = route_for({"w_router": w_router,
                                    "b_router": b_router}, x2d, moe)
    flat_ids = ids.reshape(-1)                                # (N,) N=T_l*K
    N = flat_ids.shape[0]
    dest = flat_ids // E_l                                    # owning shard
    order = jnp.argsort(flat_ids)                             # stable
    s_ids = flat_ids[order]
    s_dest = dest[order]
    # rank within destination bucket
    starts = jnp.cumsum(jnp.bincount(s_dest, length=n_model)) \
        - jnp.bincount(s_dest, length=n_model)
    rank = jnp.arange(N) - starts[s_dest]
    keep = rank < cap
    slot = s_dest * cap + jnp.where(keep, rank, 0)            # (N,)

    send_x = jnp.zeros((n_model * cap, D), x2d.dtype)
    send_id = jnp.full((n_model * cap,), -1, jnp.int32)
    src_tok = order // K                                      # token of entry
    send_x = send_x.at[slot].set(jnp.where(keep[:, None],
                                           x2d[src_tok], 0.0))
    send_id = send_id.at[slot].set(jnp.where(keep, s_ids % E_l, -1))
    dropped = (~keep).sum().astype(jnp.float32)

    # exchange: row-block i goes to shard i
    recv_x = jax.lax.all_to_all(send_x.reshape(n_model, cap, D), model_axis,
                                split_axis=0, concat_axis=0, tiled=False)
    recv_id = jax.lax.all_to_all(send_id.reshape(n_model, cap), model_axis,
                                 split_axis=0, concat_axis=0, tiled=False)
    rx = recv_x.reshape(n_model * cap, D)
    rid = recv_id.reshape(n_model * cap)

    # local expert compute (invalid slots -> expert E_l, zero group)
    valid = rid >= 0
    cid = jnp.where(valid, rid, E_l)
    lorder = jnp.argsort(cid)
    lx = rx[lorder]
    gs = jnp.bincount(jnp.where(valid, rid, E_l), length=E_l + 1
                      )[:E_l].astype(jnp.int32)
    if E_l > 1:
        # blocked grouped matmul: ragged_dot's dense-over-groups lowering
        # costs E_l x useful FLOPs (see _expert_compute_blocked)
        # slots already carry the a2a capacity factor; only a small
        # imbalance margin is needed per expert (measured: cf^2 here was
        # 2.25x FLOP waste on deepseek-v2)
        cap_e = -(-int(n_model * cap) // E_l)
        cap_e = -(-int(cap_e * 1.25) // 8) * 8
        ly, drop2 = _expert_compute_blocked(lx, gs, w1, w3, w2, act,
                                            cap_e)
        dropped = dropped + drop2
    else:
        ly = _expert_compute(lx, gs, w1, w3, w2, act)
    ry = jnp.zeros_like(ly).at[lorder].set(ly)                # back to slot order
    ry = jnp.where(valid[:, None], ry, 0.0)

    # reverse exchange
    back = jax.lax.all_to_all(ry.reshape(n_model, cap, D), model_axis,
                              split_axis=0, concat_axis=0, tiled=False)
    by = back.reshape(n_model * cap, D)

    # combine: slot -> flat entry -> token, weighted by gate
    ys = by[slot] * keep[:, None].astype(by.dtype)            # sorted order
    y = jnp.zeros((N, D), ys.dtype).at[order].set(ys)
    y = (y.reshape(T_l, K, D) *
         gates[..., None].astype(ys.dtype)).sum(axis=1)

    aux = load_balance_loss(logits, ids, E)
    aux = jax.lax.pmean(aux, all_axes)
    dropped = jax.lax.psum(dropped, all_axes)
    counts = jax.lax.psum(jnp.bincount(flat_ids, length=E).astype(jnp.int32),
                          all_axes)
    return y.astype(x2d.dtype), aux, dropped, counts


def _moe_shard_body_psum(x2d, w_router, b_router, w1, w3, w2, *,
                         moe: MoEConfig,
                         act: str, model_axis: str, n_model: int, all_axes):
    """Small-token (decode) path: tokens fully replicated, each shard
    computes only the entries routed to its OWN experts, outputs psum'd
    along the model axis.  No all-to-all, no capacity drops."""
    T, D = x2d.shape
    E, K = moe.num_experts, moe.top_k
    E_l = E // n_model
    gates, ids, logits = route_for({"w_router": w_router,
                                    "b_router": b_router}, x2d, moe)
    flat_ids = ids.reshape(-1)
    me = jax.lax.axis_index(model_axis)
    owned = (flat_ids // E_l) == me
    cid = jnp.where(owned, flat_ids % E_l, 0)
    order = jnp.argsort(cid + jnp.where(owned, 0, E_l))   # non-owned last
    xs = x2d[order // K]
    gs_all = jnp.bincount(jnp.where(owned, cid, E_l), length=E_l + 1)
    gs = gs_all[:E_l].astype(jnp.int32)                   # owned groups only
    if E_l > 1:
        cap_e = -(-(T * K) // E_l) * 2
        cap_e = -(-cap_e // 8) * 8
        ys, _ = _expert_compute_blocked(xs, gs, w1, w3, w2, act, cap_e)
    else:
        ys = _expert_compute(xs, gs, w1, w3, w2, act)
    # entries beyond sum(gs) were not computed for any owned expert
    valid = jnp.arange(T * K) < gs.sum()
    ys = jnp.where(valid[:, None], ys, 0.0)
    y = jnp.zeros_like(ys).at[order].set(ys)
    y = (y.reshape(T, K, D) * gates[..., None].astype(y.dtype)).sum(axis=1)
    y = jax.lax.psum(y, model_axis)
    aux = load_balance_loss(logits, ids, E)
    counts = jnp.bincount(flat_ids, length=E).astype(jnp.int32)
    return (y.astype(x2d.dtype), aux, jnp.zeros((), jnp.float32), counts)


def moe_ffn_sharded(params, x2d: jax.Array, moe: MoEConfig, act: str = "silu"):
    from jax.sharding import PartitionSpec as P

    pol = get_policy()
    mesh = pol.mesh
    all_axes = tuple(mesh.axis_names)
    batch = tuple(pol.batch_axes)
    mdl = pol.model_axis
    n_model = mesh.shape[mdl]
    n_tok_shards = pol.n_batch_shards * n_model
    T = x2d.shape[0]

    if T % n_tok_shards == 0 and T // n_tok_shards >= 8:
        # Token-sharded all-to-all EP: tokens split over (batch x model)
        # so each shard routes a DISTINCT slice (replicating along model
        # would duplicate every expert's work n_model times).  The
        # constraint below pins the boundary sharding in BOTH directions
        # of AD (without it the backward pays an involuntary full remat).
        from ..distributed.meshctx import constrain
        x2d = constrain(x2d, ("tokens", None))

        def body(x, wr, br, w1, w3, w2):
            return _moe_shard_body(x, wr, br, w1, w3, w2, moe=moe, act=act,
                                   model_axis=mdl, n_model=n_model,
                                   all_axes=all_axes)

        tok_spec = P(batch + (mdl,), None)
        y, aux, dropped, counts = shard_map(
            body, mesh=mesh,
            in_specs=(tok_spec, P(None, None), P(None),
                      P(mdl, None, None), P(mdl, None, None),
                      P(mdl, None, None)),
            out_specs=(tok_spec, P(), P(), P()),
        )(x2d, params["w_router"], params["b_router"],
          params["w1"], params["w3"], params["w2"])
    else:
        # decode / tiny batches: replicate tokens, psum-combine
        def body(x, wr, br, w1, w3, w2):
            return _moe_shard_body_psum(x, wr, br, w1, w3, w2, moe=moe,
                                        act=act,
                                        model_axis=mdl, n_model=n_model,
                                        all_axes=all_axes)

        y, aux, dropped, counts = shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None), P(None, None), P(None),
                      P(mdl, None, None), P(mdl, None, None),
                      P(mdl, None, None)),
            out_specs=(P(None, None), P(), P(), P()),
        )(x2d, params["w_router"], params["b_router"],
          params["w1"], params["w3"], params["w2"])
    return y, {"aux_loss": aux, "dropped": dropped, "expert_counts": counts}


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

def moe_ffn(params, x: jax.Array, cfg: ModelConfig):
    """x: (B,S,D) -> (y, metrics)."""
    from ..distributed.meshctx import constrain
    moe = cfg.moe
    B, S, D = x.shape
    # explicit reshard points on BOTH sides of the (batch)->(tokens)
    # layout change: without them the backward's cotangent junction at
    # the residual add reshards via replicate-then-partition (global
    # all-reduce of full activations, XLA's "involuntary full remat")
    x = constrain(x, ("batch", None, None))
    x2d = x.reshape(B * S, D)
    pol = get_policy()
    from ..distributed.meshctx import get_moe_hot
    hot = get_moe_hot()
    if pol is not None and pol.mesh is not None and pol.moe_impl != "local" \
            and not moe.held and moe.num_experts % pol.n_model == 0:
        y, metrics = moe_ffn_sharded(params, x2d, moe, cfg.ffn_act)
        y = constrain(y, ("tokens", None))
    elif hot and len(hot) < moe.num_experts:
        # Morpheus branch injection on the training backend: dense fast
        # path over the hot experts, guarded by the all-hot predicate
        from ..core.passes.branch_inject import moe_ffn_hotpath
        y, metrics = moe_ffn_hotpath(params, x2d, cfg, hot, cfg.ffn_act)
    else:
        y, metrics = moe_ffn_local(params, x2d, moe, cfg.ffn_act)
    y = constrain(y.reshape(B, S, D), ("batch", None, None))
    if moe.num_shared:
        y = y + ffn(params["shared"], x, cfg.ffn_act)
    return y, metrics
