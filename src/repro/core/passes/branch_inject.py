"""Branch injection (§4.3.5) — the MoE hot-expert fast path.

The router table is the `vip_map`: instrumentation finds heavy-hitter
experts; we inject a cheap whole-batch predicate BEFORE the expensive
generic dispatch:

    all(top-k expert ids in hot set) ?  dense compute over |H| hot experts
                                      : full ragged/EP dispatch

The predicate is the injected branch; the hot-expert path is the
specialized code; the generic path is the in-graph deopt target.  This is
traffic-dependent and self-guarding (the predicate IS the guard — unlike a
version guard it re-validates per batch, so router drift degrades to the
generic path instead of computing garbage)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.config import ModelConfig
from ...models.moe import _expert_compute, route
from ..instrument import SketchConfig
from ..specialize import SiteSpec
from .registry import SpecializationPass


def plan_moe_fastpath(hot: np.ndarray, coverage: float,
                      cfg: SketchConfig) -> Optional[Tuple[int, ...]]:
    if len(hot) == 0 or coverage < cfg.hot_coverage:
        return None
    return tuple(int(k) for k in hot)


class MoEFastPathPass(SpecializationPass):
    """Claims the router table's lookup site with a ``moe_fastpath``
    SiteSpec whose ``hot_keys`` are the heavy-hitter experts.  The data
    plane reads them back via ``ctx.hot_experts(table)`` and traces the
    branch-injected dense hot path; the router lookup itself dispatches
    as a plain gather."""

    name = "moe_fastpath"

    def __init__(self, router_table: Optional[str]):
        self.router_table = router_table

    def match(self, site):
        return (site.kind == "lookup"
                and self.router_table is not None
                and site.table == self.router_table)

    def plan(self, site, snapshot, stats):
        hot, coverage = stats.hot_for(site.site_id)
        keys = plan_moe_fastpath(hot, coverage, stats.sketch)
        if keys is None:
            return None
        return SiteSpec(impl="moe_fastpath", hot_keys=keys)


def moe_ffn_hotpath(params, x2d: jax.Array, cfg: ModelConfig,
                    hot_experts: Tuple[int, ...], act: str = "silu"):
    """Specialized MoE FFN: hot experts' weights are pre-sliced
    (trace-time constant indices -> contiguous fast weights); a lax.cond
    falls back to the full dropless dispatch on hot-set miss.

    Returns (y, metrics) like moe_ffn_local."""
    from ...models.moe import moe_ffn_local

    moe = cfg.moe
    T, D = x2d.shape
    E, K = moe.num_experts, moe.top_k
    H = len(hot_experts)
    hot_arr = jnp.asarray(np.asarray(hot_experts, np.int32))
    # static slice of the expert stacks (constant folded at compile time)
    w1h = params["w1"][hot_arr]
    w3h = params["w3"][hot_arr]
    w2h = params["w2"][hot_arr]

    gates, ids, logits = route(params["w_router"], x2d, K,
                               params.get("b_router"))
    # remap: global expert id -> hot slot (or -1), a trace-time constant.
    # Built on the host: as an in-graph scatter inside a lax.scan body
    # (fused windows) the TPU compiler aborts on it when the hot set is
    # 0..H-1 (indices and updates fold to the same iota).
    remap_np = np.full((E,), -1, np.int32)
    remap_np[list(hot_experts)] = np.arange(H, dtype=np.int32)
    hot_ids = jnp.asarray(remap_np)[ids]              # (T,K)
    all_hot = jnp.all(hot_ids >= 0)

    def fast():
        flat = hot_ids.reshape(-1)
        safe = jnp.maximum(flat, 0)
        order = jnp.argsort(safe)
        xs = x2d[order // K]
        gs = jnp.bincount(safe, length=H).astype(jnp.int32)
        ys = _expert_compute(xs, gs, w1h, w3h, w2h, act)
        y = jnp.zeros_like(ys).at[order].set(ys)
        y = (y.reshape(T, K, D) *
             gates[..., None].astype(ys.dtype)).sum(axis=1)
        return y.astype(x2d.dtype)

    def slow():
        y, _ = moe_ffn_local(params, x2d, moe, act)
        return y

    y = jax.lax.cond(all_hot, fast, slow)
    from ...models.moe import load_balance_loss
    aux = load_balance_loss(logits, ids, E)
    counts = jnp.bincount(ids.reshape(-1), length=E).astype(jnp.int32)
    return y, {"aux_loss": aux,
               "dropped": jnp.zeros((), jnp.float32),
               "expert_counts": counts,
               "fastpath_hit": all_hot.astype(jnp.int32)}
