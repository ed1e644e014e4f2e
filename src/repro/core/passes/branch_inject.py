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
generic path instead of computing garbage).

The hot set is a trace-time tuple, so the fast branch reads each hot
expert's weights in place, as a static slice of the expert stacks along
their leading axis, and runs its FFN densely over every row; each top-k
slot then selects the row of the expert it chose.  A fancy-index gather
of the stacks (``w[hot_arr]``) is not folded away, since the weights are
arguments: on a TPU it copies every touched column block of all E
experts on every step.

A layer that holds one chip's share of the experts (``MoEConfig.held``)
routes over all of them.  The router table then carries a ``held``
field, the pass plans hot experts among the held ones only, and the
guard asks that every slot routed to a *held* expert be hot: a slot
routed to another chip is neither a hit nor a miss, and adds nothing
on either branch."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.config import ModelConfig
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
        table = snapshot.get(site.table)
        if table is not None and "held" in table.fields and len(hot):
            hot = hot[np.asarray(table.fields["held"])[hot] > 0]
        keys = plan_moe_fastpath(hot, coverage, stats.sketch)
        if keys is None:
            return None
        return SiteSpec(impl="moe_fastpath", hot_keys=keys)


def moe_ffn_hotpath(params, x2d: jax.Array, cfg: ModelConfig,
                    hot_experts: Tuple[int, ...], act: str = "silu"):
    """Specialized MoE FFN: the hot experts' weights are read in place by
    static slices (``params["w1"][i]`` for each trace-time hot expert at
    stack index ``i``); a lax.cond falls back to the full dropless
    dispatch (over the held experts) on a hot-set miss.  The fast branch
    runs each hot expert densely over all rows.

    Returns (y, metrics) like moe_ffn_local, plus ``fastpath_hit``."""
    from ...models.moe import held_remap, moe_ffn_local, route_for

    moe = cfg.moe
    E = moe.num_experts
    H = len(hot_experts)
    stack = held_remap(moe)

    gates, ids, logits = route_for(params, x2d, moe)
    # remap: global expert id -> hot slot (or -1), a trace-time constant.
    # Built on the host: as an in-graph scatter inside a lax.scan body
    # (fused windows) the TPU compiler aborts on it when the hot set is
    # 0..H-1 (indices and updates fold to the same iota).
    remap_np = np.full((E,), -1, np.int32)
    remap_np[list(hot_experts)] = np.arange(H, dtype=np.int32)
    hot_ids = jnp.asarray(remap_np)[ids]              # (T,K)
    if moe.held:
        # slots routed to another chip's experts neither hit nor miss
        hot_ids = jnp.where(jnp.asarray(stack)[ids] >= 0, hot_ids, 0)
    all_hot = jnp.all(hot_ids >= 0)

    def fast():
        # each hot expert over every row, its weights read where they
        # lie; each top-k slot then selects its expert's row, and the
        # slots combine as in moe_ffn_local
        ys = []
        for e in hot_experts:
            i = int(stack[e])
            h1 = x2d @ params["w1"][i]
            h = (jax.nn.silu(h1) if act == "silu" else jax.nn.gelu(h1)) \
                * (x2d @ params["w3"][i])
            ys.append(h @ params["w2"][i])
        if moe.held:
            first = jnp.zeros_like(ys[0])[:, None, :]
            rest = zip(hot_experts, ys)
        else:
            first = ys[0][:, None, :]
            rest = zip(hot_experts[1:], ys[1:])
        y = first
        for e, ye in rest:
            y = jnp.where((ids == e)[..., None], ye[:, None, :], y)
        y = (y * gates[..., None].astype(y.dtype)).sum(axis=1)
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
