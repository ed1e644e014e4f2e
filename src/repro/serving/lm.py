"""The serving plane for ``models/`` configurations with latent attention
(MLA) and routed plus shared experts: DeepSeek-V2 on one chip's share.

The step is written against :class:`DataPlaneCtx` with the table sites
of the stand-in plane (``serving/dataplane.py``): ``req_class``,
``vocab_embed`` (the hot-row cache's site), ``adapters`` (empty, so
eliminated), one instrumented ``router`` lookup per MoE layer (the
hot-expert fast path's site), ``sessions`` and ``moe_stats``.  Each layer
is the model code's: :func:`~repro.models.attention.mla_forward`, the
dense :func:`~repro.models.layers.ffn` of the leading layers, and per
MoE layer the routing of ``cfg.moe`` over the full router width, the
experts this chip holds (``MoEConfig.held``; the fast path when the plan
has one) and the shared experts, outside the fast path's ``cond``.

Weights may be bfloat16: each layer's input is cast to its weights'
dtype, products accumulate in float32 on the TPU, the residual stream,
router, norms and logits stay float32.  A request is S tokens at
positions ``0..S-1``, answered one-shot with the logits at every
position; MLA gets a key block no longer than the request.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import Table, TableSet
from ..core.passes.branch_inject import moe_ffn_hotpath
from ..models.attention import mla_forward
from ..models.config import ModelConfig
from ..models.layers import dot_f32, ffn, rmsnorm
from ..models.moe import held_slots, moe_ffn_local, route_for
from .dataplane import moe_stats_table, record_moe_stats


ADAPTER_RANK = 4


@dataclass(frozen=True)
class LMServeConfig:
    model: ModelConfig
    n_classes: int = 64
    n_adapters: int = 0          # 0 => adapters table is empty (eliminated)
    n_slots: int = 256

    @property
    def n_moe_layers(self) -> int:
        return self.model.n_layers - self.model.first_k_dense


def build_lm_tables(scfg: LMServeConfig) -> TableSet:
    """The plane's tables.  ``router`` spans every routed expert; its
    ``held`` field marks the ones this chip holds, which is what the
    fast-path pass plans among.  The embedding is a placeholder the
    deployment installs through the control plane."""
    cfg = scfg.model
    rng = np.random.default_rng(0)
    e = cfg.moe.num_experts
    held = np.zeros(e, np.int32)
    held[list(cfg.moe.held_ids)] = 1
    return TableSet([
        Table("req_class",
              {"adapter_id": np.zeros(scfg.n_classes, np.int32),
               "temperature": np.ones(scfg.n_classes, np.float32),
               "flags": np.zeros(scfg.n_classes, np.int32)},
              n_valid=scfg.n_classes, max_inline=8),
        Table("vocab_embed",
              {"vec": rng.standard_normal((cfg.vocab, cfg.d_model),
                                          np.float32) * 0.02},
              n_valid=cfg.vocab, max_inline=0),
        Table("adapters",
              {"down": np.zeros((max(scfg.n_adapters, 1), cfg.d_model,
                                 ADAPTER_RANK), np.float32),
               "up": np.zeros((max(scfg.n_adapters, 1), ADAPTER_RANK,
                               cfg.d_model), np.float32)},
              n_valid=scfg.n_adapters, default={"down": 0.0, "up": 0.0}),
        Table("router", {"idx": np.arange(e, dtype=np.int32),
                         "held": held},
              n_valid=e, max_inline=0),
        Table("sessions",
              {"count": np.zeros(scfg.n_slots, np.int32),
               "last_token": np.zeros(scfg.n_slots, np.int32)},
              n_valid=scfg.n_slots, mutability="rw", instrument=False),
        moe_stats_table(scfg.n_moe_layers),
    ])


def _moe_layer(p, ctx, cfg: ModelConfig, h2d: jax.Array, site: int
               ) -> Tuple[jax.Array, tuple]:
    """One MoE layer's FFN: routing over the full width with its
    instrumented router site, the held experts (fast path or dropless),
    the shared experts; and the layer's ``moe_stats`` row."""
    moe = cfg.moe
    with jax.named_scope("moe.router"):
        _, ids, _ = route_for(p, h2d, moe)
        ctx.lookup("router", ids.reshape(-1), fields=("idx",))
    hot = ctx.hot_experts("router", site)
    if hot:
        with jax.named_scope("moe.hot"):
            y, m = moe_ffn_hotpath(p, h2d, cfg, hot, cfg.ffn_act)
        hit = m["fastpath_hit"]
    else:
        with jax.named_scope("moe.generic"):
            y, _ = moe_ffn_local(p, h2d, moe, cfg.ffn_act)
        hit = 0
    y = y.astype(jnp.float32)
    if moe.num_shared:
        with jax.named_scope("moe.shared"):
            y = y + ffn(p["shared"], h2d, cfg.ffn_act).astype(jnp.float32)
    return y, (hit, held_slots(ids, moe), ids.size)


def make_lm_serve_step(scfg: LMServeConfig):
    """Returns user_step(params, ctx, batch) -> logits (B, S, vocab)."""
    cfg = scfg.model

    def serve_step(params, ctx, batch):
        tokens = batch["tokens"]                       # (B, S)
        B, S = tokens.shape
        cls = ctx.lookup("req_class", batch["class_id"],
                         fields=("adapter_id", "temperature"))
        x = ctx.lookup("vocab_embed", tokens,
                       fields=("vec",))["vec"].astype(jnp.float32)
        positions = jnp.arange(S, dtype=jnp.int32)
        stats = []
        for i, lp in enumerate(params["layers"]):
            dt = lp["attn"]["w_dkv"].dtype
            with jax.named_scope("mla"):
                h = rmsnorm(lp["attn_norm"], x, cfg.rms_eps).astype(dt)
                a, _ = mla_forward(lp["attn"], cfg, h, positions, block=S)
                x = x + a.astype(jnp.float32)
            h = rmsnorm(lp["ffn_norm"], x, cfg.rms_eps).astype(dt)
            if i < cfg.first_k_dense:
                with jax.named_scope("mlp"):
                    f = ffn(lp["ffn"], h, cfg.ffn_act)
                x = x + f.astype(jnp.float32)
                continue
            y, row = _moe_layer(lp["ffn"], ctx, cfg, h.reshape(B * S, -1),
                                len(stats))
            stats.append(row)
            x = x + y.reshape(B, S, -1)

        # adapter branch: fully eliminated when the adapter bank is empty
        ad = ctx.lookup_or_none("adapters", cls["adapter_id"],
                                fields=("down", "up"))
        if ad is not None:
            x = x + jnp.einsum("bsd,bdr,brk->bsk", x, ad["down"],
                               ad["up"])

        x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
        w = params["unembed"]
        logits = dot_f32("bsd,dv->bsv", x.astype(w.dtype), w)
        logits = logits / cls["temperature"][:, None, None]

        if ctx.flag("track_sessions", default=True):
            next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(
                jnp.int32)
            old = ctx.lookup("sessions", batch["slot"], fields=("count",))
            ctx.update("sessions", batch["slot"],
                       {"count": old["count"] + 1, "last_token": next_tok})
        if stats:
            record_moe_stats(ctx, stats)
        return logits

    return serve_step
