"""The serving data plane — Morpheus' Katran analogue.

A batched LM serving step written against :class:`DataPlaneCtx`, with the
full table cast of the paper mapped into the ML domain:

  req_class    (RO)  vip_map:      request class -> adapter id, sampling
                                   temperature, feature bits
  vocab_embed  (RO)  backend_pool: the embedding table (large; hot-token
                                   fast-path cache applies)
  adapters     (RO)  —             LoRA adapter bank (empty => table
                                   elimination removes the whole branch)
  router       (RO)  vip_map #2:   MoE expert stats (instrumented; hot
                                   experts get the dense fast path)
  sessions     (RW)  conn_table:   per-slot session state, written by the
                                   data plane itself => site guard
  moe_stats    (RW)  —             per MoE layer, device-side counters of
                                   the fast path: steps on the fast
                                   branch, steps with held slots, held
                                   and all routed slots

Feature flags (control plane): ``vision_enabled`` (the QUIC-branch
analogue) and ``track_sessions``.

This data plane is mesh-agnostic: under a sharded runtime
(``EngineConfig(mesh=...)``) the tables are replicated, the request
batch's leading dim is sharded over the mesh, and the router/embedding
instrumentation records per device — nothing here changes.  Keep
``batch_size`` a multiple of the device count so batches shard evenly
(``plane_batch_shardings`` replicates indivisible batches instead).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import EngineConfig, SketchConfig, Table, TableSet
from ..core.passes.branch_inject import moe_ffn_hotpath
from ..models.config import ModelConfig, MoEConfig
from ..models.layers import rmsnorm
from ..models.moe import moe_ffn_local
from ..models.params import Initializer, unzip


@dataclass(frozen=True)
class ServeConfig:
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    vocab: int = 2048
    n_experts: int = 16
    top_k: int = 2
    d_ff: int = 128
    n_classes: int = 64
    n_adapters: int = 0          # 0 => adapters table is empty (eliminated)
    adapter_rank: int = 4
    n_slots: int = 256
    seq: int = 16


def build_params(cfg: ServeConfig, key) -> Dict:
    ini = Initializer(key, dtype=jnp.float32)
    d, f = cfg.d_model, cfg.d_ff
    layers = []
    for _ in range(cfg.n_layers):
        layers.append({
            "norm1": {"scale": ini.ones((d,), ("embed",),
                                        dtype=jnp.float32)},
            "wq": ini.normal((d, d), ("embed", "embed")),
            "wk": ini.normal((d, d), ("embed", "embed")),
            "wv": ini.normal((d, d), ("embed", "embed")),
            "wo": ini.normal((d, d), ("embed", "embed")),
            "norm2": {"scale": ini.ones((d,), ("embed",),
                                        dtype=jnp.float32)},
            "moe": {
                "w_router": ini.normal((d, cfg.n_experts), ("embed", None),
                                       dtype=jnp.float32),
                "b_router": ini.zeros((cfg.n_experts,), (None,),
                                      dtype=jnp.float32),
                "w1": ini.normal((cfg.n_experts, d, f),
                                 ("experts", "embed", "mlp")),
                "w3": ini.normal((cfg.n_experts, d, f),
                                 ("experts", "embed", "mlp")),
                "w2": ini.normal((cfg.n_experts, f, d),
                                 ("experts", "mlp", "embed"), fan_in=f),
            },
        })
    params = {
        "layers": layers,
        "final_norm": {"scale": ini.ones((d,), ("embed",),
                                         dtype=jnp.float32)},
        "unembed": ini.normal((d, cfg.vocab), ("embed", "vocab")),
    }
    vals, _ = unzip(params)
    return vals


def build_tables(cfg: ServeConfig, key, *, uniform_temperature=True,
                 single_adapter=True,
                 instrument_sessions: bool = False) -> TableSet:
    rng = np.random.default_rng(0)
    embed = rng.standard_normal((cfg.vocab, cfg.d_model)).astype(
        np.float32) * 0.02
    temps = (np.ones(cfg.n_classes, np.float32) if uniform_temperature
             else rng.uniform(0.5, 1.5, cfg.n_classes).astype(np.float32))
    adapter_ids = (np.zeros(cfg.n_classes, np.int32) if single_adapter
                   else rng.integers(0, max(cfg.n_adapters, 1),
                                     cfg.n_classes).astype(np.int32))
    tables = [
        Table("req_class",
              {"adapter_id": adapter_ids,
               "temperature": temps,
               "flags": np.zeros(cfg.n_classes, np.int32)},
              n_valid=cfg.n_classes, max_inline=8),
        Table("vocab_embed", {"vec": embed}, n_valid=cfg.vocab,
              max_inline=0),
        Table("adapters",
              {"down": np.zeros((max(cfg.n_adapters, 1), cfg.d_model,
                                 cfg.adapter_rank), np.float32),
               "up": np.zeros((max(cfg.n_adapters, 1), cfg.adapter_rank,
                               cfg.d_model), np.float32)},
              n_valid=cfg.n_adapters,
              default={"down": 0.0, "up": 0.0}),
        # pseudo-table: identity over expert ids — exists to give the MoE
        # router an instrumented lookup site (the paper's per-map sketch)
        Table("router", {"idx": np.arange(cfg.n_experts, dtype=np.int32)},
              n_valid=cfg.n_experts, max_inline=0),
        # instrument=False is the paper's operator opt-out (§6.5: after
        # the NAT regression, conntrack instrumentation is disabled by
        # hand); bench_worstcase flips it on to reproduce the regression
        Table("sessions",
              {"count": np.zeros(cfg.n_slots, np.int32),
               "last_token": np.zeros(cfg.n_slots, np.int32)},
              n_valid=cfg.n_slots, mutability="rw",
              instrument=instrument_sessions),
        moe_stats_table(cfg.n_layers),
    ]
    return TableSet(tables)


MOE_STATS = ("fast_steps", "held_steps", "held_slots", "slots")


def moe_stats_table(n_moe_layers: int) -> Table:
    """The ``moe_stats`` RW table: one row per MoE layer, the counters
    :func:`record_moe_stats` adds to every step."""
    return Table("moe_stats",
                 {f: np.zeros(n_moe_layers, np.int32) for f in MOE_STATS},
                 n_valid=n_moe_layers, mutability="rw", instrument=False,
                 max_inline=0)


def record_moe_stats(ctx, layers) -> None:
    """Add one step to the ``moe_stats`` counters: ``layers`` holds, per
    MoE layer, whether the step took the fast branch (0/1), how many of
    its routed slots landed on held experts, and how many slots it
    routed.  A fast-branch step counts only where it had held slots."""
    idx = jnp.arange(len(layers), dtype=jnp.int32)
    hit = jnp.stack([jnp.asarray(h, jnp.int32) for h, _, _ in layers])
    held = jnp.stack([jnp.asarray(n, jnp.int32) for _, n, _ in layers])
    slots = jnp.asarray([n for _, _, n in layers], jnp.int32)
    old = ctx.lookup("moe_stats", idx, fields=MOE_STATS)
    some = (held > 0).astype(jnp.int32)
    ctx.update("moe_stats", idx, {
        "fast_steps": old["fast_steps"] + hit * some,
        "held_steps": old["held_steps"] + some,
        "held_slots": old["held_slots"] + held,
        "slots": old["slots"] + slots})


def make_serve_step(cfg: ServeConfig):
    """Returns user_step(params, ctx, batch) -> logits."""
    moe_cfg = MoEConfig(num_experts=cfg.n_experts, top_k=cfg.top_k,
                        expert_d_ff=cfg.d_ff)
    model_cfg = ModelConfig(d_model=cfg.d_model, moe=moe_cfg)

    def attention(lp, x):
        B, S, D = x.shape
        q = x @ lp["wq"]
        k = x @ lp["wk"]
        v = x @ lp["wv"]
        H = cfg.n_heads
        hd = D // H
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, H, hd)
        v = v.reshape(B, S, H, hd)
        logits = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(hd)
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhst,bthd->bshd", p, v).reshape(B, S, D)
        return o @ lp["wo"]

    def serve_step(params, ctx, batch):
        tokens = batch["tokens"]                       # (B, S)
        B, S = tokens.shape

        cls = ctx.lookup("req_class", batch["class_id"],
                         fields=("adapter_id", "temperature"))

        x = ctx.lookup("vocab_embed", tokens, fields=("vec",))["vec"]

        hot = ctx.hot_experts("router")
        moe_stats = []
        # named scopes label the device ops of each layer part in the
        # compiled program's metadata; they cost nothing at run time
        for lp in params["layers"]:
            with jax.named_scope("attention"):
                x = x + attention(lp, rmsnorm(lp["norm1"], x))
            h = rmsnorm(lp["norm2"], x)
            h2d = h.reshape(B * S, -1)
            # instrumented router site: record expert choices
            from ..models.moe import route
            with jax.named_scope("moe.router"):
                _, ids, _ = route(lp["moe"]["w_router"], h2d, cfg.top_k,
                                  lp["moe"].get("b_router"))
                ctx.lookup("router", ids.reshape(-1), fields=("idx",))
            if hot:
                with jax.named_scope("moe.hot"):
                    y, m = moe_ffn_hotpath(lp["moe"], h2d, model_cfg, hot)
                hit = m["fastpath_hit"]
            else:
                with jax.named_scope("moe.generic"):
                    y, _ = moe_ffn_local(lp["moe"], h2d, moe_cfg)
                hit = 0
            moe_stats.append((hit, ids.size, ids.size))
            x = x + y.reshape(B, S, -1)

        # adapter branch: fully eliminated when the adapter bank is empty
        ad = ctx.lookup_or_none("adapters", cls["adapter_id"],
                                fields=("down", "up"))
        if ad is not None:
            x = x + jnp.einsum("bsd,bdr,brk->bsk", x, ad["down"],
                               ad["up"])

        if ctx.flag("vision_enabled", default=True):
            # stub vision tower (the QUIC branch): pure overhead unless a
            # class needs it — DCE removes it when the flag is pinned off
            v = x
            for _ in range(2):
                v = jnp.tanh(v @ params["unembed"][:, : v.shape[-1]])
            x = x + 0.0 * v

        x = rmsnorm(params["final_norm"], x)
        logits = x @ params["unembed"]
        logits = logits / cls["temperature"][:, None, None]

        if ctx.flag("track_sessions", default=True):
            next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(
                jnp.int32)
            old = ctx.lookup("sessions", batch["slot"], fields=("count",))
            ctx.update("sessions", batch["slot"],
                       {"count": old["count"] + 1, "last_token": next_tok})
        record_moe_stats(ctx, moe_stats)
        return logits

    return serve_step


def build_fleet(cfg: ServeConfig, key, n_planes: int,
                **table_kw) -> list:
    """N data planes for one :class:`~repro.core.controller.\
MorpheusController`: a list of ``(step_fn, tables)`` pairs with
    **distinct** :class:`TableSet` instances (each plane's control plane
    versions independently — the program guards must not couple) but one
    shared step function and identical schemas/shapes, which is what
    makes ``EngineConfig.cache_ns`` executable sharing across the fleet
    valid.  ``table_kw`` forwards to :func:`build_tables`."""
    step = make_serve_step(cfg)
    return [(step, build_tables(cfg, key, **table_kw))
            for _ in range(n_planes)]


def make_synthetic_batch(cfg: ServeConfig, key, batch_size=8,
                       locality: str = "high", hot_classes=4,
                       hot_offset: int = 0, hot_slots: int = 0,
                       slot_offset: int = 0):
    """Synthetic request stream with controllable class/token locality —
    the paper's high/low/no-locality traces.  ``hot_offset`` shifts the
    hot set (traffic drift, Fig 10); ``hot_slots`` concentrates session
    slots (the §6.5 stateful worst case)."""
    kt, kc, ks = jax.random.split(key, 3)
    if locality == "high":
        n_hot_cls, n_hot_tok = hot_classes, 32
    elif locality == "low":
        n_hot_cls, n_hot_tok = max(cfg.n_classes // 2, 1), cfg.vocab // 4
    else:
        n_hot_cls, n_hot_tok = cfg.n_classes, cfg.vocab
    class_id = (jax.random.randint(kc, (batch_size,), 0, n_hot_cls)
                + hot_offset) % cfg.n_classes
    tokens = (jax.random.randint(kt, (batch_size, cfg.seq), 0, n_hot_tok)
              + hot_offset * 7) % cfg.vocab
    n_slots = hot_slots if hot_slots else cfg.n_slots
    slot = (jax.random.randint(ks, (batch_size,), 0, n_slots)
            + slot_offset) % cfg.n_slots
    return {"tokens": tokens.astype(jnp.int32),
            "class_id": class_id.astype(jnp.int32),
            "slot": slot.astype(jnp.int32)}


def make_request_rows(cfg: ServeConfig, key, n: int, **kw) -> list:
    """N single-request payloads (each field without the batch dim) —
    what the serving frontend's :class:`Request.payload` carries.  Drawn
    from the same synthetic trace as :func:`make_synthetic_batch`
    (``kw`` forwards locality / hot_offset / ...), so frontend-driven
    benchmarks see the paper's locality mixes at request granularity."""
    batch = make_synthetic_batch(cfg, key, batch_size=n, **kw)
    batch = jax.tree.map(np.asarray, batch)
    return [{f: v[i] for f, v in batch.items()} for i in range(n)]


def make_request_batch(rows, bucket: int):
    """Pack a ragged list of per-request payload rows into one padded
    batch of leading dim ``bucket``, with an explicit validity mask.

    ``rows`` are single-request dicts (no batch dim, e.g. from
    :func:`make_request_rows` or ``Request.payload``); ``bucket`` must
    be >= ``len(rows)``.  Returns the batch dict with every payload
    field stacked+padded to ``(bucket, ...)`` plus a ``"valid"`` leaf —
    a ``(bucket,)`` bool mask that is True for the real rows.

    Padding rows REPLICATE row 0 rather than holding zeros: every pad
    row is then a well-formed request over live table keys, and — the
    subtle part — any RW scatter the data plane performs (the sessions
    table's ``.at[slot].set``) sees *identical* values on the duplicated
    slot indices, which XLA defines to be deterministic.  Masked rows
    therefore never perturb the outputs of real rows (asserted by
    tests/test_frontend.py), and the mask itself is consumed host-side
    at fan-back — the data plane never branches on it, so the pad rows
    are pure, bounded overhead exactly like Morpheus' generic fallback
    rows."""
    n = len(rows)
    if n == 0:
        raise ValueError("make_request_batch: empty request list")
    if n > bucket:
        raise ValueError(
            f"make_request_batch: {n} requests exceed bucket={bucket}")
    fields = rows[0].keys()
    out = {}
    for f in fields:
        stacked = np.stack([np.asarray(r[f]) for r in rows])
        if n < bucket:
            pad = np.broadcast_to(stacked[:1],
                                  (bucket - n,) + stacked.shape[1:])
            stacked = np.concatenate([stacked, pad], axis=0)
        out[f] = jnp.asarray(stacked)
    valid = np.zeros(bucket, bool)
    valid[:n] = True
    out["valid"] = jnp.asarray(valid)
    return out


def make_request_windows(cfg: ServeConfig, key, k: int, batch_size=8,
                         **kw) -> list:
    """K consecutive request batches for one fused serving window
    (``MorpheusRuntime.step_many`` /
    ``runtime.place_batch(..., fused=True)``): the same synthetic trace
    as :func:`make_synthetic_batch`, split across K independent subkeys so
    a fused window sees the same traffic *distribution* as K single
    steps.  ``kw`` forwards (locality / hot_offset / ...)."""
    return [make_synthetic_batch(cfg, kk, batch_size, **kw)
            for kk in jax.random.split(key, k)]
