"""Attention: GQA (with sliding window & logit softcap) and MLA.

The core primitive is :func:`attend_blocked` — a flash-style, chunked,
numerically-stable attention in pure jnp.  It is (a) the memory-sane default
used when lowering the full-size configs (the KV sequence is never
materialised as a logits matrix), and (b) the oracle for the Pallas
``flash_attention`` kernel.  On TPU, ``repro.kernels.ops.flash_attention``
dispatches to the Pallas kernel for supported shapes and falls back to this
reference elsewhere.

Position conventions: the caller always passes ``positions`` for the tokens
in ``x`` (prefill: ``arange(S)``; decode: ``[pos]``).  Caches carry their own
``pos`` array (−1 ⇒ empty slot) used for masking.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..distributed.compat import shard_map
from .config import MLAConfig, ModelConfig
from .layers import apply_rope, dot_f32, rmsnorm, yarn_mscale
from .params import Initializer

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_attention(ini: Initializer, cfg: ModelConfig):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    return {
        "wq": ini.normal((d, h, hd), ("embed", "q_heads", "head_dim")),
        "wk": ini.normal((d, hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ini.normal((d, hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ini.normal((h, hd, d), ("q_heads", "head_dim", "embed"),
                         fan_in=h * hd),
    }


def init_mla_attention(ini: Initializer, cfg: ModelConfig):
    """DeepSeek-V2's MLA: queries through a low-rank ``w_dq`` ->
    RMSNorm -> ``w_uq`` (full-rank ``wq`` when ``q_lora_rank`` is 0); the
    compressed ``c_kv`` (``w_dkv``) is RMS-normed before ``w_uk``/``w_uv``
    up-project it; one rope key (``w_krope``) is shared by every head."""
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank:
        q = {"w_dq": ini.normal((d, m.q_lora_rank), ("embed", "q_lora")),
             "q_norm": {"scale": ini.ones((m.q_lora_rank,), ("q_lora",),
                                          dtype=jnp.float32)},
             "w_uq": ini.normal((m.q_lora_rank, h, qk),
                                ("q_lora", "q_heads", "head_dim"),
                                fan_in=m.q_lora_rank)}
    else:
        q = {"wq": ini.normal((d, h, qk), ("embed", "q_heads", "head_dim"))}
    return {
        **q,
        "w_dkv": ini.normal((d, m.kv_lora_rank), ("embed", "kv_lora")),
        "kv_norm": {"scale": ini.ones((m.kv_lora_rank,), ("kv_lora",),
                                      dtype=jnp.float32)},
        "w_krope": ini.normal((d, m.qk_rope_dim), ("embed", "head_dim")),
        "w_uk": ini.normal((m.kv_lora_rank, h, m.qk_nope_dim),
                           ("kv_lora", "q_heads", "head_dim"),
                           fan_in=m.kv_lora_rank),
        "w_uv": ini.normal((m.kv_lora_rank, h, m.v_head_dim),
                           ("kv_lora", "q_heads", "head_dim"),
                           fan_in=m.kv_lora_rank),
        "wo": ini.normal((h, m.v_head_dim, d),
                         ("q_heads", "head_dim", "embed"),
                         fan_in=h * m.v_head_dim),
    }


# ---------------------------------------------------------------------------
# Flash-style blocked attention (pure jnp; oracle for the Pallas kernel)
# ---------------------------------------------------------------------------

def attend_blocked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   q_pos: jax.Array, kv_pos: jax.Array,
                   causal: bool = True,
                   window: Optional[int] = None,
                   logit_softcap: float = 0.0,
                   block: int = 512) -> jax.Array:
    """q: (B,Sq,H,D); k,v: (B,Sk,Hkv,D); q_pos: (Sq,), kv_pos: (Sk,).

    kv entries with position < 0 are masked out (empty cache slots).
    Scans over KV blocks carrying (max, sumexp, acc) — O(Sq·block) live
    memory instead of O(Sq·Sk).
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / math.sqrt(D)

    nb = -(-Sk // block)
    pad = nb * block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
    qg = q.reshape(B, Sq, Hkv, G, D)

    kb = k.reshape(B, nb, block, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block, Hkv, D).transpose(1, 0, 2, 3, 4)
    pb = kv_pos.reshape(nb, block)

    def step(carry, blk):
        m_run, l_run, acc = carry
        kblk, vblk, posblk = blk
        # logits: (B, Hkv, G, Sq, block)
        logits = dot_f32("bshgd,bthd->bhgst", qg, kblk) * scale
        if logit_softcap:
            logits = logit_softcap * jnp.tanh(logits / logit_softcap)
        mask = jnp.broadcast_to((posblk >= 0)[None, None, None, None, :],
                                logits.shape)
        if causal:
            mask &= (posblk[None, :] <= q_pos[:, None])[None, None, None]
        if window is not None:
            mask &= (q_pos[:, None] - posblk[None, :] < window)[None, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m_run, logits.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask, p, 0.0)                     # m_new == -inf safety
        l_new = l_run * alpha + p.sum(axis=-1)
        pv = dot_f32("bhgst,bthd->bshgd", p.astype(vblk.dtype), vblk)
        acc = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    # flash backward: recompute per-block p instead of stacking residuals
    step = jax.checkpoint(step,
                          policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, pb))
    l = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    out = (acc / l).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA forward (shared by train / prefill / decode / cross-attention)
# ---------------------------------------------------------------------------

def project_kv(params, kv_in: jax.Array):
    k = jnp.einsum("bsd,dhk->bshk", kv_in, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_in, params["wv"])
    return k, v


def _gqa_decode_seq_parallel(pol, q, k, v, kv_pos, positions, *,
                             window, logit_softcap):
    """Sequence-parallel flash decode for GQA (mirrors the MLA version):
    the KV cache stays seq-sharded on the model axis; each shard computes
    a partial softmax over its chunk and the (m, l, acc) partials are
    psum-combined — ~B·H·hd bytes per layer instead of gathering the
    whole cache.  q: (B,1,H,hd) -> out (B,1,H,hd)."""
    import math as _math
    from jax.sharding import PartitionSpec as P

    mdl = pol.model_axis
    # batch=1 (long_500k) cannot shard over data — the data axis then
    # joins the model axis in sharding the SEQUENCE (256-way KV split),
    # and the softmax combine spans both axes.
    if q.shape[0] % pol.n_batch_shards == 0 and pol.n_batch_shards > 1:
        batch = tuple(pol.batch_axes)
        seq_axes = (mdl,)
    else:
        batch = ()
        seq_axes = ("data", mdl)
    D = q.shape[-1]
    Hkv = k.shape[2]
    G = q.shape[2] // Hkv
    scale = 1.0 / _math.sqrt(D)

    def body(qg, kl, vl, pos):
        B, S, H, _ = qg.shape
        qh = qg.reshape(B, S, Hkv, G, D)
        logits = dot_f32("bshgd,bthd->bhgst", qh, kl) * scale
        if logit_softcap:
            logits = logit_softcap * jnp.tanh(logits / logit_softcap)
        mask = (pos >= 0)[None, :] & (pos[None, :] <= positions[:, None])
        if window is not None:
            mask &= positions[:, None] - pos[None, :] < window
        mask = jnp.broadcast_to(mask[None, None, None], logits.shape)
        logits = jnp.where(mask, logits, NEG_INF)
        m_loc = logits.max(axis=-1)
        m_glob = jax.lax.pmax(m_loc, seq_axes)
        p = jnp.where(mask, jnp.exp(logits - m_glob[..., None]), 0.0)
        l_glob = jax.lax.psum(p.sum(axis=-1), seq_axes)
        acc = jax.lax.psum(
            dot_f32("bhgst,bthd->bshgd", p.astype(vl.dtype), vl),
            seq_axes)
        l = jnp.maximum(l_glob, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return (acc / l).reshape(B, S, H, D).astype(jnp.float32)

    seq_spec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    batch_spec = batch if batch else None
    return shard_map(
        body, mesh=pol.mesh,
        in_specs=(P(batch_spec, None, None, None),
                  P(batch_spec, seq_spec, None, None),
                  P(batch_spec, seq_spec, None, None),
                  P(seq_spec)),
        out_specs=P(batch_spec, None, None, None),
    )(q, k, v, kv_pos).astype(q.dtype)


def gqa_forward(params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                *, window: Optional[int] = None, cache=None,
                kv_const=None, causal: bool = True, rope: bool = True):
    """x: (B,S,D); positions: (S,) int32 positions of x's tokens.

    cache: {"k": (B,Smax,Hkv,hd), "v": ..., "pos": (Smax,)} — written at
    ``positions`` (prefill: S entries from 0; decode: one entry).
    kv_const: (k, v, kv_pos) precomputed constants (cross-attention).
    """
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)

    if kv_const is not None:
        k, v, kv_pos = kv_const
        out = attend_blocked(q, k, v, q_pos=positions, kv_pos=kv_pos,
                             causal=False, window=None,
                             logit_softcap=cfg.attn_logit_softcap)
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), None

    k, v = project_kv(params, x)
    if rope:
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        start = positions[0]
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, start, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, start, 0, 0))
        cpos = jax.lax.dynamic_update_slice(
            cache["pos"], positions.astype(jnp.int32), (start,))
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        k, v, kv_pos = ck, cv, cpos
    else:
        kv_pos = positions

    from ..distributed.meshctx import get_policy
    pol = get_policy()
    n_seq_shards = 1
    if pol is not None and pol.mesh is not None:
        n_seq_shards = pol.n_model
        if x.shape[0] % pol.n_batch_shards or pol.n_batch_shards == 1:
            n_seq_shards *= pol.mesh.shape.get("data", 1)
    if (S == 1 and cache is not None and pol is not None
            and pol.mesh is not None and k.shape[1] % n_seq_shards == 0):
        out = _gqa_decode_seq_parallel(
            pol, q, k, v, kv_pos, positions, window=window,
            logit_softcap=cfg.attn_logit_softcap)
    else:
        out = attend_blocked(q, k, v, q_pos=positions, kv_pos=kv_pos,
                             causal=causal, window=window,
                             logit_softcap=cfg.attn_logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, new_cache


def _mla_decode_seq_parallel(pol, q_lat, q_rope, ckv, k_rope, kv_pos,
                             positions, scale):
    """Flash-decoding over the model axis: local partial softmax per seq
    shard, log-sum-exp combine via psum.  Returns ctx_lat (B,1,H,r)."""
    from jax.sharding import PartitionSpec as P

    mdl = pol.model_axis
    batch = tuple(pol.batch_axes)

    def body(ql, qr, c, r, pos):
        # ql/qr: (B,1,H,*) replicated; c: (B,Sk_l,r); pos: (Sk_l,)
        logits = (dot_f32("bshr,btr->bhst", ql, c) +
                  dot_f32("bshr,btr->bhst", qr, r)) * scale
        mask = jnp.broadcast_to(
            ((pos >= 0)[None, :] &
             (pos[None, :] <= positions[:, None]))[None, None],
            logits.shape)
        logits = jnp.where(mask, logits, NEG_INF)
        m_loc = logits.max(axis=-1)                       # (B,H,1)
        m_glob = jax.lax.pmax(m_loc, mdl)
        p = jnp.exp(logits - m_glob[..., None])
        p = jnp.where(mask, p, 0.0)
        l_loc = p.sum(axis=-1)
        acc = dot_f32("bhst,btr->bshr", p.astype(c.dtype), c)
        l_glob = jax.lax.psum(l_loc, mdl)
        acc_glob = jax.lax.psum(acc, mdl)
        out = acc_glob / jnp.maximum(
            l_glob, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(jnp.float32)

    return shard_map(
        body, mesh=pol.mesh,
        in_specs=(P(batch, None, None, None), P(batch, None, None, None),
                  P(batch, mdl, None), P(batch, mdl, None), P(mdl)),
        out_specs=P(batch, None, None, None),
    )(q_lat, q_rope, ckv, k_rope, kv_pos)


# ---------------------------------------------------------------------------
# MLA forward (absorbed attention over the compressed cache)
# ---------------------------------------------------------------------------

def mla_forward(params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                *, cache=None, block: int = 512):
    """MLA with compressed KV cache.

    Decode (S==1): *absorbed* formulation — queries projected into the
    kv_lora latent space, attention runs directly against the compressed
    cache (DeepSeek-V2's decode fast path: cache stays rank-r).

    Train/prefill (S>1): *naive* formulation — K/V up-projected from the
    compressed cache PER BLOCK inside the flash loop.  §Perf iteration:
    the absorbed form contracts scores/PV at rank r=512 instead of
    192/128, measured ~4 s/chip extra on deepseek-v2 train_4k; the naive
    per-block up-projection costs less than it saves at S>=block.

    Queries go through the q-LoRA and its RMSNorm when ``q_lora_rank`` is
    set; ``c_kv`` is RMS-normed; with ``cfg.rope_scaling`` the rope is
    YaRN's and the scores are scaled by ``mscale_all_dim``'s m^2.  The key
    block is capped at the key length.
    """
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    yarn = cfg.rope_scaling
    if m.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, params["w_dq"])
        cq = rmsnorm(params["q_norm"], cq, cfg.rms_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, yarn)

    ckv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"])
    # the cache holds c_kv normed: the norm is per token
    ckv = rmsnorm(params["kv_norm"], ckv, cfg.rms_eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, params["w_krope"])[:, :, None, :]
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta, yarn)[:, :, 0, :]

    new_cache = None
    if cache is not None:
        start = positions[0]
        cc = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, start, 0))
        cr = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
            (0, start, 0))
        cpos = jax.lax.dynamic_update_slice(
            cache["pos"], positions.astype(jnp.int32), (start,))
        new_cache = {"ckv": cc, "k_rope": cr, "pos": cpos}
        ckv, k_rope, kv_pos = cc, cr, cpos
    else:
        kv_pos = positions

    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    absorb = S == 1

    Sk = ckv.shape[1]
    # a block longer than the keys would only up-project padding
    block = min(block, Sk)
    nb = -(-Sk // block)
    pad = nb * block - Sk
    if pad:
        ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
    cb = ckv.reshape(B, nb, block, -1).transpose(1, 0, 2, 3)
    rb = k_rope.reshape(B, nb, block, -1).transpose(1, 0, 2, 3)
    pb = kv_pos.reshape(nb, block)

    if absorb:
        # q into latent space; attend against the compressed cache
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, params["w_uk"])

        # §Perf (beyond-paper): sequence-parallel flash decode.  The cache
        # is seq-sharded over the model axis; the default SPMD plan
        # all-gathers the whole compressed cache per layer (~68 GB/step on
        # deepseek-v2 decode_32k).  Instead each shard attends its local
        # chunk and the (m, l, acc) partials are psum-combined:
        # 33 MB x 2 per layer instead of 1.1 GB gathered.
        from ..distributed.meshctx import get_policy
        pol = get_policy()
        if (pol is not None and pol.mesh is not None
                and Sk % pol.n_model == 0):
            ctx_lat = _mla_decode_seq_parallel(
                pol, q_lat, q_rope, ckv, k_rope, kv_pos, positions, scale)
            ctx = jnp.einsum("bshr,rhv->bshv", ctx_lat.astype(x.dtype),
                             params["w_uv"])
            out = jnp.einsum("bshv,hvd->bsd", ctx, params["wo"])
            return out, new_cache

        def step(carry, blk):
            m_run, l_run, acc = carry
            cblk, rblk, posblk = blk
            logits = (dot_f32("bshr,btr->bhst", q_lat, cblk) +
                      dot_f32("bshr,btr->bhst", q_rope, rblk)) * scale
            mask = jnp.broadcast_to(
                ((posblk >= 0)[None, :] &
                 (posblk[None, :] <= positions[:, None]))[None, None],
                logits.shape)
            logits = jnp.where(mask, logits, NEG_INF)
            m_new = jnp.maximum(m_run, logits.max(axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(logits - m_new[..., None])
            p = jnp.where(mask, p, 0.0)
            l_new = l_run * alpha + p.sum(axis=-1)
            pv = dot_f32("bhst,btr->bshr", p.astype(cblk.dtype), cblk)
            acc = acc * alpha.transpose(0, 2, 1)[:, :, :, None] + pv
            return (m_new, l_new, acc), None

        acc_dim = m.kv_lora_rank
    else:
        # naive: up-project K/V per block inside the flash loop
        def step(carry, blk):
            m_run, l_run, acc = carry
            cblk, rblk, posblk = blk
            k_nope = dot_f32("btr,rhn->bthn", cblk, params["w_uk"])
            v_blk = dot_f32("btr,rhv->bthv", cblk, params["w_uv"])
            logits = (dot_f32("bshn,bthn->bhst",
                              q_nope.astype(jnp.float32), k_nope) +
                      dot_f32("bshr,btr->bhst", q_rope, rblk)) * scale
            mask = jnp.broadcast_to(
                ((posblk >= 0)[None, :] &
                 (posblk[None, :] <= positions[:, None]))[None, None],
                logits.shape)
            logits = jnp.where(mask, logits, NEG_INF)
            m_new = jnp.maximum(m_run, logits.max(axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(logits - m_new[..., None])
            p = jnp.where(mask, p, 0.0)
            l_new = l_run * alpha + p.sum(axis=-1)
            pv = dot_f32("bhst,bthv->bshv", p, v_blk)
            acc = acc * alpha.transpose(0, 2, 1)[:, :, :, None] + pv
            return (m_new, l_new, acc), None

        acc_dim = m.v_head_dim

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    acc0 = jnp.zeros((B, S, H, acc_dim), jnp.float32)
    step = jax.checkpoint(step,
                          policy=jax.checkpoint_policies.nothing_saveable)
    (_, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (cb, rb, pb))
    ctx = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    ctx = ctx.astype(x.dtype)
    if absorb:
        ctx = jnp.einsum("bshr,rhv->bshv", ctx, params["w_uv"])
    out = jnp.einsum("bshv,hvd->bsd", ctx, params["wo"])
    return out, new_cache
