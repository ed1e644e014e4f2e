"""Plain float32 reference of DeepSeek-V2's layers for the CPU tests, at
``HIGHEST`` precision and with nothing of the program's math (sizes are
read from its ``ModelConfig``).  The benchmark keeps its own copy in
``bench/refs/deepseek_v2.py``; this one is split by layer so that each
can be compared alone.

Equations: arXiv:2405.04434 §2.1-2.2 and the published modeling code.
The rope pairs dimension ``i`` with ``i + rope/2`` (the program's
pairing; the published code pairs ``2i`` with ``2i + 1``, a fixed
permutation of the rope columns).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def mm(eq, a, b):
    return jnp.einsum(eq, jnp.asarray(a, jnp.float32),
                      jnp.asarray(b, jnp.float32), precision=HI)


def rms(x, scale, eps):
    x = jnp.asarray(x, jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_inv_freq(dim, theta, yarn):
    def corr(rot):
        return dim * math.log(yarn.original_max_position
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extra / yarn.factor * (1.0 - mask) + extra * mask


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope(u, positions, theta, yarn):
    """Rotate the last axis of ``u`` (..., S, [H,] R) at ``positions``."""
    r = u.shape[-1]
    inv = (yarn_inv_freq(r, theta, yarn) if yarn is not None
           else 1.0 / theta ** (np.arange(0, r, 2) / r))
    ang = np.asarray(positions, np.float64)[:, None] * inv[None]
    c, s = jnp.asarray(np.cos(ang), jnp.float32), \
        jnp.asarray(np.sin(ang), jnp.float32)
    if yarn is not None:
        k = mscale(yarn.factor, yarn.mscale) \
            / mscale(yarn.factor, yarn.mscale_all_dim)
        c, s = c * k, s * k
    if u.ndim == 4:
        c, s = c[:, None], s[:, None]
    a, b = u[..., :r // 2], u[..., r // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def mla(p, cfg, x):
    """Causal MLA over positions 0..S-1 of ``x`` (B, S, D)."""
    m, eps, y = cfg.mla, cfg.rms_eps, cfg.rope_scaling
    B, S, _ = x.shape
    pos = np.arange(S)
    if m.q_lora_rank:
        q = mm("bsr,rhk->bshk", rms(mm("bsd,dr->bsr", x, p["w_dq"]),
                                    p["q_norm"]["scale"], eps), p["w_uq"])
    else:
        q = mm("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., :m.qk_nope_dim]
    q_pe = rope(q[..., m.qk_nope_dim:], pos, cfg.rope_theta, y)
    ckv = rms(mm("bsd,dr->bsr", x, p["w_dkv"]), p["kv_norm"]["scale"], eps)
    k_pe = rope(mm("bsd,dr->bsr", x, p["w_krope"]), pos, cfg.rope_theta, y)
    k = mm("bsr,rhk->bshk", ckv, p["w_uk"])
    v = mm("bsr,rhk->bshk", ckv, p["w_uv"])
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if y is not None:
        scale *= mscale(y.factor, y.mscale_all_dim) ** 2
    sc = (mm("bshk,bthk->bhst", q_nope, k)
          + mm("bshk,btk->bhst", q_pe, k_pe)) * scale
    sc = jnp.where(np.tril(np.ones((S, S), bool)), sc, -jnp.inf)
    o = mm("bhst,bthk->bshk", jax.nn.softmax(sc, -1), v)
    return mm("bshk,hkd->bsd", o, p["wo"])


def route(logits, moe):
    """DeepSeek-V2's routing of router ``logits`` (T, E) as a plain top-k
    over the softmax scores with the dropped groups masked: the best
    ``topk_group`` groups by their largest score (the lower group first
    at an exact tie).  Mixtral-style scoring (``topk_softmax``) takes
    the top-k logits, softmaxed over the k.  Returns gates (T, K) and ids
    (T, K)."""
    logits = np.asarray(logits, np.float32)
    if moe.scoring == "topk_softmax":
        ids = np.argsort(-logits, -1, kind="stable")[:, :moe.top_k]
        top = np.take_along_axis(logits, ids, -1)
        g = np.exp(top - top.max(-1, keepdims=True))
        return (g / g.sum(-1, keepdims=True)).astype(np.float32), \
            ids.astype(np.int32)
    s = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    T, E = s.shape
    G, n = moe.n_group, E // moe.n_group
    gates, ids = [], []
    for t in range(T):
        best = sorted(range(G), key=lambda g: (-s[t, g * n:(g + 1) * n].max(),
                                               g))[:moe.topk_group]
        masked = np.zeros(E, np.float32)
        for g in best:
            masked[g * n:(g + 1) * n] = s[t, g * n:(g + 1) * n]
        top = sorted(range(E), key=lambda e: (-masked[e], e))[:moe.top_k]
        ids.append(top)
        gates.append([moe.routed_scaling * masked[e] for e in top])
    return np.asarray(gates, np.float32), np.asarray(ids, np.int32)


def swiglu(x, w_gate, w_up, w_down):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, w_gate))
              * mm("td,df->tf", x, w_up), w_down)


def moe_layer(p, moe, x2d, held, logits=None):
    """Routed experts of ``held`` (their stack order) plus the shared
    ones, for tokens ``x2d`` (T, D); slots routed elsewhere add
    nothing."""
    if logits is None:
        logits = mm("td,de->te", x2d, p["w_router"]) + p["b_router"]
    gates, ids = route(logits, moe)
    y = np.zeros(x2d.shape, np.float32)
    where = {e: i for i, e in enumerate(held)}
    for e, i in where.items():
        w = (gates * (ids == e)).sum(-1)                 # (T,)
        if not w.any():
            continue
        out = swiglu(x2d, p["w1"][i], p["w3"][i], p["w2"][i])
        y += np.asarray(out) * w[:, None]
    if "shared" in p:
        sh = p["shared"]
        y += np.asarray(swiglu(x2d, sh["w_gate"], sh["w_up"], sh["w_down"]))
    return y


def forward(params, embed, tokens, cfg):
    """Logits (B, S, V) of the serving plane's layout: leading dense
    layers, then MoE layers holding ``cfg.moe.held_ids``."""
    eps = cfg.rms_eps
    x = jnp.asarray(embed, jnp.float32)[jnp.asarray(tokens)]
    B, S, D = x.shape
    for i, lp in enumerate(params["layers"]):
        x = x + mla(lp["attn"], cfg, rms(x, lp["attn_norm"]["scale"], eps))
        h = rms(x, lp["ffn_norm"]["scale"], eps).reshape(B * S, D)
        f = lp["ffn"]
        if i < cfg.first_k_dense:
            y = swiglu(h, f["w_gate"], f["w_up"], f["w_down"])
        else:
            y = moe_layer(f, cfg.moe, h, cfg.moe.held_ids)
        x = x + jnp.asarray(y).reshape(B, S, D)
    x = rms(x, params["final_norm"]["scale"], eps)
    return mm("bsd,dv->bsv", x, params["unembed"])
