"""Weights and plain reference of DeepSeek-V2 on one chip's share.

The configuration (``bench/configs/deepseek-v2-ep8-7l.json``) is the
published model cut in depth, to the 20 routed experts this chip holds
of 160, and to an eighth of the vocabulary.  A request is ``seq`` token
ids at positions ``0..seq-1``; the answer is the logits at every
position.  The equations are the paper's (arXiv:2405.04434, §2.1-2.2)
and the published modeling code's:

- MLA: ``q = W_uq RMSNorm(W_dq h)`` split into a 128-wide part and a
  64-wide rope part per head; ``c_kv = RMSNorm(W_dkv h)``, one rope key
  ``k_pe = W_krope h`` shared by every head, ``k = W_uk c_kv``,
  ``v = W_uv c_kv``; YaRN rope (factor 40 over 4096 original positions,
  beta 32/1) and scores scaled by ``m^2 / sqrt(192)``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``.
- The first layer's FFN is a dense SwiGLU; the others route: scores
  ``softmax(W_g h)`` over all 160 experts (plus the traffic's router
  bias on the logits), the best 3 of 8 groups by their largest score
  eligible, the top 6 of those, each gated by 16 x its score without
  renormalising.  Experts this chip does not hold add nothing.  Two
  shared experts (one SwiGLU of width 3072) add to every token.

:func:`make_weights` builds bfloat16 weights (router and norms float32)
on the device from the seed, in the pytree layout the program takes;
:func:`forward` computes in float32 at ``HIGHEST`` through
:func:`~bench.refs.numerics.make_matmul`, over the same weights upcast,
and imports nothing of the program.  The rope pairs dimension ``i`` with
``i + 32``, as the program does (the configuration's ``departures``).

Routing is a discontinuity.  Where a token's 6th and 7th eligible router
logits, or its 3rd and 4th groups' largest logits, lie closer than
``router_tie``, either way is right; the reference answers as routed,
with each near tie of experts taken the other way, and with each near
tie of groups taken the other way, and a served token is judged by the
answer it lies nearest (:func:`~bench.refs.serve_plane.token_gaps`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib.common import jax_key
from .numerics import HIGHEST, make_matmul, normal, rmsnorm
from .serve_plane import token_gaps  # noqa: F401  (the gap of a token)

EMBED_STD = 0.02
N_ANSWERS = 3


def dims(config: dict) -> dict:
    dep = config["deployment"]
    first = dep["held_first_expert"]
    rope = config["rope_scaling"]
    return {"d": config["hidden_size"], "h": config["num_attention_heads"],
            "ql": config["q_lora_rank"], "kvl": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"],
            "vd": config["v_head_dim"],
            "dense_f": config["intermediate_size"],
            "f": config["moe_intermediate_size"],
            "shared_f": config["n_shared_experts"]
            * config["moe_intermediate_size"],
            "e": dep["router_experts"],
            "held": tuple(range(first, first + config["n_routed_experts"])),
            "k": config["num_experts_per_tok"],
            "groups": config["n_group"], "topk_group": config["topk_group"],
            "scaling": float(config["routed_scaling_factor"]),
            "v": config["vocab_size"], "layers": config["num_hidden_layers"],
            "dense_layers": config["first_k_dense_replace"],
            "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
            "yarn": (float(rope["factor"]),
                     int(rope["original_max_position_embeddings"]),
                     float(rope["beta_fast"]), float(rope["beta_slow"]),
                     float(rope["mscale"]), float(rope["mscale_all_dim"])),
            "tie": config["router_tie"]["value"]}


def make_weights(config: dict, seed: int, router_bias: dict):
    """The weights from ``seed``; ``router_bias`` (``{"experts": [...],
    "value": b}``) is added to every MoE layer's router logits."""
    m = dims(config)
    bias = np.zeros(m["e"], np.float32)
    bias[list(router_bias.get("experts", []))] = router_bias.get("value",
                                                                  0.0)
    shape = tuple((k, m[k]) for k in ("d", "h", "ql", "kvl", "nope", "rope",
                                      "vd", "dense_f", "f", "shared_f", "e",
                                      "v", "layers", "dense_layers"))
    return _weights(jax_key(seed, "dsv2-weights"), shape,
                    len(m["held"]), jnp.asarray(bias))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _weights(key, shape, n_held, bias):
    m = dict(shape)
    d, h = m["d"], m["h"]
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 16 * m["layers"] + 1))

    def w(shape, fan_in):
        return normal(next(keys), shape, fan_in ** -0.5, bf)

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def swiglu(f):
        return {"w_up": w((d, f), d), "w_gate": w((d, f), d),
                "w_down": w((f, d), f)}

    layers = []
    for i in range(m["layers"]):
        attn = {"w_dq": w((d, m["ql"]), d), "q_norm": ones(m["ql"]),
                "w_uq": w((m["ql"], h, m["nope"] + m["rope"]), m["ql"]),
                "w_dkv": w((d, m["kvl"]), d), "kv_norm": ones(m["kvl"]),
                "w_krope": w((d, m["rope"]), d),
                "w_uk": w((m["kvl"], h, m["nope"]), m["kvl"]),
                "w_uv": w((m["kvl"], h, m["vd"]), m["kvl"]),
                "wo": w((h, m["vd"], d), h * m["vd"])}
        if i < m["dense_layers"]:
            ffn = swiglu(m["dense_f"])
        else:
            f = m["f"]
            ffn = {"w_router": normal(next(keys), (d, m["e"]), d ** -0.5),
                   "b_router": bias,
                   "w1": w((n_held, d, f), d), "w3": w((n_held, d, f), d),
                   "w2": w((n_held, f, d), f),
                   "shared": swiglu(m["shared_f"])}
        layers.append({"attn_norm": ones(d), "attn": attn,
                       "ffn_norm": ones(d), "ffn": ffn})
    return {"layers": layers, "final_norm": ones(d),
            "unembed": w((d, m["v"]), d)}


def make_embedding(config: dict, seed: int) -> jax.Array:
    m = dims(config)
    return _embedding(jax_key(seed, "dsv2-embed"), m["v"], m["d"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _embedding(key, v, d):
    return normal(key, (v, d), EMBED_STD)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rope frequencies (the published
    ``DeepseekV2YarnRotaryEmbedding``), float64 on the host."""
    def corr(rot):
        return dim * math.log(original / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def forward(weights, embed, tokens, config: dict,
            precision: str = "float32") -> jax.Array:
    """Reference logits ``(3, n, seq, vocab)`` of ``n`` requests' tokens
    ``(n, seq)``: ``[0]`` as routed, ``[1]`` with each near tie of
    experts taken the other way, ``[2]`` with each near tie of groups
    taken the other way (equal to ``[0]`` where there is none)."""
    m = dims(config)
    return _forward(weights, embed, tokens, _static(m), precision)[0]


def router_logits(weights, embed, tokens, config: dict,
                  precision: str = "float32") -> jax.Array:
    """Each MoE layer's router logits ``(layers, n * seq, experts)`` as
    routed, to measure how far a precision moves them."""
    m = dims(config)
    return _forward(weights, embed, tokens, _static(m), precision)[1]


def _static(m: dict) -> tuple:
    return tuple((k, m[k]) for k in (
        "h", "nope", "rope", "held", "k", "groups", "topk_group",
        "scaling", "dense_layers", "eps", "theta", "yarn", "tie"))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _forward(weights, embed, tokens, static, precision):
    m = dict(static)
    mm = make_matmul(precision)
    eps, tie, K = m["eps"], m["tie"], m["k"]
    n, s = tokens.shape
    a = N_ANSWERS
    x = jnp.tile(embed[tokens].astype(jnp.float32), (a, 1, 1))  # (a n, s, d)
    t = a * n * s
    answer = jnp.arange(t) // (n * s)          # which answer a row is of
    nope, rope = m["nope"], m["rope"]
    factor, original, fast, slow, msc, msc_all = m["yarn"]
    inv = jnp.asarray(yarn_inv_freq(rope, m["theta"], factor, original,
                                    fast, slow), jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos_sin = (jnp.cos(ang), jnp.sin(ang))
    ratio = yarn_mscale(factor, msc) / yarn_mscale(factor, msc_all)
    scale = yarn_mscale(factor, msc_all) ** 2 / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((s, s), bool))
    held = jnp.asarray(m["held"])
    routers = []

    def rot(u):                                 # (..., s, [h,] rope)
        c, si = cos_sin
        if u.ndim == 4:
            c, si = c[:, None, :], si[:, None, :]
        c, si = c * ratio, si * ratio
        u1, u2 = u[..., :rope // 2], u[..., rope // 2:]
        return jnp.concatenate([u1 * c - u2 * si, u2 * c + u1 * si], -1)

    def swiglu(p, u, eq_in, eq_out):
        g = mm(eq_in, u, p["w_gate"])
        return mm(eq_out, jax.nn.silu(g) * mm(eq_in, u, p["w_up"]),
                  p["w_down"])

    for i, lp in enumerate(weights["layers"]):
        at = lp["attn"]
        hh = rmsnorm(x, lp["attn_norm"]["scale"], eps)
        cq = rmsnorm(mm("nsd,dr->nsr", hh, at["w_dq"]),
                     at["q_norm"]["scale"], eps)
        q = mm("nsr,rhk->nshk", cq, at["w_uq"])
        q_nope, q_pe = q[..., :nope], rot(q[..., nope:])
        ckv = rmsnorm(mm("nsd,dr->nsr", hh, at["w_dkv"]),
                      at["kv_norm"]["scale"], eps)
        k_pe = rot(mm("nsd,dr->nsr", hh, at["w_krope"]))
        k_nope = mm("nsr,rhk->nshk", ckv, at["w_uk"])
        v = mm("nsr,rhk->nshk", ckv, at["w_uv"])
        sc = (mm("nshk,nthk->nhst", q_nope, k_nope)
              + mm("nshk,ntk->nhst", q_pe, k_pe)) * scale
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = mm("nhst,nthk->nshk", p, v)
        x = x + mm("nshk,hkd->nsd", o, at["wo"])

        f = lp["ffn"]
        hh = rmsnorm(x, lp["ffn_norm"]["scale"], eps)
        if i < m["dense_layers"]:
            x = x + swiglu(f, hh, "nsd,df->nsf", "nsf,fd->nsd")
            continue
        h2 = hh.reshape(t, -1)
        r = mm("td,de->te", h2, f["w_router"]) + f["b_router"]
        routers.append(r)
        E, G = r.shape[-1], m["groups"]
        # groups: the best topk_group by their largest logit (the
        # softmax keeps the order); a near tie of the last kept and the
        # first dropped group is taken the other way in answer 2
        gmax, gid = jax.lax.top_k(r.reshape(t, G, E // G).max(-1),
                                  m["topk_group"] + 1)
        tg = m["topk_group"]
        swap_g = (answer == 2) & (gmax[:, tg - 1] - gmax[:, tg] < tie)
        keep = gid[:, :tg].at[:, tg - 1].set(
            jnp.where(swap_g, gid[:, tg], gid[:, tg - 1]))
        ok = jax.nn.one_hot(keep, G, dtype=jnp.bool_).any(1)   # (t, G)
        ok = jnp.repeat(ok, E // G, axis=1)
        top, ids = jax.lax.top_k(jnp.where(ok, r, -jnp.inf), K + 1)
        swap_e = (answer == 1) & (top[:, K - 1] - top[:, K] < tie)
        ids = ids.at[:, K - 1].set(
            jnp.where(swap_e, ids[:, K], ids[:, K - 1]))[:, :K]
        score = jax.nn.softmax(r, axis=-1)
        gates = m["scaling"] * jnp.take_along_axis(score, ids, 1)  # (t, K)
        # (t, held): the gate of each held expert a token chose
        weight = jnp.einsum("tk,tke->te", gates,
                            (ids[:, :, None] == held[None, None, :]
                             ).astype(jnp.float32), precision=HIGHEST)
        g1 = mm("td,edf->etf", h2, f["w1"])
        g3 = mm("td,edf->etf", h2, f["w3"])
        y = mm("etf,efd->etd", jax.nn.silu(g1) * g3, f["w2"])
        y = jnp.einsum("te,etd->td", weight, y, precision=HIGHEST)
        y = y + swiglu(f["shared"], h2, "td,df->tf", "tf,fd->td")
        x = x + y.reshape(x.shape)
    x = rmsnorm(x, weights["final_norm"]["scale"], eps)
    logits = mm("nsd,dv->nsv", x, weights["unembed"])
    return logits.reshape(a, n, s, -1), jnp.stack(routers)
