"""Weights and plain reference of the serving plane.

The plane (``repro.serving.dataplane``) is one-shot scoring: a request
is ``seq`` token ids, a class and a session slot, and the answer is the
logits at every position.  Per layer: RMSNorm, causal multi-head
attention without position encoding, residual; RMSNorm, a router
(linear plus a per-expert bias) choosing ``top_k`` experts whose softmaxed
logits weight SwiGLU experts, residual.  Then RMSNorm and the unembedding,
divided by the class's temperature.  The deployment the configuration
states has no adapters, the vision flag off, and temperature 1 for every
class, so those paths contribute nothing.

:func:`make_weights` builds the plane's parameters on the device in one
jitted call from the seed, in the pytree layout the plane takes, and
:func:`make_embedding` the embedding table the benchmark installs
through the control plane.  :func:`forward` is the reference: nothing of
the program is imported, every contraction runs through
:func:`~bench.refs.numerics.make_matmul`.

Routing is a discontinuity: where a token's ``top_k``-th and next router
logits lie closer than the rounding of the configuration's precision
(``router_tie``), either expert is a right answer, and a program that
rounds its router takes the other one about once a run.  So the
reference answers twice: as routed, and with every such near tie taken
the other way; a served token is judged by the answer it lies nearer.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib.common import jax_key
from .numerics import HIGHEST, make_matmul, normal, rmsnorm

EMBED_STD = 0.02


def dims(config: dict) -> dict:
    return {"d": config["hidden_size"], "f": config["intermediate_size"],
            "h": config["num_attention_heads"],
            "e": config["num_local_experts"],
            "k": config["num_experts_per_tok"],
            "v": config["vocab_size"], "layers": config["num_hidden_layers"],
            "eps": config["departures"]["rms_norm_eps"]["as_run"],
            "tie": config["router_tie"]["value"]}


def make_weights(config: dict, seed: int, router_bias: dict):
    """The plane's float32 parameters from ``seed``; ``router_bias``
    (``{"experts": [...], "value": b}``) is added to the router of every
    layer, as a deployment whose router favours some experts."""
    m = dims(config)
    bias = np.zeros(m["e"], np.float32)
    bias[list(router_bias.get("experts", []))] = router_bias.get("value",
                                                                  0.0)
    return _weights(jax_key(seed, "serve-weights"), m["d"], m["f"],
                    m["e"], m["v"], m["layers"], jnp.asarray(bias))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _weights(key, d, f, e, v, n_layers, bias):
    keys = iter(jax.random.split(key, 8 * n_layers + 1))
    layers = []
    for _ in range(n_layers):
        layers.append({
            "norm1": {"scale": jnp.ones((d,), jnp.float32)},
            "wq": normal(next(keys), (d, d), d ** -0.5),
            "wk": normal(next(keys), (d, d), d ** -0.5),
            "wv": normal(next(keys), (d, d), d ** -0.5),
            "wo": normal(next(keys), (d, d), d ** -0.5),
            "norm2": {"scale": jnp.ones((d,), jnp.float32)},
            "moe": {
                "w_router": normal(next(keys), (d, e), d ** -0.5),
                "b_router": bias,
                "w1": normal(next(keys), (e, d, f), d ** -0.5),
                "w3": normal(next(keys), (e, d, f), d ** -0.5),
                "w2": normal(next(keys), (e, f, d), f ** -0.5),
            },
        })
    return {"layers": layers,
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "unembed": normal(next(keys), (d, v), d ** -0.5)}


def make_embedding(config: dict, seed: int) -> jax.Array:
    m = dims(config)
    return _embedding(jax_key(seed, "serve-embed"), m["v"], m["d"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _embedding(key, v, d):
    return normal(key, (v, d), EMBED_STD)


def forward(weights, embed, tokens, config: dict,
            precision: str = "float32") -> jax.Array:
    """Reference logits ``(2, n, seq, vocab)`` of ``n`` requests' tokens
    ``(n, seq)``: ``[0]`` as routed, ``[1]`` with each near tie of the
    router taken the other way (equal to ``[0]`` where there is none)."""
    m = dims(config)
    return _forward(weights, embed, tokens, m["h"], m["k"], m["eps"],
                    m["tie"], precision)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _forward(weights, embed, tokens, n_heads, top_k, eps, tie, precision):
    mm = make_matmul(precision)
    n, s = tokens.shape
    x = embed[tokens].astype(jnp.float32)                 # (n, s, d)
    x = jnp.concatenate([x, x])        # rows n.. take the near ties' other way
    other = jnp.arange(2 * n * s) >= n * s
    n2, d = 2 * n, x.shape[-1]
    hd = d // n_heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    for lp in weights["layers"]:
        h = rmsnorm(x, lp["norm1"]["scale"], eps)
        q = mm("nsd,de->nse", h, lp["wq"]).reshape(n2, s, n_heads, hd)
        k = mm("nsd,de->nse", h, lp["wk"]).reshape(n2, s, n_heads, hd)
        v = mm("nsd,de->nse", h, lp["wv"]).reshape(n2, s, n_heads, hd)
        att = mm("nshd,nthd->nhst", q, k) / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        o = mm("nhst,nthd->nshd", att, v).reshape(n2, s, d)
        x = x + mm("nsd,de->nse", o, lp["wo"])

        moe = lp["moe"]
        h = rmsnorm(x, lp["norm2"]["scale"], eps).reshape(n2 * s, d)
        r = mm("td,de->te", h, moe["w_router"]) + moe["b_router"]
        top, ids = jax.lax.top_k(r, top_k + 1)
        swap = other & (top[:, top_k - 1] - top[:, top_k] < tie)
        top = top.at[:, top_k - 1].set(
            jnp.where(swap, top[:, top_k], top[:, top_k - 1]))[:, :top_k]
        ids = ids.at[:, top_k - 1].set(
            jnp.where(swap, ids[:, top_k], ids[:, top_k - 1]))[:, :top_k]
        gates = jax.nn.softmax(top, axis=-1)               # (t, k)
        weight = jnp.zeros_like(r).at[
            jnp.arange(n2 * s)[:, None], ids].set(gates)   # (t, e)
        g1 = mm("td,edf->etf", h, moe["w1"])
        g3 = mm("td,edf->etf", h, moe["w3"])
        y = mm("etf,efd->etd", jax.nn.silu(g1) * g3, moe["w2"])
        x = x + jnp.einsum("te,etd->td", weight, y,
                           precision=HIGHEST).reshape(n2, s, d)
    x = rmsnorm(x, weights["final_norm"]["scale"], eps)
    return mm("nsd,dv->nsv", x, weights["unembed"]).reshape(2, n, s, -1)


def token_gaps(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per position, how far the reference's logit of the served
    (greedy) token lies below the reference's best logit, in whichever
    of the reference's two answers (:func:`forward`) it lies nearer."""
    ref = np.asarray(ref, np.float64)
    tok = np.asarray(served).argmax(-1)
    best = ref.max(-1)
    mine = np.take_along_axis(ref, np.broadcast_to(
        tok[None, ..., None], ref.shape[:-1] + (1,)), -1)[..., 0]
    return (best - mine).min(0)
