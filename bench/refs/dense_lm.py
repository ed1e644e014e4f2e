"""Weights, token batches and plain reference of a dense decoder LM
trained with AdamW.

The model, as the configuration states it: token embedding (tied to the
output head), then per layer RMSNorm, grouped-query causal attention
with rotary position embedding (halves rotated, base ``rope_theta``),
residual; RMSNorm, a two-matrix MLP with tanh-approximated GELU,
residual; a final RMSNorm and the tied unembedding.  The loss is the
mean token cross-entropy.  The optimizer is AdamW with global-norm
clipping, linear warm-up and cosine decay, float32 master weights and
moments.

:func:`make_state` builds the trainer's state (bfloat16 parameters and
the optimizer's float32 master copy and zero moments) on the device in
one jitted call from the seed, in the layout the program's trainer
takes.  :func:`make_batches` draws Zipf-distributed token rows on the
device.  :func:`train_steps` is the reference: float32 throughout, every
contraction through :func:`~bench.refs.numerics.make_matmul`, attention
in blocks of queries and each layer rematerialized so that it fits one
chip beside nothing else.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..lib.common import jax_key
from .numerics import make_matmul, normal, rmsnorm

Q_BLOCK = 512


def dims(config: dict) -> dict:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"d": d, "h": h, "kv": config["num_key_value_heads"],
            "hd": config.get("head_dim", d // h),
            "f": config["intermediate_size"], "v": config["vocab_size"],
            "layers": config["num_hidden_layers"],
            "eps": config["norm_epsilon"], "theta": config["rope_theta"]}


def init_params(key, m: dict, dtype=jnp.bfloat16) -> dict:
    d, h, kv, hd, f, L = m["d"], m["h"], m["kv"], m["hd"], m["f"], \
        m["layers"]
    k = iter(jax.random.split(key, 8))
    return {
        "embed": {"table": normal(next(k), (m["v"], d), d ** -0.5, dtype)},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "blocks": {"pos0": {
            "attn_norm": {"scale": jnp.ones((L, d), jnp.float32)},
            "attn": {
                "wq": normal(next(k), (L, d, h, hd), d ** -0.5, dtype),
                "wk": normal(next(k), (L, d, kv, hd), d ** -0.5, dtype),
                "wv": normal(next(k), (L, d, kv, hd), d ** -0.5, dtype),
                "wo": normal(next(k), (L, h, hd, d), (h * hd) ** -0.5,
                             dtype),
            },
            "ffn_norm": {"scale": jnp.ones((L, d), jnp.float32)},
            "ffn": {
                "w_up": normal(next(k), (L, d, f), d ** -0.5, dtype),
                "w_down": normal(next(k), (L, f, d), f ** -0.5, dtype),
            },
        }},
    }


def make_state(config: dict, seed: int):
    """``{"params": bf16 tree, "opt": {master, m, v, step}}``."""
    return _state(jax_key(seed, "train-weights"),
                  tuple(sorted(dims(config).items())))


@functools.partial(jax.jit, static_argnums=(1,))
def _state(key, m):
    params = init_params(key, dict(m))
    f32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    zeros = jax.tree.map(jnp.zeros_like, f32)
    return {"params": params,
            "opt": {"master": f32, "m": zeros,
                    "v": jax.tree.map(jnp.zeros_like, f32),
                    "step": jnp.zeros((), jnp.int32)}}


def init_master(config: dict, seed: int):
    """The initial parameters in float32 (the values the program starts
    from, before any step)."""
    return _master(jax_key(seed, "train-weights"),
                   tuple(sorted(dims(config).items())))


@functools.partial(jax.jit, static_argnums=(1,))
def _master(key, m):
    return jax.tree.map(lambda p: p.astype(jnp.float32),
                        init_params(key, dict(m)))


def make_batches(config: dict, traffic: dict, seed: int, n: int):
    """``n`` batches ``(n, batch, seq + 1)`` of token ids drawn i.i.d.
    from a Zipf(``zipf_a``) unigram over the vocabulary; row ``i`` gives
    step ``i`` its tokens ``[:-1]`` and labels ``[1:]``."""
    ranks = np.arange(1, config["vocab_size"] + 1, dtype=np.float64)
    p = ranks ** (-float(traffic["zipf_a"]))
    cdf = jnp.asarray(np.cumsum(p / p.sum()), jnp.float32)
    return _batches(jax_key(seed, "train-batches"), cdf, n,
                    traffic["batch"], traffic["seq"])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _batches(key, cdf, n, b, s):
    u = jax.random.uniform(key, (n, b, s + 1), jnp.float32)
    ids = jnp.searchsorted(cdf, u, side="right")
    return jnp.minimum(ids, cdf.shape[0] - 1).astype(jnp.int32)


def split(rows: jax.Array) -> dict:
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------

def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D), positions 0..S-1; the two halves rotate."""
    s, dim = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                             / dim))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(params, rows, m: dict, precision: str) -> jax.Array:
    mm = make_matmul(precision)
    batch = split(rows)
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    emb = params["embed"]["table"]
    x = emb[tokens]
    g = m["h"] // m["kv"]
    scale = 1.0 / math.sqrt(m["hd"])

    def layer(x, lp):
        h = rmsnorm(x, lp["attn_norm"]["scale"], m["eps"])
        q = rope(mm("bsd,dhk->bshk", h, lp["attn"]["wq"]), m["theta"])
        k = rope(mm("bsd,dhk->bshk", h, lp["attn"]["wk"]), m["theta"])
        v = mm("bsd,dhk->bshk", h, lp["attn"]["wv"])
        q = q.reshape(B, S, m["kv"], g, m["hd"])
        outs = []
        for s0 in range(0, S, Q_BLOCK):
            qb = q[:, s0:s0 + Q_BLOCK]
            att = mm("bqhgd,bthd->bhgqt", qb, k) * scale
            qpos = jnp.arange(s0, s0 + qb.shape[1])[:, None]
            att = jnp.where(jnp.arange(S)[None, :] <= qpos, att, -jnp.inf)
            att = jax.nn.softmax(att, axis=-1)
            outs.append(mm("bhgqt,bthd->bqhgd", att, v))
        o = jnp.concatenate(outs, axis=1).reshape(B, S, m["h"], m["hd"])
        x = x + mm("bshk,hkd->bsd", o, lp["attn"]["wo"])
        h = rmsnorm(x, lp["ffn_norm"]["scale"], m["eps"])
        u = jax.nn.gelu(mm("bsd,df->bsf", h, lp["ffn"]["w_up"]),
                        approximate=True)
        return x + mm("bsf,fd->bsd", u, lp["ffn"]["w_down"]), None

    layer = jax.checkpoint(layer)
    blocks = params["blocks"]["pos0"]
    for i in range(m["layers"]):
        x, _ = layer(x, jax.tree.map(lambda a: a[i], blocks))
    x = rmsnorm(x, params["final_norm"]["scale"], m["eps"])
    logits = mm("bsd,vd->bsv", x, emb)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def schedule(opt: dict, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = step / max(opt["warmup_steps"], 1)
    t = jnp.clip((step - opt["warmup_steps"])
                 / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * t))
    return opt["lr"] * jnp.where(step < opt["warmup_steps"], warm, cos)


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(0,))
def _ref_step(state, rows, step, m, opt, precision):
    """One reference AdamW step on float32 master weights.  Returns the
    new state, the loss, the per-leaf norms of the clipped gradient."""
    m, opt = dict(m), dict(opt)
    loss, grads = jax.value_and_grad(loss_fn)(state["master"], rows, m,
                                              precision)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * clip, grads)
    b1, b2 = opt["b1"], opt["b2"]
    stepf = step.astype(jnp.float32)
    lr = schedule(opt, step)

    def upd(g, p, mo, v):
        mo = b1 * mo + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = mo / (1 - b1 ** stepf)
        vhat = v / (1 - b2 ** stepf)
        p = p - lr * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                      + opt["weight_decay"] * p)
        return p, mo, v

    out = jax.tree.map(upd, grads, state["master"], state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
    return ({"master": pick(0), "m": pick(1), "v": pick(2)}, loss, norms)


def train_steps(config: dict, seed: int, rows, n_steps: int,
                precision: str = "float32") -> dict:
    """The reference's first ``n_steps`` steps from the seed's initial
    weights on ``rows[i]``: each step's loss, the first step's per-leaf
    clipped-gradient norms, and the per-leaf norms of the change of the
    float32 weights after the last step."""
    m = tuple(sorted(dims(config).items()))
    opt = tuple(sorted(config["optimizer"].items()))
    master0 = init_master(config, seed)
    state = {"master": jax.tree.map(jnp.copy, master0),
             "m": jax.tree.map(jnp.zeros_like, master0),
             "v": jax.tree.map(jnp.zeros_like, master0)}
    losses, grad_norms = [], None
    for i in range(n_steps):
        state, loss, norms = _ref_step(state, rows[i], jnp.int32(i + 1), m,
                                       opt, precision)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = leaf_values(norms)
    change = leaf_values(_diff_norms(state["master"], master0))
    return {"loss": losses, "grad_norm": grad_norms, "change": change}


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


def leaf_values(tree) -> dict:
    """``{path: float}`` of a tree of scalars."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def program_grad_norms(opt_state, b1: float) -> dict:
    """Per-leaf norms of the first (clipped) gradient as the program's
    optimizer received it, from its state after one step:
    ``m = (1 - b1) * g``."""
    return {k: v / (1 - b1) for k, v in
            leaf_values(_norms(opt_state["m"])).items()}


def program_change(opt_state, config: dict, seed: int) -> dict:
    """Per-leaf norms of the change of the program's float32 master
    weights from the seed's initial weights."""
    return leaf_values(_diff_norms(opt_state["master"],
                                   init_master(config, seed)))


def worst_leaf_gap(prog: dict, want: dict, exclude=()) -> float:
    """The largest gap between a leaf's norm in the program and in the
    reference, over the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    keys = [k for k in want if k not in exclude]
    med = float(np.median([want[k] for k in keys]))
    return max(abs(prog[k] - want[k]) / max(want[k], med) for k in keys)
