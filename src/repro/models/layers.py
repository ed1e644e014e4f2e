"""Common layers: RMSNorm, embeddings, RoPE, gated FFN, logit head."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .params import Initializer, PSpec


def dot_f32(eq: str, *ops) -> jax.Array:
    """einsum with fp32 accumulation.  On TPU this is the MXU-native
    bf16-in/f32-accumulate contraction (preferred_element_type); the XLA CPU
    thunk cannot execute mixed-precision dots, so on host backends the
    operands are upcast instead (identical FLOP count, same semantics)."""
    if jax.default_backend() == "tpu":
        return jnp.einsum(eq, *ops, preferred_element_type=jnp.float32)
    return jnp.einsum(eq, *(o.astype(jnp.float32) for o in ops))


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(ini: Initializer, d: int):
    return {"scale": ini.ones((d,), ("embed",), dtype=jnp.float32)}


def rmsnorm(params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"]).astype(dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(ini: Initializer, vocab: int, d: int):
    return {"table": ini.normal((vocab, d), ("vocab", "embed"), fan_in=d)}


def embed(params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)


def init_unembed(ini: Initializer, d: int, vocab: int):
    return {"w": ini.normal((d, vocab), ("embed", "vocab"))}


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def unembed(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    logits = jnp.einsum("...d,dv->...v", x, params["w"])
    return softcap(logits, cfg.final_logit_softcap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float, yarn=None) -> jax.Array:
    """Rope frequencies of ``dim`` / 2 pairs; with ``yarn`` (a
    :class:`~repro.models.config.YaRNConfig`) YaRN's blend: frequencies
    below the correction range are divided by ``factor``, those above it
    kept, and a linear ramp between (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``)."""
    base = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / (theta ** base)
    if yarn is None:
        return extra
    inter = extra / yarn.factor

    def corr_dim(rotations):
        return dim * math.log(yarn.original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                     # 1: extrapolate, 0: interpolate
    return inter * (1.0 - keep) + extra * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 mscale ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq).  Rotates
    the pairs (i, i + head_dim/2).  With ``yarn``, YaRN's frequencies,
    and cos/sin scaled by ``mscale(mscale) / mscale(mscale_all_dim)``."""
    dim = x.shape[-1]
    freqs = rope_freqs(dim, theta, yarn)                # (dim/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) \
            / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated FFN (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_ffn(ini: Initializer, d: int, d_ff: int, gated: bool = True):
    p = {
        "w_up": ini.normal((d, d_ff), ("embed", "mlp")),
        "w_down": ini.normal((d_ff, d), ("mlp", "embed"), fan_in=d_ff),
    }
    if gated:
        p["w_gate"] = ini.normal((d, d_ff), ("embed", "mlp"))
    return p


def ffn(params, x: jax.Array, act: str = "silu") -> jax.Array:
    actf = jax.nn.silu if act == "silu" else jax.nn.gelu
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    if "w_gate" in params:
        gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
        h = actf(gate) * up
    else:
        h = actf(up)
    return jnp.einsum("...f,fd->...d", h, params["w_down"])
