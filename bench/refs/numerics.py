"""Precisions for the plain references and their controls.

A reference computes every contraction through :func:`make_matmul` in
float32 at ``HIGHEST`` precision.  Its control computes the same thing
one precision below the configuration's: each operand is rounded to
``float8_e4m3fn`` with a per-tensor scale (its largest magnitude mapped
to the format's largest value, the usual fp8 recipe) before the
contraction.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def fp8(x: jax.Array) -> jax.Array:
    """Round ``x`` to float8_e4m3fn under a per-tensor scale."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
        * scale


QUANT = {"float32": None, "fp8": fp8}


def make_matmul(precision: str = "float32") -> Callable:
    """``mm(eq, a, b)``: an einsum in float32 at HIGHEST precision, with
    both operands first rounded to ``precision``."""
    q: Optional[Callable] = QUANT[precision]

    def mm(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        if q is not None:
            a, b = q(a), q(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST)

    return mm


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def normal(key, shape, std: float, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
