"""Public kernel entry points.

On TPU each op runs its Pallas kernel; a shape the kernel does not
support raises (there is no silent fallback).  On every other backend it
runs the pure-jnp oracle from ``ref.py`` (the same semantics).  ``force``
overrides the choice for tests: "kernel" | "ref" | "interpret".
"""
from __future__ import annotations

from typing import Optional

import jax

from . import ref as _ref


def _use_kernel(force: Optional[str]) -> bool:
    if force == "kernel" or force == "interpret":
        return True
    if force == "ref":
        return False
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, logit_softcap: float = 0.0,
                    block: int = 512, force: Optional[str] = None):
    if _use_kernel(force):
        from .flash_attention import flash_attention_kernel
        return flash_attention_kernel(
            q, k, v, causal=causal, window=window,
            logit_softcap=logit_softcap,
            interpret=(force == "interpret"))
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                    logit_softcap=logit_softcap, block=block)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int, init_state=None,
             force: Optional[str] = None):
    if _use_kernel(force):
        from .ssd_scan import ssd_scan_kernel
        return ssd_scan_kernel(x, dt, A, Bm, Cm, chunk=chunk,
                               init_state=init_state,
                               interpret=(force == "interpret"))
    return _ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk, init_state=init_state)


def ssd_decode(x, dt, A, Bm, Cm, state):
    # single-token update is tiny — ref path everywhere
    return _ref.ssd_decode_ref(x, dt, A, Bm, Cm, state)


def hot_gather(table, hot_rows, hot_ids, idx, *, force: Optional[str] = None):
    if _use_kernel(force):
        from .hot_gather import hot_gather_kernel
        return hot_gather_kernel(table, hot_rows, hot_ids, idx,
                                 interpret=(force == "interpret"))
    return _ref.hot_gather_ref(table, hot_rows, hot_ids, idx)
