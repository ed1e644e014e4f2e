"""Pure-jnp oracles for every Pallas kernel.

These are the semantics of record: kernels are validated against these in
interpret mode, and non-TPU backends execute these directly via
``kernels.ops``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# flash_attention oracle — re-export of the blocked reference
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v, *, causal=True, window=None,
                        logit_softcap=0.0, block=512):
    from ..models.attention import attend_blocked
    Sq, Sk = q.shape[1], k.shape[1]
    return attend_blocked(
        q, k, v,
        q_pos=jnp.arange(Sq, dtype=jnp.int32),
        kv_pos=jnp.arange(Sk, dtype=jnp.int32),
        causal=causal, window=window, logit_softcap=logit_softcap,
        block=block)


# ---------------------------------------------------------------------------
# ssd_scan oracle — Mamba2 state-space-duality chunked scan
# ---------------------------------------------------------------------------

def ssd_scan_ref(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                 Cm: jax.Array, chunk: int,
                 init_state: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """SSD (arXiv:2405.21060 §6) chunked scan.

    x:  (B, S, H, P)   per-head inputs
    dt: (B, S, H)      softplus'd step sizes (>0)
    A:  (H,)           negative per-head decay
    Bm: (B, S, G, N)   input projections  (G groups; heads share groups)
    Cm: (B, S, G, N)   output projections
    Returns y: (B, S, H, P) and final_state: (B, H, P, N).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = chunk
    S_orig = S
    if S % Q:
        # pad to a chunk boundary; padded steps have dt=0 => exp(dt·A)=1 and
        # zero input weight, so they are exact no-ops on the state.
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // Q
    rep = H // G

    f32 = jnp.float32
    # One sequential pass over chunks (the same schedule the Pallas kernel
    # uses: state carried chunk-to-chunk, intra-chunk matrices live only
    # for the current chunk).  A fully-vectorised version materialises
    # (B,nc,Q,Q,H) at once — measured 66 GB/device on mamba2 train_4k.
    xc = x.reshape(Bsz, nc, Q, H, P).transpose(1, 0, 2, 3, 4)
    dtc = dt.reshape(Bsz, nc, Q, H).transpose(1, 0, 2, 3)
    Bc = Bm.reshape(Bsz, nc, Q, G, N).transpose(1, 0, 2, 3, 4)
    Cc = Cm.reshape(Bsz, nc, Q, G, N).transpose(1, 0, 2, 3, 4)

    tri = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    s0 = (jnp.zeros((Bsz, H, P, N), f32) if init_state is None
          else init_state.astype(f32))
    Af = A.astype(f32)

    def step(state, inp):
        xq, dtq, Bq, Cq = inp                      # (B,Q,H,P),(B,Q,H),(B,Q,G,N)
        xq = xq.astype(f32)
        dtq = dtq.astype(f32)
        Bh = jnp.repeat(Bq.astype(f32), rep, axis=2)         # (B,Q,H,N)
        Ch = jnp.repeat(Cq.astype(f32), rep, axis=2)
        da = dtq * Af                                         # (B,Q,H) <= 0
        da_cs = jnp.cumsum(da, axis=1)
        da_tot = da_cs[:, -1, :]                              # (B,H)

        # intra-chunk: mask BEFORE exp — the upper triangle has positive
        # sums that overflow and poison the backward pass otherwise.
        seg = da_cs[:, :, None, :] - da_cs[:, None, :, :]     # (B,Q,Q,H)
        seg = jnp.where(tri, seg, -1e9)
        L = jnp.exp(seg)
        cb = jnp.einsum("bihn,bjhn->bijh", Ch, Bh)
        att = cb * L * dtq[:, None, :, :]
        y = jnp.einsum("bijh,bjhp->bihp", att, xq)

        # inter-chunk contribution from the carried state
        y = y + jnp.einsum("bqh,bqhn,bhpn->bqhp",
                           jnp.exp(da_cs), Ch, state)

        # state update
        w = jnp.exp(da_tot[:, None, :] - da_cs) * dtq         # (B,Q,H)
        new_state = (state * jnp.exp(da_tot)[:, :, None, None]
                     + jnp.einsum("bqh,bqhn,bqhp->bhpn", w, Bh, xq))
        return new_state, y.astype(x.dtype)

    # flash semantics in backward too: recompute the per-chunk L/att
    # matrices instead of stacking them across chunks (saves
    # nc x B x Q x Q x H of residuals).
    step = jax.checkpoint(step,
                          policy=jax.checkpoint_policies.nothing_saveable)
    final, ys = jax.lax.scan(step, s0, (xc, dtc, Bc, Cc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, H, P)[:, :S_orig]
    return y, final


def ssd_decode_ref(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                   Cm: jax.Array, state: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSM update.  x: (B,H,P), dt: (B,H), Bm/Cm: (B,G,N),
    state: (B,H,P,N)."""
    H, G = x.shape[1], Bm.shape[1]
    rep = H // G
    f32 = jnp.float32
    Bh = jnp.repeat(Bm.astype(f32), rep, axis=1)               # (B,H,N)
    Ch = jnp.repeat(Cm.astype(f32), rep, axis=1)
    da = dt.astype(f32) * A.astype(f32)                        # (B,H)
    new_state = (state.astype(f32) * jnp.exp(da)[:, :, None, None]
                 + jnp.einsum("bh,bhn,bhp->bhpn", dt.astype(f32), Bh,
                              x.astype(f32)))
    y = jnp.einsum("bhn,bhpn->bhp", Ch, new_state)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# hot_gather oracle — Morpheus fast-path cache lookup
# ---------------------------------------------------------------------------

def hot_gather_ref(table: jax.Array, hot_rows: jax.Array, hot_ids: jax.Array,
                   idx: jax.Array) -> jax.Array:
    """Semantics of the VMEM fast-path cache: rows whose id appears in
    ``hot_ids`` are served from ``hot_rows``; everything else from the
    full ``table``.  Numerically the result must equal ``table[idx]``
    (hot_rows is a verbatim copy) — the kernel's win is purely where the
    bytes come from (VMEM vs HBM).

    table: (V, D); hot_rows: (Hn, D); hot_ids: (Hn,); idx: (T,) -> (T, D).
    """
    match = idx[:, None] == hot_ids[None, :]                    # (T, Hn)
    hit = match.any(axis=1)
    hot_pos = jnp.argmax(match, axis=1)
    from_hot = hot_rows[hot_pos]
    from_table = table[idx]
    return jnp.where(hit[:, None], from_hot, from_table)

