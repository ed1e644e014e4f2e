"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

Grid (B, n_head_blocks, n_chunks), chunks innermost: the SSM state of
each head is carried in VMEM scratch across the sequential chunk
dimension; the quadratic intra-chunk matrices exist only as (Q x Q)
tiles in VMEM — never in HBM.  This is the hardware adaptation of SSD:
the reference jnp path materialises the per-chunk L/att tensors at
fusion boundaries (measured memory-dominant in the dry-run roofline);
the kernel removes exactly that traffic.

Layout: the wrapper hands the kernel head-major operands (x as
(B, H, S, P), dt as (B, H, S), B transposed to (B, N, S), the state as
(N, P) per head), so every block's two minor dimensions are a whole
array dimension or a multiple of the TPU's (8, 128) tile, and every
in-kernel product is a plain 2-D matmul ((Q,N)@(N,Q), (Q,Q)@(Q,P),
(Q,N)@(N,P), (N,Q)@(Q,P)).  The chunk's cumulative decay is a masked
lane sum of a (Q x Q) tile; a vector changes orientation (row <->
column) as the sum of its diagonal-masked tile, which is exact because
each sum has one nonzero term — so no in-kernel transpose is needed.

The backward pass is the VJP of the reference scan
(:func:`repro.kernels.ref.ssd_scan_ref`), attached with ``custom_vjp``.

Restrictions: n_groups == 1 (B/C shared across heads); S is padded to a
multiple of ``chunk`` here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref


def _kernel(a_ref, x_ref, dt_ref, bt_ref, c_ref, s0_ref, y_ref, fin_ref,
            state_scr, *, nc: int, hblk: int):
    hb = pl.program_id(1)
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)   # (hblk, N, P)

    f32 = jnp.float32
    Bt = bt_ref[0].astype(f32)                           # (N, Q)
    Cm = c_ref[0].astype(f32)                            # (Q, N)
    Q = Cm.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = row >= col
    diag = row == col
    cb = jnp.dot(Cm, Bt, preferred_element_type=f32)     # (Q, Q)

    def to_col(v_r):                                     # (1, Q) -> (Q, 1)
        return jnp.sum(jnp.where(diag, v_r, 0.0), axis=1, keepdims=True)

    def to_row(v_c):                                     # (Q, 1) -> (1, Q)
        return jnp.sum(jnp.where(diag, v_c, 0.0), axis=0, keepdims=True)

    for h in range(hblk):
        a = a_ref[hb * hblk + h]
        x = x_ref[0, h].astype(f32)                      # (Q, P)
        dt_r = dt_ref[0, pl.ds(h, 1), :].astype(f32)     # (1, Q)
        dt_c = to_col(dt_r)                              # (Q, 1)
        # inclusive cumulative decay, as a column and as a row
        cs_c = jnp.sum(jnp.where(lower, dt_r * a, 0.0), axis=1,
                       keepdims=True)                    # (Q, 1)
        cs_r = to_row(cs_c)                              # (1, Q)
        cs_tot = cs_c[Q - 1:Q, :]                        # (1, 1)

        # intra-chunk: L[i,j] = exp(cs[i]-cs[j]) for i>=j (masked BEFORE
        # exp — the upper triangle overflows)
        L = jnp.exp(jnp.where(lower, cs_c - cs_r, -1e9))
        att = cb * L * dt_r
        y = jnp.dot(att, x, preferred_element_type=f32)  # (Q, P)

        # inter-chunk from carried state
        state = state_scr[h]                             # (N, P)
        y = y + jnp.dot(Cm, state, preferred_element_type=f32) * \
            jnp.exp(cs_c)

        # state update
        w = jnp.exp(cs_tot - cs_c) * dt_c                # (Q, 1)
        upd = jnp.dot(Bt, x * w, preferred_element_type=f32)   # (N, P)
        state_scr[h] = state * jnp.exp(cs_tot) + upd
        y_ref[0, h] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nc - 1)
    def _finish():
        fin_ref[0] = state_scr[...]


def _forward(x, dt, A, Bm, Cm, init_state, chunk, hblk, interpret):
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    nc = S // chunk
    nh = H // hblk
    f32 = jnp.float32
    xt = x.transpose(0, 2, 1, 3)                         # (B, H, S, P)
    dt_t = dt.astype(f32).transpose(0, 2, 1)             # (B, H, S)
    Bt = Bm[:, :, 0, :].transpose(0, 2, 1)               # (B, N, S)
    C = Cm[:, :, 0, :]                                   # (B, S, N)
    s0 = init_state.astype(f32).transpose(0, 1, 3, 2)    # (B, H, N, P)

    kernel = functools.partial(_kernel, nc=nc, hblk=hblk)
    y, fin = pl.pallas_call(
        kernel,
        grid=(B, nh, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, hblk, chunk, P),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hblk, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, N, chunk), lambda b, h, c: (b, 0, c)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, hblk, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hblk, chunk, P),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hblk, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), f32),
        ],
        scratch_shapes=[pltpu.VMEM((hblk, N, P), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(f32), xt, dt_t, Bt, C, s0)
    return y.transpose(0, 2, 1, 3), fin.transpose(0, 1, 3, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssd_scan(x, dt, A, Bm, Cm, init_state, chunk, hblk, interpret):
    return _forward(x, dt, A, Bm, Cm, init_state, chunk, hblk, interpret)


def _ssd_scan_fwd(x, dt, A, Bm, Cm, init_state, chunk, hblk, interpret):
    out = _forward(x, dt, A, Bm, Cm, init_state, chunk, hblk, interpret)
    return out, (x, dt, A, Bm, Cm, init_state)


def _ssd_scan_bwd(chunk, hblk, interpret, res, cot):
    _, vjp = jax.vjp(
        lambda x, dt, A, Bm, Cm, s0: _ref.ssd_scan_ref(
            x, dt, A, Bm, Cm, chunk, init_state=s0), *res)
    return vjp(cot)


_ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "hblk", "interpret"))
def ssd_scan_kernel(x, dt, A, Bm, Cm, *, chunk: int, init_state=None,
                    hblk: int = 8, interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,1,N).
    Returns (y (B,S,H,P) in x.dtype, final_state (B,H,P,N) f32).
    Differentiable: the backward pass is the reference scan's VJP."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    if Bm.shape[2] != 1:
        raise ValueError("ssd_scan_kernel supports n_groups == 1, got "
                         f"{Bm.shape[2]}")
    hblk = min(hblk, H)
    if H % hblk:
        raise ValueError(f"hblk={hblk} does not divide H={H}")
    S_orig = S
    if S % chunk:
        # padded steps have dt=0 => exp(dt·A)=1 and zero input weight, so
        # they are exact no-ops on the state
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), jnp.float32)
    y, fin = _ssd_scan(x, dt, A, Bm, Cm, init_state, chunk, hblk,
                       interpret)
    return y[:, :S_orig], fin
