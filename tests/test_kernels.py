"""Per-kernel validation: shape/dtype sweeps, interpret mode vs the
pure-jnp oracle in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.hot_gather import hot_gather_kernel
from repro.kernels.ssd_scan import ssd_scan_kernel

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,Hkv,D,causal,window,cap",
    [
        (1, 64, 64, 4, 4, 32, True, None, 0.0),      # MHA causal
        (2, 100, 100, 4, 2, 32, True, None, 0.0),    # GQA, ragged seq
        (1, 64, 64, 4, 1, 64, True, None, 0.0),      # MQA
        (1, 96, 96, 2, 2, 32, True, 32, 50.0),       # window + softcap
        (1, 64, 64, 4, 4, 32, False, None, 0.0),     # bidirectional
        (2, 1, 128, 4, 2, 32, True, None, 0.0),      # decode-shaped q
    ])
def test_flash_attention_vs_oracle(B, Sq, Sk, H, Hkv, D, causal, window,
                                   cap, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), dtype)
    out = flash_attention_kernel(q, k, v, causal=causal, window=window,
                                 logit_softcap=cap, blk_q=32, blk_k=32,
                                 interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=causal, window=window,
                                logit_softcap=cap, block=32)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_decode_q1_matches_full_row():
    """Single-query attention equals the last row of full attention."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 33, 4, 32))
    k = jax.random.normal(ks[1], (1, 33, 4, 32))
    v = jax.random.normal(ks[2], (1, 33, 4, 32))
    full = flash_attention_kernel(q, k, v, causal=True, blk_q=16,
                                  blk_k=16, interpret=True)
    one = flash_attention_kernel(q[:, -1:], k, v, causal=False, blk_q=16,
                                 blk_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(one[0, 0]),
                               np.asarray(full[0, -1]), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk,hblk", [
    (1, 32, 4, 8, 16, 8, 4),
    (2, 48, 8, 16, 32, 16, 4),
    (1, 40, 2, 8, 16, 16, 2),       # S not divisible by chunk
    (2, 64, 8, 16, 16, 32, 8),
])
def test_ssd_scan_vs_oracle(B, S, H, P, N, chunk, hblk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(
        jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = (jax.random.normal(ks[3], (B, S, 1, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, 1, N)) * 0.3).astype(dtype)
    y, fin = ssd_scan_kernel(x, dt, A, Bm, Cm, chunk=chunk, hblk=hblk,
                             interpret=True)
    yr, finr = R.ssd_scan_ref(x, dt, A, Bm, Cm, chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr),
                               rtol=1e-3, atol=1e-3)


def test_ssd_scan_kernel_grad_matches_oracle():
    """The kernel is differentiable, and its gradients are the oracle's
    (the backward pass is the reference scan's VJP)."""
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 1, 40, 4, 8, 16
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, 1, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, 1, N)) * 0.3

    def loss(scan, *args):
        y, fin = scan(*args)
        return jnp.sum(y ** 2) + jnp.sum(fin ** 2)
    kernel = lambda *a: ssd_scan_kernel(*a, chunk=16, hblk=2,
                                        interpret=True)
    oracle = lambda *a: R.ssd_scan_ref(*a, 16)
    argnums = (1, 2, 3, 4, 5)
    g = jax.grad(loss, argnums)(kernel, x, dt, A, Bm, Cm)
    gr = jax.grad(loss, argnums)(oracle, x, dt, A, Bm, Cm)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ssd_scan_chunk_invariance():
    """The chunk size is a tiling choice — results must not depend on it."""
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 1, 64, 4, 8, 16
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, 1, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, 1, N)) * 0.3
    y8, f8 = R.ssd_scan_ref(x, dt, A, Bm, Cm, 8)
    y32, f32_ = R.ssd_scan_ref(x, dt, A, Bm, Cm, 32)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(f8), np.asarray(f32_),
                               rtol=1e-4, atol=1e-4)


def test_ssd_decode_matches_scan():
    """Step-by-step decode must track the chunked scan state."""
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 1, 16, 2, 4, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, 1, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, 1, N)) * 0.3
    y_scan, fin = R.ssd_scan_ref(x, dt, A, Bm, Cm, 8)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        y_t, state = R.ssd_decode_ref(x[:, t], dt[:, t], A, Bm[:, t],
                                      Cm[:, t], state)
        ys.append(y_t)
    y_dec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_scan),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(fin),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# hot_gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("V,D,Hn,T", [
    (64, 16, 4, 32),
    (512, 64, 8, 100),
    (128, 32, 1, 7),
])
def test_hot_gather_vs_oracle(V, D, Hn, T, dtype):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((V, D)), dtype)
    hot_ids = jnp.asarray(rng.choice(V, Hn, replace=False), jnp.int32)
    hot_rows = jnp.take(table, hot_ids, axis=0)
    idx = jnp.asarray(rng.integers(0, V, T), jnp.int32)
    out = hot_gather_kernel(table, hot_rows, hot_ids, idx, interpret=True)
    ref = R.hot_gather_ref(table, hot_rows, hot_ids, idx)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=0, atol=0)
    # exactness property: identical to a plain gather
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.take(table, idx, axis=0)))


def test_hot_gather_all_hot_and_all_cold():
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    hot_ids = jnp.asarray([1, 2, 3], jnp.int32)
    hot_rows = table[hot_ids]
    all_hot = jnp.asarray([1, 2, 3, 1, 2], jnp.int32)
    all_cold = jnp.asarray([9, 10, 11], jnp.int32)
    for idx in (all_hot, all_cold):
        out = hot_gather_kernel(table, hot_rows, hot_ids, idx,
                                interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(table[idx]))
