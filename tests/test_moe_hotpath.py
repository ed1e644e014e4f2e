"""The MoE hot-expert fast path (``moe_ffn_hotpath``) against the full
dropless dispatch (``moe_ffn_local``).

The fast branch reads the hot experts' weights in place, runs each hot
expert densely over every row and lets each top-k slot select its
expert's row.  Each case checks the output against the slow path and
which branch was taken (``fastpath_hit``), from a serving window's rows
up to a training step's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.passes.branch_inject import moe_ffn_hotpath
from repro.models.config import ModelConfig, MoEConfig
from repro.models.moe import moe_ffn_local

E, D, F = 16, 64, 128


def _params(key, hot, bias):
    k = jax.random.split(key, 4)
    b = np.zeros((E,), np.float32)
    b[list(hot)] = bias
    return {"w_router": jax.random.normal(k[0], (D, E)) / np.sqrt(D),
            "b_router": jnp.asarray(b),
            "w1": jax.random.normal(k[1], (E, D, F)) / np.sqrt(D),
            "w3": jax.random.normal(k[2], (E, D, F)) / np.sqrt(D),
            "w2": jax.random.normal(k[3], (E, F, D)) / np.sqrt(F)}


# (hot set, top_k, rows, windows, router bias toward the hot set)
CASES = {
    "hot012-rows64": ((0, 1, 2), 2, 64, 0, 6.0),
    "hot3711-rows128": ((3, 7, 11), 2, 128, 0, 6.0),
    "single-rows64": ((5,), 1, 64, 0, 6.0),
    "hot012-rows480": ((0, 1, 2), 2, 480, 0, 6.0),
    "hot012-rows481": ((0, 1, 2), 2, 481, 0, 6.0),
    "hot3711-rows512": ((3, 7, 11), 2, 512, 0, 6.0),
    "single-rows512": ((5,), 1, 512, 0, 6.0),
    "hot012-fused-window": ((0, 1, 2), 2, 64, 3, 6.0),
    "hot3711-fused-window": ((3, 7, 11), 2, 128, 2, 6.0),
    "hot3711-miss": ((3, 7, 11), 2, 64, 0, 0.0),
}


@pytest.mark.parametrize("hot,top_k,rows,windows,bias", CASES.values(),
                         ids=CASES.keys())
def test_hotpath_matches_local(hot, top_k, rows, windows, bias):
    cfg = ModelConfig(d_model=D, moe=MoEConfig(num_experts=E, top_k=top_k,
                                               expert_d_ff=F))
    key = jax.random.PRNGKey(sum(hot) * 1000 + rows)
    params = _params(key, hot, bias)
    shape = (max(windows, 1), rows, D)
    xs = jax.random.normal(jax.random.fold_in(key, 7), shape)

    def hotpath(x):
        y, m = moe_ffn_hotpath(params, x, cfg, hot)
        return y, m["fastpath_hit"]

    if windows:
        y, hit = jax.jit(lambda xs: jax.lax.scan(
            lambda c, x: (c, hotpath(x)), 0, xs)[1])(xs)
    else:
        y, hit = jax.jit(hotpath)(xs[0])
        y, hit = y[None], hit[None]

    want = jnp.stack([moe_ffn_local(params, x, cfg.moe)[0] for x in xs])
    assert (np.asarray(hit) == (1 if bias else 0)).all()
    # float32 sums of up to F products in another order
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
