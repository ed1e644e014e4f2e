"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode runs a kernel's logic but not the TPU compiler, which
refuses blocks that break the (8, 128) tiling, kernels that need more
fast memory than they may use, and programs that do not fit.  These
tests compile for a *described* ``v5e:2x2`` topology (no chip needed)
and check that the kernel survived into the executable as a
``tpu_custom_call``.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.passes.branch_inject import moe_ffn_hotpath
from repro.kernels import ops as kops
from repro.models.config import ModelConfig, MoEConfig
from repro.serving import ServeConfig


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_text(fn, *avals) -> str:
    return jax.jit(fn).lower(*avals).compile().as_text()


def _ssd_avals(one_chip):
    """One mamba2-1.3b layer's scan operands at sequence 2048."""
    cfg = get_config("mamba2-1.3b")
    s = cfg.ssm
    H = cfg.d_model * s.expand // s.head_dim
    P, N, S = s.head_dim, s.d_state, 2048

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    avals = (spec((1, S, H, P), jnp.bfloat16), spec((1, S, H)), spec((H,)),
             spec((1, S, s.n_groups, N), jnp.bfloat16),
             spec((1, S, s.n_groups, N), jnp.bfloat16))
    return avals, s.chunk


def test_hot_gather_compiles_on_serving_table(one_chip):
    """The serve path's ``hot_cache`` site: the (vocab, d_model) float32
    embedding table at phi3.5-moe widths."""
    cfg = ServeConfig(d_model=4096, vocab=32064)
    n_hot, n_tokens = 4, 8 * cfg.seq

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compile_text(
        lambda t, r, h, i: kops.hot_gather(t, r, h, i, force="kernel"),
        spec((cfg.vocab, cfg.d_model)), spec((n_hot, cfg.d_model)),
        spec((n_hot,), jnp.int32), spec((n_tokens,), jnp.int32))
    assert "tpu_custom_call" in text


def test_ssd_scan_forward_compiles(one_chip):
    avals, chunk = _ssd_avals(one_chip)
    text = _compile_text(
        lambda *a: kops.ssd_scan(*a, chunk=chunk, force="kernel"), *avals)
    assert "tpu_custom_call" in text


def test_ssd_scan_grad_compiles(one_chip):
    """A mamba2 train step differentiates through the kernel: the
    forward stays the kernel, the backward is the reference's VJP."""
    avals, chunk = _ssd_avals(one_chip)

    def loss(x, dt, A, Bm, Cm):
        y, fin = kops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                               force="kernel")
        return jnp.sum(y.astype(jnp.float32) ** 2) + jnp.sum(fin ** 2)
    text = _compile_text(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *avals)
    assert "tpu_custom_call" in text


def test_moe_hotpath_compiles_in_fused_window(one_chip):
    """The ``moe_fastpath`` site inside a ``lax.scan`` body (a fused
    K-step serving window), with the hot set 0..H-1 that a router biased
    toward its first experts yields.  Its expert-id remap once was an
    in-graph scatter on which the TPU compiler aborted in this shape."""
    E, D, F, T = 16, 256, 512, 256
    cfg = ModelConfig(d_model=D, moe=MoEConfig(num_experts=E, top_k=2,
                                               expert_d_ff=F))

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    params = {"w_router": spec((D, E)), "b_router": spec((E,)),
              "w1": spec((E, D, F)), "w3": spec((E, D, F)),
              "w2": spec((E, F, D))}

    def window(params, xs):
        def body(carry, x):
            y, _ = moe_ffn_hotpath(params, x, cfg, (0, 1, 2))
            return carry, y
        return jax.lax.scan(body, 0, xs)[1]
    _compile_text(window, params, spec((2, T, D)))


@pytest.mark.parametrize("hot", [(0, 1, 2), (3, 7, 11)])
def test_moe_hotpath_reads_hot_weights_in_place(one_chip, hot):
    """At phi3.5-moe widths and a serving window's 128 rows the fast
    branch reads the hot experts' weights where they lie.  A gather of
    the stacks by a constant index once lowered to one mini-gather per
    128-column block of all 16 experts (``f32[16,4096,128]``), a
    ``while`` picking the hot rows and a ``dynamic-update-slice``
    writing a fresh ``f32[3,4096,6400]`` stack, on every step."""
    E, D, F, T = 16, 4096, 6400, 128
    cfg = ModelConfig(d_model=D, moe=MoEConfig(num_experts=E, top_k=2,
                                               expert_d_ff=F))

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    params = {"w_router": spec((D, E)), "b_router": spec((E,)),
              "w1": spec((E, D, F)), "w3": spec((E, D, F)),
              "w2": spec((E, F, D))}
    text = _compile_text(
        lambda p, x: moe_ffn_hotpath(p, x, cfg, hot)[0], params,
        spec((T, D)))
    H = len(hot)
    for banned in ("while", "dynamic-update-slice", f"f32[{E},{D},128]",
                   f"f32[{H},{D},{F}]", f"f32[{H},{F},{D}]"):
        assert banned not in text, banned
