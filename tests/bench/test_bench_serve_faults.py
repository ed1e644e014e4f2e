"""A serving run of ``phi35moe-skewed`` on the CPU at a small size,
whole but for the chip: a sound plane comes out correct, and each fault
planted under the timed path (inside the plane's step, which the
runtime compiles and the frontend drives) makes ``correct`` come out
false."""
import jax.numpy as jnp
import pytest

import repro.serving
from bench_tiny import load_cell, run

CELL = "phi35moe-skewed"


class _NoWrites:
    """The plane's data-plane context with its table writes dropped: the
    step returns its state unchanged."""

    def __init__(self, ctx):
        self._ctx = ctx

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    def update(self, *args, **kwargs):
        return None


def _state_unchanged(step):
    return lambda params, ctx, batch: step(params, _NoWrites(ctx), batch)


def _half_batch(step):
    def faulty(params, ctx, batch):
        out = step(params, ctx, batch)
        h = out.shape[0] // 2
        return jnp.concatenate([out[:out.shape[0] - h], out[:h]], axis=0)
    return faulty


def _answer_altered(step):
    """Each request gets its neighbour's answer."""
    def faulty(params, ctx, batch):
        return jnp.roll(step(params, ctx, batch), 1, axis=0)
    return faulty


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}




def faulty_run(cell, fault, monkeypatch, seed=7):
    """A small run of ``cell`` with ``fault`` planted in every plane;
    batches hold several requests, so that half of one holds requests."""
    real = repro.serving.build_fleet

    def build_fleet(cfg, key, n, **kw):
        return [(FAULTS[fault](step), tables)
                for step, tables in real(cfg, key, n, **kw)]

    monkeypatch.setattr(repro.serving, "build_fleet", build_fleet)
    _, config, _ = load_cell(cell)
    serving = {**config["serving"], "max_wait_ms": 100.0}
    return run(cell, seed=seed, config={"serving": serving})


def test_sound_serving_run_is_correct():
    res = run(CELL, seed=2**31 + 11)
    assert res["correct"], res["compared"]
    assert res["attempted"] == 40 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_p50_ms", "serve_tokens_per_s",
                                   "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    res = faulty_run(CELL, fault, monkeypatch)
    assert not res["correct"], res["compared"]


def test_window_served_generic_is_not_correct(monkeypatch):
    """From the window on, every formed window bumps the tables' version,
    as a batcher that mispredicts every bucket would: the program guard
    sends the window's steps to the generic executable, which answers
    right but is not the specialized plan the cell measures."""
    from bench.drivers import serve_plane
    from repro.serving.frontend.batcher import DynamicBatcher
    armed = []
    real_maybe_deopt = DynamicBatcher._maybe_deopt
    real_window_requests = serve_plane.window_requests

    def maybe_deopt(self, n_batches, mispredicts):
        if not armed:
            return real_maybe_deopt(self, n_batches, mispredicts)
        self.rt.tables.bump_version("planted-mispredict")

    def window_requests(*args, **kwargs):
        armed.append(True)
        return real_window_requests(*args, **kwargs)

    monkeypatch.setattr(DynamicBatcher, "_maybe_deopt", maybe_deopt)
    monkeypatch.setattr(serve_plane, "window_requests", window_requests)
    res = run(CELL, seed=5)
    assert res["compared"]["generic_share"]["value"] > 0.5, res["compared"]
    assert not res["correct"], res["compared"]
