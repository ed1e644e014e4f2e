"""A training run on the CPU at a small size, whole but for the chip: a
sound trainer comes out correct, and each fault planted in the train
step the supervisor compiles makes ``correct`` come out false."""
import jax
import pytest

import repro.training.supervisor as supervisor
from bench_tiny import run


def _state_unchanged(step):
    def faulty(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return faulty


def _half_batch(step):
    def faulty(state, batch):
        half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return step(state, half)
    return faulty


def _answer_altered(step):
    def faulty(state, batch):
        new, metrics = step(state, batch)
        master = new["opt"]["master"]
        master["embed"]["table"] = master["embed"]["table"] * 1.01
        return new, metrics
    return faulty


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


def test_sound_training_run_is_correct():
    res = run("starcoder2-train", seed=2**31 + 5)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    real = supervisor.make_train_step

    def make_train_step(*args, **kwargs):
        return FAULTS[fault](real(*args, **kwargs))

    monkeypatch.setattr(supervisor, "make_train_step", make_train_step)
    res = run("starcoder2-train", seed=3)
    assert not res["correct"], res["compared"]
