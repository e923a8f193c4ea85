"""Rehearsals of the training driver at a tiny size through the real
entries (``BSP().init``, the prefetcher, ``train_iter``), on the CPU:
a sound run ends in a well-formed line with ``correct`` true; with the
timed path broken underneath, ``correct`` comes out false."""

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import failed_names, well_formed, rehearse, tiny_cell

from benchmarks import reference, train


@pytest.mark.parametrize("kind,n", [("train", 1), ("bsp4", 4)])
def test_sound_run_is_correct(kind, n):
    line = rehearse(kind, n)
    well_formed(line, "train_tokens_per_s_per_chip")
    assert line["correct"] is True, line["compared"]
    assert line["extra"]["window_s"] >= 1.0


def _patch_build(monkeypatch, alter):
    real = train.build

    def build(*a, **kw):
        trainer = real(*a, **kw)
        alter(trainer)
        return trainer
    monkeypatch.setattr(train, "build", build)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    def alter(trainer):
        step = trainer._step_fn

        def stuck(params, state, opt_state, batch, lr, i):
            keep = jax.tree.map(jnp.copy, (params, state, opt_state))
            return (*keep, step(params, state, opt_state, batch, lr, i)[3])
        trainer._step_fn = stuck
    _patch_build(monkeypatch, alter)
    line = rehearse("train", 1)
    assert line["correct"] is False
    assert {"first_grad_gap", "change_gap"} <= set(failed_names(line))
    assert line["compared"]["first_grad_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    def alter(trainer):
        real = trainer.train_iter

        def halved(batch, lr, recorder=None):
            # the mean over a half repeated twice is the mean over that half
            def fold(x):
                h = x.shape[0] // 2
                return jnp.concatenate([x[:h], x[:h]])
            return real({k: fold(v) for k, v in batch.items()}, lr, recorder)
        trainer.train_iter = halved
    _patch_build(monkeypatch, alter)
    line = rehearse("train", 1)
    assert line["correct"] is False and failed_names(line), line["compared"]


def test_the_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    from theanompi_tpu.parallel.exchanger import Exchanger

    monkeypatch.setattr(Exchanger, "exchange",
                        lambda self, grads, rng=None, step=None: grads)
    line = rehearse("bsp4", 4)
    assert line["correct"] is False and failed_names(line), line["compared"]


def test_the_fp8_control_is_not_correct():
    """The reference put in the program's place, one precision down."""
    import numpy as np

    _, loaded = tiny_cell("train")
    cfg, limits = loaded["cfg"], loaded["cell"]["limits"]
    rng = np.random.Generator(np.random.PCG64(5))
    rows = [rng.integers(0, cfg["vocab_size"], size=(2, 65)) for _ in range(3)]
    fed = [(r[:, :-1], r[:, 1:]) for r in rows]
    hp = (0.01, 0.9, 1.0)
    keys = ("losses", "first_grad", "change")
    ref = dict(zip(keys, reference.train_steps(cfg, 9, fed, hp)))
    ctl = dict(zip(keys, reference.train_steps(cfg, 9, fed, hp, "fp8")))
    same = train.compare(ref, ref, limits)
    assert all(c["value"] == 0 for c in same)
    off = train.compare(ctl, ref, limits)
    assert any(c["value"] > c["limit"] for c in off), off
