"""A run of a cell with the timed path broken underneath comes out not
correct: the drivers' whole run (minus the look for a chip) on the tiny
cells of `cells.py`, on the CPU, once sound and once for each fault the
cell can have.  Limits here are the tiny cells' own, set from CPU
readings of sound runs (program) and of faulty ones."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.drivers import train
from bench.tests.cells import tiny_cell

SEED = 2 ** 40 + 9
# tiny-cell limits, from CPU readings (seeds 1-6, 2**40 + 9): sound runs
# read at most loss 1.1e-7, first gradient 1.4e-6, change 7.2e-6; the
# control at least loss 2.2e-3, first gradient 1.6e-2, change 4.5e-3;
# half the batch at least loss 6.4e-3, first gradient 0.115, change
# 0.187; a state left unchanged reads change 1.
TRAIN_LIMITS = {"loss_gap": 1e-5, "first_grad_gap": 1e-4,
                "change_gap": 5e-4}


def _run(module, cell, monkeypatch, limits):
    monkeypatch.setattr(harness, "limits", lambda name: limits)
    return module.run_cell(cell, SEED, 1.0, jax.devices(),
                           time.perf_counter(), None)


def _patch_step(monkeypatch, wrap):
    import repro.train as rt
    orig = rt.build_train_step

    def build(*a, **kw):
        init_fn, step_fn = orig(*a, **kw)
        return init_fn, wrap(step_fn)
    monkeypatch.setattr(rt, "build_train_step", build)


def test_train_sound_run_is_correct(monkeypatch):
    out = _run(train, tiny_cell("train.s4096"), monkeypatch, TRAIN_LIMITS)
    assert out["correct"], out["checks"].items


def test_train_state_unchanged_is_caught(monkeypatch):
    def wrap(step_fn):
        def step(state, batch):
            _, metrics = step_fn(state, batch)
            return jax.tree.map(jnp.copy, state), metrics
        return step
    _patch_step(monkeypatch, wrap)
    out = _run(train, tiny_cell("train.s4096"), monkeypatch, TRAIN_LIMITS)
    assert not out["correct"]
    assert out["checks"].items["change_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch_is_caught(monkeypatch):
    def wrap(step_fn):
        def step(state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step_fn(state, half)
        return step
    _patch_step(monkeypatch, wrap)
    out = _run(train, tiny_cell("train.s4096"), monkeypatch, TRAIN_LIMITS)
    assert not out["correct"], out["checks"].items

