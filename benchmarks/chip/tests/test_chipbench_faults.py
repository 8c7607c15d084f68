"""A run with the timed path broken underneath comes out not correct.

Each test skips the look for a chip, plants one fault in the program and
drives the rest of a run at a tiny size on the CPU: a step that returns
its state unchanged, half of the batch left out with the mean over the
rest, an answer altered where it is produced."""

from __future__ import annotations

import time

import numpy as np
import pytest

from benchmarks.chip import controls, sweep, train
from benchmarks.chip.clock import CompileClock

from chipbench_helpers import sim_cell, train_cell


@pytest.fixture(scope="module")
def clock():
    return CompileClock()


def _sweep_run(name, clock, **mix_kw):
    cell, cfg, mix, ref = sim_cell(name, **mix_kw)
    # every (sweep, seed) pair checked, so a fault in any seed shows
    mix["check_seeds"] = 10 ** 6
    return sweep.run_cell(cell, cfg, mix, ref, 11, 0.0, False,
                          time.perf_counter(), clock)


def _patch_batches(monkeypatch, edit):
    """Wrap the sweep's batch simulator so ``edit`` alters its traces."""
    import repro.exp.runner as runner

    real = runner.simulate_batch

    def faulty(*a, **kw):
        batch = real(*a, **kw)
        for row in batch.traces:
            edit(row)
        return batch

    monkeypatch.setattr(runner, "simulate_batch", faulty)


@pytest.mark.parametrize("name", ["sim.msync_mgrid.exp"])
def test_sound_sweep_is_correct(name, clock):
    run = _sweep_run(name, clock)
    assert run.correct, run.checks
    assert run.attempted > 0 and run.failed == 0


@pytest.mark.parametrize("name", ["sim.msync_mgrid.exp"])
def test_an_answer_altered_where_produced_fails(name, clock, monkeypatch):
    def alter(row):
        row[-1].total_time = row[-1].total_time * (1 + 1e-4)

    _patch_batches(monkeypatch, alter)
    assert not _sweep_run(name, clock).correct


@pytest.mark.parametrize("name", ["sim.msync_mgrid.exp"])
def test_half_the_seeds_left_out_fails(name, clock, monkeypatch):
    def halve(row):
        half = len(row) // 2
        row[half:] = row[:len(row) - half]

    _patch_batches(monkeypatch, halve)
    assert not _sweep_run(name, clock).correct


def test_msync_round_that_returns_its_state_unchanged_fails(clock,
                                                            monkeypatch):
    import repro.core.batch_jax as bj

    real = bj._timing_round

    def stuck(ft, ver, comp, k, cand, m, use_pallas):
        _, _, _, T, acc = real(ft, ver, comp, k, cand, m, use_pallas)
        return ft, ver, comp, T, acc

    monkeypatch.setattr(bj, "_timing_round", stuck)
    assert not _sweep_run("sim.msync_mgrid.exp", clock).correct


def _train_run(monkeypatch, clock):
    cell, cfg, mix, ref = train_cell(monkeypatch, steps_per_call=2,
                                     warmup_steps=1)
    return train.run_cell(cell, cfg, mix, ref, 5, 0.0, False,
                          time.perf_counter(), clock)


def test_sound_training_is_correct(monkeypatch, clock):
    run = _train_run(monkeypatch, clock)
    assert run.correct, run.checks


def test_training_step_that_returns_its_state_unchanged_fails(monkeypatch,
                                                              clock):
    from repro.train.trainer import Trainer

    real = Trainer._build_step

    def build(self):
        step = real(self)

        def unchanged(params, opt_state, batch, weights, i, grad_params):
            import jax
            import jax.numpy as jnp

            kept = jax.tree.map(jnp.copy, (params, opt_state))
            _, _, metrics = step(params, opt_state, batch, weights, i,
                                 grad_params)
            return (*kept, metrics)

        return unchanged

    monkeypatch.setattr(Trainer, "_build_step", build)
    run = _train_run(monkeypatch, clock)
    assert not run.correct
    grad = next(c for c in run.checks if c.name == "grad_gap")
    assert grad.value == pytest.approx(1.0)


def test_training_on_half_the_batch_fails(monkeypatch, clock):
    with controls.half_batch_loss():
        run = _train_run(monkeypatch, clock)
    assert not run.correct
    assert np.isfinite([c.value for c in run.checks]).all()
