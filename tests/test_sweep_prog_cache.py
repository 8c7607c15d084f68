"""The unsharded m-sync round scan is built once per law and shape.

``_general_run`` keeps its jitted program in ``_SWEEP_PROGS`` under a key
of the shape, the parameters, the x64 and PRNG settings, the law by value
and the problem by identity. These tests count builds and hits with the
``sweep_prog_builds`` / ``sweep_prog_hits`` counters and read tracing and
lowering from ``jax.monitoring``, as ``benchmarks/chip/clock.py`` does.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import SubExponentialTimes
from repro.core import batch_jax
from repro.core.batch_jax import quadratic_worst_case_jax, simulate_batch_jax
from repro.core.strategies import MSync
from repro.core.time_models import exponential_times, shifted_exponential_times
from repro.exp import run_experiment

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_EVENTS: list = []
_RECORDING = [False]


def _on_duration(event, duration, **_):
    if _RECORDING[0] and event in (TRACE, LOWER):
        _EVENTS.append(event)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


class _Watch:
    """Builds, hits and trace/lower events while the block runs."""

    def __enter__(self):
        self._c0 = telemetry.counters()
        _EVENTS.clear()
        _RECORDING[0] = True
        return self

    def __exit__(self, *exc):
        _RECORDING[0] = False
        c1 = telemetry.counters()
        self.builds = (c1.get("sweep_prog_builds", 0)
                       - self._c0.get("sweep_prog_builds", 0))
        self.hits = (c1.get("sweep_prog_hits", 0)
                     - self._c0.get("sweep_prog_hits", 0))
        self.traced = list(_EVENTS)
        return False


def _clear_msync_progs():
    for key in [k for k in batch_jax._SWEEP_PROGS
                if k and k[0] == "msync_scan"]:
        del batch_jax._SWEEP_PROGS[key]


BASE = dict(lam=1.0, n=8, S=2, K=20, m=2, gamma=0.0, x64=False)


def _run(lam, n, S, K, m, gamma, x64, model=None, problem=None, seeds=None):
    model = exponential_times(lam, n) if model is None else model
    seeds = list(range(3, 3 + S)) if seeds is None else seeds
    return simulate_batch_jax(MSync(m=m), model, K, problem=problem,
                              gamma=gamma, seeds=seeds, x64=x64)


def _same(a, b):
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        for f in dataclasses.fields(ta):
            va, vb = getattr(ta, f.name), getattr(tb, f.name)
            if isinstance(va, np.ndarray) or isinstance(vb, np.ndarray):
                np.testing.assert_array_equal(va, vb, err_msg=f.name)
            else:
                assert va == vb, f.name


def test_fresh_law_objects_share_one_program():
    _clear_msync_progs()
    with _Watch() as first:
        _run(**BASE)
    with _Watch() as second:
        hit = _run(**BASE)
    assert (first.builds, first.hits) == (1, 0)
    assert (second.builds, second.hits) == (0, 1)
    assert second.traced == []
    _clear_msync_progs()
    with _Watch() as rebuilt:
        fresh = _run(**BASE)
    assert rebuilt.builds == 1
    _same(hit, fresh)


@pytest.mark.parametrize("change", [
    {"lam": 2.0}, {"n": 9}, {"S": 3}, {"K": 21}, {"m": 3}, {"gamma": 0.5},
    {"x64": True}], ids=lambda c: next(iter(c)))
def test_a_changed_key_part_misses(change):
    _run(**BASE)
    with _Watch() as w:
        _run(**dict(BASE, **change))
    assert (w.builds, w.hits) == (1, 0)
    assert w.traced


def test_array_closures_keyed_by_value():
    n = 6

    def law(mu):
        return shifted_exponential_times(np.full(n, mu), np.ones(n))

    _run(**dict(BASE, n=n), model=law(0.5))
    with _Watch() as same:
        _run(**dict(BASE, n=n), model=law(0.5))
    with _Watch() as other:
        _run(**dict(BASE, n=n), model=law(0.25))
    assert (same.builds, same.hits) == (0, 1)
    assert (other.builds, other.hits) == (1, 0)


def _device_closure_law(n):
    scale = jnp.ones(n)                   # a device array: not keyed by value

    def jax_sampler(key):
        return jax.random.exponential(key, (n,)) * scale

    return SubExponentialTimes(np.ones(n), lambda i, rng: 1.0, R=1.0,
                               jax_sampler=jax_sampler)


def test_unkeyable_closure_falls_back_to_identity():
    n = 8
    a, b = _device_closure_law(n), _device_closure_law(n)
    with _Watch() as w:
        _run(**BASE, model=a)
        _run(**BASE, model=b)
        _run(**BASE, model=a)
    assert (w.builds, w.hits) == (2, 1)


def test_universal_model_keeps_identity_key():
    from repro.core import powers_figure3

    def law():
        return powers_figure3(n=8, seed=0, t_max=300.0)

    a = law()
    with _Watch() as w:
        _run(**BASE, model=a, seeds=[0])
        _run(**BASE, model=a, seeds=[1])
        _run(**BASE, model=law(), seeds=[0])
    assert (w.builds, w.hits) == (2, 1)


def test_jax_problem_keeps_identity_key():
    args = dict(BASE, gamma=0.1)
    p = quadratic_worst_case_jax(d=5, p=1.0)
    with _Watch() as w:
        _run(**args, problem=p)
        _run(**args, problem=p, seeds=[7, 8])
        _run(**args, problem=quadratic_worst_case_jax(d=5, p=1.0))
    assert (w.builds, w.hits) == (2, 1)


def test_a_rebound_round_function_builds_anew(monkeypatch):
    _run(**BASE)
    real = batch_jax._timing_round

    def stuck(ft, ver, comp, k, cand, m, use_pallas):
        _, _, _, T, acc = real(ft, ver, comp, k, cand, m, use_pallas)
        return ft, ver, comp, T, acc

    monkeypatch.setattr(batch_jax, "_timing_round", stuck)
    with _Watch() as w:
        _run(**BASE)
    assert (w.builds, w.hits) == (1, 0)


def test_back_to_back_sweeps_trace_nothing_the_second_time():
    def sweep(seeds):
        return run_experiment(("msync", {"m": 1}), "exponential", 16, 20,
                              seeds=seeds, grid={"m": [1, 10]},
                              backend="jax")

    sweep([101, 102, 103])
    with _Watch() as w:
        sweep([201, 202, 203])
    assert (w.builds, w.hits) == (0, 2)
    assert w.traced == []
