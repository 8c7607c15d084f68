"""Tests for the computation-time models (Assumptions 2.2/3.1/5.1/5.4)."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (FixedTimes, PartialParticipationModel,
                        UniversalModel, chi2_times, exponential_times,
                        gamma_times, powers_figure3, powers_figure4,
                        shifted_exponential_times, truncated_normal_times,
                        uniform_times)
from repro.core.time_models import philox_rngs


def _all_subexp_factories(n=8):
    """Every SubExponentialTimes factory, with whether its batch_sampler
    is documented stream-equal to sequential scalar draws (truncnorm's
    vectorized rejection resamples in a different order)."""
    taus = np.linspace(1.0, 4.0, n)
    return [
        (exponential_times(0.8, n), True),
        (shifted_exponential_times(taus, np.full(n, 2.0)), True),
        (gamma_times(taus, var=0.25), True),
        (uniform_times(taus, 0.5), True),
        (chi2_times(1 + np.arange(n) % 5), True),
        (truncated_normal_times(taus, 0.5), False),
    ]


def test_fixed_times_sorted_factories():
    m = FixedTimes.sqrt_law(10)
    assert np.all(np.diff(m.taus) > 0)
    assert m.sample_time(3, np.random.default_rng(0)) == pytest.approx(2.0)


def test_subexp_samplers_match_reported_means():
    rng = np.random.default_rng(0)
    models = [
        exponential_times(0.5, 8),
        truncated_normal_times(np.linspace(1, 5, 8), 0.5),
        gamma_times(np.linspace(1, 5, 8), var=0.25),
        uniform_times(np.linspace(2, 6, 8), 1.0),
        chi2_times([4, 9, 16, 25]),
        shifted_exponential_times(np.ones(4), np.ones(4) * 2.0),
    ]
    for model in models:
        for i in range(model.n):
            s = np.mean([model.sample_time(i, rng) for _ in range(4000)])
            assert s == pytest.approx(model.mean_times()[i], rel=0.1), model.name


def test_batch_and_jax_sampler_parity_sweep():
    """ISSUE 3 satellite: for EVERY SubExponentialTimes factory, the
    batch_sampler and jax_sampler agree with the scalar sampler —
    distribution-equal via moment checks everywhere, stream-equal where
    documented (all but truncnorm's rejection resampling)."""
    import jax

    for model, stream_equal in _all_subexp_factories():
        n = model.n
        assert model.batch_sampler is not None, model.name
        assert model.jax_sampler is not None, model.name
        # batch_sampler moments
        rng = np.random.default_rng(0)
        draws = np.stack([model.sample_times(np.arange(n), rng)
                          for _ in range(3000)])
        np.testing.assert_allclose(draws.mean(axis=0), model.mean_times(),
                                   rtol=0.1, err_msg=model.name)
        # jax_sampler moments (mean AND variance against NumPy draws)
        keys = jax.random.split(jax.random.PRNGKey(0), 3000)
        jdraws = np.asarray(jax.vmap(model.jax_sampler)(keys))
        np.testing.assert_allclose(jdraws.mean(axis=0),
                                   model.mean_times(), rtol=0.1,
                                   err_msg=model.name)
        np.testing.assert_allclose(jdraws.var(axis=0), draws.var(axis=0),
                                   rtol=0.25, atol=1e-3,
                                   err_msg=model.name)
        assert np.all(jdraws >= 0.0), model.name
        # stream equality: one batched call == sequential scalar draws
        if stream_equal:
            a = model.sample_times(np.arange(n), np.random.default_rng(5))
            r = np.random.default_rng(5)
            b = np.array([model.sample_time(i, r) for i in range(n)])
            np.testing.assert_array_equal(a, b, err_msg=model.name)


def test_sample_times_tensor_contract():
    """Stream rows replay successive sample_times calls; counter rows are
    per-seed reproducible pure functions of the seed value."""
    model = gamma_times(np.linspace(1.0, 3.0, 6), var=0.25)
    w = np.arange(6)
    # stream: row r == r-th successive sample_times call on default_rng(s)
    got = model.sample_times_tensor(w, 3, [0, 9], rng_scheme="stream")
    for row, s in zip(got, (0, 9)):
        rng = np.random.default_rng(s)
        for r in range(3):
            np.testing.assert_array_equal(row[r],
                                          model.sample_times(w, rng))
    # counter: deterministic per seed value, regardless of sweep
    a = model.sample_times_tensor(w, 4, [3], rng_scheme="counter")
    b = model.sample_times_tensor(w, 4, [0, 3], rng_scheme="counter")
    np.testing.assert_array_equal(a[0], b[1])
    # stateful generators continue the stream across chunked calls
    rngs = philox_rngs([3])
    c1 = model.sample_times_tensor(w, 2, rngs, rng_scheme="counter")
    c2 = model.sample_times_tensor(w, 2, rngs, rng_scheme="counter")
    np.testing.assert_array_equal(np.concatenate([c1, c2], axis=1), b[1:])
    # moments survive the tiled bulk draw
    big = model.sample_times_tensor(w, 2000, [0], rng_scheme="counter")
    np.testing.assert_allclose(big[0].mean(axis=0), model.mean_times(),
                               rtol=0.1)
    with pytest.raises(ValueError):
        model.sample_times_tensor(w, 2, [0], rng_scheme="philox")
    # FixedTimes: pure broadcast, no RNG
    fixed = FixedTimes(np.array([2.0, 1.0]))
    np.testing.assert_array_equal(
        fixed.sample_times_tensor([1, 0], 2, [0, 1]),
        np.full((2, 2, 2), [1.0, 2.0]))


def test_all_samples_nonnegative():
    rng = np.random.default_rng(1)
    model = truncated_normal_times(np.full(4, 0.1), 2.0)  # heavy truncation
    samples = [model.sample_time(i, rng) for i in range(4) for _ in range(500)]
    assert min(samples) >= 0.0


def test_truncated_normal_mean_exceeds_mu_under_truncation():
    model = truncated_normal_times([0.5], sigma=1.0)
    assert model.mean_times()[0] > 0.5


def test_universal_constant_power_N():
    grid = np.arange(0.0, 100.0, 0.5)
    powers = np.full((2, len(grid)), 2.0)  # 2 grads/sec
    m = UniversalModel(grid, powers)
    assert m.N(0, 0.0, 1.0) == 2
    assert m.N(0, 0.0, 0.49) == 0
    assert m.time_for_integral(0, 0.0, 1.0) == pytest.approx(0.5, abs=1e-6)
    # extrapolation past grid end uses final power
    assert m.N(0, 0.0, 200.0) == 400


def test_universal_zero_power_never_finishes():
    grid = np.arange(0.0, 10.0, 0.5)
    powers = np.zeros((1, len(grid)))
    m = UniversalModel(grid, powers)
    assert m.time_for_integral(0, 0.0, 1.0) == np.inf


@given(st.floats(0.1, 5.0), st.floats(0.0, 20.0), st.floats(0.1, 10.0))
@settings(max_examples=50, deadline=None)
def test_universal_integral_additivity(v, t0, dt):
    grid = np.arange(0.0, 50.0, 0.25)
    m = UniversalModel(grid, np.full((1, len(grid)), v))
    mid = t0 + dt / 2
    total = m.integral(0, t0, t0 + dt)
    assert total == pytest.approx(
        m.integral(0, t0, mid) + m.integral(0, mid, t0 + dt), rel=1e-6,
        abs=1e-9)
    assert total == pytest.approx(v * dt, rel=1e-6, abs=1e-9)


def test_finish_times_vectorized_matches_scalar_inversion():
    """ISSUE 3 satellite: the batched searchsorted/quadratic inversion
    must match the scalar 80-iteration bisection to 1e-9 on the
    Figure 3/4 grids, including the constant-tail extrapolation."""
    for model in (powers_figure3(n=12, seed=0, t_max=80.0),
                  powers_figure4(n=12, seed=1, t_max=80.0)):
        w = np.arange(model.n)
        for t0 in (0.0, 2.31, 17.9, 79.0):
            got = model.finish_times(w, t0, 1.0)
            want = np.array([model.time_for_integral(i, t0, 1.0)
                             for i in range(model.n)])
            np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)
        # per-worker t0 arrays (the fast-path restart shape)
        t0s = np.linspace(0.0, 60.0, model.n)
        got = model.finish_times(w, t0s, 1.0)
        want = np.array([model.time_for_integral(i, float(t0s[i]), 1.0)
                         for i in range(model.n)])
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)
        # constant-tail extrapolation: targets far past the grid end
        got = model.finish_times(w, 79.9, 50.0)
        want = np.array([model.time_for_integral(i, 79.9, 50.0)
                         for i in range(model.n)])
        assert np.all(got > 80.0)
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


def test_finish_times_zero_power_and_inf_branches():
    grid = np.arange(0.0, 10.0, 0.5)
    powers = np.zeros((3, len(grid)))
    powers[1] = 1.0
    powers[2, :10] = 2.0          # power dies mid-grid: zero tail
    m = UniversalModel(grid, powers)
    got = m.finish_times([0, 1, 2], 0.0, 1.0)
    assert np.isinf(got[0])                       # v = 0 forever
    assert got[1] == pytest.approx(1.0, abs=1e-9)
    assert got[2] == pytest.approx(0.5, abs=1e-9)
    # target unreachable before the zero tail => inf
    assert np.isinf(m.finish_times([2], 0.0, 100.0)[0])
    # inf start times stay inf (never-finishing restarts propagate)
    np.testing.assert_array_equal(m.finish_times([1, 1], [np.inf, 0.0]),
                                  [np.inf, 1.0])
    # partial participation grids go through the same vectorized path
    pp = PartialParticipationModel(n=10, v=1.0, p=0.2, period=2.0,
                                   t_max=40.0)
    w = np.arange(10)
    got = pp.finish_times(w, 3.3, 1.0)
    want = np.array([pp.time_for_integral(i, 3.3, 1.0) for i in range(10)])
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


def test_finish_times_jax_matches_scalar_to_1e9():
    """ISSUE 4 acceptance: the jit-compatible finish_times_jax must
    match the scalar/NumPy inversion to 1e-9 on the Fig 3/4 grids,
    including the constant-tail extrapolation branch (t past the grid)
    — under x64, since the engines' float32 default cannot express that
    tolerance."""
    import jax

    for mk in (powers_figure3, powers_figure4):
        model = mk(n=16, seed=0, t_max=60.0)
        w = np.arange(16)
        for t0 in (0.0, 7.3, np.linspace(0.0, 80.0, 16)):  # 80 > grid end
            ref = model.finish_times(w, t0)
            with jax.enable_x64(True):
                got = np.asarray(model.finish_times_jax(
                    np.broadcast_to(np.asarray(t0, dtype=np.float64),
                                    (16,))))
            np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-9)


def test_finish_times_jax_tail_inf_and_worker_branches():
    """v = 0 tail => inf, t0 = inf => inf, and the explicit ``workers``
    indexing the arrival-indexed engine uses — all against the NumPy
    path."""
    import jax

    grid = np.arange(0.0, 10.0, 0.1)
    powers = np.ones((2, len(grid)))
    powers[1, 50:] = 0.0                 # power dies at t = 5
    m = UniversalModel(grid, powers)
    with jax.enable_x64(True):
        got = np.asarray(m.finish_times_jax(np.array([9.9, 9.0]),
                                            target=5.0))
        ref = m.finish_times([0, 1], np.array([9.9, 9.0]), target=5.0)
        assert np.isinf(got[1]) and np.isinf(ref[1])
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-9)
        gi = np.asarray(m.finish_times_jax(np.array([np.inf, 1.0]),
                                           workers=np.array([0, 1])))
        assert np.isinf(gi[0])
        np.testing.assert_allclose(gi[1], m.finish_times([1], 1.0)[0],
                                   rtol=1e-9)
    # batched (seeds, workers) shape — the engine's actual call form
    m3 = powers_figure3(n=6, seed=1, t_max=40.0)
    t0 = np.random.default_rng(0).uniform(0.0, 30.0, (3, 6))
    got = np.asarray(m3.finish_times_jax(t0.astype(np.float32)))
    ref = np.stack([m3.finish_times(np.arange(6), row) for row in t0])
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-3)


def test_jax_sampler_item_matches_marginals():
    """ISSUE 4 tentpole: every factory's single-draw jax_sampler_item
    (the keyed Async path) draws from the same per-worker marginal as
    the scalar sampler — mean check per worker, nonnegative always."""
    import jax

    for model, _ in _all_subexp_factories():
        assert model.jax_sampler_item is not None, model.name
        keys = jax.random.split(jax.random.PRNGKey(0), 4000)
        for i in (0, model.n - 1):
            d = np.asarray(jax.vmap(
                lambda k: model.jax_sampler_item(k, i))(keys))
            assert (d >= 0).all(), model.name
            assert np.mean(d) == pytest.approx(model.mean_times()[i],
                                               rel=0.1), model.name


def test_jax_worker_key_grid_contract():
    """Grid rows are pure functions of the seed VALUE: independent of
    the sweep composition and of call order (the per-worker keyed-draw
    contract in DESIGN.md §3b)."""
    from repro.core.time_models import jax_worker_key_grid

    a = np.asarray(jax_worker_key_grid([0, 3], 5))
    b = np.asarray(jax_worker_key_grid([5, 3, 9], 5))
    assert a.shape == (2, 5, 2)
    np.testing.assert_array_equal(a[1], b[1])     # seed 3 row identical
    np.testing.assert_array_equal(
        a, np.asarray(jax_worker_key_grid([0, 3], 5)))
    # distinct workers get distinct stream roots
    assert len({tuple(k) for k in a[0]}) == 5


def test_figure3_powers_shape_and_bounds():
    m = powers_figure3(n=50, seed=0, t_max=50.0)
    assert m.n == 50
    assert np.all(m.powers >= 0)
    assert np.max(m.powers) <= 1.0 + 1.0  # sin + noise margin


def test_figure4_powers_floor():
    m = powers_figure4(n=50, seed=0, t_max=50.0)
    assert np.all(m.powers >= 0.1 - 1e-12)


def test_partial_participation_bound():
    n, p = 20, 0.25
    m = PartialParticipationModel(n=n, v=1.0, p=p, t_max=60.0)
    # at every grid instant at most floor(p*n) powers are zero
    zeros_per_t = np.sum(m.powers == 0.0, axis=0)
    assert np.max(zeros_per_t) <= int(p * n)
    # and all nonzero powers equal v
    nz = m.powers[m.powers > 0]
    assert np.allclose(nz, 1.0)
