"""Tests for the mesh-level m-sync engine (core/sync_engine)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FixedTimes, SimulatedStraggler, SyncMode, SyncPolicy,
                        first_m_mask, participation_example_weights,
                        uniform_times)


def test_first_m_mask():
    times = np.array([5.0, 1.0, 3.0, 2.0])
    mask = first_m_mask(times, 2)
    np.testing.assert_array_equal(mask, [False, True, False, True])
    assert first_m_mask(times, 4).all()


def test_first_m_mask_ties_stable():
    mask = first_m_mask(np.array([1.0, 1.0, 1.0]), 2)
    np.testing.assert_array_equal(mask, [True, True, False])


def test_participation_weights_mean_preserving():
    # weighted mean over the batch == mean over participating groups
    mask = jnp.asarray([True, False, True, False])
    w = participation_example_weights(mask, 4, 16)
    assert w.shape == (16,)
    assert float(w.sum()) == pytest.approx(16.0)  # mean-preserving
    # nonparticipants weighted 0, participants n/m = 2
    np.testing.assert_allclose(np.asarray(w[:4]), 2.0)
    np.testing.assert_allclose(np.asarray(w[4:8]), 0.0)


def test_straggler_m_sync_duration_is_mth_order_stat():
    model = FixedTimes(np.array([1.0, 2.0, 3.0, 100.0]))
    st = SimulatedStraggler(model, SyncPolicy(SyncMode.M_SYNC, m=3))
    mask, m, dur = st.step()
    assert m == 3
    assert dur == pytest.approx(3.0)
    np.testing.assert_array_equal(mask, [True, True, True, False])


def test_straggler_full_waits_for_max():
    model = FixedTimes(np.array([1.0, 50.0]))
    st = SimulatedStraggler(model, SyncPolicy(SyncMode.FULL))
    _, m, dur = st.step()
    assert (m, dur) == (2, pytest.approx(50.0))


def test_deadline_mask_and_fallback():
    model = FixedTimes(np.array([0.5, 0.9, 30.0]))
    st = SimulatedStraggler(model, SyncPolicy(SyncMode.DEADLINE,
                                              deadline=1.0))
    mask, m, dur = st.step()
    assert m == 2 and dur <= 1.0
    # deadline so tight nobody finishes: falls back to the fastest worker
    st2 = SimulatedStraggler(model, SyncPolicy(SyncMode.DEADLINE,
                                               deadline=0.1))
    mask2, m2, _ = st2.step()
    assert m2 == 1 and mask2[0]


def test_auto_m_warmup_uses_all_workers():
    model = uniform_times(np.ones(4), 0.1)
    st = SimulatedStraggler(model, SyncPolicy(SyncMode.AUTO_M))
    _, m, _ = st.step()  # estimator has no sigma yet -> full participation
    assert m == 4


def test_wallclock_accumulates():
    model = FixedTimes(np.array([1.0, 2.0]))
    st = SimulatedStraggler(model, SyncPolicy(SyncMode.FULL))
    for _ in range(5):
        st.step()
    assert st.wallclock == pytest.approx(10.0)


def test_masked_group_mean_shard_map():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import masked_group_mean
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    grads = jnp.arange(4.0)          # per-group scalar "gradient"
    mask = jnp.asarray([1.0, 0.0, 1.0, 0.0])

    def f(g, mk):
        return masked_group_mean(g, mk, "dp")

    out = jax.shard_map(f, mesh=mesh, in_specs=(P("dp"), P("dp")),
                    out_specs=P("dp"))(grads, mask)
    # every group holds the m-sync estimator: (0 + 2)/2 = 1
    np.testing.assert_allclose(np.asarray(out), 1.0)
