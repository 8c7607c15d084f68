"""The plain references, tied to the program at tiny sizes on the CPU:
the simulator's m-sync event loop to ``repro.core.strategies.simulate``
under fixed compute times, and the NanoGPT reference to ``repro.models``."""

from __future__ import annotations

import numpy as np
import pytest

from benchmarks.chip import registry

SIM = registry.reference("sim-paper-n1000")
GPT = registry.reference("nanogpt-paper")


def _fixed(n, seed=0):
    return np.random.default_rng(seed).uniform(1.0, 2.0, n)


@pytest.mark.parametrize("m", [1, 3, 7, 9])
def test_msync_event_loop_matches_the_serial_engine(m):
    from repro.core import FixedTimes, simulate
    from repro.core.strategies import make_strategy

    n, K = 9, 30
    taus = _fixed(n)
    d = np.broadcast_to(taus, (K, 2, n))
    ref = SIM.msync_from_draws(taus, d, K, m, clock="float64")
    tr = simulate(make_strategy("msync", m=m), FixedTimes(taus), K)
    assert ref["total_time"] == pytest.approx(tr.total_time, rel=1e-12)
    assert ref["gradients_computed"] == tr.gradients_computed
    assert ref["gradients_used"] == tr.gradients_used


@pytest.mark.parametrize("m", [1, 3, 20])
def test_sim_reference_follows_the_device_engine_per_seed(m):
    """Keyed draws: the reference and the ``jax`` engine agree seed by
    seed, exactly in time and counts."""
    from repro.exp import run_experiment

    cfg = dict(registry.config("sim-paper-n1000"), n=20, K=30,
               record_every=30)
    seeds = [5, 2**31 - 2]
    res = run_experiment(("msync", {"m": m}), "exponential", 20, 30,
                         seeds=seeds, backend="jax")
    for s, t in zip(seeds, res.batch.traces[0]):
        r = SIM.simulate("msync", {"m": m}, cfg, s)
        assert r["total_time"] == t.total_time
        assert r["gradients_computed"] == t.gradients_computed
        assert r["gradients_used"] == t.gradients_used


def test_bfloat16_clocks_read_far_from_float32():
    cfg = dict(registry.config("sim-paper-n1000"), n=50, K=200)
    want = SIM.simulate("msync", {"m": 5}, cfg, 3)
    low = SIM.simulate("msync", {"m": 5}, cfg, 3, clock="bfloat16")
    assert abs(low["total_time"] - want["total_time"]) \
        > 1e-3 * want["total_time"]


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from repro.models import build_model
    from chipbench_helpers import tiny_model_config

    cfg = __import__("dataclasses").replace(tiny_model_config(),
                                            dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ref_cfg = {"n_layer": 2, "n_head": 2, "n_embd": 32, "norm_eps": 1e-5,
               "workers": 4, "m": 3}
    return model, params, ref_cfg


def test_nanogpt_reference_logits_match_the_model(tiny_model):
    import jax
    import jax.numpy as jnp

    model, params, cfg = tiny_model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 512)
    got, _ = model.apply(params, tokens)
    want = GPT.logits(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    assert want.dtype == jnp.float32


def test_nanogpt_reference_loss_and_grads_match_the_model(tiny_model):
    import jax
    import jax.numpy as jnp

    model, params, cfg = tiny_model
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    tokens = np.asarray(jax.random.randint(k1, (8, 16), 0, 512), np.int32)
    labels = np.asarray(jax.random.randint(k2, (8, 16), 0, 512), np.int32)
    w = GPT.example_weights(cfg, 8)
    np.testing.assert_allclose(w, [4 / 3] * 6 + [0.0] * 2, rtol=1e-7)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels),
             "loss_mask": jnp.ones((8, 16))}
    (loss, _), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch, example_weights=jnp.asarray(w)),
        has_aux=True)(params)
    rl, rg = GPT.loss_and_grads(params, tokens, labels, w, cfg)
    assert rl == pytest.approx(float(loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(rg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-6)


def test_nanogpt_reference_adamw_matches_the_program_optimizer(tiny_model):
    import jax
    import jax.numpy as jnp

    from repro.optim import adamw, cosine_schedule

    _, params, _ = tiny_model
    opt_cfg = {"lr": 3e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
               "weight_decay": 0.1, "clip_norm": 1.0, "warmup": 1,
               "schedule_steps": 20, "min_ratio": 0.1}
    sched = cosine_schedule(3e-3, warmup=1, total=20)
    opt = adamw(lr=sched)
    for step in range(4):
        assert GPT.lr_at(step, opt_cfg) == pytest.approx(float(sched(step)),
                                                         rel=1e-6)
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 64))
    grads = [jax.tree.map(lambda a: jax.random.normal(next(keys), a.shape),
                          params) for _ in range(3)]
    p, state = params, opt.init(params)
    q = params
    m = v = jax.tree.map(jnp.zeros_like, params)
    for step, g in enumerate(grads):
        p, state = opt.update(g, state, p, jnp.int32(step))
        q, m, v = GPT.adamw(q, m, v, GPT.clip(g, 1.0), step, opt_cfg)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
