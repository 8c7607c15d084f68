"""Program spans and the host-sync counter (``repro.telemetry``): the
spans land in the profiler's trace with their nesting, ``host_syncs``
counts each blocking call once, and both work with the profiler off."""

from __future__ import annotations

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_config, reduced
from repro.core import FixedTimes, SyncMode, SyncPolicy
from repro.data import SyntheticLM
from repro.exp import run_experiment
from repro.models import build_model
from repro.optim import sgd
from repro.train import Trainer

STEP_CHILDREN = ("repro.train.feed", "repro.train.participation",
                 "repro.train.put", "repro.train.dispatch",
                 "repro.train.sync")
POINT_CHILDREN = ("repro.engine.prep", "repro.engine.dispatch",
                  "repro.engine.wait", "repro.batch.assemble")


def _syncs():
    return telemetry.counters().get(telemetry.HOST_SYNCS, 0)


def _traced(tmp_path, body):
    """Run ``body()`` under the profiler; the ``repro.`` events of the
    thread that ran it, as ``[(name, start_ns, end_ns, stats)]``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(path)
    best = []
    for plane in prof.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("repro.")]
            if len(evs) > len(best):
                best = evs
    return out, sorted(best, key=lambda t: (t[1], -t[2]))


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _children(events, parent, names):
    return [e for e in events if e[0] in names and _inside(e, parent)]


def _trainer():
    cfg = reduced(get_config("nanogpt-paper"), d_model=32,
                  layers_per_stage=1, vocab=64)
    tr = Trainer(build_model(cfg), sgd(lr=0.1), n_workers=4,
                 sync_policy=SyncPolicy(SyncMode.M_SYNC, m=2),
                 time_model=FixedTimes(np.array([1.0, 1.0, 2.0, 50.0])))
    data = iter(SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                            batch_size=4, seed=0))
    state = tr.init_state()
    tr.run(state, data, num_steps=1)            # compiles the step
    return tr, data


def test_trainer_spans_nest_under_each_step(tmp_path):
    tr, data = _trainer()
    _, evs = _traced(tmp_path, lambda: tr.run(tr.final_state, data,
                                              num_steps=2, log_every=2))
    steps = [e for e in evs if e[0] == "repro.train.step"]
    assert len(steps) == 2
    # the profiler knows each as a training step, numbered by state.step
    assert [int(e[3]["step_num"]) for e in steps] == [1, 2]
    for step in steps:
        kids = _children(evs, step, STEP_CHILDREN)
        assert sorted(k[0] for k in kids) == sorted(STEP_CHILDREN)
        # in the order the loop runs them
        assert [k[0] for k in kids] == list(STEP_CHILDREN)
    # no trainer span outside a step
    trainer = [e for e in evs if e[0].startswith("repro.train.")
               and e[0] != "repro.train.step"]
    assert all(any(_inside(e, s) for s in steps) for e in trainer)
    # step 2 logs (2 % 2 == 0) and step 3 is the call's last: both log
    assert sum(e[0] == "repro.train.log" for e in evs) == 2


@pytest.mark.parametrize("num_steps,log_every,logged", [
    (5, 2, 3),      # steps 2, 4 and the last, 6 (the first call ran one)
    (4, 10, 1),     # only the last
    (3, 1, 3),      # every step
])
def test_trainer_counts_one_sync_per_step_plus_one_per_log(
        num_steps, log_every, logged):
    tr, data = _trainer()
    before = _syncs()
    hist = tr.run(tr.final_state, data, num_steps=num_steps,
                  log_every=log_every)
    assert len(hist.losses) == logged
    assert _syncs() - before == num_steps + logged


def test_history_has_no_step_timer():
    tr, data = _trainer()
    hist = tr.run(tr.final_state, data, num_steps=1)
    assert not hasattr(hist, "step_times")


def _sweep(**kw):
    return run_experiment(("msync", {"m": 2}), "exponential", 8, 20,
                          seeds=[1, 2], grid={"m": [1, 2]}, backend="jax",
                          **kw)


def test_sweep_spans_nest_from_the_experiment_to_the_engine(tmp_path):
    _sweep()                                    # compiles nothing twice
    res, evs = _traced(tmp_path, lambda: _sweep(
        json_path=str(tmp_path / "sweep.json")))
    assert (tmp_path / "sweep.json").exists()
    run, = [e for e in evs if e[0] == "repro.exp.run"]
    points = [e for e in evs if e[0] == "repro.batch.point"]
    assert len(points) == 2 and all(_inside(p, run) for p in points)
    assert [p[3]["engine"] for p in points] == ["jax", "jax"]
    for p in points:
        kids = _children(evs, p, POINT_CHILDREN)
        assert [k[0] for k in kids] == [
            "repro.engine.prep", "repro.engine.dispatch",
            "repro.engine.wait", "repro.batch.assemble",
            "repro.engine.wait"]
    report, = [e for e in evs if e[0] == "repro.exp.report"]
    assert _inside(report, run) and report[1] >= points[-1][2]
    assert all(_inside(e, run) for e in evs)
    assert [r["chosen"] for r in res.meta["routing"]] == ["jax", "jax"]


def test_sweep_host_syncs_are_two_per_grid_point():
    # m-sync on a sampled law: the round scan's wait and the one fetch
    # of assemble_traces, per grid point
    _sweep()
    before = _syncs()
    _sweep()
    assert _syncs() - before == 4


def test_the_fixed_times_sweep_and_the_sharded_sweep_count_their_syncs():
    from repro.core import simulate_batch

    before = _syncs()
    simulate_batch(("msync", {"m": 2}), FixedTimes(np.arange(1.0, 9.0)),
                   20, seeds=[1, 2], backend="jax")
    assert _syncs() - before == 2
    before = _syncs()
    batch = simulate_batch(("msync", {"m": 2}), FixedTimes(
        np.arange(1.0, 9.0)), 20, seeds=[1, 2], grid={"m": [1, 2]},
        backend="jax_sharded")
    # one sharded program for both points: its wait, one fetch of its
    # outputs, then one fetch a point in assemble_traces finds on the host
    assert _syncs() - before == 2
    rec = batch.routing[0]["shard"]
    assert rec["exec_s"] > 0 and "compile_s" in rec


def test_spans_time_and_counters_count_with_the_profiler_off():
    with telemetry.span("repro.test", attr=1) as sp:
        time.sleep(0.01)
    assert sp.seconds >= 0.01
    with telemetry.step_span("repro.test.step", 3) as st:
        pass
    assert 0 <= st.seconds < sp.seconds
    before = _syncs()
    x = jnp.arange(4.0) * 2
    assert telemetry.wait(x) is not None
    got = telemetry.fetch({"a": x, "b": np.ones(2)})
    assert isinstance(got["a"], np.ndarray) and got["a"][3] == 6.0
    with telemetry.sync("repro.test.sync") as s:
        float(x[0])
    assert s.seconds >= 0
    assert _syncs() - before == 3
    # host arrays block on nothing: not a sync, not a span
    host = np.ones(3)
    assert telemetry.wait(host) is host and telemetry.fetch(host) is host
    assert _syncs() - before == 3


def test_counters_returns_a_copy():
    telemetry.count("host_syncs", 0)
    c = telemetry.counters()
    c["host_syncs"] += 100
    assert telemetry.counters()["host_syncs"] == c["host_syncs"] - 100
