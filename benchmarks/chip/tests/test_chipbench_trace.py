"""The trace reduction on small synthetic traces: busy time as a union,
idle share, self time and top operations, and idle gaps put to the host event that covers them."""

from __future__ import annotations

from dataclasses import dataclass, field

import pytest

from benchmarks.chip import trace as tr


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


@dataclass
class Prof:
    planes: list


def _dev(i, ops):
    return Plane(f"/device:TPU:{i}", [
        Line("XLA Modules", [Ev("jit_step(123)", 0, 60)]),
        Line("XLA Ops", [Ev(n, s, d) for n, s, d in ops])])


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total(tr.union([(0, 2), (1, 3)])) == 3


def test_self_time_takes_nested_ops_off_their_parent():
    evs = [("while", 0, 100), ("body.1", 10, 30), ("body.2", 40, 60)]
    st = dict(tr.self_times(evs))
    assert st == {"while": 60, "body.1": 20, "body.2": 20}


def test_busy_idle_and_top_ops_of_one_device():
    # ops cover [0, 30) and [50, 70) of a 100 ns trace, one nested pair
    prof = Prof([_dev(0, [("fusion.1", 0, 30), ("copy.2", 10, 5),
                          ("fusion.1", 50, 20)]),
                 Plane("/host:CPU", [])])
    red = tr.reduce(prof)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(50e-9)
    top = dict(red["device_ops"])
    assert top["jit_step/fusion.1"] == pytest.approx(45e-9)
    assert top["jit_step/copy.2"] == pytest.approx(5e-9)
    assert list(top) == ["jit_step/fusion.1", "jit_step/copy.2"]


def test_ops_are_named_short_under_their_program():
    assert tr.op_name("%fusion.399 = (f32[16]{0}) fusion(%a), kind=kLoop") \
        == "fusion.399"
    assert tr.module_name("jit_step_fn(7096203147581258495)") \
        == "jit_step_fn"
    prof = Prof([_dev(0, [("%fusion.1 = f32[] fusion()", 10, 10),
                          ("%copy.3 = f32[] copy()", 70, 10)])])
    assert [n for n, _ in tr.reduce(prof)["device_ops"]] == \
        ["jit_step/fusion.1", "copy.3"]


def test_busy_is_averaged_over_the_devices_that_ran():
    prof = Prof([_dev(0, [("a", 0, 40)]), _dev(1, [("a", 0, 20)]),
                 Plane("/device:TPU:2", [Line("XLA Ops", [])])])
    red = tr.reduce(prof)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(30e-9)


def test_idle_gaps_go_to_the_innermost_host_event():
    host = Plane("/host:CPU", [
        Line("other thread", [Ev("bench.noise", 0, 5)]),
        Line("main", [Ev("bench.window", 0, 100),
                      Ev("bench.feed", 30, 20),
                      Ev("$trainer.py run", 60, 30)])])
    prof = Prof([_dev(0, [("fusion", 0, 30), ("fusion", 50, 10),
                          ("fusion", 90, 10)]), host])
    red = tr.reduce(prof)
    gaps = dict(red["idle_gaps"])
    assert gaps == {"bench.feed": pytest.approx(20e-9),
                    "$trainer.py run": pytest.approx(30e-9)}


def test_a_trace_without_device_ops_reads_nothing():
    red = tr.reduce(Prof([Plane("/host:CPU", [])]))
    assert red["devices"] == 0 and red["busy_s"] == 0.0


def test_a_recorded_cpu_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    prof = tr.load(str(tmp_path))
    assert any(p.name == tr.HOST_PLANE for p in prof.planes)
    assert any("bench.window" in line for line in tr.describe(prof))
    # the CPU has no /device:TPU plane: nothing to read, and no error
    assert tr.reduce(prof)["devices"] == 0
