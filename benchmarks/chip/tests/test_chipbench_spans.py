"""The program's spans and named scopes read from a trace: span counts
and self times, device time per named scope, idle gaps put down to the
innermost program or benchmark span, the children's cover, and the two
``host_syncs`` readers. ``trace.reduce`` keeps its keys and values."""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field

import pytest

from benchmarks.chip import registry, spans, sweep, xspace
from benchmarks.chip import trace as tr
from benchmarks.chip.cell import out_dir
from benchmarks.chip.clock import CompileClock

from chipbench_helpers import sim_cell


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


#: device ops as (name, start_ns, duration_ns, op path): two named
#: scopes, the backward of ``head`` under ``transpose(jvp(...))``, and a
#: ``header`` that is another name
OPS = [("fusion.1", 30, 40, "jit(step_fn)/jit(main)/head/dot"),
       ("fusion.2", 60, 20, "jit(step_fn)/transpose(jvp(head))/dot_general"),
       ("fusion.3", 100, 50, "jit(step_fn)/blocks/attn/dot"),
       ("fusion.4", 140, 5, "jit(run)/while/body/order_stat/min"),
       ("fusion.5", 145, 5, "jit(step_fn)/header/add")]


def _space(devices, by_ref=False):
    """An ``XSpace`` with one device plane per list of ``OPS``-like ops;
    the op path is the ``tf_op`` stat of each op's metadata, in place or
    (``by_ref``) as a reference to a stat metadata entry."""
    space = xspace.schema()()
    for i, ops in enumerate(devices):
        plane = space.planes.add(name=f"/device:TPU:{i}")
        plane.stat_metadata[1].name = "tf_op"
        line = plane.lines.add(name="XLA Ops", timestamp_ns=1)
        for j, (name, start, dur, path) in enumerate(ops, start=1):
            md = plane.event_metadata[j]
            md.id, md.name = j, name
            if by_ref:
                plane.stat_metadata[100 + j].name = path
                md.stats.add(metadata_id=1, ref_value=100 + j)
            else:
                md.stats.add(metadata_id=1, str_value=path)
            line.events.add(metadata_id=j, offset_ps=start * 1000 - 1000,
                            duration_ps=dur * 1000)
    space.planes.add(name="/host:CPU")
    return space


def _prof():
    """A 200 ns window: a step whose feed, dispatch and sync spans hold
    the device's idle gaps, and the device ops of ``OPS``."""
    host = Plane("/host:CPU", [
        Line("other thread", [Ev("repro.engine.wait", 0, 200)]),
        Line("main", [
            Ev("bench.window", 0, 200),
            Ev("repro.train.step", 0, 200),
            Ev("repro.train.feed", 0, 20),
            Ev("bench.feed", 10, 9),
            Ev("repro.train.dispatch", 20, 10),
            Ev("repro.train.sync", 30, 160),
            Ev("np.asarray(jax.Array)", 150, 40),
        ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step_fn(1)", 30, 120)]),
        Line("XLA Ops", [Ev(n, s, d) for n, s, d, _ in OPS])])
    return Prof([dev, host])


def test_span_table_counts_totals_and_self_times():
    table = spans.span_table(tr.host_events(_prof()))
    assert set(table) == {"repro.train.step", "repro.train.feed",
                          "repro.train.dispatch", "repro.train.sync"}
    step = table["repro.train.step"]
    assert step["count"] == 1
    assert step["total_s"] == pytest.approx(200e-9)
    # the nested program spans come off; bench. and JAX events do not
    assert step["self_s"] == pytest.approx(10e-9)
    assert table["repro.train.feed"]["self_s"] == pytest.approx(20e-9)
    assert table["repro.train.sync"]["self_s"] == pytest.approx(160e-9)


@pytest.mark.parametrize("by_ref", [False, True])
def test_scope_busy_unions_the_ops_whose_path_names_the_scope(by_ref):
    got = spans.scope_busy(_space([OPS], by_ref))
    # head: [30, 70) and [60, 80) -> 50 ns; "header" is another name
    assert got["head"] == pytest.approx(50e-9)
    assert got["order_stat"] == pytest.approx(5e-9)
    assert spans.scope_busy(_space([])) == {}


def test_scope_busy_is_a_mean_over_the_devices_that_ran():
    got = spans.scope_busy(_space([
        [("fusion.1", 0, 40, "jit(f)/head/dot")],
        [("fusion.1", 0, 20, "jit(f)/head/dot")], []]))
    assert got == {"head": pytest.approx(30e-9), "order_stat": 0.0}


def test_an_xplane_file_reads_back_as_the_same_space(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space([OPS]).SerializeToString())
    space = xspace.load(str(path))
    assert xspace.metadata_stat(space.planes[0], "tf_op")[4] == OPS[3][3]
    assert spans.scope_busy(space)["head"] == pytest.approx(50e-9)


def test_idle_goes_to_the_innermost_program_span_else_a_bare_bench_span():
    prof = _prof()
    idle = spans.idle_by_span(prof, tr.host_events(prof))
    # gaps [0, 30), [80, 100), [150, 200) by their midpoints: the feed's
    # own bench. span lies inside the program's feed span, and the JAX
    # event inside the sync span does not take the last gap
    assert idle == {"repro.train.feed": pytest.approx(30e-9),
                    "repro.train.sync": pytest.approx(70e-9)}
    # a gap whose midpoint no program span covers goes to the bench.
    # span around it: the window runs on to 300 ns, an op at [290, 300)
    prof.planes[1].lines[1].events[0] = Ev("bench.window", 0, 300)
    prof.planes[0].lines[1].events.append(Ev("fusion.6", 290, 10))
    idle = spans.idle_by_span(prof, tr.host_events(prof))
    assert idle == {"repro.train.feed": pytest.approx(30e-9),
                    "repro.train.sync": pytest.approx(20e-9),
                    "bench.window": pytest.approx(140e-9)}


def test_children_cover_their_parent():
    host = tr.host_events(_prof())
    assert spans.coverage(host, "repro.train.step") == pytest.approx(0.95)
    assert spans.coverage(host, "repro.exp.run") is None


def test_reduce_keeps_its_keys_and_values_beside_the_span_tables():
    prof = _prof()
    before = tr.reduce(prof)
    extra = spans.reduce_spans(prof, _space([OPS]))
    assert tr.reduce(prof) == before
    assert set(before) == {"devices", "busy_s", "device_ops", "idle_gaps"}
    assert set(extra) == {"spans", "scopes", "idle_by_span", "coverage"}
    assert before["busy_s"] == pytest.approx(100e-9)
    assert extra["scopes"]["head"] == pytest.approx(50e-9)
    json.dumps(extra)


def test_the_spans_command_adds_its_tables_and_restores_the_trace_reader(
        monkeypatch, capsys, tmp_path):
    from benchmarks.chip import run

    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(_space([OPS]).SerializeToString())
    plain_load, plain_reduce = tr.load, tr.reduce

    def fake_main(argv):
        assert tr.load is not plain_load and tr.reduce is not plain_reduce
        tr.load(str(tmp_path))             # the driver's traced window
        tr.reduce(_prof())
        return 0

    monkeypatch.setattr(run, "main", fake_main)
    assert spans.main(["--workload", "test.spans", "--seed", "1"]) == 0
    assert (tr.load, tr.reduce) == (plain_load, plain_reduce)
    got = json.loads((out_dir("test.spans") / "spans.json").read_text())
    assert got["coverage"]["repro.train.step"] == pytest.approx(0.95)
    assert got["scopes"]["order_stat"] == pytest.approx(5e-9)
    err = capsys.readouterr().err
    assert "repro.train.sync" in err and "children's cover" in err


def test_a_cpu_sweep_trace_shows_the_program_spans(monkeypatch):
    """The sweep driver's traced window, reduced with the span tables:
    the experiment, one span a grid point, and the engine spans inside."""
    seen = {}
    plain = tr.reduce

    def reduce_with_spans(prof):
        seen.update(spans.reduce_spans(prof))
        return plain(prof)

    monkeypatch.setattr(tr, "reduce", reduce_with_spans)
    cell, cfg, mix, ref = sim_cell("sim.msync_mgrid.exp", check_seeds=1,
                                   trace_sweeps=1)
    sweep.run_cell(cell, cfg, mix, ref, 2**31 + 11, 0.2, True,
                   time.perf_counter(), CompileClock())
    table = seen["spans"]
    assert table["repro.exp.run"]["count"] == 1
    assert table["repro.batch.point"]["count"] == len(mix["grid"]["m"])
    for name in ("repro.engine.prep", "repro.engine.dispatch",
                 "repro.engine.wait", "repro.batch.assemble",
                 "repro.exp.report"):
        assert table[name]["count"] >= 1, name
    assert 0.5 < seen["coverage"]["repro.exp.run"] <= 1.0
    assert seen["scopes"] == {} and seen["idle_by_span"] == []  # no TPU


@pytest.mark.parametrize("name,obs,per_unit", [
    # 6 syncs a sweep: 1 warm-up, 4 window and 2 traced sweeps
    ("sim.host_syncs_per_sweep",
     {"mix": {"warmup_sweeps": 1}, "units": 4, "traced_units": 2}, 6.0),
    # 12 syncs a 10-step call; 3 compared steps at 2 and warm-up at 1.5
    ("train.host_syncs_per_step",
     {"mix": {"warmup_steps": 2}, "units": 20, "traced_units": 10},
     (3 * 2 + 2 * 1.5 + 30 * 1.2) / 35),
])
def test_host_sync_readers_divide_the_program_counter(monkeypatch, name,
                                                      obs, per_unit):
    from repro import telemetry

    units = {"sim.host_syncs_per_sweep": 7,
             "train.host_syncs_per_step": 35}[name]
    total = per_unit * units
    monkeypatch.setattr(telemetry, "_COUNTERS", {"host_syncs": total})
    reader = registry.metric_reader(name)
    assert reader.read(obs) == pytest.approx(per_unit)
    # without the mix (a driver's own obs) or a counter: nothing
    assert reader.read(dict(obs, mix=None)) is None
    monkeypatch.setattr(telemetry, "_COUNTERS", {})
    assert reader.read(obs) is None
