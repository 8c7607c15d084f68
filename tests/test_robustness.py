"""Robustness layer tests — the graceful-degradation ladder of
``backend="fastest"`` (an engine failure completes on the next rung with
a routing record; a forced device backend raises instead),
crash-safe checkpoint/resume (kill after k of N points, resume, final
JSON byte-identical), atomic artifact writes, the cost-constants
warning, the sharded-sweep failure contract, and the ROB001/ROB002
analyzer rules (good/bad fixture twins + the live tree staying clean).
"""

from __future__ import annotations

import json
import textwrap
import warnings
from pathlib import Path

import numpy as np
import pytest

import repro.core.batch_jax as bj
from repro.core import exponential_times, simulate_batch
from repro.core.batch import ENGINE_LADDER, load_cost_constants
from repro.core.strategies import Trace
from repro.exp import run_experiment
from repro.exp.runner import atomic_write_json

ROOT = Path(__file__).resolve().parents[1]


# ------------------------------------------------------- degradation ladder
def test_ladder_order_and_exposure():
    assert ENGINE_LADDER == ("jax_sharded", "jax", "vectorized", "serial")


def _route_to(monkeypatch, engine):
    """Make ``backend="fastest"`` pick ``engine`` for every point."""
    import repro.core.batch as batch_mod

    monkeypatch.setattr(batch_mod, "_route_fastest",
                        lambda *a, **k: (engine, {"chosen": engine,
                                                  "reason": "test"}))


def test_forced_jax_failure_falls_back_with_routing_record(monkeypatch):
    """A forced jax engine failure raises (no host engine takes over);
    the same failure under ``fastest`` retries once, completes on the
    next rung, and records the downgrade in routing."""
    calls = {"n": 0}

    def boom(*args, **kwargs):
        calls["n"] += 1
        raise RuntimeError("injected engine failure")

    monkeypatch.setattr(bj, "simulate_batch_jax", boom)
    model = exponential_times(1.0, 6)
    with pytest.raises(RuntimeError, match="injected engine failure"):
        simulate_batch(("msync", {"m": 2}), model, K=20, seeds=4,
                       backend="jax")
    assert calls["n"] == 1                      # no retry, no downgrade

    _route_to(monkeypatch, "jax")
    tb = simulate_batch(("msync", {"m": 2}), model, K=20, seeds=4,
                        backend="fastest")
    assert calls["n"] == 3                      # retry-once before downgrade
    assert tb.backend == "vectorized"           # next eligible rung
    downs = tb.routing[0]["downgrades"]
    assert downs == [{"from": "jax", "to": "vectorized",
                      "error": "RuntimeError",
                      "reason": "injected engine failure",
                      "retried": True}]
    assert np.all(tb.total_time > 0)


def test_forced_jax_failure_reaches_serial_for_noneligible(monkeypatch):
    """Rennala has no vectorized fast path, so under ``fastest`` the
    ladder lands on serial; forced, the failure raises."""
    monkeypatch.setattr(bj, "simulate_batch_jax",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("injected")))
    model = exponential_times(1.0, 6)
    with pytest.raises(RuntimeError, match="injected"):
        simulate_batch(("rennala", {"batch": 2}), model, K=15, seeds=3,
                       backend="jax")
    _route_to(monkeypatch, "jax")
    tb = simulate_batch(("rennala", {"batch": 2}), model, K=15, seeds=3,
                        backend="fastest")
    assert tb.backend == "serial"
    assert tb.routing[0]["downgrades"][0]["to"] == "serial"


def test_ladder_preserves_contract_errors(monkeypatch):
    """Validation failures (unsupported combos on a forced jax backend)
    must still raise — the ladder only absorbs execution failures."""
    model = exponential_times(1.0, 4)
    with pytest.raises(NotImplementedError):
        simulate_batch(("deadline", {"deadline": 1.0}), model, K=10,
                       seeds=2, backend="jax")


def test_exhausted_ladder_reraises(monkeypatch):
    """When every rung of the ``fastest`` ladder fails the last
    exception propagates (after the downgrade records were written
    along the way)."""
    import repro.core.strategies as strategies_mod

    monkeypatch.setattr(bj, "simulate_batch_jax",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("jax down")))
    monkeypatch.setattr(strategies_mod, "simulate",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("serial down")))
    import repro.core.batch as batch_mod
    monkeypatch.setattr(batch_mod, "simulate",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("serial down")))
    _route_to(monkeypatch, "jax")
    model = exponential_times(1.0, 4)
    with pytest.raises(RuntimeError, match="serial down"):
        simulate_batch(("rennala", {"batch": 2}), model, K=10, seeds=2,
                       backend="fastest")


# --------------------------------------------------- per-bucket sweep retry
def test_sharded_bucket_failure_falls_back_per_point(monkeypatch):
    """A failing sharded bucket raises out of the sweep and out of a
    forced ``jax_sharded`` run; under ``fastest`` every deferred point
    downgrades to the plain jax engine with the record kept."""
    from repro.core.strategies import MSync
    from repro.launch.sweep import SweepPoint, run_sharded_sweep

    monkeypatch.setattr(bj, "sharded_msync_run",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("shard program died")))
    model = exponential_times(1.0, 6)
    points = [SweepPoint(index=0, strategy=MSync(m=2), K=12),
              SweepPoint(index=1, strategy=MSync(m=4), K=12)]
    with pytest.raises(RuntimeError, match="shard program died"):
        run_sharded_sweep(points, model, None, seeds=[0, 1])
    with pytest.raises(RuntimeError, match="shard program died"):
        simulate_batch("msync", model, K=12, seeds=2,
                       grid={"m": [2, 4]}, backend="jax_sharded")

    _route_to(monkeypatch, "jax_sharded")
    tb = simulate_batch("msync", model, K=12, seeds=2, grid={"m": [2, 4]},
                        backend="fastest")
    assert tb.backend == "jax"
    for rec in tb.routing:
        assert rec["downgrades"][0]["from"] == "jax_sharded"
        assert rec["downgrades"][0]["error"] == "RuntimeError"
        assert "shard" not in rec
    assert np.all(tb.total_time > 0)


# ------------------------------------------------------- checkpoint / resume
def _run_kwargs(tmp_path, **extra):
    kw = dict(seeds=4, grid={"m": [2, 4, 8]}, backend="vectorized",
              target_frac=0.5)
    kw.update(extra)
    return kw


def test_kill_and_resume_byte_identical_json(tmp_path, monkeypatch):
    """ISSUE 8 acceptance: run killed after k of N grid points, resumed
    with resume=True, final JSON byte-identical to the uninterrupted
    run's."""
    import repro.exp.runner as runner

    a = tmp_path / "a.json"
    run_experiment("msync", "crash_restart", 8, 40, json_path=str(a),
                   checkpoint_dir=str(tmp_path / "ck_a"),
                   **_run_kwargs(tmp_path))

    # plain uncheckpointed run must agree too (vectorized traces are
    # float64 end-to-end, so serialization is lossless)
    p = tmp_path / "p.json"
    run_experiment("msync", "crash_restart", 8, 40, json_path=str(p),
                   **_run_kwargs(tmp_path))

    ck_b = tmp_path / "ck_b"
    orig = runner.simulate_batch
    calls = {"n": 0}

    def kill_on_third(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] >= 3:
            raise KeyboardInterrupt("simulated kill")
        return orig(*args, **kwargs)

    monkeypatch.setattr(runner, "simulate_batch", kill_on_third)
    with pytest.raises(KeyboardInterrupt):
        run_experiment("msync", "crash_restart", 8, 40,
                       checkpoint_dir=str(ck_b), **_run_kwargs(tmp_path))
    monkeypatch.setattr(runner, "simulate_batch", orig)

    done = sorted(f.name for f in ck_b.glob("point-*.json"))
    assert done == ["point-00000.json", "point-00001.json"]

    b = tmp_path / "b.json"
    run_experiment("msync", "crash_restart", 8, 40, json_path=str(b),
                   checkpoint_dir=str(ck_b), resume=True,
                   **_run_kwargs(tmp_path))
    assert a.read_bytes() == b.read_bytes()
    assert a.read_bytes() == p.read_bytes()


def test_resume_skips_completed_points(tmp_path, monkeypatch):
    import repro.exp.runner as runner

    ck = tmp_path / "ck"
    run_experiment("msync", "crash_restart", 8, 40,
                   checkpoint_dir=str(ck), **_run_kwargs(tmp_path))
    monkeypatch.setattr(runner, "simulate_batch",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("must not recompute")))
    res = run_experiment("msync", "crash_restart", 8, 40,
                         checkpoint_dir=str(ck), resume=True,
                         **_run_kwargs(tmp_path))
    assert len(res.rows) == 3


def test_resume_refuses_mismatched_manifest(tmp_path):
    ck = tmp_path / "ck"
    run_experiment("msync", "crash_restart", 8, 40,
                   checkpoint_dir=str(ck), **_run_kwargs(tmp_path))
    with pytest.raises(ValueError, match="manifest mismatch"):
        run_experiment("msync", "crash_restart", 8, 50,
                       checkpoint_dir=str(ck), resume=True,
                       **_run_kwargs(tmp_path))


def test_trace_dict_round_trip():
    tr = Trace(times=np.array([0.5, 1.5]), values=np.array([3.0, np.nan]),
               grad_norms=np.array([9.0, 1.0]), iterations=2,
               total_time=1.5, gradients_used=4, gradients_computed=5,
               x_final=np.array([0.1, -0.2]))
    rt = Trace.from_dict(json.loads(json.dumps(tr.as_dict())))
    np.testing.assert_array_equal(rt.times, tr.times)
    np.testing.assert_array_equal(rt.grad_norms, tr.grad_norms)
    assert np.isnan(rt.values[1]) and rt.values[0] == 3.0
    assert rt.total_time == tr.total_time
    np.testing.assert_array_equal(rt.x_final, tr.x_final)
    assert rt.discard_fraction == tr.discard_fraction


# ------------------------------------------------------------- atomic writes
def test_atomic_write_json_no_tmp_left(tmp_path):
    out = tmp_path / "artifact.json"
    atomic_write_json(str(out), {"a": [1.25, "x"]})
    assert json.loads(out.read_text()) == {"a": [1.25, "x"]}
    assert list(tmp_path.glob("*.tmp")) == []
    # overwrite keeps the old file intact until the rename
    atomic_write_json(str(out), {"b": 2})
    assert json.loads(out.read_text()) == {"b": 2}


def test_atomic_write_failure_preserves_previous_artifact(tmp_path):
    out = tmp_path / "artifact.json"
    atomic_write_json(str(out), {"good": True})
    with pytest.raises(TypeError):
        atomic_write_json(str(out), {"bad": object()})   # not serializable
    assert json.loads(out.read_text()) == {"good": True}


# ------------------------------------------------- cost-constants warning
def test_load_cost_constants_warns_once_on_bad_file(tmp_path):
    bad = tmp_path / "calib.json"
    bad.write_text("{not json")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        merged = load_cost_constants(str(bad), apply=False)
    msgs = [w for w in rec if issubclass(w.category, UserWarning)]
    assert len(msgs) == 1
    assert str(bad) in str(msgs[0].message)
    assert "JSONDecodeError" in str(msgs[0].message) \
        or "ValueError" in str(msgs[0].message)
    assert merged["np_elem"] > 0                 # defaults still served

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        load_cost_constants(str(tmp_path / "absent.json"), apply=False)
    assert any("absent.json" in str(w.message) for w in rec)


def test_load_cost_constants_warning_is_once_per_path(tmp_path):
    bad = tmp_path / "stale.json"
    bad.write_text("{not json")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        load_cost_constants(str(bad), apply=False)
        load_cost_constants(str(bad), apply=False)    # memoized: silent
        load_cost_constants(str(bad), apply=False)
    msgs = [w for w in rec if issubclass(w.category, UserWarning)]
    assert len(msgs) == 1, "same stale path must warn exactly once"
    # a DIFFERENT unreadable path still gets its own warning
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        load_cost_constants(str(tmp_path / "other.json"), apply=False)
    assert any("other.json" in str(w.message) for w in rec)


def test_load_cost_constants_rejects_non_object_json(tmp_path):
    arr = tmp_path / "array.json"
    arr.write_text("[1.0, 2.0, 3.0]")            # valid JSON, wrong shape
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        merged = load_cost_constants(str(arr), apply=False)
    msgs = [w for w in rec if issubclass(w.category, UserWarning)]
    assert len(msgs) == 1
    assert "ValueError" in str(msgs[0].message)
    assert str(arr) in str(msgs[0].message)
    assert merged["np_elem"] > 0                 # defaults still served


# ------------------------------------------------------ ROB001/ROB002 rules
from repro.analysis import analyze, load_module  # noqa: E402
from repro.analysis.robustness import run_robustness_pass  # noqa: E402


def _mod(tmp_path, src, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return load_module(p, rel=name)


def _rules(findings):
    return sorted(f.rule for f in findings)


def test_rob001_flags_bare_and_swallowed_excepts(tmp_path):
    mod = _mod(tmp_path, """
        def a():
            try:
                risky()
            except:
                pass

        def b():
            try:
                risky()
            except Exception:
                pass
    """)
    assert _rules(run_robustness_pass(mod)) == ["ROB001", "ROB001"]


def test_rob001_good_twins_stay_silent(tmp_path):
    mod = _mod(tmp_path, """
        def ladder(run, record):
            try:
                return run()
            except Exception as exc:       # handled: recorded, rethrown
                record.append(type(exc).__name__)
                raise

        def narrow():
            try:
                risky()
            except ValueError:
                pass

        def pragma_ok():
            try:
                risky()
            except Exception:  # repcheck: ignore[ROB001]
                pass
    """)
    assert _rules(run_robustness_pass(mod)) == []


def test_rob002_flags_nonatomic_json_dump(tmp_path):
    mod = _mod(tmp_path, """
        import json

        def write(path, obj):
            with open(path, "w") as fh:
                json.dump(obj, fh, indent=2)
    """)
    assert _rules(run_robustness_pass(mod)) == ["ROB002"]


def test_rob002_atomic_pattern_and_reads_stay_silent(tmp_path):
    mod = _mod(tmp_path, """
        import json
        import os

        def atomic(path, obj):
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(obj, fh)
            os.replace(tmp, path)

        def read(path):
            with open(path) as fh:
                return json.load(fh)

        def text_write(path, s):
            with open(path, "w") as fh:
                fh.write(s)
    """)
    assert _rules(run_robustness_pass(mod)) == []


def test_rob_scope_gating(tmp_path):
    src = """
        import json

        def f(path, obj):
            try:
                g()
            except Exception:
                pass
            with open(path, "w") as fh:
                json.dump(obj, fh)
    """
    mod = _mod(tmp_path, src)
    assert _rules(run_robustness_pass(mod, exceptions=True, io=False)) \
        == ["ROB001"]
    assert _rules(run_robustness_pass(mod, exceptions=False, io=True)) \
        == ["ROB002"]


def test_live_tree_is_rob_clean():
    """The shipped tree carries no ROB findings (CI repcheck lane)."""
    findings = analyze(ROOT, registry=False)
    assert [f for f in findings if f.rule.startswith("ROB")] == []
