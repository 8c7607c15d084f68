"""Tests for the ``backend="jax_sharded"`` fused sweep (ISSUE 7): the
:mod:`repro.launch.sweep` orchestrator, its bitwise-parity contract
with the unsharded jax backend, the shape-bucket keys, the
``jax_sharded`` arm of the cost model / ``backend="fastest"`` router,
and the per-machine cost-constant loader.

The multi-device lane runs ONE subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (same pattern
as ``test_hlo_analysis.py``) so the main pytest process keeps its
single-device view; everything it checks — uneven shards, multi-bucket
grids, 4-device routing records — is asserted from the subprocess's
JSON report. Single-device parity runs in-process: the sweep layer is
device-count-agnostic, only the mesh size changes.
"""

import json
import os
import sys
import textwrap

import numpy as np
import pytest

from repro.core import STRATEGIES, simulate_batch
from repro.core import batch as batch_mod
from repro.core.batch import (COST_CONSTANTS, _DEFAULT_COST_CONSTANTS,
                              estimate_backend_seconds,
                              load_cost_constants)
from repro.core.batch_jax import quadratic_worst_case_jax
from repro.exp import make_scenario
from repro.launch.sweep import (SweepPoint, _bucket_key, is_coordinator,
                                shardable_kind, sweep_device_count,
                                sweep_mesh)


def _assert_bitwise(tb_a, tb_b):
    for ga, gb in zip(tb_a.traces, tb_b.traces):
        for a, b in zip(ga, gb):
            assert a.total_time == b.total_time
            assert a.gradients_computed == b.gradients_computed
            assert a.gradients_used == b.gradients_used
            assert np.array_equal(a.times, b.times)
            assert np.array_equal(a.values, b.values)
            assert np.array_equal(a.grad_norms, b.grad_norms)


# ---------------------------------------------------------------- parity (D=1)


def test_msync_timing_grid_parity_and_record():
    model = make_scenario("exponential", n=48)
    kw = dict(K=25, seeds=4, grid={"m": [3, 7, 11]})
    tb_j = simulate_batch(("msync", {"m": 5}), model, backend="jax", **kw)
    tb_s = simulate_batch(("msync", {"m": 5}), model,
                          backend="jax_sharded", **kw)
    _assert_bitwise(tb_j, tb_s)
    assert tb_s.backend == "jax_sharded"
    # the whole m-grid fused into ONE traced-m bucket
    recs = [r["shard"] for r in tb_s.routing]
    assert {r["bucket"] for r in recs} == {"msync-timing/25"}
    for r in recs:
        assert r["points_in_bucket"] == 3
        assert r["units"] == 12
        assert r["devices"] >= 1
        assert r["exec_s"] >= 0.0
        assert isinstance(r["cache_hit"], bool)
        if not r["cache_hit"]:
            assert r["compile_s"] > 0.0


def test_msync_math_gamma_grid_parity():
    model = make_scenario("exponential", n=40)
    prob = quadratic_worst_case_jax(d=24)
    kw = dict(K=20, seeds=3, problem=prob, grid={"gamma": [0.01, 0.05]},
              record_every=4)
    tb_j = simulate_batch(("msync", {"m": 6}), model, backend="jax", **kw)
    tb_s = simulate_batch(("msync", {"m": 6}), model,
                          backend="jax_sharded", **kw)
    _assert_bitwise(tb_j, tb_s)
    # one math bucket: gamma is traced, m static
    assert tb_s.routing[0]["shard"]["bucket"] == "msync-math/20/6"


def test_arrival_scan_parity_and_meta():
    model = make_scenario("exponential", n=48)
    for spec in ["async", ("ringmaster", {"max_delay": 6})]:
        tb_j = simulate_batch(spec, model, K=40, seeds=4, backend="jax")
        tb_s = simulate_batch(spec, model, K=40, seeds=4,
                              backend="jax_sharded")
        _assert_bitwise(tb_j, tb_s)
        rec = tb_s.routing[0]["shard"]
        assert rec["bucket"].startswith("arrival/")
        assert rec["chain_s"] >= 0.0          # chain build instrumented


def test_round_scan_family_sharded_zero_fallback():
    """ISSUE 10 acceptance: the round-scan family (Rennala / Malenia /
    Ringleader) runs NATIVELY inside ``backend="jax_sharded"`` — zero
    ``fallback`` routing records, bitwise per-seed parity with
    ``backend="jax"``, and per-kind shape-bucket keys."""
    model = make_scenario("exponential", n=40)
    for spec, bucket in [(("rennala", {"batch": 8}), "rennala/20/8/0.0"),
                         (("malenia", {"S": 2.0}), "malenia/20/2.0/0.0"),
                         ("ringleader", "ringleader/20/0.0")]:
        tb_j = simulate_batch(spec, model, K=20, seeds=3, backend="jax")
        tb_s = simulate_batch(spec, model, K=20, seeds=3,
                              backend="jax_sharded")
        _assert_bitwise(tb_j, tb_s)
        rec = tb_s.routing[0]["shard"]
        assert rec["bucket"] == bucket
        assert "fallback" not in rec
        assert rec["units"] == 3
        assert rec["devices"] >= 1


def test_tol_early_exit_rejected():
    model = make_scenario("exponential", n=40)
    with pytest.raises(NotImplementedError):
        simulate_batch(("msync", {"m": 4}), model, K=20, seeds=2,
                       backend="jax_sharded", tol_grad_sq=1e-6)


# ------------------------------------------------------------- bucket keys


def _point(idx, spec, K=30, gamma=0.0, n=40):
    name, kwargs = spec if isinstance(spec, tuple) else (spec, {})
    strat = STRATEGIES[name](**kwargs)
    strat.bind(n)
    return SweepPoint(index=idx, strategy=strat, K=K, gamma=gamma)


def test_bucket_keys_fuse_and_split():
    model = make_scenario("exponential", n=40)
    # timing m-sync: heterogeneous m fuses (m is traced row-wise)
    k3 = _bucket_key("msync", _point(0, ("msync", {"m": 3})), math=False)
    k9 = _bucket_key("msync", _point(1, ("msync", {"m": 9})), math=False)
    assert k3 == k9 == ("msync-timing", 30)
    # different K => different compiled shape => different bucket
    assert _bucket_key("msync", _point(2, ("msync", {"m": 3}), K=50),
                       math=False) != k3
    # math m-sync: m is static (oracle batch splits m ways), gamma traced
    m3 = _bucket_key("msync", _point(0, ("msync", {"m": 3}),
                                     gamma=0.1), math=True)
    m9 = _bucket_key("msync", _point(1, ("msync", {"m": 9}),
                                     gamma=0.2), math=True)
    assert m3 == ("msync-math", 30, 3)
    assert m3 != m9
    # arrival scan: gamma is static in math mode, absent in timing mode
    a1 = _bucket_key("async", _point(0, "async", gamma=0.1), math=True)
    a2 = _bucket_key("async", _point(1, "async", gamma=0.2), math=True)
    assert a1 != a2
    t1 = _bucket_key("async", _point(0, "async", gamma=0.1), math=False)
    t2 = _bucket_key("async", _point(1, "async", gamma=0.2), math=False)
    assert t1 == t2
    # ringmaster keys include max_delay
    r1 = _bucket_key("ringmaster",
                     _point(0, ("ringmaster", {"max_delay": 4})),
                     math=False)
    r2 = _bucket_key("ringmaster",
                     _point(1, ("ringmaster", {"max_delay": 8})),
                     math=False)
    assert r1 != r2
    # round-scan family (ISSUE 10): batch/S are static program shapes,
    # so they split buckets; gamma is static only in math mode
    b4 = _bucket_key("rennala", _point(0, ("rennala", {"batch": 4})),
                     math=False)
    b8 = _bucket_key("rennala", _point(1, ("rennala", {"batch": 8})),
                     math=False)
    assert b4 == ("rennala", 30, 4, 0.0)
    assert b4 != b8
    s1 = _bucket_key("malenia", _point(0, ("malenia", {"S": 1.0})),
                     math=False)
    s2 = _bucket_key("malenia", _point(1, ("malenia", {"S": 2.0})),
                     math=False)
    assert s1 != s2
    g1 = _bucket_key("ringleader", _point(0, "ringleader", gamma=0.1),
                     math=True)
    g2 = _bucket_key("ringleader", _point(1, "ringleader", gamma=0.2),
                     math=True)
    assert g1 != g2
    assert _bucket_key("ringleader", _point(0, "ringleader", gamma=0.1),
                       math=False) == ("ringleader", 30, 0.0)
    # every jax engine family shards; a kind without a sharded program
    # is an error, never a silent per-point fallback
    with pytest.raises(ValueError, match="no sharded program"):
        _bucket_key(None, _point(5, ("rennala", {"batch": 4})), math=False)
    for name, kw in [("rennala", {"batch": 4}), ("malenia", {"S": 2.0}),
                     ("ringleader", {})]:
        assert shardable_kind(_point(0, (name, kw)).strategy,
                              model, None) == name
    assert shardable_kind(_point(0, ("msync", {"m": 3})).strategy,
                          model, None) == "msync"


# ------------------------------------------- cost model + router (devices>1)


def test_estimate_jax_sharded_divides_compute_not_compile():
    model = make_scenario("exponential", n=1000)
    strat = STRATEGIES["msync"](m=10)
    strat.bind(1000)
    S, K = 64, 3000
    t_jax = estimate_backend_seconds("jax", strat, model, S, K, 1000)
    t_d4 = estimate_backend_seconds("jax_sharded", strat, model, S, K,
                                    1000, devices=4)
    compile_s = COST_CONSTANTS["jit_compile"]
    # compute shrinks 4x, the (host-bound) compile term does not
    assert t_d4 == pytest.approx((t_jax - compile_s) / 4 + compile_s)
    assert t_d4 < t_jax
    # devices beyond S cannot help: shard factor is min(devices, S)
    t_huge = estimate_backend_seconds("jax_sharded", strat, model, 2, K,
                                      1000, devices=64)
    t_two = estimate_backend_seconds("jax_sharded", strat, model, 2, K,
                                     1000, devices=2)
    assert t_huge == pytest.approx(t_two)
    # ISSUE 10: the round-scan family is priced sharded too (round_elem
    # compute divides by the shard factor, compile still does not)
    renn = STRATEGIES["rennala"](batch=8)
    renn.bind(1000)
    t_renn_jax = estimate_backend_seconds("jax", renn, model, S, K, 1000)
    t_renn_d4 = estimate_backend_seconds("jax_sharded", renn, model, S, K,
                                         1000, devices=4)
    assert t_renn_d4 == pytest.approx(
        (t_renn_jax - compile_s) / 4 + compile_s)
    assert t_renn_d4 < t_renn_jax


def test_router_picks_jax_sharded_with_devices(monkeypatch):
    model = make_scenario("exponential", n=1000)
    strat = STRATEGIES["msync"](m=10)
    strat.bind(1000)
    monkeypatch.setattr(batch_mod, "_DEVICE_COUNT", 4)
    chosen, info = batch_mod._route_fastest(strat, model, None, 3000, 64,
                                            "counter", None)
    assert chosen == "jax_sharded"
    assert info["devices"] == 4
    assert info["est_seconds"]["jax_sharded"] < info["est_seconds"]["jax"]
    # a JaxProblem point still routes among the jax engines only
    prob = quadratic_worst_case_jax(d=100)
    chosen_p, info_p = batch_mod._route_fastest(strat, model, prob, 3000,
                                                64, "counter", None)
    assert chosen_p == "jax_sharded"
    assert "only a jax engine" in info_p["reason"]
    # below the per-device work floor the sharded arm is not even priced
    chosen_s, info_s = batch_mod._route_fastest(strat, model, None, 40, 4,
                                                "counter", None)
    assert "jax_sharded" not in info_s.get("est_seconds", {})


def test_router_single_device_never_sharded(monkeypatch):
    model = make_scenario("exponential", n=1000)
    strat = STRATEGIES["msync"](m=10)
    strat.bind(1000)
    monkeypatch.setattr(batch_mod, "_DEVICE_COUNT", 1)
    chosen, info = batch_mod._route_fastest(strat, model, None, 3000, 64,
                                            "counter", None)
    assert chosen != "jax_sharded"
    assert "jax_sharded" not in info.get("est_seconds", {})


# ------------------------------------------------------- constants loader


def test_load_cost_constants_roundtrip(tmp_path):
    try:
        flat = tmp_path / "flat.json"
        flat.write_text(json.dumps({"jax_elem": 9e-9, "bogus_key": 1.0,
                                    "np_elem": -1.0}))
        merged = load_cost_constants(str(flat), apply=False)
        assert merged["jax_elem"] == 9e-9
        assert "bogus_key" not in merged              # unknown: ignored
        assert merged["np_elem"] == \
            _DEFAULT_COST_CONSTANTS["np_elem"]        # non-positive: ignored
        assert COST_CONSTANTS["jax_elem"] == \
            _DEFAULT_COST_CONSTANTS["jax_elem"]       # apply=False: untouched

        # the --calibrate artifact shape, applied in place
        nested = tmp_path / "calib.json"
        nested.write_text(json.dumps(
            {"meta": {"source": "test"},
             "constants": {"jit_compile": 0.123}}))
        load_cost_constants(str(nested))
        assert COST_CONSTANTS["jit_compile"] == 0.123

        # unreadable file: defaults win, no exception
        assert load_cost_constants(str(tmp_path / "missing.json"),
                                   apply=False) == _DEFAULT_COST_CONSTANTS
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert load_cost_constants(str(bad),
                                   apply=False) == _DEFAULT_COST_CONSTANTS
    finally:
        COST_CONSTANTS.clear()
        COST_CONSTANTS.update(_DEFAULT_COST_CONSTANTS)


def test_single_process_is_coordinator():
    assert is_coordinator()
    assert sweep_device_count() >= 1
    mesh = sweep_mesh()
    assert mesh.axis_names == ("data",)


# --------------------------------------------------- 4-device subprocess lane


_SUB_CODE = textwrap.dedent("""
    import json
    import numpy as np
    import jax
    from repro.core import simulate_batch
    from repro.core import batch as batch_mod
    from repro.core.strategies import STRATEGIES
    from repro.exp import make_scenario

    def bitwise(tb1, tb2):
        return all(
            a.total_time == b.total_time
            and a.gradients_computed == b.gradients_computed
            and np.array_equal(a.times, b.times)
            and np.array_equal(a.values, b.values)
            for ga, gb in zip(tb1.traces, tb2.traces)
            for a, b in zip(ga, gb))

    out = {"devices": jax.local_device_count()}
    model = make_scenario("exponential", n=48)

    # uneven shard: 3 points x 5 seeds = 15 units, 15 % 4 != 0
    kw = dict(K=25, seeds=5, grid={"m": [3, 7, 11]})
    tb_j = simulate_batch(("msync", {"m": 5}), model, backend="jax", **kw)
    tb_s = simulate_batch(("msync", {"m": 5}), model,
                          backend="jax_sharded", **kw)
    rec = tb_s.routing[0]["shard"]
    out["uneven_bitwise"] = bitwise(tb_j, tb_s)
    out["uneven_padded"] = rec["padded_units"]
    out["uneven_devices"] = rec["devices"]
    out["uneven_units"] = rec["units"]

    # mixed-shape grid: K varies => two shape buckets
    kw = dict(K=25, seeds=4, grid={"K": [20, 30]})
    tb_j = simulate_batch(("msync", {"m": 4}), model, backend="jax", **kw)
    tb_s = simulate_batch(("msync", {"m": 4}), model,
                          backend="jax_sharded", **kw)
    out["mixed_bitwise"] = bitwise(tb_j, tb_s)
    out["mixed_buckets"] = sorted({r["shard"]["bucket"]
                                   for r in tb_s.routing})

    # arrival scan with seeds % devices != 0
    tb_j = simulate_batch("async", model, K=30, seeds=6, backend="jax")
    tb_s = simulate_batch("async", model, K=30, seeds=6,
                          backend="jax_sharded")
    out["async_bitwise"] = bitwise(tb_j, tb_s)
    out["async_padded"] = tb_s.routing[0]["shard"]["padded_units"]

    # round-scan family shards across the 4 devices (ISSUE 10)
    tb_j = simulate_batch(("rennala", {"batch": 6}), model, K=20, seeds=6,
                          backend="jax")
    tb_s = simulate_batch(("rennala", {"batch": 6}), model, K=20, seeds=6,
                          backend="jax_sharded")
    rec = tb_s.routing[0]["shard"]
    out["rennala_bitwise"] = bitwise(tb_j, tb_s)
    out["rennala_fallback"] = "fallback" in rec
    out["rennala_devices"] = rec["devices"]

    # router at paper scale actually sees the 4 devices
    strat = STRATEGIES["msync"](m=10)
    strat.bind(1000)
    big = make_scenario("exponential", n=1000)
    chosen, info = batch_mod._route_fastest(strat, big, None, 3000, 64,
                                            "counter", None)
    out["routed"] = chosen
    out["routed_devices"] = info.get("devices")

    print(json.dumps(out))
""")


@pytest.mark.slow_subprocess
def test_four_device_subprocess_lane():
    """Runs through the shared benchmarks.subproc timeout+retry runner:
    a hung XLA compile now fails the lane at the deadline instead of
    stalling CI, and the cold-compile flake mode gets one warm retry."""
    from benchmarks.subproc import run_json_worker

    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    out = run_json_worker([sys.executable, "-c", _SUB_CODE],
                          label="4-device sharded-sweep lane", env=env,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert out["devices"] == 4
    assert out["uneven_bitwise"] is True
    assert out["uneven_padded"] == 1          # 15 units -> 16 = 4 x 4
    assert out["uneven_devices"] == 4
    assert out["uneven_units"] == 15
    assert out["mixed_bitwise"] is True
    assert out["mixed_buckets"] == ["msync-timing/20", "msync-timing/30"]
    assert out["async_bitwise"] is True
    assert out["async_padded"] == 2           # 6 seeds -> 8 = 4 x 2
    assert out["rennala_bitwise"] is True
    assert out["rennala_fallback"] is False
    assert out["rennala_devices"] == 4
    assert out["routed"] == "jax_sharded"
    assert out["routed_devices"] == 4
