"""The phases of ``chip_smoke.py`` at tiny sizes on the CPU.

The script runs them at full size on the chip; here each phase function
runs with small ``n``/``K``/seeds and a cut-down model, covering the
comparisons against the serial engine and the routing/no-downgrade
assertions. The device check is not covered (it exits without a TPU).
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import pytest

import repro.core.batch as batch_mod

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load()

TINY_TRAIN = dict(reduced=True, d_model=64)


@pytest.fixture
def route_small_sweeps_to_jax(monkeypatch):
    """At these sizes ``fastest`` would keep every sweep on the host
    engines (below the probe floor, compile-dominated); price them as
    the chip would see paper-scale sweeps."""
    monkeypatch.setattr(batch_mod, "JAX_MIN_WORK", 0)
    monkeypatch.setattr(batch_mod, "_ACCEL_PRESENT", True)
    monkeypatch.setitem(batch_mod.COST_CONSTANTS, "jit_compile", 0.0)


def test_round_scan_phase_tiny(route_small_sweeps_to_jax):
    out = cs.phase_round_scan(16, 40, 8, (1, 4, 16))
    assert set(out) == {"fixed_sqrt", "exponential"}
    for res in out.values():
        assert [r["chosen"] for r in res.meta["routing"]] == ["jax"] * 3


def test_arrival_scan_phase_tiny(route_small_sweeps_to_jax):
    # n=3: the sqrt(i) times have irrational ratios, so no two workers
    # ever finish at exactly the same moment. With exact ties the serial
    # heap (push order) and the device scan (worker index) apply tied
    # gradients in different orders, which at a tiny n moves the
    # objective by more than float32 rounding does.
    out = cs.phase_arrival_scan(3, 60, 8, 20)
    assert set(out) == {"async", "ringmaster"}


def test_trainer_phase_tiny():
    out = cs.phase_trainer("nanogpt-paper", 8, 32, 6, **TINY_TRAIN)
    assert len(out["losses"]) == 6
    assert out["pallas_rel_err"] < cs.BF16_REL


def test_routing_check_rejects_downgrades():
    ok = types.SimpleNamespace(
        name="x", meta={"backend": "jax",
                        "routing": [{"chosen": "jax"}]})
    cs.require_jax_routing(ok)
    down = types.SimpleNamespace(
        name="x", meta={"backend": "jax", "routing": [
            {"chosen": "jax", "downgrades": [{"from": "jax",
                                              "to": "vectorized"}]}]})
    with pytest.raises(AssertionError, match="downgrade"):
        cs.require_jax_routing(down)
    host = types.SimpleNamespace(
        name="x", meta={"backend": "vectorized",
                        "routing": [{"chosen": "vectorized"}]})
    with pytest.raises(AssertionError, match="did not run on jax"):
        cs.require_jax_routing(host)


def test_mean_and_close_checks():
    cs._same_mean("same", [1.0, 2.0, 3.0], [1.5, 2.5, 2.0])
    with pytest.raises(AssertionError, match="4 SE"):
        cs._same_mean("far", [1.0, 1.1, 0.9], [9.0, 9.1, 8.9])
    assert cs._close("c", [1.0, 1.00001], [1.0, 1.0], 1e-4) < 1e-4
    with pytest.raises(AssertionError, match="relative error"):
        cs._close("c", [1.1], [1.0], 1e-4)


def test_all_reduce_count():
    hlo = textwrap.dedent("""
        %all-reduce.1 = f32[] all-reduce(f32[] %a), replica_groups={}
        %ars = (f32[4]) all-reduce-start(f32[4] %b)
        %ard = f32[4] all-reduce-done((f32[4]) %ars)
        %x = f32[] add(f32[] %all-reduce.1, f32[] %c)
    """)
    assert cs._all_reduces(hlo) == 2


def test_four_chip_phase_tiny():
    """The four-device phase on four forced host devices (a fresh
    process: the device count is fixed when jax starts)."""
    code = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        out = cs.phase_four_chips(16, 30, 6, (1, 4, 16), 20,
                                  "nanogpt-paper", 8, 32, 4,
                                  reduced=True, d_model=64)
        print(json.dumps({{"all_reduces": out["all_reduces"],
                           "metrics": out["metrics"]}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["all_reduces"] >= 1
    assert out["metrics"]["mesh"]["loss"] == pytest.approx(
        out["metrics"]["one"]["loss"], rel=cs.STEP_RTOL)
