"""Each cell's unit of work at a tiny size on the CPU, through the
drivers the command uses, and the command's refusal without a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.chip import registry, sweep, train
from benchmarks.chip.cell import CellRun, Check
from benchmarks.chip.clock import CompileClock
from benchmarks.chip.registry import REPO
from benchmarks.chip.run import per_layer_values, result_line

from chipbench_helpers import BENCH, sim_cell, train_cell


@pytest.fixture(scope="module")
def clock():
    return CompileClock()


def test_the_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.run", "--workload",
         "sim.msync_mgrid.exp", "--seed", str(2**31 + 7), "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("name", ["sim.msync_mgrid.exp"])
def test_sweep_cell_window_and_trace(name, clock):
    cell, cfg, mix, ref = sim_cell(name, check_seeds=3, trace_sweeps=1)
    run = sweep.run_cell(cell, cfg, mix, ref, 2**31 + 99, 0.5, True,
                         time.perf_counter(), clock)
    assert run.correct, run.checks
    assert run.rates["sim_steps_per_s"] > 0 and run.setup_s > 0
    # steps: K per seed per grid point, for every sweep of the window
    points = len(mix["grid"]["m"]) if mix["grid"] else 1
    assert run.attempted % (cfg["seeds_per_sweep"] * points) == 0
    assert run.obs["units"] >= 1 and run.obs["traced_units"] == 1
    assert run.obs["compile"]["compiles"] >= 1
    assert run.window_s > 0 and run.busy_s == 0.0      # no TPU plane
    vals = per_layer_values(BENCH, cell, run.obs)
    # the compile readers find their counters; the device readers find
    # no device in a CPU trace and say nothing
    assert set(vals) == {"sim.compile_s_per_sweep", "sim.compiles_per_sweep"}


def test_train_cell_window(monkeypatch, clock):
    cell, cfg, mix, ref = train_cell(monkeypatch, steps_per_call=3,
                                     warmup_steps=1)
    run = train.run_cell(cell, cfg, mix, ref, 2**31 + 5, 0.5, False,
                         time.perf_counter(), clock)
    assert run.correct, run.checks
    assert run.attempted % 3 == 0 and run.attempted >= 3
    # 6 of 8 groups of 1 row, 16 tokens each
    assert run.obs["tokens_per_step"] == 6 * 1 * 16
    assert run.rates["train_tokens_per_s"] > 0


def test_same_seed_gives_the_same_inputs():
    from benchmarks.chip.train import ZipfFeed

    a = ZipfFeed(1000, 8, 4, 1.2, 2**31 + 3).batch_at(5)
    b = ZipfFeed(1000, 8, 4, 1.2, 2**31 + 3).batch_at(5)
    c = ZipfFeed(1000, 8, 4, 1.2, 2**31 + 3).batch_at(6)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert (a[0] != c[0]).any()
    assert (a[0][:, 1:] == a[1][:, :-1]).all()


def _run(trace_on):
    return CellRun(setup_s=12.5, rates={"train_tokens_per_s": 1.5e5},
                   attempted=40, failed=0,
                   checks=[Check("loss_gap", 1e-4, 1e-3),
                           Check("grad_gap", 2e-3, 5e-3)],
                   memory_peak_bytes=123,
                   obs={"rates": {"train_tokens_per_s": 1.5e5},
                        "config": registry.config("nanogpt-paper"),
                        "peaks": {"bf16_flops": 197e12}, "chips": 1,
                        "trace": {"devices": 1, "busy_s": 0.3},
                        "traced_units": 10, "window_s": 0.4},
                   busy_s=0.3 if trace_on else None,
                   window_s=0.4 if trace_on else None,
                   breakdown={"device_ops": [["fusion", 0.2]],
                              "idle_gaps": [["bench.feed", 0.05]]}
                   if trace_on else None)


def test_result_line_keeps_to_the_contract():
    cell = registry.workload(BENCH, "train.nanogpt.msync")
    info = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = result_line(BENCH, cell, _run(False), info, False)
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["device"]["memory_peak_bytes"] == 123
    json.dumps(line)

    traced = result_line(BENCH, cell, _run(True), info, True)
    assert list(traced)[-1] == "checks"
    assert traced["device"]["busy_s"] == 0.3
    assert traced["breakdown"]["idle_gaps"] == [["bench.feed", 0.05]]
    m = traced["metrics"]
    assert set(m) == {"train.mfu", "train.step_device_ms",
                      "train.device_idle_share"}
    assert m["train.step_device_ms"]["value"] == pytest.approx(30.0)
    assert m["train.device_idle_share"]["value"] == pytest.approx(0.25)
    mfu = 100 * 193.757184e6 * 1.5e5 / 197e12
    assert m["train.mfu"] == {"value": pytest.approx(mfu), "unit": "%"}


def test_a_failed_check_makes_the_run_not_correct():
    run = _run(False)
    run.checks.append(Check("update_gap", float("nan"), 1.0))
    assert not run.correct
    run.checks.pop()
    run.failed = 1
    assert not run.correct


def test_the_harness_keeps_the_program_compile_cache_settings(monkeypatch):
    """The command turns the cache on as the program's entry points do and
    leaves JAX's minimum compile time for caching as the program has it,
    so what the program compiles anew on every call is paid in the
    window."""
    import jax
    import repro.launch.compile_cache as cc

    from benchmarks.chip import run

    before = jax.config.jax_persistent_cache_min_compile_time_secs
    seen = {}

    class Driver:
        @staticmethod
        def run_cell(cell, cfg, mix, ref, seed, seconds, trace_on, t0,
                     clock):
            seen["min_s"] = \
                jax.config.jax_persistent_cache_min_compile_time_secs
            return _run(False)

    monkeypatch.setattr(cc, "use_compile_cache", lambda: "cache")
    monkeypatch.setattr(run.device, "check_device",
                        lambda chips: {"platform": "tpu",
                                       "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "_drivers",
                        lambda: {"sweep": Driver, "train": Driver})
    assert run.main(["--workload", "train.nanogpt.msync", "--seed", "5",
                     "--seconds", "1", "--trace", "0"]) == 0
    assert seen["min_s"] == before
