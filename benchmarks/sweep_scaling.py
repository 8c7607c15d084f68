"""Sharded-sweep scaling lane: ``backend="jax_sharded"`` vs the
unsharded ``backend="jax"`` sweep as a function of device count.

The device count is forced with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — a flag jax
reads at first import, so each measurement runs in a fresh WORKER
subprocess (``--worker N``; same pattern as
``tests/test_hlo_analysis.py``) and the module's ``run()`` is the
driver that spawns one worker per N in ``DEVICES``, cross-checks them
and writes ``BENCH_sweep.json``.

Each worker runs a paper-scale m-sync ``m``-sweep (the Figure 8 /
Theorem 2.3 shape: one grid point per ``m``, S seeds each) twice, both
COLD:

* unsharded ``backend="jax"`` — the engine vmaps seeds but serializes
  grid points, and the closure-compiled timing program recompiles per
  ``m`` (``m`` is static there): the sweep pays ``len(M_GRID)``
  compiles;
* ``backend="jax_sharded"`` — the :mod:`repro.launch.sweep` backend
  fuses the whole sweep into ONE shape bucket (``m`` is traced
  row-wise), pays one AOT compile, and ``shard_map``s the
  (point × seed) units across the forced devices.

On the single-core CI host the speedup is therefore mostly compile
amortization plus fusion (forced host "devices" share one core); on a
real multi-device host the same lane additionally measures data
parallelism. Both effects are exactly what the backend exists for, and
the floor asserted here (``>= {MIN_SPEEDUP_D4}x`` at 4 devices) holds
on the weakest case.

Workers also verify per-seed BITWISE parity between the two backends
(the sharded sweep's core contract) and report the simulated
``total_time_mean``; the driver asserts the value is identical across
device counts — sharding must not change a single bit of the
simulation — and writes it as a machine-independent drift detector.

``BENCH_sweep.json`` sections (gated by ``benchmarks/perf_gate.py``
against ``benchmarks/baselines/BENCH_sweep.json``):

* ``speedup_vs_unsharded.dN`` — one-sided floors (higher is better);
  the committed baseline is seeded so the -30% floor at d4 lands on
  the acceptance 2.5x.
* ``total_time_mean.*`` — two-sided simulated outputs (exact,
  machine-independent).
"""

from __future__ import annotations

import json
import os
import sys
import time

BENCH_JSON = os.environ.get("REPRO_BENCH_SWEEP_JSON", "BENCH_sweep.json")

#: forced host device counts, one worker subprocess each
DEVICES = (1, 2, 4)
MIN_SPEEDUP_D4 = 2.5

# paper-scale sweep shape: an m-grid wide enough that the unsharded
# backend's per-point closure compiles dominate (Theorem 2.3 m-sweep)
SCENARIO = "exponential"
N = 400
S = 16
K = 120
M_GRID = (2, 4, 6, 10, 16, 24, 40, 64)

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def _worker(devices: int) -> dict:
    """Measure one forced-device-count point (runs in a subprocess)."""
    import jax
    import numpy as np

    from repro.core import simulate_batch
    from repro.exp import make_scenario

    assert jax.local_device_count() == devices, (
        f"XLA_FLAGS did not take: {jax.local_device_count()} != {devices}")
    model = make_scenario(SCENARIO, N)
    spec = ("msync", {"m": M_GRID[0]})
    grid = {"m": list(M_GRID)}

    t0 = time.perf_counter()
    tb_j = simulate_batch(spec, model, K=K, seeds=S, grid=grid,
                          backend="jax")
    t_unsharded = time.perf_counter() - t0

    t0 = time.perf_counter()
    tb_s = simulate_batch(spec, model, K=K, seeds=S, grid=grid,
                          backend="jax_sharded")
    t_sharded = time.perf_counter() - t0

    bitwise = all(
        a.total_time == b.total_time
        and a.gradients_computed == b.gradients_computed
        and np.array_equal(a.times, b.times)
        for ga, gb in zip(tb_j.traces, tb_s.traces)
        for a, b in zip(ga, gb))

    # warm re-run: the fused program is AOT-cached, so this isolates
    # execute time (reported as context, never gated — machine-bound)
    t0 = time.perf_counter()
    tb_w = simulate_batch(spec, model, K=K, seeds=S, grid=grid,
                          backend="jax_sharded")
    t_sharded_warm = time.perf_counter() - t0
    cold = tb_s.routing[0]["shard"]
    warm = tb_w.routing[0]["shard"]

    return {
        "devices": devices,
        "t_unsharded": t_unsharded,
        "t_sharded": t_sharded,
        "t_sharded_warm": t_sharded_warm,
        "speedup": t_unsharded / t_sharded,
        "bitwise_equal": bool(bitwise),
        "bucket": cold["bucket"],
        "warm_cache_hit": bool(warm["cache_hit"]),
        "compile_s": cold.get("compile_s"),
        "exec_s": cold.get("exec_s"),
        "total_time_mean": float(tb_s.total_time.mean()),
    }


#: per-worker-launch wall-clock ceiling (re-exported for the baseline
#: meta; the shared runner owns the retry policy)
SPAWN_TIMEOUT_S = 600


def _spawn(devices: int) -> dict:
    """Run ``--worker devices`` in a subprocess with the XLA flag set,
    through the shared :func:`benchmarks.subproc.run_json_worker`
    timeout+retry runner (compile-cache warmup makes the second attempt
    much cheaper)."""
    from .subproc import run_json_worker

    env = dict(os.environ)
    # forced host devices by design: the worker must never take the chip
    env["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={devices}"
    env["XLA_FLAGS"] = f"{env.get('XLA_FLAGS', '')} {flag}".strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_SRC, env.get("PYTHONPATH")) if p)
    return run_json_worker(
        [sys.executable, "-m", "benchmarks.sweep_scaling",
         "--worker", str(devices)],
        label=f"sweep_scaling worker d={devices}", env=env,
        timeout_s=SPAWN_TIMEOUT_S,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(fast: bool = True):
    # one fixed config: the baseline's meta must match bit for bit
    del fast
    results = {d: _spawn(d) for d in DEVICES}

    for d, r in results.items():
        assert r["bitwise_equal"], (
            f"sharded sweep at {d} devices is NOT bitwise equal to the "
            f"unsharded jax backend — parity contract broken")
    sims = {r["total_time_mean"] for r in results.values()}
    assert len(sims) == 1, (
        f"simulated total_time_mean differs across device counts: "
        f"{sorted(sims)} — sharding changed the simulation")

    speedups = {f"d{d}": r["speedup"] for d, r in results.items()}
    assert speedups[f"d{max(DEVICES)}"] >= MIN_SPEEDUP_D4, (
        f"sharded sweep only {speedups[f'd{max(DEVICES)}']:.2f}x over the "
        f"unsharded jax backend at {max(DEVICES)} forced devices "
        f"(need >= {MIN_SPEEDUP_D4}x)")

    rows = []
    for d, r in results.items():
        rows.append((
            f"sweep_scaling/n={N}/S={S}/G={len(M_GRID)}/d{d}/unsharded_s",
            r["t_unsharded"], f"{len(M_GRID)} per-point compiles (cold)"))
        rows.append((
            f"sweep_scaling/n={N}/S={S}/G={len(M_GRID)}/d{d}/sharded_s",
            r["t_sharded"],
            f"speedup={r['speedup']:.1f}x cold; bucket={r['bucket']} "
            f"compile={r['compile_s']:.2f}s exec={r['exec_s']:.3f}s"))
        rows.append((
            f"sweep_scaling/d{d}/sharded_warm_s", r["t_sharded_warm"],
            f"AOT cache hit={r['warm_cache_hit']}"))
    rows.append((
        f"sweep_scaling/speedup_d{max(DEVICES)}",
        speedups[f"d{max(DEVICES)}"],
        f"acceptance: >= {MIN_SPEEDUP_D4}x, bitwise-identical traces"))

    from repro.exp.runner import atomic_write_json
    atomic_write_json(BENCH_JSON, {
        "meta": {"scenario": SCENARIO, "n": N, "S": S, "K": K,
                 "m_grid": list(M_GRID), "devices": list(DEVICES)},
        "speedup_vs_unsharded": speedups,
        "total_time_mean": {
            "exponential_msync_sweep": results[DEVICES[0]]
            ["total_time_mean"],
        },
    })
    return rows


def main() -> None:
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        print(json.dumps(_worker(int(sys.argv[2]))))
        return
    for name, val, derived in run():
        print(f"{name},{val},{derived}")


if __name__ == "__main__":
    main()
