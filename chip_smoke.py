"""Bring-up smoke run on the TPU: the simulator sweep and the NanoGPT
trainer, through the entry points a user calls.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the paths that span four chips

One process holds the chip for the whole run. The default run checks
the device, then:

1. the m-sync round scan: ``run_experiment`` with ``backend="fastest"``
   over ``m in {1, 10, 100}`` on ``fixed_sqrt`` and ``exponential`` at
   paper scale (n=1000, K=2000, 32 seeds), every grid point routed to
   ``jax`` with no downgrade, compared with the serial event engine,
   plus the Pallas top-m kernel (``use_pallas=True``) bitwise equal to
   the default selection;
2. the arrival scan: ``async`` and ``ringmaster`` on the d=1000
   worst-case quadratic, on ``exponential`` (noisy oracle) and on
   ``fixed_sqrt`` (noiseless oracle), compared with the serial engine;
3. the trainer: ``nanogpt-paper`` at its published widths (block 512,
   batch 16) under m-sync (m=6 of 8 workers, sqrt time law) for 20
   steps through :func:`repro.launch.train.build_run`, plus one forward
   through the compiled flash-attention kernel against the reference
   attention.

``--four-chips`` runs only the four-device paths and what they are
compared with: the ``jax_sharded`` sweep against the one-device ``jax``
run (bitwise per seed), and the m-sync masked step on a 4-device
``("data",)`` mesh against the same step on one device.

Each phase is a plain function that takes its sizes, so the tests run
them at tiny sizes on the CPU; the script itself has no size option and
no CPU mode. Wall and compile seconds per phase are printed as bring-up
observations, not metrics. Any failure raises: the exit code is non-zero
and the ``ok`` line is not printed. The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.core import simulate_batch  # noqa: E402
from repro.core.batch_jax import quadratic_worst_case_jax  # noqa: E402
from repro.core.oracle import quadratic_worst_case  # noqa: E402
from repro.exp import make_scenario, run_experiment  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.train import build_run  # noqa: E402

# paper scale of the simulator phases
N, K, SEEDS, D = 1000, 2000, 32, 1000
M_GRID = (1, 10, 100)
# the trainer phase: published widths, batch sized for one v5e chip
ARCH, BATCH, SEQ, STEPS = "nanogpt-paper", 16, 512, 20
MSYNC_M, WORKERS = 6, 8
FOUR = 4

#: float32 tolerance for a simulated time or objective accumulated over
#: K=2000 steps, against the float64 serial engine
F32_RTOL = 1e-4
#: relative error of a bf16 forward through flash attention against the
#: reference attention (logits, Frobenius norm)
BF16_REL = 2e-2
#: masked data-parallel step on four devices against one device
STEP_RTOL = 1e-3


def check_device(need: int) -> dict:
    """Print the device and the installed versions; exit non-zero
    unless JAX sees at least ``need`` TPU devices."""
    import importlib.metadata

    import jax
    import jaxlib

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (platform "
                         f"{info['platform']!r}); nothing was run")
    if info["count"] < need:
        raise SystemExit(f"chip_smoke: needs {need} TPU devices, JAX sees "
                         f"{info['count']}")
    return info


def require_jax_routing(result) -> None:
    """Every grid point of ``result`` ran on ``jax``, with no downgrade."""
    for g, rec in enumerate(result.meta["routing"]):
        if rec["chosen"] != "jax" or rec.get("downgrades"):
            raise AssertionError(
                f"{result.name} grid point {g} did not run on jax without "
                f"a downgrade: {rec}")
    if result.meta["backend"] != "jax":
        raise AssertionError(f"{result.name} ran on "
                             f"{result.meta['backend']!r}, not jax")


def _close(name: str, got, want, rtol: float) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    rel = float(np.max(np.abs(got - want) / np.abs(want)))
    if not np.all(np.isfinite(got)) or rel > rtol:
        raise AssertionError(f"{name}: max relative error {rel} > {rtol}")
    return rel


def _same_mean(name: str, a, b) -> float:
    """Means of two independent seed samples agree within 4 standard
    errors; returns the gap in standard errors."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    se = math.sqrt(a.var(ddof=1) / a.size + b.var(ddof=1) / b.size)
    gap = abs(a.mean() - b.mean())
    # float32 floor: two seed samples can agree to the last bits
    if not np.all(np.isfinite(a)) or gap > 4.0 * se + F32_RTOL * abs(
            b.mean()):
        raise AssertionError(f"{name}: means {a.mean()} vs {b.mean()} "
                             f"differ by {gap} > 4 SE ({se})")
    return gap / se if se else 0.0


def _final_values(batch, g: int = 0) -> np.ndarray:
    """Last recorded objective of every seed at grid point ``g``."""
    return np.array([tr.values[-1] for tr in batch.traces[g]])


def phase_round_scan(n: int, K: int, seeds: int, m_grid) -> dict:
    """The m-sync timing sweep on the device engine, against the serial
    event engine, and the Pallas top-m kernel against the default
    selection."""
    spec = ("msync", {"m": m_grid[0]})
    grid = {"m": list(m_grid)}
    out = {}
    for scen in ("fixed_sqrt", "exponential"):
        res = run_experiment(spec, scen, n, K, seeds=seeds, grid=grid)
        require_jax_routing(res)
        model = make_scenario(scen, n)
        fixed = scen == "fixed_sqrt"
        # fixed times: every seed runs the same schedule, one serial run
        ref = simulate_batch(spec, model, K, seeds=1 if fixed else seeds,
                             grid=grid, backend="serial")
        for g, m in enumerate(m_grid):
            got, want = res.batch.total_time[g], ref.total_time[g]
            if fixed:
                err = _close(f"{scen} m={m}", got,
                             np.broadcast_to(want, got.shape), F32_RTOL)
                print(f"round scan {scen} m={m}: total time "
                      f"{got.mean()!r} vs serial {want[0]!r} "
                      f"(max rel err {err:.3g})", flush=True)
            else:
                gap = _same_mean(f"{scen} m={m}", got, want)
                print(f"round scan {scen} m={m}: mean total time "
                      f"{got.mean()!r} vs serial {want.mean()!r} "
                      f"({gap:.2f} SE)", flush=True)
        out[scen] = res
    pal = run_experiment(spec, "fixed_sqrt", n, K, seeds=seeds, grid=grid,
                         use_pallas=True)
    require_jax_routing(pal)
    base = out["fixed_sqrt"].batch
    for g, m in enumerate(m_grid):
        a, b = pal.batch.traces[g], base.traces[g]
        if any(x.total_time != y.total_time
               or x.gradients_computed != y.gradients_computed
               for x, y in zip(a, b)):
            raise AssertionError(f"use_pallas=True differs from the "
                                 f"default selection at m={m}")
    print("round scan: use_pallas=True bitwise equal to the default "
          "selection at every grid point", flush=True)
    return out


def phase_arrival_scan(n: int, K: int, seeds: int, d: int) -> dict:
    """Async and Ringmaster on the worst-case quadratic: the chain draw,
    the device ``smallest_k`` merge and the arrival scan, against the
    serial event engine."""
    gamma = 0.5 / n                 # stable under delays up to ~n steps
    out = {}
    for name in ("async", "ringmaster"):
        res = run_experiment(name, "exponential", n, K, seeds=seeds,
                             problem=quadratic_worst_case_jax(d),
                             gamma=gamma, record_every=K)
        require_jax_routing(res)
        ref = simulate_batch(name, make_scenario("exponential", n), K,
                             problem=quadratic_worst_case(d), gamma=gamma,
                             seeds=seeds, record_every=K, backend="serial")
        gap_t = _same_mean(f"{name} exponential total time",
                           res.batch.total_time[0], ref.total_time[0])
        gap_f = _same_mean(f"{name} exponential final objective",
                           _final_values(res.batch), _final_values(ref))
        print(f"arrival scan {name} exponential: mean total time "
              f"{res.batch.total_time[0].mean()!r} ({gap_t:.2f} SE), "
              f"mean final objective {_final_values(res.batch).mean()!r} "
              f"({gap_f:.2f} SE) against serial", flush=True)

        # noiseless oracle (p=1) under fixed times: deterministic runs
        fix = run_experiment(name, "fixed_sqrt", n, K, seeds=seeds,
                             problem=quadratic_worst_case_jax(d, p=1.0),
                             gamma=gamma, record_every=K)
        require_jax_routing(fix)
        ref = simulate_batch(name, make_scenario("fixed_sqrt", n), K,
                             problem=quadratic_worst_case(d, p=1.0),
                             gamma=gamma, seeds=1, record_every=K,
                             backend="serial")
        got, want = _final_values(fix.batch), _final_values(ref)[0]
        err = _close(f"{name} fixed_sqrt final objective", got,
                     np.broadcast_to(want, got.shape), F32_RTOL)
        print(f"arrival scan {name} fixed_sqrt: final objective "
              f"{got.mean()!r} vs serial {want!r} "
              f"(max rel err {err:.3g})", flush=True)
        out[name] = res
    return out


def phase_trainer(arch: str, batch: int, seq: int, steps: int,
                  **build_kw) -> dict:
    """m-sync training through the launcher's run: the loss starts at
    about ln(vocab), stays finite and falls; then one forward through
    the flash-attention kernel against the reference attention."""
    import jax
    import jax.numpy as jnp

    cfg, trainer, data = build_run(
        arch, steps=steps, batch=batch, seq=seq, policy="m_sync",
        m=MSYNC_M, workers=WORKERS, time_model="sqrt", **build_kw)
    print(f"trainer: {cfg.name} params={cfg.param_count()} "
          f"vocab={cfg.vocab_size} block={seq} batch={batch}", flush=True)
    hist = trainer.run(trainer.init_state(), iter(data), num_steps=steps,
                       log_every=1)
    losses = np.asarray(hist.losses)
    print("trainer losses: " + " ".join(repr(float(x)) for x in losses),
          flush=True)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if abs(losses[0] - math.log(cfg.vocab_size)) > 0.5:
        raise AssertionError(f"step-0 loss {losses[0]} is not within 0.5 "
                             f"of ln({cfg.vocab_size})")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses[0]} -> "
                             f"{losses[-1]}")
    if set(hist.m_used) != {MSYNC_M}:
        raise AssertionError(f"m-sync used m={set(hist.m_used)}")

    model, params = trainer.model, trainer.final_state.params
    tokens = jnp.asarray(data.batch(0)["tokens"])
    ref, _ = jax.jit(lambda p, t: model.apply(p, t, impl="ref"))(
        params, tokens)
    pal, _ = jax.jit(lambda p, t: model.apply(p, t, impl="pallas"))(
        params, tokens)
    ref = np.asarray(ref, np.float32)
    pal = np.asarray(pal, np.float32)
    rel = float(np.linalg.norm(pal - ref) / np.linalg.norm(ref))
    print(f"trainer: flash-attention forward vs reference, relative "
          f"error {rel:.3g}", flush=True)
    if not np.all(np.isfinite(pal)) or rel > BF16_REL:
        raise AssertionError(f"pallas forward relative error {rel} > "
                             f"{BF16_REL}")
    return {"losses": losses, "pallas_rel_err": rel}


def _all_reduces(hlo: str) -> int:
    """All-reduce instructions in compiled HLO text (an async pair
    counts once, at its start)."""
    return len(re.findall(r"\ball-reduce(?:-start)?\(", hlo))


def phase_four_chips(n: int, K: int, seeds: int, m_grid, d: int,
                     arch: str, batch: int, seq: int, devices: int,
                     **build_kw) -> dict:
    """The two paths that span devices, each against one device: the
    ``jax_sharded`` sweep (bitwise per seed) and the m-sync masked
    data-parallel step."""
    from repro.launch.mesh import make_mesh_auto
    from repro.sharding.specs import ShardCtx

    sweeps = [
        (("msync", {"m": m_grid[0]}), dict(grid={"m": list(m_grid)})),
        ("async", dict(problem=quadratic_worst_case_jax(d), gamma=0.5 / n,
                       record_every=K)),
    ]
    for spec, kw in sweeps:
        sh = run_experiment(spec, "exponential", n, K, seeds=seeds,
                            backend="jax_sharded", **kw)
        one = run_experiment(spec, "exponential", n, K, seeds=seeds,
                             backend="jax", **kw)
        for g, rec in enumerate(sh.meta["routing"]):
            shard = rec.get("shard", {})
            if (shard.get("devices") != devices or "fallback" in shard
                    or rec.get("downgrades") or shard.get("downgrades")):
                raise AssertionError(f"{sh.name} point {g}: shard record "
                                     f"{rec}")
        for g, (ta, tb) in enumerate(zip(sh.batch.traces, one.batch.traces)):
            for s, (a, b) in enumerate(zip(ta, tb)):
                if (a.total_time != b.total_time
                        or a.gradients_computed != b.gradients_computed
                        or not np.array_equal(a.values, b.values)):
                    raise AssertionError(f"{sh.name} point {g} seed {s}: "
                                         f"sharded != one device")
        print(f"four chips: {sh.name} jax_sharded on {devices} devices "
              f"bitwise equal to jax per seed "
              f"({len(sh.batch.traces)} points x {seeds} seeds; buckets "
              f"{sorted({r['shard']['bucket'] for r in sh.meta['routing']})})",
              flush=True)

    mesh = make_mesh_auto((devices,), ("data",))
    ctx = ShardCtx(mesh=mesh, dp_axes=("data",), model_axis=None)
    metrics, hlo = {}, None
    for label, c in (("mesh", ctx), ("one", None)):
        _, trainer, data = build_run(
            arch, steps=1, batch=batch, seq=seq, policy="m_sync", m=MSYNC_M,
            workers=WORKERS, time_model="sqrt", ctx=c, **build_kw)
        args, _, _ = trainer.step_inputs(trainer.init_state(),
                                         data.batch(0))
        if c is not None:
            hlo = trainer.step_program.lower(*args).compile().as_text()
        _, _, met = trainer.step_program(*args)
        metrics[label] = {k: float(met[k]) for k in ("loss", "grad_sq")}
    n_ar = _all_reduces(hlo)
    print(f"four chips: masked step on a {devices}-device data mesh, "
          f"loss {metrics['mesh']['loss']!r} vs one device "
          f"{metrics['one']['loss']!r}, grad_sq "
          f"{metrics['mesh']['grad_sq']!r} vs {metrics['one']['grad_sq']!r}"
          f"; all-reduces in the compiled step: {n_ar}", flush=True)
    for k in ("loss", "grad_sq"):
        _close(f"masked step {k} on {devices} devices", metrics["mesh"][k],
               metrics["one"][k], STEP_RTOL)
    return {"metrics": metrics, "all_reduces": n_ar}


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache lookups
    included) between resets."""

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def reset(self):
        self.seconds, self.count = 0.0, 0


def _observe(clock: CompileClock, name: str, fn, *args, **kwargs):
    clock.reset()
    t0 = time.perf_counter()
    fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    tag = "bring-up observation, not a metric"
    print(f"[{tag}] {name} wall_seconds={wall!r}", flush=True)
    print(f"[{tag}] {name} compile_seconds={clock.seconds!r} "
          f"compiles={clock.count}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips")
    args = ap.parse_args(argv)

    info = check_device(FOUR if args.four_chips else 1)
    cache = use_compile_cache()
    print(f"compile cache: {cache}", flush=True)
    clock = CompileClock()
    if args.four_chips:
        _observe(clock, "four_chips", phase_four_chips, N, K, SEEDS, M_GRID,
                 D, ARCH, BATCH, SEQ, FOUR)
    else:
        _observe(clock, "round_scan", phase_round_scan, N, K, SEEDS, M_GRID)
        _observe(clock, "arrival_scan", phase_arrival_scan, N, K, SEEDS, D)
        _observe(clock, "trainer", phase_trainer, ARCH, BATCH, SEQ, STEPS)
    entries = sum(1 for p in Path(cache).rglob("*") if p.is_file()) \
        if Path(cache).is_dir() else 0
    print(f"compile cache: {entries} files in {cache}", flush=True)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
