"""Driver for ``kind: sweep`` mixes: simulator sweeps back to back.

Each unit of work is one ``repro.exp.run_experiment`` call, as a user
runs it: the configuration's law, ``n``, ``K`` and seeds per sweep, the
mix's strategy, grid and backend, and a JSON artifact written. Every
sweep gets fresh seeds drawn from ``--seed``; warm-up and traced sweeps
draw from streams of their own.

The window runs sweeps until ``--seconds`` have passed and closes when
the last one returns. ``sim_steps_per_s`` is every simulated server step
of the window (``K`` per seed per grid point) over its wall time.

``correct``: a sample of (sweep, seed) pairs drawn from ``--seed`` is run
again through the configuration's plain reference, every grid point of
each, and the per-seed answers are compared: total simulated time,
gradients computed and gradients used.
"""

from __future__ import annotations

import time

import numpy as np

from . import trace as tr
from .cell import (CHECK, TRACED, WARMUP, WINDOW, CellRun, Check,
                   out_dir, rel_gap, rng, say)
from .device import memory_peak_bytes

#: the per-seed answers of a sweep, as the reference names them
ANSWERS = ("total_time", "gradients_computed", "gradients_used")


class Sweeps:
    """The cell's unit of work: one sweep, and the answers it gives."""

    def __init__(self, cell: dict, cfg: dict, mix: dict):
        self.cfg, self.mix = cfg, mix
        self.n, self.K, self.S = cfg["n"], cfg["K"], cfg["seeds_per_sweep"]
        self.spec = (mix["strategy"], dict(mix["params"]))
        grid = mix["grid"]
        self.n_points = len(next(iter(grid.values()))) if grid else 1
        self.kw = dict(grid=grid, record_every=cfg["record_every"],
                       backend=mix["backend"],
                       scenario_kwargs={"lam": cfg["law"]["lam"]},
                       json_path=str(out_dir(cell["name"]) / "sweep.json"))
        self.steps = self.K * self.S * self.n_points

    def run(self, seeds) -> dict:
        from repro.exp import run_experiment

        res = run_experiment(self.spec, self.cfg["law"]["scenario"], self.n,
                             self.K, seeds=list(seeds), **self.kw)
        traces = res.batch.traces

        def per_seed(get):
            return np.array([[get(t) for t in row] for row in traces],
                            np.float64)

        return {
            "seeds": list(seeds),
            "points": [dict(p) for p in res.batch.grid] or [{}],
            "total_time": per_seed(lambda t: t.total_time),
            "gradients_computed": per_seed(lambda t: t.gradients_computed),
            "gradients_used": per_seed(lambda t: t.gradients_used),
            "engines": sorted({r.get("chosen", "?")
                               for r in res.meta["routing"] or []}),
        }

    def draw(self, gen) -> list:
        return [int(s) for s in gen.integers(0, 2**31 - 1, size=self.S)]


def _failed(ans: dict) -> int:
    return int(np.sum(~np.isfinite(ans["total_time"])))


def check(work: Sweeps, answers: list, ref, seed: int, limits: dict):
    """Compare a sample of the answers with the reference."""
    gen = rng(seed, CHECK)
    pairs = [(a, s) for a in range(len(answers)) for s in range(work.S)]
    take = min(work.mix["check_seeds"], len(pairs))
    picked = [pairs[i] for i in gen.choice(len(pairs), take, replace=False)]
    got = {k: [] for k in ANSWERS}
    want = {k: [] for k in ANSWERS}
    for a, s in picked:
        ans = answers[a]
        for g, point in enumerate(ans["points"]):
            params = dict(work.spec[1], **point)
            r = ref.simulate(work.spec[0], params, work.cfg, ans["seeds"][s])
            for k in ANSWERS:
                got[k].append(ans[k][g, s])
                want[k].append(r[k])
    checks = [
        Check("time_gap", rel_gap(got["total_time"], want["total_time"]),
              limits["time_gap"]),
        Check("computed_gap", rel_gap(got["gradients_computed"],
                                      want["gradients_computed"]),
              limits["computed_gap"]),
        Check("used_gap", float(np.max(np.abs(np.subtract(
            got["gradients_used"], want["gradients_used"])))),
            limits["used_gap"]),
    ]
    return checks, picked


def run_cell(cell, cfg, mix, ref, seed, seconds, trace_on, t0, clock):
    import jax

    work = Sweeps(cell, cfg, mix)
    say(f"sweep cell {cell['name']}: {work.spec} grid={mix['grid']} "
        f"n={work.n} K={work.K} S={work.S}")
    warm = rng(seed, WARMUP)
    for _ in range(mix["warmup_sweeps"]):
        ans = work.run(work.draw(warm))
        say(f"warm-up sweep: engines {ans['engines']}")
    setup_s = time.perf_counter() - t0
    say(f"set-up {setup_s:.2f} s")

    gen = rng(seed, WINDOW)
    answers, ends = [], []
    clock.reset()
    start = time.perf_counter()
    while True:
        answers.append(work.run(work.draw(gen)))
        ends.append(time.perf_counter())
        if ends[-1] - start >= seconds:
            break
    elapsed = ends[-1] - start
    compile_window = clock.snapshot()
    rate = work.steps * len(answers) / elapsed
    say(f"window: {len(answers)} sweeps in {elapsed!r} s, each "
        f"{np.round(np.diff([start] + ends), 3).tolist()} s; compile events "
        f"{compile_window}")

    obs = {"units": len(answers), "compile": compile_window,
           "rates": {"sim_steps_per_s": rate}}
    busy_s = window_s = breakdown = None
    if trace_on:
        tgen = rng(seed, TRACED)

        def traced_sweeps():
            for _ in range(mix["trace_sweeps"]):
                with jax.profiler.TraceAnnotation("bench.sweep"):
                    answers.append(work.run(work.draw(tgen)))

        red, window_s = tr.traced_window(
            str(out_dir(cell["name"]) / "trace"), traced_sweeps)
        busy_s = red["busy_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        obs.update(traced_units=mix["trace_sweeps"], trace=red,
                   window_s=window_s)

    peak = memory_peak_bytes(jax.local_devices()[:cell["chips"]])
    checks, picked = check(work, answers, ref, seed, mix["limits"])
    say(f"checked (sweep, seed) pairs {picked} at every grid point")
    attempted = sum(a["total_time"].size for a in answers)
    return CellRun(setup_s=setup_s, rates=obs["rates"], attempted=attempted,
                   failed=sum(_failed(a) for a in answers), checks=checks,
                   memory_peak_bytes=peak, obs=obs, busy_s=busy_s,
                   window_s=window_s, breakdown=breakdown)
