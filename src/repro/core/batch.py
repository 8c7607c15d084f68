"""Batched multi-seed simulation: :func:`simulate_batch` and
:class:`TraceBatch` — the vectorized sibling of :func:`repro.core.simulate`.

The paper's claims are statements about *distributions* of wall-clock time
(Assumptions 2.2/3.1/5.1/5.4), so every figure needs seed sweeps, not
single runs. ``simulate_batch`` runs one strategy under one time model
across ``S`` seeds and an optional parameter grid in a single call and
returns a :class:`TraceBatch` with cross-seed summaries (mean ± std,
time-to-target quantiles).

Backends (``backend=``):

* ``"serial"`` — per-(grid-point, seed) :func:`simulate` calls. Works for
  every strategy/model/problem combination and is trace-for-trace
  identical to scalar runs by construction.
* ``"vectorized"`` — the seed-batched round-vectorized m-sync timing
  engine (:func:`repro.core.strategies._fast_msync_timing_batch`): one
  ``(seeds, rounds, workers)`` array program. Timing-only m-sync family,
  including universal models (deterministic — computed once and
  replicated across seeds). ``rng_scheme`` picks the draw contract for
  random models: ``"counter"`` (default) draws the whole time tensor
  from per-seed Philox counter streams in bulk (fast, distribution-equal
  to scalar runs), ``"stream"`` consumes each seed's
  ``default_rng(seed)`` stream in the scalar path's exact order (exact
  per-seed parity with the scalar fast path).
* ``"jax"`` — :mod:`repro.core.batch_jax`: jitted ``lax.scan`` programs
  over ``(seeds, workers)`` state (optionally using the Pallas top-m
  partial-sort kernel for the per-round m-th order statistic). Covers
  the m-sync family, Rennala and Malenia (renewal-batched rounds) and
  Async/Ringmaster (renewal-chain arrival scan) under every model
  class — FixedTimes, sampled (``jax_sampler``) and universal
  (``finish_times_jax``) — the full DESIGN.md §3b coverage matrix.
  Distribution-equal, not RNG-stream-equal; matches NumPy within float
  tolerance for deterministic models/oracles in generic position
  (adversarially tie-heavy instances, e.g. partial participation, can
  diverge by whole events under the worker-index tie-break).
* ``"jax_sharded"`` — :mod:`repro.launch.sweep`: the jax engines, but
  every (grid point × seed) pair becomes one work unit, units are
  packed into shape buckets (same compiled program — m-sync buckets
  even fuse heterogeneous ``m``/``gamma`` as traced per-unit inputs)
  and each bucket is ``shard_map``ped over a 1-D ``data`` mesh of the
  local devices. Per-seed results are bitwise identical to
  ``backend="jax"`` (the per-seed key streams are sweep-independent);
  the per-point routing records carry the bucket, compile-vs-execute
  wall times and program-cache hits. Every jax engine family shards
  (``repro.launch.sweep.SHARDED_KINDS``); a failing bucket raises.
* ``"auto"`` (default) — ``vectorized`` when eligible, else ``serial``.
* ``"fastest"`` — like ``auto`` but routes each grid point through a
  **per-engine cost model** (:func:`estimate_backend_seconds`): the
  estimated wall-clock of the host engine and of the jax engine that
  would run this (round scan, arrival scan, or serial event loop — as a
  function of S, K, n, the strategy's batching parameters, math vs
  timing-only, and whether an accelerator is attached) are compared and
  the cheaper one runs. A :class:`~repro.core.batch_jax.JaxProblem`
  bypasses the comparison — only jax can execute it. One deterministic
  exception: timing-only m-sync under a universal model replicates ONE
  scalar run across seeds on the ``vectorized`` backend, so there is
  nothing for a device sweep to win and ``fastest`` keeps it there.
  The backend that actually ran AND the routing decision (estimates,
  accelerator flag, reason) are recorded per grid point in the
  :class:`TraceBatch`. This is what :func:`repro.exp.run_experiment`
  uses.

Under ``backend="fastest"`` (and ``"auto"``) engine *execution*
failures do not abort a sweep: every grid point runs under a degradation
ladder (``jax_sharded`` → ``jax`` → ``vectorized`` → ``serial``,
retry-once per rung, skipping rungs that cannot run the point) and each
downgrade is recorded in the point's ``TraceBatch.routing`` entry
(``downgrades``: engine, exception class, reason, fallback target) —
only the last rung's failure propagates. A forced backend never
downgrades: its engine failure raises. Contract errors on a forced
backend (unsupported strategy/model, ``tol_grad_sq`` on jax) raise up
front. See DESIGN.md §3c.

Grid semantics: ``grid`` maps parameter names to value sequences and the
cartesian product is swept. Keys in :data:`SIM_GRID_KEYS` override the
corresponding :func:`simulate` argument; every other key is passed to the
strategy factory (so ``{"m": [1, 4, 16]}`` sweeps ``MSync(m=...)``).
"""

from __future__ import annotations

import dataclasses
import itertools
import os
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Union

import numpy as np

from .. import telemetry
from .strategies import (AggregationStrategy, MSync, STRATEGIES, Trace,
                         _fast_msync_timing_batch, make_strategy, simulate)
from .time_models import FixedTimes, TimeModel, UniversalModel, philox_rngs

__all__ = ["TraceBatch", "simulate_batch", "SIM_GRID_KEYS", "JAX_MIN_WORK",
           "estimate_backend_seconds", "load_cost_constants"]

# grid keys routed to simulate() itself; everything else goes to the
# strategy factory
SIM_GRID_KEYS = ("K", "gamma", "record_every", "tol_grad_sq")

#: DEPRECATED — the PR 3/4 flat ``seeds * K * n`` jax gate. Routing now
#: goes through the per-engine cost model (:func:`estimate_backend_seconds`);
#: this name stays importable for downstream callers and survives inside
#: the router as the *probe floor*: sweeps whose element work is below it
#: go straight to the host engines with no support probe or cost
#: estimate — at that scale jit compilation dominates any jax engine,
#: so there is nothing to price.
JAX_MIN_WORK = 1_000_000

# ---------------------------------------------------------------------------
# the per-engine cost model behind backend="fastest"
# ---------------------------------------------------------------------------

#: Hard-coded fallback cost-model constants, calibrated on this
#: container's CPU via ``benchmarks/simbatch_speed.py`` shapes (n=1000,
#: S=32). They only need to get the ORDERING right near the routing
#: boundaries, not absolute wall-clock.
_DEFAULT_COST_CONSTANTS = {
    "heap_event": 2.6e-6,    # serial event-loop seconds per heap pop
    "np_elem": 1.1e-7,       # serial m-sync fast path, per S*K*n element
    "vec_elem": 2.0e-8,      # vectorized counter engine, per element
    "jax_elem": 1.6e-8,      # jitted m-sync round scan, per element (warm)
    "round_elem": 1.6e-8,    # renewal round scans (rennala/malenia/
                             # ringleader), per pool element (warm)
    "pool_elem": 4.0e-8,     # arrival-scan chain draw + merge, per pool elem
    "scan_step": 3.2e-6,     # arrival-scan step at S=32 (scales ~S/32)
    "jit_compile": 0.6,      # closure-compiled program, per call
    "accel_speedup": 20.0,   # discount on jax COMPUTE (not compile) terms
}

#: The ACTIVE cost-model constants the router reads. Self-calibrating:
#: ``benchmarks/simbatch_speed.py --calibrate`` measures this machine's
#: engines and persists a JSON that :func:`load_cost_constants` merges
#: over the defaults (set ``REPRO_COST_CONSTANTS=/path.json`` to load at
#: import, or call the loader explicitly). Mutated in place so every
#: importer sees the calibrated values.
COST_CONSTANTS = dict(_DEFAULT_COST_CONSTANTS)


def load_cost_constants(path: Optional[str] = None,
                        apply: bool = True) -> Dict[str, float]:
    """Merge measured per-machine cost constants over the hard-coded
    defaults and (by default) install them as the active
    :data:`COST_CONSTANTS`.

    ``path`` defaults to the ``REPRO_COST_CONSTANTS`` environment
    variable. The JSON may be flat or ``{"constants": {...}}`` (the
    ``--calibrate`` artifact shape); unknown keys are ignored and an
    unreadable/invalid file (including valid JSON whose top level is
    not an object) falls back to the defaults with a ``UserWarning``
    naming the file and the error, emitted ONCE per path per process —
    routing must never *fail* because a calibration file went stale,
    but it must not silently ignore one either, and a sweep that calls
    the router thousands of times must not drown the log in repeats.
    """
    import json
    import os
    import warnings

    merged = dict(_DEFAULT_COST_CONSTANTS)
    if path is None:
        path = os.environ.get("REPRO_COST_CONSTANTS", "")
    if path:
        try:
            with open(path) as fh:
                data = json.load(fh)
            consts = data.get("constants", data) \
                if isinstance(data, dict) else data
            if not isinstance(consts, dict):
                raise ValueError(
                    f"cost-constants JSON must be an object (or "
                    f"{{'constants': {{...}}}}), got "
                    f"{type(consts).__name__}")
            merged.update({k: float(v) for k, v in consts.items()
                           if k in merged and float(v) > 0.0})
        except (OSError, ValueError, TypeError) as exc:
            # stale/bad calibration: defaults win, but say so once
            if path not in _COST_WARNED_PATHS:
                _COST_WARNED_PATHS.add(path)
                warnings.warn(
                    f"REPRO_COST_CONSTANTS file {path!r} could not be used "
                    f"({type(exc).__name__}: {exc}); falling back to the "
                    f"default cost constants", UserWarning, stacklevel=2)
    if apply:
        COST_CONSTANTS.clear()
        COST_CONSTANTS.update(merged)
    return merged


#: paths already warned about by :func:`load_cost_constants` (one
#: warning per bad file per process, however often the router reloads)
_COST_WARNED_PATHS: set = set()


if os.environ.get("REPRO_COST_CONSTANTS"):
    load_cost_constants()


def _accelerator_present() -> bool:
    """True when jax reports a non-CPU default backend. Cached; only
    called once the probe floor passed, so the jax import it forces is
    already amortized by the sweep."""
    global _ACCEL_PRESENT
    if _ACCEL_PRESENT is None:
        import jax
        _ACCEL_PRESENT = jax.default_backend() != "cpu"
    return _ACCEL_PRESENT


_ACCEL_PRESENT = None


def _device_count() -> int:
    """Local jax device count (the sharded sweep's mesh size). Cached
    like :func:`_accelerator_present` — only consulted once a sweep is
    big enough that the jax import is already amortized."""
    global _DEVICE_COUNT
    if _DEVICE_COUNT is None:
        import jax
        _DEVICE_COUNT = jax.local_device_count()
    return _DEVICE_COUNT


_DEVICE_COUNT = None


def estimate_backend_seconds(backend: str, strategy: "AggregationStrategy",
                             model, S: int, K: int, n: int,
                             accelerator: bool = False,
                             devices: Optional[int] = None) -> float:
    """Estimated wall-clock seconds for one timing-only grid point.

    ``backend`` is ``"serial"``, ``"vectorized"`` or ``"jax"``;
    ``strategy`` must be bound. The estimate is engine-aware:

    * serial — the event loop pays :data:`COST_CONSTANTS` ``heap_event``
      per pop (K pops for Async, ``~K * (1 + sqrt(n/(max_delay+1)))``
      for Ringmaster's discard storms, ``K * batch`` for Rennala,
      ``>= K * n`` for Malenia), except timing-only m-sync, which runs
      the round-vectorized fast path at ``np_elem`` per S*K*n element.
    * vectorized — ``vec_elem`` per element (m-sync timing only).
    * jax round scans (m-sync / Rennala / Malenia / Ringleader) —
      ``jax_elem`` per scanned element plus one ``jit_compile`` for the
      closure-compiled programs (the FixedTimes timing m-sync program
      is module-cached: no compile term). Ringleader prices its single
      global chain tensor plus the round scan at ``2 * work``.
    * jax arrival scan (Async / Ringmaster / OptimalASGD) — ``pool_elem`` per
      renewal-chain pool element (the same pool the engine would draw,
      via :func:`repro.core.batch_jax.arrival_scan_work`) plus
      ``scan_step`` per window arrival when a scan is needed
      (Ringmaster; timing-only Async is sort-and-slice). These programs
      are jit-cached by shape, so no per-call compile term.

    ``accelerator=True`` divides the jax COMPUTE terms by
    ``accel_speedup`` (compile is host-bound and stays). Host engines
    never get the discount — they run on the CPU regardless.

    ``backend="jax_sharded"`` prices the sharded sweep of THIS point's
    units (its S seeds) on ``devices`` devices (default: the local
    device count): jax compute terms divide by ``min(devices, S)``,
    compile does not — it is host-bound and paid once per shape bucket,
    and the per-point estimate conservatively charges it in full (the
    sweep layer's cross-point fusion can only make reality cheaper).
    """
    C = COST_CONSTANTS
    kind = _engine_kind(strategy)
    if kind is None:
        raise ValueError(
            f"no cost model for {getattr(strategy, 'name', strategy)!r}: "
            f"only strategies with a jax engine classification are "
            f"priced (event-loop-only strategies never route)")
    work = float(S) * float(K) * float(n)
    if backend == "vectorized":
        return work * C["vec_elem"]
    if backend == "serial":
        if kind == "msync":
            return work * C["np_elem"]
        if kind == "async":
            events = float(K)
        elif kind in ("ringmaster", "optimal_asgd"):
            md = int(getattr(strategy, "max_delay", 1))
            events = K * (1.0 + float(np.sqrt(n / (md + 1.0))))
        elif kind == "rennala":
            events = float(K) * max(int(getattr(strategy, "batch", 1)), 1)
        else:           # malenia/ringleader: every worker >= 1 per round
            events = float(K) * n
        return S * events * C["heap_event"]
    if backend not in ("jax", "jax_sharded"):
        raise ValueError(f"no cost model for backend {backend!r}")
    shard = 1.0
    if backend == "jax_sharded":
        from ..launch.sweep import SHARDED_KINDS
        if kind in SHARDED_KINDS:
            D = _device_count() if devices is None else int(devices)
            shard = float(max(min(D, S), 1))
    accel = C["accel_speedup"] if accelerator else 1.0
    if kind in ("async", "ringmaster", "optimal_asgd"):
        from .batch_jax import arrival_scan_work
        ring = kind in ("ringmaster", "optimal_asgd")
        md = int(getattr(strategy, "max_delay", 0)) if ring else 0
        pool, window = arrival_scan_work(model, n, K, ringmaster=ring,
                                         max_delay=md)
        cost = S * pool * C["pool_elem"]
        if ring:
            cost += window * C["scan_step"] * (S / 32.0)
        return cost / accel / shard  # jit-cached: no compile term
    if kind == "rennala":
        elems = work * max(int(getattr(strategy, "batch", 1)), 1)
    elif kind == "malenia":
        elems = work * 2.0 * max(float(getattr(strategy, "S", 1.0)), 1.0)
    elif kind == "ringleader":      # one global chain, round scan over it
        elems = work * 2.0
    else:
        elems = work
    elem_c = C["jax_elem"] if kind == "msync" else C["round_elem"]
    cost = elems * elem_c / accel / shard
    fixed_timing_cached = kind == "msync" and isinstance(model, FixedTimes)
    if backend == "jax_sharded" or not fixed_timing_cached:
        cost += C["jit_compile"]    # closure-/AOT-compiled per call
    return cost


def _engine_kind(strategy) -> Optional[str]:
    """Which jax engine family would run ``strategy`` (None: event-loop
    only). Pure classification — no jax import."""
    from .batch_jax import _classify
    return _classify(strategy)

StrategySpec = Union[str, AggregationStrategy,
                     "tuple[str, Dict[str, Any]]", Callable[..., Any]]


@dataclasses.dataclass
class TraceBatch:
    """Traces of ``G`` grid points × ``S`` seeds plus cross-seed reducers.

    ``traces[g][s]`` is the full per-run :class:`Trace` (timing-only
    backends leave the recorded arrays empty, exactly like the scalar fast
    path). Scalar per-run fields are exposed as ``(G, S)`` arrays through
    :meth:`stat`, and :meth:`summary` produces the mean ± std rows the
    benchmark layer reports.
    """

    strategy: str                      # display name of the swept strategy
    grid: List[Dict[str, Any]]         # one kwargs dict per grid point
    seeds: np.ndarray                  # (S,) seeds, in run order
    traces: List[List[Trace]]          # [G][S]
    backend: str                       # backend that actually ran
    rng_scheme: str = "counter"        # EFFECTIVE draw contract of the
    #                                    run: the requested scheme for
    #                                    the vectorized engine, "stream"
    #                                    for serial (per-seed parity by
    #                                    construction), "jax.random" for
    #                                    the jax backend
    routing: Optional[List[Dict[str, Any]]] = None
    #                                    one record per grid point: the
    #                                    chosen backend plus, for
    #                                    backend="fastest", the cost-model
    #                                    estimates/reason (see
    #                                    _route_fastest); explicit backends
    #                                    record {"chosen": ..., "forced":
    #                                    True}. Surfaced in run_experiment
    #                                    JSON meta.

    # ------------------------------------------------------------ arrays
    def stat(self, field: str) -> np.ndarray:
        """``(G, S)`` array of a scalar Trace field/property."""
        return np.array([[getattr(tr, field) for tr in row]
                         for row in self.traces], dtype=float)

    @property
    def total_time(self) -> np.ndarray:
        return self.stat("total_time")

    def time_to_target(self, frac: float = 0.25) -> np.ndarray:
        """``(G, S)`` wall-clock time at which ``||∇f||²`` first drops to
        ``frac`` × its initial recorded value (``inf`` if never; ``nan``
        for timing-only traces)."""
        out = np.full((len(self.traces), len(self.seeds)), np.nan)
        for g, row in enumerate(self.traces):
            for s, tr in enumerate(row):
                if len(tr.grad_norms) == 0:
                    continue
                tgt = frac * tr.grad_norms[0]
                hit = np.flatnonzero(tr.grad_norms <= tgt)
                out[g, s] = tr.times[hit[0]] if hit.size else np.inf
        return out

    # ----------------------------------------------------------- summary
    def summary(self, target_frac: Optional[float] = None,
                quantiles: Sequence[float] = (0.1, 0.5, 0.9)) -> List[dict]:
        """One dict per grid point: mean ± std across seeds of total time,
        seconds per useful gradient and discard fraction, plus
        time-to-target quantiles when ``target_frac`` is given."""
        tt = self.total_time
        used = np.maximum(self.stat("gradients_used"), 1.0)
        per_grad = tt / used
        disc = self.stat("discard_fraction")
        rows = []
        for g, params in enumerate(self.grid):
            row = {
                "strategy": self.strategy,
                "params": dict(params),
                "seeds": len(self.seeds),
                "backend": self.backend,
                "rng_scheme": self.rng_scheme,
                "total_time_mean": float(tt[g].mean()),
                "total_time_std": float(tt[g].std()),
                "s_per_useful_grad_mean": float(per_grad[g].mean()),
                "s_per_useful_grad_std": float(per_grad[g].std()),
                "discard_fraction_mean": float(disc[g].mean()),
                "iterations_mean": float(self.stat("iterations")[g].mean()),
            }
            if target_frac is not None:
                t2t = self.time_to_target(target_frac)[g]
                finite = t2t[np.isfinite(t2t)]
                row["time_to_target_frac"] = target_frac
                row["time_to_target_hit_rate"] = (
                    float(np.mean(np.isfinite(t2t))) if len(t2t) else 0.0)
                for q in quantiles:
                    row[f"time_to_target_q{int(round(q * 100))}"] = (
                        float(np.quantile(finite, q)) if finite.size
                        else float("inf"))
            rows.append(row)
        return rows


# ---------------------------------------------------------------------------
# strategy specs and grids
# ---------------------------------------------------------------------------

def _as_spec(strategy: StrategySpec):
    """Normalize to ``(display_name, factory(**kw), base_kwargs)``."""
    if isinstance(strategy, str):
        if strategy not in STRATEGIES:
            make_strategy(strategy)    # raises KeyError with known names
        return strategy, STRATEGIES[strategy], {}
    if isinstance(strategy, tuple):
        name, kw = strategy
        make_strategy(name, **kw)      # validate early, with a clear error
        return name, STRATEGIES[name], dict(kw)
    if isinstance(strategy, AggregationStrategy):
        inst = strategy

        def factory(**kw):
            if kw:
                raise ValueError(
                    "grid sweeps over strategy parameters need a re-"
                    "instantiable spec — pass a name or (name, kwargs), "
                    f"not the instance {inst.name!r}")
            return inst
        return inst.name, factory, {}
    if callable(strategy):
        return getattr(strategy, "name", getattr(strategy, "__name__",
                                                 "strategy")), strategy, {}
    raise TypeError(f"bad strategy spec: {strategy!r}")


def _grid_points(grid: Optional[Mapping[str, Sequence]]) -> List[Dict]:
    if not grid:
        return [{}]
    keys = list(grid)
    return [dict(zip(keys, combo))
            for combo in itertools.product(*(grid[k] for k in keys))]


def _vectorized_eligible(strategy: AggregationStrategy, model,
                         problem, K: int, tol_grad_sq) -> bool:
    """Mirror of the scalar fast-path guard in :func:`simulate`."""
    return (problem is None and tol_grad_sq is None
            and not strategy.uses_alarm
            and isinstance(strategy, MSync)
            and type(strategy).on_arrival is MSync.on_arrival
            and type(strategy).on_step is AggregationStrategy.on_step
            and K > 0)


def _is_jax_problem(problem) -> bool:
    if problem is None:
        return False
    from .batch_jax import JaxProblem        # deferred import
    return isinstance(problem, JaxProblem)


def _route_fastest(strat: AggregationStrategy, model, problem, K_pt: int,
                   S: int, rng_scheme: str, tol_pt) -> "tuple[str, Dict]":
    """The ``backend="fastest"`` router: pick the cheapest *eligible*
    engine for one grid point and say why.

    Hard rules first (executability and contracts beat estimates):
    a :class:`~repro.core.batch_jax.JaxProblem` runs on jax or raises;
    deterministic universal m-sync timing replicates one scalar run on
    ``vectorized`` (nothing for a device sweep to win); an explicit
    ``rng_scheme="stream"`` request on a sampled model is a parity
    contract jax cannot honor; ``tol_grad_sq`` early exit needs the
    event loop. Sweeps below the :data:`JAX_MIN_WORK` probe floor stay
    on the host engines with no probe or estimate (jit compilation
    dominates any jax engine there). Everything else is decided by
    comparing :func:`estimate_backend_seconds` for the host engine vs
    the jax engine, with the accelerator probe folded in.

    Returns ``(chosen, info)`` where ``info`` is the routing record
    stored per grid point in :class:`TraceBatch.routing`.
    """
    n = model.n
    kind = _engine_kind(strat)
    vec_ok = _vectorized_eligible(strat, model, problem, K_pt, tol_pt)
    host = "vectorized" if vec_ok else "serial"
    info: Dict[str, Any] = {"engine": kind or "event-loop",
                            "work": int(S) * int(K_pt) * int(n)}

    def pick(chosen, reason):
        info.update(chosen=chosen, reason=reason)
        return chosen, info

    if _is_jax_problem(problem):
        from .batch_jax import _check_supported, jax_supported
        if tol_pt is None and K_pt > 0 and jax_supported(strat, model,
                                                         problem):
            devices = _device_count()
            from ..launch.sweep import SHARDED_KINDS
            if (devices > 1 and kind in SHARDED_KINDS
                    and info["work"] / devices >= JAX_MIN_WORK):
                accel = _accelerator_present()
                est = {"jax": estimate_backend_seconds(
                           "jax", strat, model, S, K_pt, n,
                           accelerator=accel),
                       "jax_sharded": estimate_backend_seconds(
                           "jax_sharded", strat, model, S, K_pt, n,
                           accelerator=accel, devices=devices)}
                info["est_seconds"] = {k: round(v, 6)
                                       for k, v in est.items()}
                info["devices"] = devices
                info["accelerator"] = accel
                return pick(min(est, key=est.get),
                            "jax-problem: only a jax engine can run it")
            return pick("jax", "jax-problem: only jax can execute it")
        # raise the precise unsupported-combination error instead of
        # letting the serial engine crash inside the jax oracle
        _check_supported(strat, model, problem)
        raise NotImplementedError(
            "JaxProblem sweeps run on the jax backend only, which does "
            "not support tol_grad_sq early exit or K <= 0; use a NumPy "
            "Problem with backend='serial'")
    if isinstance(model, UniversalModel) and vec_ok:
        # deterministic universal m-sync timing replicates ONE scalar
        # run across seeds — no sweep for a device engine to win
        return pick("vectorized", "deterministic-replication")
    if tol_pt is not None or K_pt <= 0:
        return pick(host, "tol-early-exit needs the event loop")
    if kind is None:
        return pick(host, "no jax engine for this strategy")
    if (rng_scheme == "stream"
            and not isinstance(model, (FixedTimes, UniversalModel))):
        return pick(host, "stream-parity contract excludes jax")
    if info["work"] < JAX_MIN_WORK:
        return pick(host, "below the JAX_MIN_WORK probe floor")
    from .batch_jax import jax_supported
    if not jax_supported(strat, model, problem):
        return pick(host, "model/oracle unsupported by the jax engines")
    accel = _accelerator_present()
    est = {host: estimate_backend_seconds(host, strat, model, S, K_pt, n),
           "jax": estimate_backend_seconds("jax", strat, model, S, K_pt, n,
                                           accelerator=accel)}
    devices = _device_count()
    from ..launch.sweep import SHARDED_KINDS
    if (devices > 1 and kind in SHARDED_KINDS
            and info["work"] / devices >= JAX_MIN_WORK):
        # sharded sweep: only with real devices to spread over AND
        # enough per-device work to clear the same probe floor
        est["jax_sharded"] = estimate_backend_seconds(
            "jax_sharded", strat, model, S, K_pt, n, accelerator=accel,
            devices=devices)
        info["devices"] = devices
    info["est_seconds"] = {k: round(v, 6) for k, v in est.items()}
    info["accelerator"] = accel
    chosen = min(est, key=est.get)
    return pick(chosen, "cost-model")


def _jax_eligible(strategy: AggregationStrategy, model, problem,
                  tol_grad_sq, K: int, S: int) -> bool:
    """DEPRECATED shim (PR 3/4 signature): True when ``fastest`` would
    route this combination to jax. Routing decisions now come from
    :func:`_route_fastest` / :func:`estimate_backend_seconds`."""
    try:
        chosen, _ = _route_fastest(strategy, model, problem, K, S,
                                   "counter", tol_grad_sq)
    except NotImplementedError:
        return False
    return chosen == "jax"


# ---------------------------------------------------------------------------
# the degradation ladder: engine execution failures downgrade, not raise
# ---------------------------------------------------------------------------

#: Downgrade order for engine *execution* failures under
#: ``backend="fastest"`` and ``"auto"`` (contract errors — unsupported
#: strategy/model combos — raise at validation time, before any engine
#: runs). A failing engine is retried once, then the point falls to the
#: next rung that can run it; every hop is recorded in the point's
#: routing entry (``routing[g]["downgrades"]``). Only when the last rung
#: fails does the exception propagate. A FORCED backend never
#: downgrades: its engine failure raises, so a run that asked for the
#: device engine never quietly lands on a host engine.
ENGINE_LADDER = ("jax_sharded", "jax", "vectorized", "serial")


def _ladder_below(chosen: str, strat, model, problem, K_pt: int,
                  tol_pt) -> List[str]:
    """Engines below ``chosen`` on the ladder able to run this point."""
    if chosen not in ENGINE_LADDER:
        return []
    out = []
    for eng in ENGINE_LADDER[ENGINE_LADDER.index(chosen) + 1:]:
        if eng == "jax":
            from .batch_jax import jax_supported
            if tol_pt is not None \
                    or not jax_supported(strat, model, problem):
                continue
        elif eng == "vectorized":
            if not _vectorized_eligible(strat, model, problem, K_pt,
                                        tol_pt):
                continue
        out.append(eng)
    return out


def _run_point_laddered(chosen: str, run_engine: Callable[[str], Any],
                        downgrade_to: Sequence[str],
                        route_info: Dict[str, Any]):
    """Run one grid point with retry-once-then-downgrade semantics.

    Returns ``(engine_that_ran, row)``. ``run_engine`` must be
    stateless per call (every engine rebuilds its RNG state from the
    seed list), so a retry reproduces the attempt exactly.
    """
    rungs = [chosen] + [e for e in downgrade_to if e != chosen]
    for pos, engine in enumerate(rungs):
        try:
            return engine, run_engine(engine)
        except Exception:
            try:
                return engine, run_engine(engine)      # retry once
            except Exception as exc:
                nxt = rungs[pos + 1] if pos + 1 < len(rungs) else None
                route_info.setdefault("downgrades", []).append({
                    "from": engine, "to": nxt,
                    "error": type(exc).__name__,
                    "reason": str(exc)[:300], "retried": True})
                if nxt is None:
                    raise
    raise AssertionError("unreachable")    # pragma: no cover


# ---------------------------------------------------------------------------
# the batched driver
# ---------------------------------------------------------------------------

def simulate_batch(strategy: StrategySpec,
                   model: Union[TimeModel, UniversalModel],
                   K: int,
                   problem=None,
                   gamma: float = 0.0,
                   seeds: Union[int, Sequence[int]] = 8,
                   grid: Optional[Mapping[str, Sequence]] = None,
                   record_every: int = 1,
                   tol_grad_sq: Optional[float] = None,
                   backend: str = "auto",
                   rng_scheme: str = "counter",
                   use_pallas: bool = False,
                   x64: bool = False) -> TraceBatch:
    """Run ``strategy`` under ``model`` across ``seeds`` × ``grid``.

    ``seeds`` is an int (→ ``range(seeds)``) or an explicit sequence.
    With ``seeds=[s]``, the default backends and ``rng_scheme="stream"``
    the result reproduces scalar ``simulate(..., seed=s)``
    trace-for-trace; the default ``rng_scheme="counter"`` draws random
    models from per-seed Philox counter streams instead — equal in
    distribution, much faster for sweeps, and independent of which other
    seeds are in the sweep. ``rng_scheme`` only affects the
    ``vectorized`` backend (``serial`` always consumes the scalar
    streams; ``jax`` always draws with ``jax.random`` — per-seed
    reproducible and sweep-independent like ``counter``, stream-equal
    to nothing). ``backend="jax"`` covers every registered paper
    strategy (m-sync family, rennala, malenia, async, ringmaster) under
    every time-model class, timing-only or with a
    :class:`~repro.core.batch_jax.JaxProblem`; ``deadline``/``dropout``
    and NumPy oracles stay on the host engines. ``x64=True`` runs the
    jax backend in float64 — slower, but gives per-run tie parity with
    the float64 NumPy event heap on adversarially tie-heavy instances
    (flat-power partial participation) where float32 tie-breaking
    diverges by whole events; the NumPy engines are always float64, so
    the flag only affects grid points that run on jax. See the module
    docstring for backend and grid semantics.
    """
    seed_list = list(range(seeds)) if isinstance(seeds, (int, np.integer)) \
        else [int(s) for s in seeds]
    if not seed_list:
        raise ValueError("need at least one seed")
    if backend not in ("auto", "fastest", "serial", "vectorized", "jax",
                       "jax_sharded"):
        raise ValueError(f"unknown backend {backend!r}")
    if rng_scheme not in ("counter", "stream"):
        raise ValueError(f"unknown rng_scheme {rng_scheme!r}; "
                         "use 'counter' or 'stream'")
    forced = backend not in ("auto", "fastest")
    name, factory, base_kw = _as_spec(strategy)
    points = _grid_points(grid)

    traces: List[List[Trace]] = []
    used_backends = []
    used_schemes = []
    used_routing: List[Dict[str, Any]] = []
    sharded_points = []        # (grid index, SweepPoint) → one fused sweep
    for pt in points:
        with telemetry.span("repro.batch.point") as point:
            sim_kw = {k: pt[k] for k in pt if k in SIM_GRID_KEYS}
            strat_kw = {**base_kw, **{k: v for k, v in pt.items()
                                      if k not in SIM_GRID_KEYS}}
            K_pt = int(sim_kw.pop("K", K))
            gamma_pt = float(sim_kw.pop("gamma", gamma))
            re_pt = int(sim_kw.pop("record_every", record_every))
            tol_pt = sim_kw.pop("tol_grad_sq", tol_grad_sq)

            strat = factory(**strat_kw)
            if isinstance(strat, str):     # factory returned a registry name
                strat = make_strategy(strat)
            strat.bind(model.n)

            if backend == "auto":
                chosen = "vectorized" if _vectorized_eligible(
                    strat, model, problem, K_pt, tol_pt) else "serial"
                route_info = {"chosen": chosen, "forced": False,
                              "reason": "auto: vectorized when eligible",
                              "engine": _engine_kind(strat) or "event-loop"}
            elif backend == "fastest":
                chosen, route_info = _route_fastest(strat, model, problem,
                                                    K_pt, len(seed_list),
                                                    rng_scheme, tol_pt)
            else:
                chosen = backend
                route_info = {"chosen": chosen, "forced": True,
                              "engine": _engine_kind(strat) or "event-loop"}
            # contract errors on a forced/chosen backend raise up front, so
            # the ladder below only ever sees *execution* failures
            if chosen == "vectorized" and not _vectorized_eligible(
                    strat, model, problem, K_pt, tol_pt):
                raise ValueError(
                    "vectorized backend needs timing-only m-sync arrival "
                    "semantics")
            if chosen in ("jax", "jax_sharded"):
                if tol_pt is not None:
                    raise NotImplementedError(
                        "tol_grad_sq early exit is not supported by the jax "
                        "backends (fixed-length scan); use backend='serial'")
                from .batch_jax import _check_supported
                _check_supported(strat, model, problem)

            def run_engine(engine, strat=strat, strat_kw=dict(strat_kw),
                           K_pt=K_pt, gamma_pt=gamma_pt, re_pt=re_pt,
                           tol_pt=tol_pt):
                if engine == "vectorized":
                    if rng_scheme == "counter" \
                            and not isinstance(model, UniversalModel):
                        rngs = philox_rngs(seed_list)
                    else:
                        rngs = [np.random.default_rng(s) for s in seed_list]
                    return _fast_msync_timing_batch(strat._m, model, K_pt,
                                                    rngs,
                                                    rng_scheme=rng_scheme)
                if engine == "jax":
                    from .batch_jax import simulate_batch_jax
                    return simulate_batch_jax(strat, model, K_pt,
                                              problem=problem, gamma=gamma_pt,
                                              seeds=seed_list,
                                              record_every=re_pt,
                                              use_pallas=use_pallas, x64=x64)
                return [simulate(factory(**strat_kw), model, K_pt,
                                 problem=problem, gamma=gamma_pt, seed=s,
                                 record_every=re_pt, tol_grad_sq=tol_pt)
                        for s in seed_list]

            if chosen == "jax_sharded":
                from ..launch.sweep import SweepPoint
                sharded_points.append(
                    (len(traces), SweepPoint(index=len(traces), strategy=strat,
                                             K=K_pt, gamma=gamma_pt,
                                             record_every=re_pt),
                     run_engine, strat, K_pt, tol_pt))
                row = None             # filled by the fused sweep below
                actual = chosen
            elif forced:
                actual, row = chosen, run_engine(chosen)
            else:
                downs = _ladder_below(chosen, strat, model, problem, K_pt,
                                      tol_pt)
                actual, row = _run_point_laddered(chosen, run_engine, downs,
                                                  route_info)
            traces.append(row)
            used_backends.append(actual)
            used_schemes.append({"serial": "stream", "jax": "jax.random",
                                 "jax_sharded": "jax.random"
                                 }.get(actual, rng_scheme))
            used_routing.append(route_info)
            point.annotate(engine=actual)

    if sharded_points:
        # one more point span: the fused run of every point routed here
        with telemetry.span("repro.batch.point", engine="jax_sharded",
                            points=len(sharded_points)):
            # ONE fused, shape-bucketed, shard_mapped launch for every grid
            # point routed to the sharded sweep backend. Forced: a failure
            # raises. Routed by fastest: retry once, then each deferred
            # point falls down the ladder from "jax"
            from ..launch.sweep import run_sharded_sweep

            def run_sweep():
                return run_sharded_sweep(
                    [sp for _, sp, *_ in sharded_points], model, problem,
                    seed_list, use_pallas=use_pallas, x64=x64)

            results = fused_exc = None
            if forced:
                results = run_sweep()
            else:
                for _attempt in range(2):
                    try:
                        results = run_sweep()
                        break
                    except Exception as exc:
                        fused_exc = exc
            if results is not None:
                for g, *_ in sharded_points:
                    row, shard_rec = results[g]
                    traces[g] = row
                    used_routing[g] = {**used_routing[g], "shard": shard_rec}
            else:
                for g, _sp, run_engine, strat, K_pt, tol_pt in sharded_points:
                    route_info = used_routing[g]
                    route_info.setdefault("downgrades", []).append({
                        "from": "jax_sharded", "to": "jax",
                        "error": type(fused_exc).__name__,
                        "reason": str(fused_exc)[:300], "retried": True})
                    downs = _ladder_below("jax", strat, model, problem, K_pt,
                                          tol_pt)
                    actual, row = _run_point_laddered("jax", run_engine,
                                                      downs, route_info)
                    traces[g] = row
                    used_backends[g] = actual
                    used_schemes[g] = {"serial": "stream",
                                       "jax": "jax.random"}.get(actual,
                                                                rng_scheme)

    # auto can pick different backends per grid point; report faithfully
    backend_label = used_backends[0] if len(set(used_backends)) == 1 \
        else "+".join(sorted(set(used_backends)))
    scheme_label = used_schemes[0] if len(set(used_schemes)) == 1 \
        else "+".join(sorted(set(used_schemes)))
    return TraceBatch(strategy=name, grid=points,
                      seeds=np.asarray(seed_list), traces=traces,
                      backend=backend_label, rng_scheme=scheme_label,
                      routing=used_routing)
