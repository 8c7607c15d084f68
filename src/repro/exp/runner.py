"""``run_experiment`` — the one driver every figure/benchmark goes through.

Couples a strategy spec, a named scenario (or a model instance), a seed
sweep and an optional parameter grid into a single
:func:`repro.core.simulate_batch` call, then reduces the
:class:`~repro.core.batch.TraceBatch` into summary rows (mean ± std
across seeds, time-to-target quantiles) with JSON output for CI
artifacts. :func:`csv_rows` renders a summary as plain harness-style
``(name, value, derived)`` triples for callers that don't need custom
derived columns (the in-tree benchmarks hand-format richer ones).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Mapping, Optional, Sequence, Union

import numpy as np

from repro import telemetry
from repro.core.batch import (TraceBatch, _as_spec, _grid_points,
                              simulate_batch)
from repro.core.strategies import Trace

from .scenarios import make_scenario

__all__ = ["ExperimentResult", "run_experiment", "csv_rows",
           "atomic_write_json"]


def atomic_write_json(path: str, obj: Any, *, indent: int = 2,
                      default=None) -> None:
    """Write ``obj`` as JSON via tmp-file + :func:`os.replace` so a
    crash mid-write never leaves a truncated artifact: readers see
    either the previous complete file or the new complete file. The
    tmp file lives next to the target (same filesystem — ``os.replace``
    is atomic only within one)."""
    path = os.fspath(path)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as fh:
        json.dump(obj, fh, indent=indent, default=default)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)


@dataclasses.dataclass
class ExperimentResult:
    """A named experiment: its meta, the raw TraceBatch and summary rows."""

    name: str
    meta: Dict[str, Any]
    batch: TraceBatch
    rows: List[Dict[str, Any]]

    def to_json(self, path: str) -> None:
        atomic_write_json(path, sanitize_json(self.as_dict()),
                          default=_jsonable)

    def as_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "meta": self.meta, "rows": self.rows}


def _jsonable(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def sanitize_json(obj):
    """Replace non-finite floats with strings: ``json.dump`` would emit
    the bare token ``Infinity`` (invalid JSON — rejected by jq /
    ``JSON.parse``) for inf time-to-target quantiles."""
    if isinstance(obj, dict):
        return {k: sanitize_json(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [sanitize_json(v) for v in obj]
    if isinstance(obj, (float, np.floating)) and not np.isfinite(obj):
        return str(obj)                          # "inf" / "-inf" / "nan"
    return obj


def _join_labels(labels: Sequence[str]) -> str:
    """Replicate :func:`simulate_batch`'s backend/scheme label join so a
    checkpoint-reassembled batch reports identically to a one-shot run."""
    return labels[0] if len(set(labels)) == 1 \
        else "+".join(sorted(set(labels)))


def _checkpointed_batch(strategy, model, K, *, problem, gamma, seed_list,
                        grid, record_every, tol_grad_sq, backend,
                        rng_scheme, use_pallas, x64, checkpoint_dir,
                        resume) -> TraceBatch:
    """Crash-safe sweep: one :func:`simulate_batch` call per grid point,
    each checkpointed to ``checkpoint_dir/point-NNNNN.json`` with an
    atomic tmp-then-rename write the moment it finishes. Per-seed draw
    streams are sweep-independent (DESIGN §3b), so per-point results
    equal the one-shot sweep's; the final batch is assembled by reading
    every checkpoint back, so a resumed run and an uninterrupted run
    flow through byte-identical data. With ``resume=True`` points whose
    checkpoint already exists are skipped (a ``manifest.json``
    fingerprint guards against resuming someone else's sweep)."""
    name, _factory, _kw = _as_spec(strategy)
    points = _grid_points(grid)
    os.makedirs(checkpoint_dir, exist_ok=True)

    manifest = {"version": 1, "strategy": name,
                "model": getattr(model, "name", type(model).__name__),
                "n": int(model.n), "K": int(K),
                "seeds": [int(s) for s in seed_list],
                "grid": points, "gamma": float(gamma),
                "record_every": int(record_every),
                "tol_grad_sq": tol_grad_sq, "backend": backend,
                "rng_scheme": rng_scheme, "math": problem is not None,
                "use_pallas": bool(use_pallas), "x64": bool(x64)}
    # normalize through a JSON round trip so the fingerprint comparison
    # sees exactly what a reloaded manifest would
    manifest = json.loads(json.dumps(sanitize_json(manifest),
                                     default=_jsonable))
    manifest_path = os.path.join(checkpoint_dir, "manifest.json")
    if resume and os.path.exists(manifest_path):
        with open(manifest_path) as fh:
            prev = json.load(fh)
        if prev != manifest:
            raise ValueError(
                f"checkpoint dir {checkpoint_dir!r} holds a different "
                "sweep (manifest mismatch); refusing to resume into it")
    with telemetry.span("repro.exp.report"):
        atomic_write_json(manifest_path, manifest)

    def _point_path(g: int) -> str:
        return os.path.join(checkpoint_dir, f"point-{g:05d}.json")

    for g, pt in enumerate(points):
        if resume and os.path.exists(_point_path(g)):
            continue
        sub = simulate_batch(strategy, model, K, problem=problem,
                             gamma=gamma, seeds=seed_list,
                             grid={k: [v] for k, v in pt.items()} or None,
                             record_every=record_every,
                             tol_grad_sq=tol_grad_sq, backend=backend,
                             rng_scheme=rng_scheme, use_pallas=use_pallas,
                             x64=x64)
        with telemetry.span("repro.exp.report"):
            rec = {"version": 1, "point": pt, "backend": sub.backend,
                   "rng_scheme": sub.rng_scheme,
                   "routing": sub.routing[0] if sub.routing else None,
                   "traces": [t.as_dict() for t in sub.traces[0]]}
            atomic_write_json(_point_path(g), sanitize_json(rec),
                              default=_jsonable)

    traces: List[List[Trace]] = []
    backends: List[str] = []
    schemes: List[str] = []
    routing: List[Any] = []
    for g in range(len(points)):
        with open(_point_path(g)) as fh:
            rec = json.load(fh)
        traces.append([Trace.from_dict(t) for t in rec["traces"]])
        backends.append(rec["backend"])
        schemes.append(rec["rng_scheme"])
        routing.append(rec["routing"])
    return TraceBatch(strategy=name, grid=points,
                      seeds=np.asarray(seed_list), traces=traces,
                      backend=_join_labels(backends),
                      rng_scheme=_join_labels(schemes), routing=routing)


def run_experiment(strategy,
                   scenario: Union[str, object],
                   n: int,
                   K: int,
                   *,
                   seeds: Union[int, Sequence[int]] = 8,
                   grid: Optional[Mapping[str, Sequence]] = None,
                   problem=None,
                   gamma: float = 0.0,
                   record_every: int = 1,
                   tol_grad_sq: Optional[float] = None,
                   backend: str = "fastest",
                   rng_scheme: str = "counter",
                   use_pallas: bool = False,
                   x64: bool = False,
                   scenario_kwargs: Optional[Dict[str, Any]] = None,
                   target_frac: Optional[float] = None,
                   json_path: Optional[str] = None,
                   name: Optional[str] = None,
                   checkpoint_dir: Optional[str] = None,
                   resume: bool = False) -> ExperimentResult:
    """Run ``strategy`` under ``scenario`` across ``seeds`` × ``grid``.

    ``scenario`` is a name from :data:`~repro.exp.scenarios.SCENARIOS`
    (built with ``n`` and ``scenario_kwargs``) or an already-constructed
    time model (then ``n`` must equal ``model.n``). ``target_frac``
    enables time-to-target reporting: wall-clock until ``||∇f||²`` falls
    to that fraction of its initial value, quantiled across seeds.
    ``json_path`` writes the summary as a JSON artifact.

    The default ``backend="fastest"`` routes each grid point through the
    per-engine cost model
    (:func:`repro.core.batch.estimate_backend_seconds`): the host engine
    and the jax engine that would run the combination are priced as a
    function of engine kind (round scan / arrival scan / event loop),
    S, K, n, math vs timing-only and accelerator presence, and the
    cheaper one runs. The backend that actually ran is recorded in the
    JSON artifact's ``meta.backend`` (plus per-row
    ``backend``/``rng_scheme``) and the full per-grid-point routing
    decision — estimates, accelerator flag, reason — lands in
    ``meta.routing``. On multi-device hosts the router may pick
    ``backend="jax_sharded"`` (the :mod:`repro.launch.sweep` fused
    sweep); its per-bucket shard/compile/cache meta appears under each
    routing entry's ``shard`` key. ``x64=True`` runs jax grid points in
    float64 for per-run tie parity on tie-heavy instances (partial
    participation).

    ``json_path`` is written only on the coordinator process
    (:func:`repro.launch.sweep.is_coordinator`) so a multi-host launch
    produces one artifact, not one per host.

    ``checkpoint_dir`` makes the sweep crash-safe (DESIGN §3c): each
    grid point runs as its own :func:`simulate_batch` call and lands in
    ``checkpoint_dir/point-NNNNN.json`` the moment it completes
    (atomic tmp-then-rename, like every JSON this module writes). A
    killed run restarted with ``resume=True`` skips every point whose
    checkpoint exists and produces a final artifact byte-identical to
    the uninterrupted checkpointed run's — both assemble the batch from
    the checkpoint files, and DESIGN §3b sweep independence makes
    per-point results equal the one-shot sweep's. (Two caveats: grid
    points are never *fused* into one sharded program in checkpoint
    mode, and sharded routing records carry wall-clock compile times —
    use a deterministic backend when asserting byte equality.)
    """
    with telemetry.span("repro.exp.run"):
        if isinstance(scenario, str):
            model = make_scenario(scenario, n, **(scenario_kwargs or {}))
            scen_name = scenario
        else:
            model = scenario
            scen_name = getattr(model, "name", type(model).__name__)
        if model.n != n:
            raise ValueError(f"scenario has n={model.n}, asked for n={n}")

        if checkpoint_dir is not None:
            seed_list = list(range(seeds)) \
                if isinstance(seeds, (int, np.integer)) \
                else [int(s) for s in seeds]
            batch = _checkpointed_batch(
                strategy, model, K, problem=problem, gamma=gamma,
                seed_list=seed_list, grid=grid, record_every=record_every,
                tol_grad_sq=tol_grad_sq, backend=backend,
                rng_scheme=rng_scheme, use_pallas=use_pallas, x64=x64,
                checkpoint_dir=checkpoint_dir, resume=resume)
        else:
            batch = simulate_batch(strategy, model, K, problem=problem,
                                   gamma=gamma, seeds=seeds, grid=grid,
                                   record_every=record_every,
                                   tol_grad_sq=tol_grad_sq,
                                   backend=backend, rng_scheme=rng_scheme,
                                   use_pallas=use_pallas, x64=x64)
        with telemetry.span("repro.exp.report"):
            rows = batch.summary(target_frac=target_frac)
            for row in rows:
                row["scenario"] = scen_name
                row["n"] = n
                row["K"] = K
            meta = {"strategy": batch.strategy, "scenario": scen_name,
                    "n": n, "K": K, "seeds": list(map(int, batch.seeds)),
                    "backend": batch.backend,
                    "rng_scheme": batch.rng_scheme,
                    "routing": batch.routing,
                    "grid": batch.grid if grid else None}
            result = ExperimentResult(
                name=name or f"{batch.strategy}@{scen_name}", meta=meta,
                batch=batch, rows=rows)
            if json_path:
                from repro.launch.sweep import is_coordinator
                if is_coordinator():
                    result.to_json(json_path)
        return result


def csv_rows(result: ExperimentResult, prefix: str,
             value_key: str = "total_time_mean"):
    """Benchmark-harness ``(name, value, derived)`` triples: one per grid
    point, value = ``value_key``, derived = ``± std`` plus seed count."""
    out = []
    std_key = value_key.replace("_mean", "_std")
    for row in result.rows:
        params = "/".join(f"{k}={v}" for k, v in row["params"].items())
        label = f"{prefix}/{params}" if params else prefix
        std = row.get(std_key)
        derived = (f"±{std:.4g} over {row['seeds']} seeds"
                   if std is not None else f"{row['seeds']} seeds")
        out.append((label, row[value_key], derived))
    return out
