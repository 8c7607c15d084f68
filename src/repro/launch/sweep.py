"""Sharded sweep backend: ``shard_map`` the flattened (grid point × seed)
work units of a :func:`repro.core.simulate_batch` sweep across devices.

The paper's claims are statements about whole (scenario × strategy ×
seed) grids, but every jax engine in :mod:`repro.core.batch_jax` vmaps
seeds on a single device, so paper-scale sweeps serialize over grid
points — and each point runs a program of its own (the unsharded m-sync
round scan is cached per law and shape; the other families' programs
key by model and oracle identity). This module is the ``backend="jax_sharded"``
orchestrator that fixes both:

* **Flatten** — every (grid point, seed) pair becomes one *work unit*;
  the unit axis is the thing sharded. Per-seed draw streams are already
  sweep-independent pure functions of ``PRNGKey(seed)`` (the DESIGN §3b
  RNG contract), so flattening units across grid points needs no RNG
  re-plumbing and preserves per-seed bitwise parity with the unsharded
  ``backend="jax"`` path.
* **Shape-bucket** — units whose compiled program would be identical
  (same engine family, ``(n, K)``, model/oracle identity, static
  strategy params) share one *bucket* → one compiled program. The
  m-sync family goes further: timing-only buckets fuse heterogeneous
  ``m`` (traced row-wise selection) and math buckets fuse heterogeneous
  ``gamma`` (traced per-unit stepsize), so a whole ``m``- or
  ``gamma``-sweep is ONE program instead of one compile per point.
* **Shard** — each bucket's unit batch is padded to a multiple of the
  mesh size (repeating unit 0 — rows are independent, so padding is
  inert) and ``shard_map``ped over the 1-D ``data`` axis built from
  :func:`repro.launch.mesh.make_mesh_auto`; the per-device programs hit
  the same jit cache. Outputs come back replicated/gathered (GSPMD
  all-gather on the unit axis), are sliced back per point, and packaged
  with the same :func:`repro.core.batch_jax.assemble_traces` the
  unsharded backend uses.

Engine support: the m-sync round scan (fused + sharded), the
Async/Ringmaster arrival scan (chain build + scan sharded over units;
pool merge and compaction host-side as in the unsharded engine), and
the whole round-scan family — Rennala and Malenia renewal round scans
and the Ringleader chunked ragged-chain round scan — each
``shard_map``ped over the unit rows with AOT program caching. A bucket
that fails raises; the caller decides whether to downgrade
(:func:`repro.core.simulate_batch` does so only under
``backend="fastest"``).

Multi-host: the mesh covers the local process's devices;
:func:`is_coordinator` (``jax.process_index() == 0``) gates artifact
writing in :func:`repro.exp.run_experiment` so an N-host launch writes
one JSON, not N.

Instrumentation: every bucket records compile vs execute wall time and
program-cache hits (AOT ``lower().compile()`` in the engine layer);
:func:`repro.core.simulate_batch` surfaces the record per grid point in
``TraceBatch.routing`` meta.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry

__all__ = ["SweepPoint", "sweep_device_count", "is_coordinator",
           "sweep_mesh", "sweep_shard_ctx", "shardable_kind",
           "run_sharded_sweep"]

#: jax engine families with a sharded program (every family
#: :func:`repro.core.batch_jax._classify` knows)
SHARDED_KINDS = ("msync", "async", "ringmaster", "optimal_asgd",
                 "rennala", "malenia", "ringleader")


@dataclasses.dataclass
class SweepPoint:
    """One grid point of a sharded sweep: a bound strategy plus the
    per-point :func:`simulate` arguments the grid may override."""

    index: int                         # position in the TraceBatch grid
    strategy: Any                      # bound AggregationStrategy
    K: int
    gamma: float = 0.0
    record_every: int = 1


def sweep_device_count() -> int:
    """Devices visible to this process (the 1-D ``data`` mesh size)."""
    import jax

    return jax.local_device_count()


def is_coordinator() -> bool:
    """True on the process that should write gathered artifacts."""
    import jax

    return jax.process_index() == 0


def sweep_mesh(devices: Optional[int] = None):
    """The sweep's 1-D ``("data",)`` mesh over the local devices."""
    from .mesh import make_mesh_auto

    return make_mesh_auto((devices or sweep_device_count(),), ("data",))


def sweep_shard_ctx(devices: Optional[int] = None):
    """A :class:`repro.sharding.specs.ShardCtx` for the sweep mesh:
    data-parallel only (``model_axis=None``) — sweeps shard work units,
    never parameters."""
    from ..sharding.specs import ShardCtx

    return ShardCtx(mesh=sweep_mesh(devices), dp_axes=("data",),
                    model_axis=None)


def shardable_kind(strategy, model, problem) -> Optional[str]:
    """The engine family a sharded program exists for, or None."""
    from ..core.batch_jax import _classify

    kind = _classify(strategy)
    return kind if kind in SHARDED_KINDS else None


def _bucket_key(kind: Optional[str], point: SweepPoint, math: bool):
    """Static program signature: points with equal keys share one
    compiled program. ``m`` is traced for timing m-sync (any ``m``
    fuses), static for math m-sync (the oracle batch splits ``m``
    ways); ``gamma`` is traced for math m-sync, static for the arrival
    scan."""
    if kind == "msync":
        if math:
            return ("msync-math", int(point.K), int(point.strategy._m))
        return ("msync-timing", int(point.K))
    if kind in ("async", "ringmaster", "optimal_asgd"):
        md = int(point.strategy.max_delay) \
            if kind in ("ringmaster", "optimal_asgd") else int(point.K) + 1
        adaptive = bool(getattr(point.strategy, "delay_adaptive", False))
        return ("arrival", kind, int(point.K), md, adaptive,
                float(point.gamma) if math else 0.0)
    if kind == "rennala":
        return ("rennala", int(point.K), int(point.strategy.batch),
                float(point.gamma) if math else 0.0)
    if kind == "malenia":
        return ("malenia", int(point.K), float(point.strategy.S),
                float(point.gamma) if math else 0.0)
    if kind == "ringleader":
        return ("ringleader", int(point.K),
                float(point.gamma) if math else 0.0)
    raise ValueError(f"no sharded program for engine kind {kind!r}")


def run_sharded_sweep(points: Sequence[SweepPoint], model, problem,
                      seeds: Sequence[int], use_pallas: bool = False,
                      x64: bool = False, mesh=None,
                      ) -> Dict[int, Tuple[List[Any], Dict[str, Any]]]:
    """Run every grid point × seed as one sharded, shape-bucketed sweep.

    Returns ``{point.index: (traces, record)}`` where ``traces`` is the
    per-seed :class:`~repro.core.strategies.Trace` list (bitwise equal
    to the unsharded ``backend="jax"`` run of that point) and
    ``record`` is the per-point shard meta for ``TraceBatch.routing``.
    """
    import jax

    if x64 and not jax.config.jax_enable_x64:
        with jax.enable_x64(True):
            return run_sharded_sweep(points, model, problem, seeds,
                                     use_pallas=use_pallas, x64=False,
                                     mesh=mesh)

    from ..core import batch_jax as bj

    if mesh is None:
        mesh = sweep_mesh()
    D = int(mesh.devices.size)
    n = model.n
    S = len(seeds)
    math = problem is not None
    for p in points:
        p.strategy.bind(n)
        bj._check_supported(p.strategy, model, problem)

    buckets: Dict[tuple, List[SweepPoint]] = {}
    for p in points:
        kind = shardable_kind(p.strategy, model, problem)
        buckets.setdefault(_bucket_key(kind, p, math), []).append(p)

    def host(comp, T, x, val, gn, *rest):
        """A bucket's outputs on the host in one fetch; the iterate and
        the recorded values only on the math path."""
        if math:
            return telemetry.fetch((comp, T, x, val, gn, *rest))
        comp, T, *rest = telemetry.fetch((comp, T, *rest))
        return (comp, T, None, None, None, *rest)

    def _run_bucket(bkey, bpoints
                    ) -> Dict[int, Tuple[List[Any], Dict[str, Any]]]:
        out: Dict[int, Tuple[List[Any], Dict[str, Any]]] = {}
        base_rec = {"bucket": "/".join(str(b) for b in bkey),
                    "devices": D, "points_in_bucket": len(bpoints),
                    "units": len(bpoints) * S}
        # flatten point-major so each point's seeds are one column slice
        unit_seeds = [int(s) for p in bpoints for s in seeds]
        U0 = len(unit_seeds)
        pad = (-U0) % D
        unit_seeds += [unit_seeds[0]] * pad         # inert: rows independent
        meta: Dict[str, Any] = {}

        if bkey[0].startswith("msync"):
            K = bpoints[0].K
            m_units = [int(p.strategy._m) for p in bpoints for _ in seeds]
            g_units = [float(p.gamma) for p in bpoints for _ in seeds]
            m_units += [m_units[0]] * pad
            g_units += [g_units[0]] * pad
            comp, x, T, val, gn = bj.sharded_msync_run(
                model, problem, n, len(unit_seeds), K, unit_seeds,
                m_units, g_units, use_pallas, mesh, meta=meta)
            comp, T, x, val, gn = host(comp, T, x, val, gn)
            for i, p in enumerate(bpoints):
                c = slice(i * S, (i + 1) * S)
                traces = bj.assemble_traces(
                    comp[c], None if not math else x[c], T[:, c],
                    None if not math else val[:, c],
                    None if not math else gn[:, c],
                    int(p.strategy._m) * K, S, K, p.record_every, problem)
                out[p.index] = (traces, {**base_rec, "padded_units": pad,
                                         **meta})
        elif bkey[0] == "arrival":
            _, kind, K, md, adaptive, gamma = bkey
            comp, x, T, val, gn = bj._chain_scan_run(
                model, problem, kind in ("ringmaster", "optimal_asgd"),
                md, adaptive, n, len(unit_seeds), K, gamma, unit_seeds,
                mesh=mesh, meta=meta)
            comp, T, x, val, gn = host(comp, T, x, val, gn)
            for i, p in enumerate(bpoints):
                c = slice(i * S, (i + 1) * S)
                traces = bj.assemble_traces(
                    comp[c], None if not math else x[c], T[:, c],
                    None if not math else val[:, c],
                    None if not math else gn[:, c],
                    K, S, K, p.record_every, problem)
                out[p.index] = (traces, {**base_rec, "padded_units": pad,
                                         **meta})
        else:                                       # round-scan family
            fam = bkey[0]
            if fam == "rennala":
                _, K, B, gamma = bkey
                comp, x, T, val, gn = bj._rennala_run(
                    model, problem, B, n, len(unit_seeds), K, gamma,
                    use_pallas, unit_seeds, mesh=mesh, meta=meta)
                used = np.full(len(unit_seeds), B * K)
            elif fam == "malenia":
                _, K, S_t, gamma = bkey
                comp, x, T, val, gn, used = bj._malenia_run(
                    model, problem, S_t, n, len(unit_seeds), K, gamma,
                    unit_seeds, mesh=mesh, meta=meta)
            else:                                   # ringleader
                _, K, gamma = bkey
                comp, x, T, val, gn, used = bj._ringleader_run(
                    model, problem, n, len(unit_seeds), K, gamma,
                    unit_seeds, mesh=mesh, meta=meta)
            comp, T, x, val, gn, used = host(comp, T, x, val, gn, used)
            for i, p in enumerate(bpoints):
                c = slice(i * S, (i + 1) * S)
                traces = bj.assemble_traces(
                    comp[c], None if not math else x[c], T[:, c],
                    None if not math else val[:, c],
                    None if not math else gn[:, c],
                    used[c], S, K, p.record_every, problem)
                out[p.index] = (traces, {**base_rec, "padded_units": pad,
                                         **meta})
        return out

    out: Dict[int, Tuple[List[Any], Dict[str, Any]]] = {}
    for bkey, bpoints in buckets.items():
        out.update(_run_bucket(bkey, bpoints))
    return out
