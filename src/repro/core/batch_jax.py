"""JAX backend for :func:`repro.core.batch.simulate_batch`.

Runs device-resident simulation as ONE array program over a
``(seeds, workers)`` state batch, one jitted recursion per strategy
family:

* **m-sync family** — a ``lax.scan`` over rounds whose body is pure
  elementwise work plus the per-round m-th order statistic from
  :mod:`repro.kernels.order_stats` (iterative tie-class extraction for
  small ``m``, counting-bisection selection for large ``m``, optionally
  the Pallas top-m partial-sort kernel via ``use_pallas=True``).
* **Rennala** — the same renewal structure, per round accumulating
  ``batch`` arrivals: each worker's within-round arrivals form a renewal
  chain (successive finish times), the round ends at the ``batch``-th
  smallest chain entry, and every worker's next pending computation is
  its first chain entry past the round end.
* **Malenia** — the renewal-chain scan generalized to a *per-worker
  count predicate*: the round ends at the first arrival time ``T`` at
  which every worker has delivered at least one fresh gradient AND the
  harmonic mean ``n / sum_i 1/B_i(T)`` of the per-worker counts reaches
  the strategy's ``S`` (the paper's §6 heterogeneous batching rule,
  preserved exactly). ``T`` is found by a monotone counting bisection
  over the chain pool plus an exact snap-to-arrival step; boundary ties
  are consumed one arrival at a time (worker-major) so the predicate
  first becomes true exactly as in the event engine.
* **Async / Ringmaster** — a renewal-chain **arrival scan**: because a
  popped worker always restarts immediately (accept or discard), its
  arrival times form a renewal chain independent of the server state, so
  the engine pre-draws every worker's chain in bulk
  (:func:`~repro.core.time_models.jax_chain_draws` — prefix-stable
  ``fold_in``-keyed duration rows, auto-sized ``L`` with doubling
  retries), merges the ``(S, n*L)`` pool into global arrival order ONCE
  (:func:`~repro.kernels.order_stats.smallest_k` — host stable argsort
  on CPU, device sort on accelerators), and runs ONE ``lax.scan`` over
  the ordered arrival window with O(1) per-arrival state transitions
  (worker id gather, Ringmaster delay test, version/snapshot scatter).
  Timing-only Async needs no scan at all: the first ``K`` merged
  arrivals ARE the steps. This replaces the PR 4 arrival-indexed
  ``lax.while_loop`` (kept as :func:`_arrival_while_run`, a
  benchmark/cross-check reference only), whose O(S·n) argmin per arrival
  and K serialized iterations made async the slowest device path.
  Per-worker start-iterate snapshots make the delayed-gradient math path
  exact. **OptimalASGD** (the Maranjyan bounded-staleness rule with the
  ``n``-scaled delay threshold and delay-adaptive stepsize) is the same
  recursion with its own ``max_delay`` and the adaptive multiplier — no
  new program, just routing.
* **Ringleader** — a round-indexed ``lax.scan`` over ONE global renewal
  chain per worker: Ringleader never idles and never discards, so each
  worker's arrival times are a pure renewal process from ``t = 0`` and
  the whole run consumes a single prefix-stable ``(S, n, L)`` chain
  tensor. Round ``k`` ends at ``T_k = max_i`` (worker ``i``'s first
  chain entry past ``T_{k-1}``) — the waste-free "everyone contributed"
  predicate — and the serial engine's version bookkeeping bounds
  staleness by one round, so the math path carries only
  ``x^{k-1}``/``x^k`` plus the previous round's triggering worker.

Time models: :class:`FixedTimes` (no RNG), any
:class:`~repro.core.time_models.SubExponentialTimes` carrying a
``jax_sampler`` (every in-tree factory does; the keyed Async path also
prefers ``jax_sampler_item``), and :class:`UniversalModel` /
:class:`PartialParticipationModel` via the deterministic
``finish_times_jax`` inversion (batched ``searchsorted`` on the
cumulative-power grid + closed-form quadratic segment solve) — every
strategy family above accepts all three classes, so the full paper
coverage matrix (DESIGN.md §3b) runs device-resident. Fault-wrapped
models (:class:`repro.core.faults.FaultyTimes`, DESIGN §3c) ARE
``SubExponentialTimes`` whose samplers compose the base draw with
fault noise on disjoint ``fold_in`` streams, so they ride this whole
sampled-model path — including ``jax_chain_draws`` renewal rows and
the sharded sweep — with no engine changes; an identity wrapper passes
the base samplers through by object identity and shares their jit
caches (bitwise no-op).

The math-carrying paths evaluate a :class:`JaxProblem` oracle under
``jax.vmap`` over seeds — n=1000 × 32-seed sweeps execute as a single
jitted program instead of 32 serial event loops (~6x over the serial
fast path on CPU here, far more on real accelerators).

Exactness contract (documented in DESIGN.md): the NumPy engines break
wall-clock ties by exact event-heap sequence numbers; this backend breaks
them by worker index (and within-worker arrival index for the renewal
chains) and draws with ``jax.random`` instead of NumPy ``Generator``
streams. For deterministic models in generic position the recursions are
identical and results match the NumPy backends to float tolerance; for
random models the results are equal in distribution, not per-seed.
"""

from __future__ import annotations

import dataclasses
import math as _math
import types
from typing import Callable, List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..telemetry import ENGINE_DISPATCH, ENGINE_PREP
from .strategies import (AggregationStrategy, Async, Malenia, MSync,
                         OptimalASGD, Rennala, Ringleader, Ringmaster,
                         Trace)
from .time_models import FixedTimes, SubExponentialTimes, UniversalModel

__all__ = ["JaxProblem", "quadratic_worst_case_jax", "simulate_batch_jax",
           "jax_supported", "arrival_scan_work"]

# Malenia round-end search: value-bisection passes over the chain pool,
# then snap-to-arrival passes (each consumes >= 1 tie class; more than a
# couple after the bisection is pathological and flags the run)
_MAL_BISECT_ITERS = 48
_MAL_SNAP_ITERS = 32


@dataclasses.dataclass
class JaxProblem:
    """A :class:`~repro.core.strategies.Problem` twin with JAX callables.

    ``stoch_grad(x, key)`` replaces the NumPy oracle's
    ``stoch_grad(x, rng)`` so gradient noise comes from ``jax.random``
    and the whole seed sweep stays inside one jitted program. Backend
    contract: a ``JaxProblem`` runs on ``backend="jax"`` ONLY (the NumPy
    engines cannot execute it, and ``backend="fastest"`` therefore
    always routes it to jax). RNG contract: oracle noise keys derive
    from ``jax.random.PRNGKey(seed)`` splits — reproducible per seed
    value, never stream-equal to any NumPy ``Generator`` path. All three
    callables must be jit-traceable; ``f``/``grad`` are the recording
    oracle only (never differentiated through by the engine).
    """

    x0: "np.ndarray"
    f: Callable
    grad: Callable
    stoch_grad: Callable


def quadratic_worst_case_jax(d: int = 1000, p: float = 0.1,
                             scale: float = 0.25) -> JaxProblem:
    """JAX twin of :func:`repro.core.oracle.quadratic_worst_case` —
    same tridiagonal quadratic, same eq. (27) progress-gated Bernoulli
    oracle, with ``jax.random`` noise."""
    import jax
    import jax.numpy as jnp

    main = 2.0 * scale * np.ones(d)
    off = -scale * np.ones(d - 1)
    b_np = np.zeros(d)
    b_np[0] = -scale
    A = np.diag(main) + np.diag(off, 1) + np.diag(off, -1)
    x_star = np.linalg.solve(A, b_np)
    f_star = float(0.5 * x_star @ (A @ x_star) - b_np @ x_star)

    b = jnp.asarray(b_np)
    sc = scale

    def matvec(x):
        y = 2.0 * sc * x
        y = y.at[:-1].add(-sc * x[1:])
        y = y.at[1:].add(-sc * x[:-1])
        return y

    def f(x):
        # elementwise products + sums, not dots: a float32 dot may run
        # as a reduced-precision matmul pass on TPU
        return jnp.sum(x * (0.5 * matvec(x) - b)) - f_star

    def grad(x):
        return matvec(x) - b

    def stoch_grad(x, key):
        g = grad(x)
        nz = x != 0
        # prog(x) = max{i >= 1 : x_i != 0} (1-indexed), 0 if x == 0
        pr = jnp.max(jnp.where(nz, jnp.arange(1, d + 1), 0))
        xi = jax.random.bernoulli(key, p).astype(x.dtype)
        gate = jnp.where(jnp.arange(d) < pr, 1.0, xi / p)
        return g * gate

    x0 = np.zeros(d)
    x0[0] = np.sqrt(d)
    return JaxProblem(x0=x0, f=f, grad=grad, stoch_grad=stoch_grad)


def _classify(strategy: AggregationStrategy) -> Optional[str]:
    """Which jitted recursion runs ``strategy`` (None => unsupported)."""
    if (isinstance(strategy, MSync)
            and type(strategy).on_arrival is MSync.on_arrival
            and type(strategy).on_step is AggregationStrategy.on_step
            and not strategy.uses_alarm
            and strategy.grads_by_worker is None):
        return "msync"
    # exact types: subclasses may override semantics the scans hard-code
    if type(strategy) is Rennala:
        return "rennala"
    if type(strategy) is Malenia and strategy.grads_by_worker is None:
        return "malenia"
    if type(strategy) is Async:
        return "async"
    if type(strategy) is Ringmaster:
        return "ringmaster"
    if type(strategy) is OptimalASGD:
        return "optimal_asgd"
    if type(strategy) is Ringleader:
        return "ringleader"
    return None


def _model_supported(model) -> bool:
    return (isinstance(model, (FixedTimes, UniversalModel))
            or (isinstance(model, SubExponentialTimes)
                and getattr(model, "jax_sampler", None) is not None))


def jax_supported(strategy: AggregationStrategy, model, problem) -> bool:
    """Non-raising eligibility probe (``backend="fastest"`` uses this)."""
    return (_classify(strategy) is not None and _model_supported(model)
            and (problem is None or isinstance(problem, JaxProblem)))


def _check_supported(strategy: AggregationStrategy, model, problem) -> str:
    kind = _classify(strategy)
    if kind is None:
        raise NotImplementedError(
            f"jax backend supports the unmodified m-sync family, Rennala, "
            f"Malenia (homogeneous oracle), Async/Ringmaster and "
            f"Ringleader/OptimalASGD, not "
            f"{strategy.name!r}; use backend='serial'")
    if not _model_supported(model):
        raise NotImplementedError(
            f"jax backend needs FixedTimes, a UniversalModel, or a "
            f"SubExponentialTimes with a jax_sampler (got "
            f"{type(model).__name__}); use backend='serial' or "
            f"'vectorized'")
    if problem is not None and not isinstance(problem, JaxProblem):
        raise NotImplementedError(
            "jax backend takes a JaxProblem (jax.random oracle), not the "
            "NumPy Problem; use backend='serial' for NumPy oracles")
    return kind


def _timing_round(ft, ver, comp, k, cand, m, use_pallas):
    """Shared m-sync round update on ``(S, n)`` state (see module doc).
    The selection (m-th order statistic and tie rank) runs under the
    ``order_stat`` name scope."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..kernels.order_stats import mth_smallest

    stale = ver < k
    with jax.named_scope("order_stat"):
        T = mth_smallest(cand, m, use_pallas=use_pallas)
        leq = cand <= T[:, None]

        def exact_acc(_):
            # ties straddle the m-boundary somewhere: rank tied candidates
            # by worker index and accept only up to the per-row quota
            # (cumsum is ~40% of the round cost, so it only runs on tie
            # rounds)
            c_lt = (cand < T[:, None]).sum(axis=1)
            tie = cand == T[:, None]
            tie_rank = jnp.cumsum(tie, axis=1) - 1
            return (cand < T[:, None]) | (tie
                                          & (tie_rank < (m - c_lt)[:, None]))

        acc = lax.cond(jnp.all(leq.sum(axis=1) == m),
                       lambda _: leq, exact_acc, operand=None)
    popped = stale & (ft < T[:, None])
    # int32 sums: under x64 bool sums default to int64 and would promote
    # the carried counters out of their scan-carry dtype
    comp = comp + m + popped.sum(axis=1, dtype=jnp.int32)
    ft = jnp.where(popped, cand, ft)
    ver = jnp.where(popped, k, ver)
    return ft, ver, comp, T, acc


def _timing_round_rowwise(ft, ver, comp, k, cand, m_vec):
    """:func:`_timing_round` with a TRACED per-row ``m`` — the sharded
    sweep backend fuses grid points with different ``m`` into one
    compiled program, so ``m`` arrives as a ``(rows,)`` int32 tensor.

    Bitwise parity with the static-``m`` round: the row-wise selection
    returns the same element value as :func:`mth_smallest`, and the
    tie fast path is output-equivalent by construction — when every
    row's ``<= T`` count equals its ``m``, the quota acceptance accepts
    exactly the ``leq`` mask, so whichever branch the (per-shard local)
    ``lax.cond`` takes, the accept mask is identical.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..kernels.order_stats import mth_smallest_rowwise

    stale = ver < k
    with jax.named_scope("order_stat"):
        T = mth_smallest_rowwise(cand, m_vec)
        leq = cand <= T[:, None]

        def exact_acc(_):
            c_lt = (cand < T[:, None]).sum(axis=1)
            tie = cand == T[:, None]
            tie_rank = jnp.cumsum(tie, axis=1) - 1
            return (cand < T[:, None]) | (
                tie & (tie_rank < (m_vec - c_lt)[:, None]))

        acc = lax.cond(jnp.all(leq.sum(axis=1) == m_vec),
                       lambda _: leq, exact_acc, operand=None)
    popped = stale & (ft < T[:, None])
    comp = comp + m_vec + popped.sum(axis=1, dtype=jnp.int32)
    ft = jnp.where(popped, cand, ft)
    ver = jnp.where(popped, k, ver)
    return ft, ver, comp, T, acc


def _fixed_timing_run(taus, S: int, m: int, K: int, use_pallas: bool):
    """Timing-only m-sync under FixedTimes: module-level jit, cached
    across calls (the benchmark-smoke hot path — no RNG at all)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = taus.shape[0]

    def step(carry, k):
        ft, ver, comp = carry
        stale = ver < k
        cand = jnp.where(stale, ft + taus, ft)
        ft, ver, comp, T, acc = _timing_round(ft, ver, comp, k, cand, m,
                                              use_pallas)
        ft = jnp.where(acc, T[:, None] + taus, ft)
        ver = jnp.where(acc, k + 1, ver)
        return (ft, ver, comp), T

    init = (jnp.broadcast_to(taus, (S, n)), jnp.zeros((S, n), jnp.int32),
            jnp.zeros(S, jnp.int32))
    (_, _, comp), T = lax.scan(step, init, jnp.arange(K, dtype=jnp.int32))
    return comp, T


_fixed_timing_jit = None


def _engine_dtype():
    """float64 under the ``x64=True`` engine mode, float32 otherwise."""
    import jax
    import jax.numpy as jnp

    # The one traced-reachable site allowed to name both dtypes: this IS
    # the selector every engine derives its dtype from, and it reads the
    # x64 flag — so it cannot pin the wrong precision.
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32  # repcheck: ignore[JIT005]


def _keys_and_x(problem, S, n, seeds):
    """Per-seed PRNG keys and the broadcast initial iterate (``(S, 1)``
    zeros for timing-only runs)."""
    import jax
    import jax.numpy as jnp

    keys0 = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    if problem is not None:
        dt = _engine_dtype()
        x_init = jnp.broadcast_to(
            jnp.asarray(problem.x0, dtype=dt),
            (S,) + np.shape(problem.x0)).astype(dt)
    else:
        x_init = jnp.zeros((S, 1))
    return keys0, x_init


def _finish_factory(model, S, n):
    """``finish_all(round_keys, t0) -> (S, n)`` ABSOLUTE finish times of
    computations started at ``t0`` (scalar/broadcastable): duration draw
    plus start for sampled models, ``t0 + tau`` for FixedTimes, the
    deterministic ``finish_times_jax`` inversion for universal models
    (``round_keys`` unused by the draw-free cases)."""
    import jax
    import jax.numpy as jnp

    if isinstance(model, FixedTimes):
        taus = jnp.asarray(model.taus)

        def finish_all(round_keys, t0):           # no RNG consumed
            return jnp.broadcast_to(t0 + taus, (S, n))
    elif isinstance(model, UniversalModel):
        def finish_all(round_keys, t0):           # deterministic inversion
            return model.finish_times_jax(jnp.broadcast_to(t0, (S, n)))
    else:
        sampler = model.jax_sampler

        def finish_all(round_keys, t0):           # one (n,) draw per seed
            return t0 + jax.vmap(sampler)(round_keys)
    return finish_all


def _chain_factory(model, S, n):
    """``chain(round_keys, base, L) -> (S, n, L + 1)`` renewal chains:
    entry 0 is ``base`` (each worker's first fresh arrival), entry ``j``
    its ``j``-th subsequent arrival — cumulative duration draws for
    sampled models, ``base + j * tau`` for FixedTimes, iterated
    ``finish_times_jax`` for universal models."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if isinstance(model, FixedTimes):
        taus = jnp.asarray(model.taus)

        def chain(round_keys, base, L):
            steps = taus[None, :, None] * jnp.arange(1, L + 1)
            return jnp.concatenate(
                [base[..., None], base[..., None] + steps], axis=-1)
    elif isinstance(model, UniversalModel):
        def chain(round_keys, base, L):
            def body(c, _):
                nxt = model.finish_times_jax(c)
                return nxt, nxt

            _, out = lax.scan(body, base, None, length=L)  # (L, S, n)
            return jnp.concatenate(
                [base[..., None], jnp.moveaxis(out, 0, -1)], axis=-1)
    else:
        sampler = model.jax_sampler

        def chain(round_keys, base, L):
            ks = jax.vmap(lambda k: jax.random.split(k, L))(round_keys)
            d = jax.vmap(jax.vmap(sampler))(ks)            # (S, L, n)
            return jnp.concatenate(
                [base[..., None],
                 base[..., None] + jnp.cumsum(jnp.moveaxis(d, 1, 2),
                                              axis=-1)], axis=-1)
    return chain


def _grad_mean_fn(problem, B):
    """vmap-over-seeds mean of ``B`` stochastic gradients at ``x``."""
    import jax

    def grad_mean(x, round_keys):
        gkeys = jax.vmap(lambda k: jax.random.split(k, B))(round_keys)
        per_seed = jax.vmap(jax.vmap(problem.stoch_grad, (None, 0)),
                            (0, 0))
        return per_seed(x, gkeys).mean(axis=1)

    return grad_mean


def _value_key(v, seen=()):
    """A hashable key equal for equal values, or ``None`` where ``v`` is
    not keyed by value: Python scalars, ``str``, ``None``, NumPy arrays
    (dtype, shape, bytes), tuples of these, and plain functions by their
    code, defaults and closure cells (recursively)."""
    if v is None or type(v) in (bool, int, str):
        return (type(v), v)
    if type(v) is float:
        return (float, v.hex())             # by bits: -0.0 apart from 0.0
    if isinstance(v, (np.ndarray, np.generic)):
        a = np.asarray(v)
        if a.dtype.hasobject:
            return None
        return ("array", a.dtype.str, a.shape, a.tobytes())
    if type(v) is tuple:
        parts = tuple(_value_key(x, seen) for x in v)
        return None if None in parts else ("tuple", parts)
    if type(v) is types.FunctionType and id(v) not in seen:
        seen = seen + (id(v),)
        try:
            cells = tuple(c.cell_contents for c in v.__closure__ or ())
        except ValueError:                  # an empty cell
            return None
        kw = tuple(sorted((v.__kwdefaults__ or {}).items()))
        parts = tuple(_value_key(x, seen)
                      for x in (v.__defaults__ or ()) + kw + cells)
        if None in parts:
            return None
        return ("fn", v.__module__, v.__qualname__, v.__code__, parts)
    return None


def _law_key(model):
    """The part of an m-sync program's key that stands for the time law.

    A sampled law is keyed by the value of its ``jax_sampler`` (its code
    and what it closes over), so two ``exponential_times(1.0, n)``
    objects share one program; FixedTimes by its taus. Anything the key
    cannot read by value — a universal model's bound method, a closure
    over a device array or an object — keys by identity."""
    if isinstance(model, FixedTimes):
        return ("fixed", _value_key(np.asarray(model.taus)))
    if isinstance(model, SubExponentialTimes):
        key = _value_key(model.jax_sampler)
        if key is not None:
            return ("sampled", key)
    return _ById(model)


def _msync_scan_prog(model, problem, m, n, S, K, gamma, use_pallas):
    """The jitted m-sync round scan ``run(keys0, x_init) -> (comp, x, T,
    val, gn)`` for one law, problem and shape."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    math = problem is not None
    finish_all = _finish_factory(model, S, n)
    if math:
        grad_mean = _grad_mean_fn(problem, m)

    def step(carry, k):
        ft, ver, comp, x, keys = carry
        sub = jax.vmap(lambda kk: jax.random.split(kk, 4))(keys)
        keys = sub[:, 0]
        stale = ver < k
        cand = jnp.where(stale, finish_all(sub[:, 1], ft), ft)
        ft, ver, comp, T, acc = _timing_round(ft, ver, comp, k, cand, m,
                                              use_pallas)
        ft = jnp.where(acc, finish_all(sub[:, 2],
                                       jnp.broadcast_to(T[:, None],
                                                        (S, n))), ft)
        ver = jnp.where(acc, k + 1, ver)
        if math:
            x = x - gamma * grad_mean(x, sub[:, 3])
            val = jax.vmap(problem.f)(x)
            gn = jax.vmap(lambda xx: jnp.sum(problem.grad(xx) ** 2))(x)
        else:
            val = gn = jnp.zeros(S)
        return (ft, ver, comp, x, keys), (T, val, gn)

    def run(keys, x_init):
        sub = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
        ft0 = finish_all(sub[:, 1], jnp.zeros((S, n)))
        init = (ft0, jnp.zeros((S, n), jnp.int32), jnp.zeros(S, jnp.int32),
                x_init, sub[:, 0])
        (_, _, comp, x, _), (T, val, gn) = lax.scan(
            step, init, jnp.arange(K, dtype=jnp.int32))
        return comp, x, T, val, gn

    return jax.jit(run)


def _general_run(model, problem, m, n, S, K, gamma, use_pallas, seeds):
    """RNG-threading m-sync scan: random/universal time models and/or a
    JaxProblem oracle.

    Every seed's draw stream is a pure function of its ``PRNGKey(seed)``
    (a 4-way split of its own carried key per round). The program takes
    the keys and the initial iterate as arguments and is built once per
    key in :data:`_SWEEP_PROGS`: the shape and parameters, the x64 and
    PRNG settings, the law by value (:func:`_law_key`), the problem by
    identity and the module functions it is traced from. Later calls
    with an equal key reuse it without tracing (counters
    ``sweep_prog_builds`` / ``sweep_prog_hits``).
    """
    import jax

    math = problem is not None
    with telemetry.span(ENGINE_PREP):
        keys0, x_init = _keys_and_x(problem, S, n, seeds)
        # the module functions the trace reads are keyed too, so a
        # rebound one (a patched round) builds anew
        key = ("msync_scan", m, n, S, K, float(gamma), bool(use_pallas),
               math, bool(jax.config.jax_enable_x64),
               str(jax.config.jax_default_prng_impl), _law_key(model),
               _ById(problem) if math else None,
               (_finish_factory, _grad_mean_fn, _timing_round))
        prog = _SWEEP_PROGS.get(key)
        hit = prog is not None
        if hit:
            telemetry.count("sweep_prog_hits")
        else:
            telemetry.count("sweep_prog_builds")
            prog = _prog_cache_put(
                _SWEEP_PROGS, key,
                _msync_scan_prog(model, problem, m, n, S, K, gamma,
                                 use_pallas))

    with telemetry.span(ENGINE_DISPATCH, cache_hit=hit):
        out = prog(keys0, x_init)
    return telemetry.wait(out)


def _aot_run(key, wrapped, args, meta):
    """Run the sweep program cached under ``key`` in :data:`_SWEEP_PROGS`
    (lowering and compiling ``wrapped`` for ``args`` first on a miss)
    and wait for its outputs. ``meta`` (if given) receives
    ``cache_hit``, ``compile_s`` (the compile's dispatch span) and
    ``exec_s`` (the call's dispatch and wait spans)."""
    import jax

    hit = key in _SWEEP_PROGS
    compile_s = 0.0
    if not hit:
        with telemetry.span(ENGINE_DISPATCH, phase="compile") as built:
            compiled = jax.jit(wrapped).lower(*args).compile()
        compile_s = built.seconds
        _prog_cache_put(_SWEEP_PROGS, key, compiled)
    with telemetry.span(ENGINE_DISPATCH) as run:
        out = _SWEEP_PROGS[key](*args)
    with telemetry.sync() as done:
        out = jax.block_until_ready(out)
    if meta is not None:
        meta.update(cache_hit=hit, compile_s=round(compile_s, 4),
                    exec_s=round(run.seconds + done.seconds, 4))
    return out


class _ById:
    """Identity-keyed hashable wrapper: models/problems (unhashable
    dataclasses, closures over arrays) key the sweep program cache by
    object identity; the strong reference pins the id for the cache
    entry's lifetime."""

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _ById) and other.obj is self.obj


#: Sweep programs, FIFO like _CHAIN_PROGS/_SCAN_PROGS: the AOT-compiled
#: sharded ones, key = (family, static shape/params, mesh devices,
#: model/problem ids), and the unsharded m-sync round scans of
#: :func:`_general_run`, key = ("msync_scan", shape/params, settings,
#: law key, problem id).
_SWEEP_PROGS: dict = {}


def _mesh_cache_key(mesh):
    return tuple(d.id for d in mesh.devices.flat)


def sharded_msync_run(model, problem, n, S, K, seeds, m_list, gamma_list,
                      use_pallas, mesh, meta=None):
    """Fused + sharded m-sync family run over ``S = len(seeds)`` work
    units (one unit = one (grid point, seed) pair; the caller has
    already flattened and padded to a multiple of the mesh size).

    One compiled program covers every unit: timing-only units fuse
    heterogeneous ``m`` through the traced row-wise selection
    (:func:`_timing_round_rowwise`), math units fuse heterogeneous
    ``gamma`` as a traced per-unit stepsize vector (``m`` stays static
    for math — the oracle batch splits ``m`` ways). Per-unit draw
    streams are byte-for-byte the :func:`_general_run` streams (the
    same 4-way per-round key split of ``PRNGKey(seed)``), so each
    unit's outputs are bitwise identical to the unsharded
    ``backend="jax"`` run of its grid point. The program is
    ``shard_map``ped over the mesh's 1-D ``data`` axis and AOT-compiled
    (``lower().compile()``) so compile vs execute wall time and cache
    hits are observable; ``meta`` (if given) receives
    ``compile_s``/``exec_s``/``cache_hit``.

    ``use_pallas`` is accepted for signature symmetry but the row-wise
    counting selection always runs the fused elementwise path — the
    selected value is the same element either way.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec

    math = problem is not None
    m_static = int(m_list[0]) if math else None
    with telemetry.span(ENGINE_PREP):
        keys0, x_init = _keys_and_x(problem, S, n, seeds)
        if math:
            grad_mean = _grad_mean_fn(problem, m_static)
        dt = _engine_dtype()
        m_in = jnp.asarray(m_list, jnp.int32)
        g_in = jnp.asarray(gamma_list, dt)

    def unit_prog(keys, m_vec, gamma_vec, x0):
        U = keys.shape[0]                     # local block under shard_map
        finish_all = _finish_factory(model, U, n)

        def step(carry, k):
            ft, ver, comp, x, kk = carry
            sub = jax.vmap(lambda q: jax.random.split(q, 4))(kk)
            kk = sub[:, 0]
            stale = ver < k
            cand = jnp.where(stale, finish_all(sub[:, 1], ft), ft)
            ft, ver, comp, T, acc = _timing_round_rowwise(ft, ver, comp, k,
                                                          cand, m_vec)
            ft = jnp.where(acc, finish_all(sub[:, 2],
                                           jnp.broadcast_to(T[:, None],
                                                            (U, n))), ft)
            ver = jnp.where(acc, k + 1, ver)
            if math:
                x = x - gamma_vec[:, None] * grad_mean(x, sub[:, 3])
                val = jax.vmap(problem.f)(x)
                gn = jax.vmap(lambda xx: jnp.sum(problem.grad(xx) ** 2))(x)
            else:
                val = gn = jnp.zeros(U)
            return (ft, ver, comp, x, kk), (T, val, gn)

        sub = jax.vmap(lambda q: jax.random.split(q, 2))(keys)
        ft0 = finish_all(sub[:, 1], jnp.zeros((U, n)))
        init = (ft0, jnp.zeros((U, n), jnp.int32), jnp.zeros(U, jnp.int32),
                x0, sub[:, 0])
        (_, _, comp, x, _), (T, val, gn) = lax.scan(
            step, init, jnp.arange(K, dtype=jnp.int32))
        return comp, x, T, val, gn

    P = PartitionSpec
    # check_vma=False: the program has no collectives, so there is no
    # varying-across-devices type to check
    wrapped = jax.shard_map(
        unit_prog, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P(None, "data"), P(None, "data"),
                   P(None, "data")),
        check_vma=False)

    key = ("msync", math, m_static, n, S, K,
           bool(jax.config.jax_enable_x64), _mesh_cache_key(mesh),
           _ById(model), _ById(problem))
    return _aot_run(key, wrapped, (keys0, m_in, g_in, x_init), meta)


def _rennala_run(model, problem, B, n, S, K, gamma, use_pallas, seeds,
                 mesh=None, meta=None):
    """Rennala as a renewal-batched ``lax.scan``: per round, each worker's
    fresh arrivals form a renewal chain, the round ends at the ``B``-th
    smallest chain entry, every worker's next pending computation is its
    first chain entry past the round end, and the stepping worker alone
    restarts at the new iterate. Ties are broken by (worker,
    within-round arrival index). For ``B`` beyond the iterative-kernel
    range the pool selection runs the counting-bisection path of
    :func:`~repro.kernels.order_stats.mth_smallest` — no ``top_k``
    lowering inside the scan.

    With a ``mesh`` the per-unit program is ``shard_map``ped over the
    1-D ``data`` axis and AOT-compiled into :data:`_SWEEP_PROGS` (the
    :func:`sharded_msync_run` treatment): every unit row is a pure
    function of its own ``PRNGKey``, so sharded outputs are bitwise the
    unsharded ``backend="jax"`` outputs. ``meta`` (if given) receives
    ``cache_hit``/``compile_s``/``exec_s``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec

    from ..kernels.order_stats import mth_smallest

    math = problem is not None
    with telemetry.span(ENGINE_PREP):
        keys0, x_init = _keys_and_x(problem, S, n, seeds)

    def unit_prog(keys, x0):
        U = keys.shape[0]                     # local block under shard_map
        finish_all = _finish_factory(model, U, n)
        chain_fn = _chain_factory(model, U, n)
        grad_mean = _grad_mean_fn(problem, B) if math else None
        widx = jnp.arange(n)
        flat_idx = jnp.arange(n * B)

        def step(carry, k):
            ft, ver, comp, x, keys = carry
            sub = jax.vmap(lambda kk: jax.random.split(kk, 4))(keys)
            keys = sub[:, 0]
            stale = ver < k
            # first fresh arrival: a stale pending pops at ft and restarts
            base = jnp.where(stale, finish_all(sub[:, 1], ft), ft)
            chain = chain_fn(sub[:, 2], base, B)      # (U, n, B+1)
            pool = chain[..., :B].reshape(U, n * B)
            T = mth_smallest(pool, B, use_pallas=use_pallas)
            lt = pool < T[:, None]
            eq = pool == T[:, None]
            quota = (B - lt.sum(axis=1))[:, None]
            acc = lt | (eq & ((jnp.cumsum(eq, axis=1) - 1) < quota))
            cnt = acc.reshape(U, n, B).sum(axis=2)    # accepted per worker
            popped = stale & (ft < T[:, None])        # discarded stale pops
            comp = comp + B + popped.sum(axis=1, dtype=jnp.int32)
            # the B-th (stepping) arrival: last accepted entry at exactly
            # T; its worker restarts at the new iterate (version k + 1)
            stepper = jnp.argmax(jnp.where(acc & eq, flat_idx[None, :], -1),
                                 axis=1) // B
            live = (~stale) | popped                  # chain materialized
            nxt = jnp.take_along_axis(chain, cnt[..., None], axis=2)[..., 0]
            ft = jnp.where(live, nxt, ft)
            ver = jnp.where(live, k, ver)
            ver = jnp.where(widx[None, :] == stepper[:, None], k + 1, ver)
            if math:
                x = x - gamma * grad_mean(x, sub[:, 3])
                val = jax.vmap(problem.f)(x)
                gn = jax.vmap(lambda xx: jnp.sum(problem.grad(xx) ** 2))(x)
            else:
                val = gn = jnp.zeros(U)
            return (ft, ver, comp, x, keys), (T, val, gn)

        sub = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
        init = (finish_all(sub[:, 1], jnp.zeros((U, n))),
                jnp.zeros((U, n), jnp.int32),
                jnp.zeros(U, jnp.int32), x0, sub[:, 0])
        (_, _, comp, x, _), (T, val, gn) = lax.scan(
            step, init, jnp.arange(K, dtype=jnp.int32))
        return comp, x, T, val, gn

    if mesh is None:
        with telemetry.span(ENGINE_DISPATCH):
            out = jax.jit(unit_prog)(keys0, x_init)
        return telemetry.wait(out)

    P = PartitionSpec
    wrapped = jax.shard_map(
        unit_prog, mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data"), P(None, "data"), P(None, "data"),
                   P(None, "data")),
        check_vma=False)
    key = ("rennala", math, B, n, S, K, float(gamma), bool(use_pallas),
           bool(jax.config.jax_enable_x64), _mesh_cache_key(mesh),
           _ById(model), _ById(problem))
    return _aot_run(key, wrapped, (keys0, x_init), meta)


def _malenia_grad_fn(problem, n, L):
    """Malenia math update: ``(1/n) sum_i (1/B_i) sum_{j<B_i} g_ij`` at
    ``x^k`` — a **count-compacted** slot loop: slot ``j`` draws only
    while some worker still has an accepted arrival there
    (``j < max_i B_i``), so the per-round oracle volume is
    ``n * max(B)`` instead of the full masked ``n * L`` pool. ``L`` is
    sized for the model's speed *spread* (a fast worker's chain must
    cover the slowest worker's first delivery), so on sparse rounds —
    near-homogeneous speeds, ``B_i ~ ceil(S)`` — ``max(B) << L`` and the
    compaction cuts most of the draw volume. Slot keys are still split
    ``L`` ways up front, so the drawn values per occupied slot are
    bitwise-identical to the uncompacted loop (zero-weight slots are
    skipped, never re-keyed); memory stays ``(S, n, d)`` per slot."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def upd(x, B, round_keys):
        slot_keys = jax.vmap(lambda k: jax.random.split(k, L))(round_keys)
        w = 1.0 / (jnp.maximum(B, 1).astype(x.dtype) * n)  # (S, n)
        Bmax = jnp.max(B)

        def cond(c):
            return c[0] < Bmax

        def body(c):
            j, acc = c
            kcol = slot_keys[:, j]                         # (S, 2)
            gk = jax.vmap(lambda k: jax.random.split(k, n))(kcol)
            g = jax.vmap(jax.vmap(problem.stoch_grad, (None, 0)),
                         (0, 0))(x, gk)                    # (S, n, d)
            wj = jnp.where(j < B, w, 0.0)
            return j + 1, acc + (g * wj[..., None]).sum(axis=1)

        _, out = lax.while_loop(cond, body,
                                (jnp.zeros((), jnp.int32),
                                 jnp.zeros_like(x)))
        return out

    return upd


def _malenia_run(model, problem, S_target, n, S, K, gamma, seeds,
                 chain_len=None, mesh=None, meta=None):
    """Malenia as the Rennala renewal scan generalized to the per-worker
    count predicate (see module doc): per round, each worker's fresh
    arrivals form an ``L``-slot renewal chain, and the round ends at the
    first arrival time ``T`` with ``min_i B_i(T) >= 1`` and harmonic
    mean ``n / sum_i 1/B_i(T) >= S_target``. The predicate is monotone
    in ``T``, so ``T`` comes from a value bisection over the pool, an
    exact snap onto the triggering arrival, and a worker-major
    tie-consumption search that reproduces the event engine's
    one-arrival-at-a-time predicate check (ties broken by worker index —
    the backend's documented contract).

    ``L`` must cover every worker's in-round arrival count: a fast
    worker keeps accumulating arrivals while the slowest delivers its
    first, so the default scales with both ``ceil(S)`` and the
    mean-speed spread. Rounds where a chain is exhausted anyway (a
    worker's ``L+1``-th arrival lands before the round end — e.g. a
    heavy-tailed slow draw) are flagged, and the engine retries with
    doubled chains a few times before raising — never silently
    mis-batched.

    With a ``mesh`` the per-unit program is ``shard_map``ped over the
    1-D ``data`` axis and AOT-compiled into :data:`_SWEEP_PROGS` (every
    unit row is a pure function of its own key — sharded outputs are
    bitwise the unsharded ones); ``meta`` (if given) receives
    ``cache_hit``/``compile_s``/``exec_s``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec

    math = problem is not None
    ceilS = int(_math.ceil(S_target))
    if chain_len:
        L = int(chain_len)
    else:
        taus = np.asarray(model.mean_times(), dtype=float) \
            if not isinstance(model, UniversalModel) else None
        spread = (float(np.max(taus) / max(np.min(taus), 1e-12))
                  if taus is not None and len(taus) else 1.0)
        L = max(2 * ceilS, int(np.ceil(3.0 * spread)) + ceilS, 8)
    if L < ceilS:
        raise ValueError(f"chain_len={L} cannot certify harmonic mean "
                         f"S={S_target} (need >= {ceilS})")
    with telemetry.span(ENGINE_PREP):
        keys0, x_init = _keys_and_x(problem, S, n, seeds)

    def P_of_counts(B):
        ok1 = jnp.all(B >= 1, axis=-1)
        # engine dtype, not hard-coded f32: the x64 tie-parity mode needs
        # the harmonic-mean threshold test at float64 like the NumPy heap
        hm = n / jnp.sum(1.0 / jnp.maximum(B, 1).astype(_engine_dtype()),
                         axis=-1)
        return ok1 & (hm >= S_target)

    def attempt(L):
        upd_fn = _malenia_grad_fn(problem, n, L) if math else None
        tie_iters = int(np.ceil(np.log2(n * L + 2))) + 2

        def unit_prog(keys, x0):
            U = keys.shape[0]                 # local block under shard_map
            finish_all = _finish_factory(model, U, n)
            chain_fn = _chain_factory(model, U, n)
            widx = jnp.arange(n)

            def step(carry, k):
                ft, ver, comp, used, x, keys, bad = carry
                sub = jax.vmap(lambda kk: jax.random.split(kk, 4))(keys)
                keys = sub[:, 0]
                stale = ver < k
                base = jnp.where(stale, finish_all(sub[:, 1], ft), ft)
                ch = chain_fn(sub[:, 2], base, L)     # (U, n, L+1)
                cand = ch[..., :L]

                def Pt(T):
                    return P_of_counts(
                        (cand <= T[:, None, None]).sum(axis=-1))

                # bisection invariants: no arrival at or before t_lo (B = 0,
                # false); every worker has >= ceil(S) arrivals by t_hi (true)
                t_lo = base.min(axis=1) - 1.0
                t_hi = cand[..., ceilS - 1].max(axis=1)

                def bisect(_, lh):
                    lo, hi = lh
                    mid = 0.5 * (lo + hi)
                    ok = Pt(mid)
                    return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

                lo, _ = lax.fori_loop(0, _MAL_BISECT_ITERS, bisect,
                                      (t_lo, t_hi))

                # snap onto the exact triggering arrival: smallest pool
                # entry above lo; sub-threshold entries can survive a wide
                # interval, so advance past them (bounded; non-convergence
                # flags the run)
                def cond(c):
                    _, _, done, it = c
                    return jnp.any(~done) & (it < _MAL_SNAP_ITERS)

                def snap(c):
                    lo, T, done, it = c
                    cnd = jnp.where(cand > lo[:, None, None], cand,
                                    jnp.inf).min(axis=(1, 2))
                    ok = Pt(cnd)
                    T = jnp.where(done, T, cnd)
                    lo = jnp.where(done | ok, lo, cnd)
                    return lo, T, done | ok, it + 1

                _, T, done, _ = lax.while_loop(
                    cond, snap, (lo, jnp.zeros(U), jnp.zeros(U, bool),
                                 jnp.zeros((), jnp.int32)))
                bad_k = ~done

                # per-worker counts at T, consuming boundary ties one
                # arrival at a time in worker-major order until the
                # predicate first holds
                Tb = T[:, None, None]
                lt = (cand < Tb).sum(axis=-1)         # (U, n)
                tie = (cand == Tb).sum(axis=-1)
                prev = jnp.cumsum(tie, axis=1) - tie

                def consumed(tc):
                    return jnp.clip(tc[:, None] - prev, 0, tie)

                def cbisect(_, lh):                   # minimal tc, P true
                    lo_c, hi_c = lh
                    mid = (lo_c + hi_c) // 2
                    ok = P_of_counts(lt + consumed(mid))
                    return (jnp.where(ok, lo_c, mid),
                            jnp.where(ok, mid, hi_c))

                # U, not S: under shard_map the local block is smaller
                # than the global unit count (S would break the carry)
                _, tc = lax.fori_loop(0, tie_iters, cbisect,
                                      (jnp.zeros(U, jnp.int32),
                                       tie.sum(axis=1).astype(jnp.int32)))
                cons = consumed(tc)
                B = lt + cons                         # accepted per worker
                stepper = jnp.max(jnp.where(cons > 0, widx[None, :], -1),
                                  axis=1)

                popped = stale & (ft < T[:, None])    # discarded stale pops
                comp = (comp + B.sum(axis=1, dtype=jnp.int32)
                        + popped.sum(axis=1, dtype=jnp.int32))
                used = used + B.sum(axis=1, dtype=jnp.int32)
                # chain exhausted: an (L+1)-th arrival before the round end
                bad = bad | bad_k | (ch[..., L] <= T[:, None]).any(axis=1)

                live = (~stale) | popped              # chain materialized
                nxt = jnp.take_along_axis(ch, B[..., None], axis=2)[..., 0]
                ft = jnp.where(live, nxt, ft)
                ver = jnp.where(live, k, ver)
                ver = jnp.where(widx[None, :] == stepper[:, None], k + 1, ver)
                if math:
                    x = x - gamma * upd_fn(x, B, sub[:, 3])
                    val = jax.vmap(problem.f)(x)
                    gn = jax.vmap(lambda xx: jnp.sum(problem.grad(xx) ** 2))(x)
                else:
                    val = gn = jnp.zeros(U)
                return (ft, ver, comp, used, x, keys, bad), (T, val, gn)

            sub = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
            init = (finish_all(sub[:, 1], jnp.zeros((U, n))),
                    jnp.zeros((U, n), jnp.int32), jnp.zeros(U, jnp.int32),
                    jnp.zeros(U, jnp.int32), x0, sub[:, 0],
                    jnp.zeros(U, bool))
            (_, _, comp, used, x, _, bad), (T, val, gn) = lax.scan(
                step, init, jnp.arange(K, dtype=jnp.int32))
            return comp, used, x, T, val, gn, bad

        if mesh is None:
            with telemetry.span(ENGINE_DISPATCH):
                out = jax.jit(unit_prog)(keys0, x_init)
            return telemetry.wait(out)

        P = PartitionSpec
        wrapped = jax.shard_map(
            unit_prog, mesh=mesh,
            in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data"), P(None, "data"),
                       P(None, "data"), P(None, "data"), P("data")),
            check_vma=False)
        key = ("malenia", math, float(S_target), L, n, S, K, float(gamma),
               bool(jax.config.jax_enable_x64), _mesh_cache_key(mesh),
               _ById(model), _ById(problem))
        return _aot_run(key, wrapped, (keys0, x_init), meta)

    for _ in range(4):
        comp, used, x, T, val, gn, bad = attempt(L)
        if not bool(np.any(telemetry.fetch(bad))):
            return comp, x, T, val, gn, used
        L *= 2                                    # outran the chains: retry
    raise RuntimeError(
        f"malenia jax engine could not certify a round within its "
        f"{L // 2}-slot renewal chains even after doubling retries "
        f"(extreme speed heterogeneity?); pass a larger chain_len to "
        f"simulate_batch_jax or use backend='serial'")


def _ringleader_grad_fn(problem, n):
    """Ringleader math update: ``(1/n) sum_i (1/B_i) sum_{j<B_i} g_ij``
    — the Malenia count-compacted slot loop with one twist: slot 0 (each
    worker's FIRST in-round arrival) evaluates at the previous iterate
    ``x^{k-1}`` (``x^k`` for the worker that triggered the previous
    round's step — it alone restarted at the fresh iterate), all later
    slots at ``x^k``. That two-point rule is exact, not an
    approximation: the serial engine restarts every worker at the
    current iterate on every (always-accepted) arrival, and every worker
    delivers at least once per round, so staleness never exceeds one
    round. Slot ``j``'s key is ``fold_in(round_key, j)`` — independent
    of any chain budget, so window growth and chunk re-runs leave
    completed rounds' draws bitwise unchanged."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    widx = jnp.arange(n)

    def upd(x_prev, x_cur, trig_prev, B, round_keys):
        w = 1.0 / (jnp.maximum(B, 1).astype(x_cur.dtype) * n)  # (S, n)
        Bmax = jnp.max(B)
        first_pt = jnp.where(
            (widx[None, :] == trig_prev[:, None])[..., None],
            x_cur[:, None, :], x_prev[:, None, :])             # (S, n, d)
        later_pt = jnp.broadcast_to(x_cur[:, None, :], first_pt.shape)

        def cond(c):
            return c[0] < Bmax

        def body(c):
            j, acc = c
            kcol = jax.vmap(
                lambda k: jax.random.fold_in(k, j))(round_keys)  # (S, 2)
            gk = jax.vmap(lambda k: jax.random.split(k, n))(kcol)
            pts = jnp.where(j == 0, first_pt, later_pt)
            g = jax.vmap(jax.vmap(problem.stoch_grad, (0, 0)),
                         (0, 0))(pts, gk)                      # (S, n, d)
            wj = jnp.where(j < B, w, 0.0)
            return j + 1, acc + (g * wj[..., None]).sum(axis=1)

        _, out = lax.while_loop(cond, body,
                                (jnp.zeros((), jnp.int32),
                                 jnp.zeros_like(x_cur)))
        return out

    return upd


def _ringleader_run(model, problem, n, S, K, gamma, seeds, chain_len=None,
                    mesh=None, meta=None):
    """Ringleader as a chunked round scan over ONE ragged global renewal
    chain per worker (see module doc): workers never idle and never
    discard, so their arrival times are pure renewal processes from
    ``t = 0`` and the whole run consumes a single prefix-stable
    worker-major flat pool from :func:`_chain_builder` with per-worker
    budgets from :func:`_chain_plan_ragged` — no per-round redraw, no
    rectangular ``n x max(L_i)`` tax under skewed rates. Round ``k``
    ends at ``T_k = max_i`` (worker ``i``'s first chain entry past
    ``T_{k-1}``); worker ``i`` contributes the ``B_i >= 1`` entries in
    ``(T_{k-1}, T_k]`` and the pointer update is pure counting
    (``newp = #{entries <= T_k}`` — a layout-independent per-worker
    count). Ties at the round end break by worker index (the backend's
    documented contract).

    The ``K`` rounds run in chunks of at most 64; the scan carry
    ``(p, comp, x_prev, x_cur, trig, keys)`` is saved at every chunk
    boundary. A pointer reaching its budget means the pool may hide
    arrivals inside the round: the failed chunk's outputs are
    discarded, the budgets double, :func:`_chain_builder` draws ONLY
    the extension slots (anchored, prefix-stable), and the SAME chunk
    re-runs from the saved carry — completed chunks are never re-drawn
    or re-scanned, and the re-run's completed rounds are bitwise
    unchanged (round keys are carried, slot keys are
    ``fold_in(round_key, j)``). With a ``mesh`` the chunk program is
    ``shard_map``ped over the seed rows; ``meta`` (if given) collects
    chain/window/chunk accounting and program-cache hits."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec

    from .time_models import ragged_layout

    math = problem is not None
    with telemetry.span(ENGINE_PREP):
        if chain_len:
            budgets = np.full(n, int(chain_len), np.int64)
        else:
            # expected global arrivals per round: every worker delivers ~
            # rate_i / min(rate) times while the slowest delivers once
            rates = _model_rates(model)
            per_round = float(rates.sum() / max(rates.min(), 1e-12))
            fluct = (1.0 if isinstance(model, (FixedTimes, UniversalModel))
                     else 1.0 + float(np.log(max(n, 1))))
            budgets = _chain_plan_ragged(
                model, n, int(np.ceil(K * per_round * fluct)))
        keys0, x_init = _keys_and_x(problem, S, n, seeds)
        sub0 = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys0)
        round_root, chain_root = sub0[:, 0], sub0[:, 1]
        upd_fn = _ringleader_grad_fn(problem, n) if math else None
        dt = _engine_dtype()

    # windowed ragged chain state (host): canonical per-worker pool
    # segments, drawn slot counts, carried last-absolute-time anchors
    drawn = np.zeros(n, np.int64)
    segs = [np.zeros((S, 0)) for _ in range(n)]
    anchors = jnp.zeros((S, n), dt)
    if meta is not None:
        meta.setdefault("chain_s", 0.0)
        meta.update(windows=0, drawn_slots=[], chunk_runs=0)

    def draw_to(budgets_new):
        nonlocal drawn, anchors
        ext = budgets_new - drawn
        with telemetry.span(ENGINE_PREP):
            builder = _chain_builder(model, S, n, ext, starts=drawn,
                                     mesh=mesh)
        with telemetry.span(ENGINE_DISPATCH) as run:
            flat_ext, anchors = builder(chain_root, anchors)
        with telemetry.sync() as done:
            flat_ext = jax.block_until_ready(flat_ext)
        if meta is not None:
            meta["chain_s"] = round(
                meta["chain_s"] + run.seconds + done.seconds, 4)
            meta["windows"] += 1
            meta["drawn_slots"].append(int(ext.sum()))
        ext_np = telemetry.fetch(flat_ext)
        with telemetry.span(ENGINE_PREP):
            eoff, _, _, _ = ragged_layout(ext, drawn)
            for i in range(n):
                segs[i] = np.concatenate(
                    [segs[i], ext_np[:, eoff[i]:eoff[i] + ext[i]]], axis=1)
            drawn = budgets_new.copy()
            return jnp.asarray(np.concatenate(segs, axis=1))

    def chunk_prog(buds, Kc):
        offs, widx_flat, _, _ = ragged_layout(buds)
        offs_c = offs.astype(np.int32)
        buds_c = buds.astype(np.int32)
        widx_c = widx_flat.astype(np.int32)

        key = ("ringleader", math, n, S, K, Kc, float(gamma),
               buds.tobytes(), bool(jax.config.jax_enable_x64),
               None if mesh is None else _mesh_cache_key(mesh),
               _ById(model), _ById(problem))
        hit = key in _SWEEP_PROGS
        if meta is not None:
            meta["cache_hit"] = hit
        if hit:
            return _SWEEP_PROGS[key]

        def unit_prog(ch_flat, p, comp, x_prev, x_cur, trig, keys):
            U = keys.shape[0]                 # local block under shard_map
            offs_d = jnp.asarray(offs_c)
            buds_d = jnp.asarray(buds_c)
            widx_d = jnp.asarray(widx_c)

            def step(carry, _):
                p, comp, x_prev, x_cur, trig, keys, bad = carry
                sub = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
                keys = sub[:, 0]
                # flat slot offs_i + p_i is worker i's first arrival
                # past T_{k-1} (p_i is a layout-independent count)
                gidx = offs_d[None, :] + jnp.minimum(p, buds_d[None, :] - 1)
                nxt = jnp.take_along_axis(ch_flat, gidx, axis=1)   # (U, n)
                T = nxt.max(axis=1)
                trig_new = nxt.argmax(axis=1).astype(jnp.int32)
                le = (ch_flat <= T[:, None]).astype(jnp.int32)
                newp = jax.vmap(lambda m: jax.ops.segment_sum(
                    m, widx_d, num_segments=n))(le)                # (U, n)
                B = newp - p
                bad = bad | (newp >= buds_d[None, :]).any(axis=1)
                comp = comp + B.sum(axis=1, dtype=jnp.int32)
                if math:
                    g = upd_fn(x_prev, x_cur, trig, B, sub[:, 1])
                    x_new = x_cur - gamma * g
                    val = jax.vmap(problem.f)(x_new)
                    gn = jax.vmap(
                        lambda xx: jnp.sum(problem.grad(xx) ** 2))(x_new)
                else:
                    x_new = x_cur
                    val = gn = jnp.zeros(U)
                return (newp, comp, x_cur, x_new, trig_new, keys, bad), \
                    (T, val, gn)

            init = (p, comp, x_prev, x_cur, trig, keys,
                    jnp.zeros(U, bool))
            (p, comp, x_prev, x_cur, trig, keys, bad), (T, val, gn) = \
                lax.scan(step, init, None, length=Kc)
            return p, comp, x_prev, x_cur, trig, keys, bad, T, val, gn

        if mesh is None:
            return _prog_cache_put(_SWEEP_PROGS, key, jax.jit(unit_prog))
        P = PartitionSpec
        wrapped = jax.shard_map(
            unit_prog, mesh=mesh,
            in_specs=(P("data"),) * 7,
            out_specs=(P("data"),) * 7 + (P(None, "data"),) * 3,
            check_vma=False)
        return _prog_cache_put(_SWEEP_PROGS, key, jax.jit(wrapped))

    ch_flat = draw_to(budgets)
    Kc = min(K, 64)
    T_all = np.zeros((K, S))
    vals = np.zeros((K, S))
    gns = np.zeros((K, S))
    # trig = -1: round 0 has no previous trigger and x_prev == x_cur ==
    # x0, so the first-slot rule is vacuous
    carry = (jnp.zeros((S, n), jnp.int32), jnp.zeros(S, jnp.int32),
             x_init, x_init, jnp.full(S, -1, jnp.int32), round_root)
    done = 0
    grows = 0
    while done < K:
        kc = min(Kc, K - done)
        with telemetry.span(ENGINE_PREP):
            prog = chunk_prog(drawn, kc)
        with telemetry.span(ENGINE_DISPATCH):
            out = prog(ch_flat, *carry)
        out = telemetry.wait(out)
        if meta is not None:
            meta["chunk_runs"] += 1
        p, comp, x_prev, x_cur, trig, rkeys, bad, T, val, gn = out
        if bool(np.any(telemetry.fetch(bad))):
            # pool may hide arrivals inside this chunk: discard its
            # outputs, double the budgets, draw ONLY the extension and
            # re-run the SAME chunk from the saved chunk-start carry
            if grows >= 4:
                raise RuntimeError(
                    f"ringleader jax engine outran its per-worker renewal "
                    f"chains (max {int(drawn.max())} slots) even after "
                    f"doubling windows (extreme speed heterogeneity?); "
                    f"pass a larger async_chain to simulate_batch_jax or "
                    f"use backend='serial'")
            grows += 1
            ch_flat = draw_to(drawn * 2)
            continue
        if math:
            T_all[done:done + kc], vals[done:done + kc], \
                gns[done:done + kc] = telemetry.fetch((T, val, gn))
        else:
            T_all[done:done + kc] = telemetry.fetch(T)
        carry = (p, comp, x_prev, x_cur, trig, rkeys)
        done += kc
    comp_np = telemetry.fetch(carry[1])
    x = carry[3]
    return comp_np, x, T_all, vals, gns, comp_np  # waste-free: used == comp


# --------------------------------------------------------------------------
# Async / Ringmaster: the renewal-chain arrival-scan engine
# --------------------------------------------------------------------------

# timing-only chain/scan programs are cached here so repeated same-shape
# sweeps (grid points, benchmark loops) skip recompilation; math programs
# close over the oracle and recompile per call like the other engines.
# Keys are (hashable sampler/model handle, static shape ints, x64 flag).
# Bounded FIFO: long sessions sweeping many model instances would
# otherwise retain one compiled program (plus its captured closure) per
# instance forever.
_CHAIN_PROGS: dict = {}
_SCAN_PROGS: dict = {}
_PROG_CACHE_CAP = 64


def _prog_cache_put(cache: dict, key, value):
    if len(cache) >= _PROG_CACHE_CAP:
        cache.pop(next(iter(cache)))          # FIFO: dicts keep insert order
    cache[key] = value
    return value

# arrival-scan sizing: chain-length safety factors and retry budget
_CHAIN_GROWTH = 1.25
_CHAIN_SLACK = 8.0
_CHAIN_RETRIES = 5


def _model_rates(model) -> np.ndarray:
    """Per-worker mean arrival rates (host), the sizing input for both
    chain plans: inverse mean times for fixed/sampled models, mean
    cumulative power for universal models."""
    if isinstance(model, UniversalModel):
        span = float(model.grid[-1] - model.grid[0]) or 1.0
        return np.maximum(np.asarray(model.cum[:, -1], dtype=float) / span,
                          1e-9)
    taus = np.asarray(model.mean_times(), dtype=float)
    return 1.0 / np.maximum(taus, 1e-12)


def _chain_plan(model, n: int, arrivals: int) -> int:
    """Rectangular per-worker chain length ``L`` for a window of
    ``arrivals`` global pops: expected max per-worker share of the
    window from the model's mean rates, a fluctuation cushion, capped at
    ``arrivals + 1`` (one worker can own at most the whole window). This
    sizes every worker to the *fastest* worker's share — the
    ``layout="rect"`` mode and the baseline the ragged plan is gated
    against; the engine itself defaults to :func:`_chain_plan_ragged`."""
    rates = _model_rates(model)
    share = float(rates.max() / max(rates.sum(), 1e-12))
    exp_max = arrivals * share
    L = int(np.ceil(_CHAIN_GROWTH * exp_max
                    + 4.0 * np.sqrt(max(exp_max, 1.0)) + _CHAIN_SLACK))
    return max(min(L, arrivals + 1), int(np.ceil(arrivals / n)) + 1, 4)


def _chain_plan_ragged(model, n: int, arrivals: int) -> np.ndarray:
    """Per-worker slot budgets ``L_i`` for a window of ``arrivals``
    global pops: each worker gets its own expected share
    ``arrivals * rate_i / sum(rates)`` with the same growth factor,
    sqrt fluctuation cushion and additive slack as the rectangular
    plan. Under skewed rates the flat pool ``sum(L_i)`` stays
    ``O(arrivals)`` where the rectangle pays ``n * max(L_i)``; at
    uniform rates every budget equals the rectangular share. Budgets
    are clamped to ``[4, arrivals + 1]`` per worker; the windowed
    engine doubles them (drawing only the extension) when a chain is
    outrun anyway."""
    rates = _model_rates(model)
    share = rates / max(float(rates.sum()), 1e-12)
    exp = arrivals * share
    L = np.ceil(_CHAIN_GROWTH * exp + 4.0 * np.sqrt(np.maximum(exp, 1.0))
                + _CHAIN_SLACK).astype(np.int64)
    return np.maximum(np.minimum(L, arrivals + 1), 4)


def _ring_pop_budget(n: int, K: int, max_delay: int) -> int:
    """Extra-arrival budget for the Ringmaster window: the engine pops
    ~``1 + sqrt(n / (max_delay + 1))`` arrivals per accept (empirical fit
    on the exponential model — the discard rate self-limits because a
    stalled server drives delays back to zero), plus slack; exhaustion
    retries quadruple it."""
    pops = 1.0 + float(np.sqrt(n / (max_delay + 1.0)))
    return int(K * min(float(n), pops - 1.0)) + 2 * n


def arrival_scan_work(model, n: int, K: int, ringmaster: bool = False,
                      max_delay: int = 0) -> "tuple[int, int]":
    """``(pool_elements, window_arrivals)`` the arrival-scan engine would
    process for this shape — the same sizing the engine itself uses
    (:func:`_chain_plan_ragged` budgets, :func:`_ring_pop_budget`
    window). The cost-model router in :mod:`repro.core.batch` consumes
    this; pure host arithmetic, no jax import."""
    budget = _ring_pop_budget(n, K, max_delay) if ringmaster else 0
    total = int(_chain_plan_ragged(model, n, K + budget).sum())
    return total, min(K + budget, total)


def _shard_wrap(fn, mesh, in_specs, out_specs):
    """``shard_map`` + jit a per-row program over the 1-D ``data`` axis
    (None mesh: plain jit — the unsharded path is the same program)."""
    import jax

    if mesh is None:
        return jax.jit(fn)
    # check_vma=False: these programs have no collectives, so there is
    # no varying-across-devices type to check
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _mesh_rows(S: int, mesh) -> int:
    """Per-device row block for a ``(S, ...)`` batch on a 1-D mesh."""
    if mesh is None:
        return S
    D = mesh.devices.size
    if S % D:
        raise ValueError(
            f"sharded arrival scan needs rows % devices == 0 (got "
            f"S={S}, D={D}); the sweep layer pads units before calling")
    return S // D


def _chain_builder(model, S: int, n: int, budgets, starts=None, mesh=None):
    """``chains(chain_keys, anchors) -> (flat, anchors_out)`` — ragged
    per-worker renewal chains over ONE worker-major flat buffer.

    ``budgets[i]`` slots are drawn for worker ``i`` starting at global
    slot ``starts[i]`` (0 for a fresh window); ``flat`` is ``(S,
    sum(budgets))`` ABSOLUTE arrival times laid out by
    :func:`~repro.core.time_models.ragged_layout`, and ``anchors_out``
    is each worker's last absolute time — the carry a window extension
    feeds back as ``anchors`` so accumulation continues the exact float
    recurrence (sequential adds, bitwise split-invariant; ``jnp.cumsum``
    would not be). Slot ``(i, g)``'s duration is the fold-in keyed
    :func:`~repro.core.time_models.jax_chain_draws_ragged` contract
    draw, so growing budgets or extending windows appends slots and
    leaves certified prefixes bitwise unchanged. FixedTimes is the
    closed form ``(g + 1) * tau`` (no RNG); universal models iterate
    the deterministic ``finish_times_jax`` inversion from ``anchors``.
    Programs are jit-cached (keyed by sampler/model identity, the
    budget/start layout bytes, x64 and the mesh); with a ``mesh`` the
    program is ``shard_map``ped over the seed/unit axis — every chain
    row is a pure function of its own key and anchor row, so sharded
    rows are bitwise the unsharded rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from .time_models import ragged_layout

    b = np.asarray(budgets, dtype=np.int64)
    s0 = (np.zeros(n, np.int64) if starts is None
          else np.asarray(starts, dtype=np.int64))
    offsets, widx, gslot, total = ragged_layout(b, s0)
    jmin = int(s0.min()) if n else 0
    jmax = int((s0 + b).max()) if n else 0
    steps = max(jmax - jmin, 0)

    x64 = bool(jax.config.jax_enable_x64)
    rows = _mesh_rows(S, mesh)
    mk = None if mesh is None else _mesh_cache_key(mesh)
    layout_key = (b.tobytes(), s0.tobytes())
    dt = _engine_dtype()

    if isinstance(model, FixedTimes):
        key = ("fixed", S, n, layout_key, x64, mk)
        if key not in _CHAIN_PROGS:
            gs = jnp.asarray(gslot)
            wi = jnp.asarray(widx)
            bd = jnp.asarray(b)
            sd = jnp.asarray(s0)

            def fixed_chain(taus, chain_keys, anchors):  # keys/anchors: no RNG
                flat = jnp.broadcast_to(taus[wi] * (gs + 1), (rows, total))
                out_anchor = jnp.broadcast_to(taus * (sd + bd).astype(taus.dtype),
                                              (rows, n))
                return flat, out_anchor

            _prog_cache_put(_CHAIN_PROGS, key,
                            _shard_wrap(fixed_chain, mesh,
                                        (P(), P("data"), P("data")),
                                        (P("data"), P("data"))))
        prog = _CHAIN_PROGS[key]
        taus = model.taus
        return lambda chain_keys, anchors: prog(jnp.asarray(taus, dt),
                                                chain_keys, anchors)

    # in-budget mask and flat destination per global slot (host consts);
    # out-of-budget entries scatter to index `total` and drop
    jg = np.arange(jmin, jmax, dtype=np.int64)[:, None]
    rel = jg - s0[None, :]
    in_b = (rel >= 0) & (rel < b[None, :])
    dest_np = np.where(in_b, offsets[None, :] + rel, total).astype(np.int32)

    if isinstance(model, UniversalModel):
        key = (model, S, n, layout_key, x64, mk)    # identity-hashed
        if key not in _CHAIN_PROGS:
            mask = jnp.asarray(in_b)
            dest = jnp.asarray(dest_np)

            def universal_chain(chain_keys, anchors):    # keys unused
                def body(carry, inp):
                    c, buf = carry
                    m, d = inp
                    nxt = model.finish_times_jax(c)
                    c = jnp.where(m[None, :], nxt, c)
                    buf = buf.at[:, d].set(c, mode="drop")
                    return (c, buf), None

                buf0 = jnp.zeros((rows, total), dt)
                (c, buf), _ = lax.scan(body, (anchors, buf0), (mask, dest))
                return buf, c

            _prog_cache_put(_CHAIN_PROGS, key,
                            _shard_wrap(universal_chain, mesh,
                                        (P("data"), P("data")),
                                        (P("data"), P("data"))))
        return _CHAIN_PROGS[key]

    sampler = model.jax_sampler
    key = (sampler, S, n, layout_key, x64, mk)
    if key not in _CHAIN_PROGS:
        mask = jnp.asarray(in_b)
        dest = jnp.asarray(dest_np)
        jgd = jnp.arange(jmin, jmax)

        def sampled_chain(chain_keys, anchors):
            def per_seed(ck, anchor):
                def body(carry, inp):
                    tot, buf = carry
                    j, m, d = inp
                    row = sampler(jax.random.fold_in(ck, j))
                    tot = jnp.where(m, tot + row, tot)
                    buf = buf.at[d].set(tot, mode="drop")
                    return (tot, buf), None

                buf0 = jnp.zeros((total,), dt)
                (tot, buf), _ = lax.scan(body, (anchor, buf0),
                                         (jgd, mask, dest))
                return buf, tot

            return jax.vmap(per_seed)(chain_keys, anchors)

        _prog_cache_put(_CHAIN_PROGS, key,
                        _shard_wrap(sampled_chain, mesh,
                                    (P("data"), P("data")),
                                    (P("data"), P("data"))))
    return _CHAIN_PROGS[key]


def _ring_timing_prog(S: int, n: int, K: int, max_delay: int, A: int,
                      mesh=None):
    """Cached timing-only Ringmaster arrival-scan *window*: O(1)
    per-arrival work (version gather, delay test, version scatter) over
    ``A`` pre-merged arrivals, gated by a per-(arrival, seed) ``valid``
    mask and resumed from a carried ``(k, ver, comp)`` state — window
    extensions scan only newly certified arrivals, never the certified
    prefix. Returns ``(k, ver, comp, accept)``; wall-clock times stay
    host-side (the merged order already carries them). With a ``mesh``
    the scan is ``shard_map``ped over the seed/unit columns — the
    recursion is column-independent, so sharding is bitwise-free."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    key = (S, n, K, max_delay, A, bool(jax.config.jax_enable_x64),
           None if mesh is None else _mesh_cache_key(mesh))
    if key in _SCAN_PROGS:
        return _SCAN_PROGS[key]

    R = _mesh_rows(S, mesh)
    rows = jnp.arange(R)

    def run(w_seq, valid, k0, ver0, comp0):         # (A, R) x2, carry-in
        def body(carry, inp):
            k, ver, comp = carry
            w, v = inp
            vw = ver[rows, w]
            live = v & (k < K)
            acc = live & ((k - vw) <= max_delay)
            k = k + acc
            ver = ver.at[rows, w].set(jnp.where(live, k, vw))
            comp = comp + live
            return (k, ver, comp), acc

        (kf, ver, comp), acc = lax.scan(body, (k0, ver0, comp0),
                                        (w_seq, valid))
        return kf, ver, comp, acc                   # acc: (A, R)

    return _prog_cache_put(
        _SCAN_PROGS, key,
        _shard_wrap(run, mesh,
                    (P(None, "data"), P(None, "data"), P("data"),
                     P("data"), P("data")),
                    (P("data"), P("data"), P("data"), P(None, "data"))))


def _arrival_math_prog(problem, gamma, delay_adaptive, S, n, K, max_delay,
                       mesh=None):
    """Math-path arrival-scan *window* (Async and Ringmaster): per
    arrival, one oracle draw at the popped worker's start-iterate
    snapshot, a masked step, and version/snapshot scatters — gated by a
    per-(arrival, seed) ``valid`` mask and resumed from a carried
    ``(k, ver, comp, x, xs)`` state, so window extensions scan only the
    newly certified arrivals. Gradient keys are ``fold_in(seed key,
    global arrival index)`` (the ``pos`` input) — prefix-stable, so
    extensions and chain growth leave already-certified arrivals
    bitwise unchanged. Closes over the oracle: compiles per call, like
    :func:`_general_run`. With a ``mesh`` the seed/unit axis is
    ``shard_map``ped (every column's recursion is independent)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    R = _mesh_rows(S, mesh)
    rows = jnp.arange(R)

    def run(w_seq, valid, pos, gkey_root, k0, ver0, comp0, x0, xs0):
        def body(carry, inp):
            k, ver, comp, x, xs = carry
            w, v, a = inp
            gk = jax.vmap(lambda kk: jax.random.fold_in(kk, a))(gkey_root)
            vw = ver[rows, w]
            live = v & (k < K)
            acc = live & ((k - vw) <= max_delay)
            g = jax.vmap(problem.stoch_grad)(xs[rows, w], gk)
            mult = (1.0 / (1.0 + (k - vw).astype(g.dtype) / n)
                    if delay_adaptive else jnp.ones(R, g.dtype))
            x = jnp.where(acc[:, None], x - gamma * mult[:, None] * g, x)
            val = jax.vmap(problem.f)(x)
            gn = jax.vmap(lambda xx: jnp.sum(problem.grad(xx) ** 2))(x)
            k = k + acc
            ver = ver.at[rows, w].set(jnp.where(live, k, vw))
            xs = xs.at[rows, w].set(
                jnp.where(live[:, None], x, xs[rows, w]))
            comp = comp + live
            return (k, ver, comp, x, xs), (acc, val, gn)

        (kf, ver, comp, x, xs), (acc, val, gn) = lax.scan(
            body, (k0, ver0, comp0, x0, xs0), (w_seq, valid, pos))
        return kf, ver, comp, x, xs, acc, val, gn

    return _shard_wrap(
        run, mesh,
        (P(None, "data"), P(None, "data"), P(None), P("data"), P("data"),
         P("data"), P("data"), P("data"), P("data")),
        (P("data"), P("data"), P("data"), P("data"), P("data"),
         P(None, "data"), P(None, "data"), P(None, "data")))


def _chain_scan_run(model, problem, ringmaster, max_delay, delay_adaptive,
                    n, S, K, gamma, seeds, chain_len=None, mesh=None,
                    meta=None, layout="ragged"):
    """Async/Ringmaster as the ragged, windowed renewal-chain arrival
    scan (module doc): a popped worker restarts immediately whether its
    gradient is used or discarded, so every worker's arrival times form
    a renewal chain that is INDEPENDENT of the server recursion. The
    engine pre-draws per-worker-budgeted chains
    (:func:`_chain_plan_ragged` — the flat worker-major pool is
    ``sum(L_i)`` instead of the rectangle's ``n * max(L_i)``), merges
    the pool into global arrival order (ties by (worker, arrival
    index) — the backend's documented contract, preserved by the
    worker-major ragged layout), and replays the server recursion over
    the *certified* prefix — the arrivals strictly before the seed's
    certified horizon ``h_s = min_i`` (worker ``i``'s last drawn
    time), which provably contains no unmodeled arrival:

    * timing-only Async — no recursion at all: every certified arrival
      is a step, so the first ``K`` merged arrivals ARE the step times;
    * Ringmaster / any math path — a ``lax.scan`` whose body is O(1)
      per arrival (gather the popped worker's version, delay-test,
      masked step, scatter version/snapshot), vs the while_loop's
      O(S·n) argmin per arrival and K serialized pops.

    On chain exhaustion (a seed needs arrivals at or past its horizon)
    the engine does NOT cold-restart: it doubles the budgets, draws
    ONLY the extension slots (fold-in keyed prefix-stable draws,
    anchored sequential accumulation), re-merges, and resumes the scan
    from the carried ``(k, versions, snapshots, x)`` state over only
    the newly certified arrivals — the certified prefix is never
    re-drawn or re-scanned (``meta['scan_ranges']`` records the
    disjoint per-window position ranges). ``layout="rect"`` forces
    uniform rectangular budgets (:func:`_chain_plan`) for benchmarking;
    results are bitwise ``layout="ragged"`` under x64 (resume parity).

    Exactness: identical event order to the serial heap for
    deterministic models in generic position (delayed-gradient math via
    the same per-worker snapshots); distribution-equal for sampled
    models. After :data:`_CHAIN_RETRIES` windows the engine raises
    rather than silently dropping arrivals.

    ``mesh`` shards the chain build and the arrival scan over the
    seed/unit rows (``shard_map`` on the 1-D ``data`` axis; rows must be
    a multiple of the mesh size — the sweep layer pads). The merged pool
    sort and the per-seed compaction stay host-side exactly as in the
    unsharded path, and every device-side row is a pure function of its
    own key, so sharded results are bitwise the unsharded results.
    ``meta`` (if given) collects chain/scan wall times, program-cache
    hits, window count and draw/scan accounting for the routing
    record."""
    import jax
    import jax.numpy as jnp

    from ..kernels.order_stats import smallest_k
    from .time_models import ragged_layout

    math = problem is not None
    with telemetry.span(ENGINE_PREP):
        keys0, x_init = _keys_and_x(problem, S, n, seeds)
        sub = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys0)
        gkey_root, chain_root = sub[:, 0], sub[:, 1]
        if math:
            xs_init = jnp.broadcast_to(x_init[:, None, :],
                                       (S, n) + x_init.shape[1:])

        # Async never discards: the window is exactly K. Ringmaster gets
        # the empirical discard budget (see _ring_pop_budget).
        budget = _ring_pop_budget(n, K, max_delay) if ringmaster else 0
        if chain_len:
            budgets = np.full(n, int(chain_len), np.int64)
        elif layout == "rect":
            budgets = np.full(n, _chain_plan(model, n, K + budget),
                              np.int64)
        elif layout == "ragged":
            budgets = _chain_plan_ragged(model, n, K + budget)
        else:
            raise ValueError(f"unknown chain layout {layout!r}; "
                             "use 'ragged' or 'rect'")
    scan_needed = math or ringmaster
    dt = _engine_dtype()

    # host window state: per-worker drawn slot counts, the canonical
    # worker-major pool segments, and the per-seed progress counters
    drawn = np.zeros(n, np.int64)
    segs = [np.zeros((S, 0)) for _ in range(n)]
    anchors = jnp.zeros((S, n), dt)
    carry = None                        # device scan carry across windows
    c_prev = np.zeros(S, np.int64)      # certified arrivals consumed
    kfin = np.zeros(S, np.int64)
    comp = np.zeros(S, np.int64)
    filled = np.zeros(S, np.int64)      # accepted steps committed
    T = np.zeros((K, S))
    vK = np.zeros((K, S)) if math else None
    gK = np.zeros((K, S)) if math else None
    x = val = gn = None
    if meta is not None:
        meta.setdefault("chain_s", 0.0)
        meta.setdefault("scan_s", 0.0)
        meta.update(layout=layout, windows=0, drawn_slots=[],
                    scan_ranges=[])

    for _ in range(_CHAIN_RETRIES):
        # draw ONLY the extension slots, anchored at the carried last
        # absolute times (window 0: everything, anchored at t = 0)
        ext = budgets - drawn
        with telemetry.span(ENGINE_PREP):
            builder = _chain_builder(model, S, n, ext, starts=drawn,
                                     mesh=mesh)
        with telemetry.span(ENGINE_DISPATCH) as run:
            flat_ext, anchors = builder(chain_root, anchors)
        with telemetry.sync() as done_chain:
            flat_ext = jax.block_until_ready(flat_ext)
        if meta is not None:
            meta["chain_s"] = round(
                meta["chain_s"] + run.seconds + done_chain.seconds, 4)
            meta["windows"] += 1
            meta["drawn_slots"].append(int(ext.sum()))
        ext_np = telemetry.fetch(flat_ext)
        with telemetry.span(ENGINE_PREP):
            eoff, _, _, _ = ragged_layout(ext, drawn)
            for i in range(n):
                segs[i] = np.concatenate(
                    [segs[i], ext_np[:, eoff[i]:eoff[i] + ext[i]]], axis=1)
            drawn = budgets.copy()
            pool = np.concatenate(segs, axis=1)     # canonical (S, total)
            _, widx_flat, _, total = ragged_layout(drawn)
        if meta is not None:
            meta["pool_elems"] = total

        # merged global arrival order + certified horizon per seed
        h = telemetry.fetch(anchors).min(axis=1)    # (S,)
        A_cap = int(min(K + budget, total))
        with telemetry.span(ENGINE_DISPATCH):
            t_seq, idx = smallest_k(jnp.asarray(pool), A_cap)
        t_host, idx = telemetry.fetch((t_seq, idx))  # (S, A_cap) ascending
        w_all = widx_flat[idx]                      # (S, A_cap) worker ids
        done = kfin >= K
        # certified: strictly before the horizon (an arrival AT the
        # horizon could tie with an undrawn slot of the slowest worker)
        c_new = np.array([np.searchsorted(t_host[s], h[s], side="left")
                          for s in range(S)], dtype=np.int64)
        c_new = np.where(done, c_prev,
                         np.clip(c_new, c_prev, A_cap))

        live_seeds = np.flatnonzero(~done)
        p0 = int(c_prev[live_seeds].min()) if live_seeds.size else 0
        p1 = int(c_new.max()) if live_seeds.size else 0

        if not scan_needed:
            # timing-only Async: every certified arrival is a step
            for s in live_seeds:
                take = min(int(c_new[s] - c_prev[s]), K - int(kfin[s]))
                if take > 0:
                    lo = int(c_prev[s])
                    T[int(filled[s]):int(filled[s]) + take, s] = \
                        t_host[s, lo:lo + take]
                    filled[s] += take
                    kfin[s] += take
                    comp[s] += take
            if meta is not None:
                meta["scan_ranges"].append((p0, p1))
        elif p1 > p0:
            W = p1 - p0
            pos_idx = np.arange(p0, p1, dtype=np.int64)
            w_win = jnp.asarray(
                w_all[:, p0:p1].T.astype(np.int32))          # (W, S)
            valid = jnp.asarray(
                (pos_idx[:, None] >= c_prev[None, :])
                & (pos_idx[:, None] < c_new[None, :]))       # (W, S)
            if carry is None:
                k0 = jnp.zeros(S, jnp.int32)
                ver0 = jnp.zeros((S, n), jnp.int32)
                comp0 = jnp.zeros(S, jnp.int32)
                carry = ((k0, ver0, comp0, x_init, xs_init) if math
                         else (k0, ver0, comp0))
            if math:
                with telemetry.span(ENGINE_PREP):
                    prog = _arrival_math_prog(problem, gamma,
                                              delay_adaptive, S, n, K,
                                              max_delay, mesh=mesh)
                    pos = jnp.asarray(pos_idx.astype(np.int32))
                    args = (w_win, valid, pos, gkey_root, *carry)
            else:
                scan_key_known = (
                    S, n, K, max_delay, W,
                    bool(jax.config.jax_enable_x64),
                    None if mesh is None else _mesh_cache_key(mesh)
                ) in _SCAN_PROGS
                if meta is not None:
                    meta["scan_cache_hit"] = scan_key_known
                with telemetry.span(ENGINE_PREP):
                    prog = _ring_timing_prog(S, n, K, max_delay, W,
                                             mesh=mesh)
                    args = (w_win, valid, *carry)
            with telemetry.span(ENGINE_DISPATCH) as run:
                out = prog(*args)
            with telemetry.sync() as done_scan:
                out = jax.block_until_ready(out)
            scan_s = run.seconds + done_scan.seconds
            if math:
                kf, ver, cmp_, x_c, xs_c, acc, v_w, g_w = out
                carry = (kf, ver, cmp_, x_c, xs_c)
                with telemetry.sync() as got:
                    v_w, g_w = jax.device_get((v_w, g_w))
                scan_s += got.seconds
            else:
                kf, ver, cmp_, acc = out
                carry = (kf, ver, cmp_)
            if meta is not None:
                meta["scan_s"] = round(meta["scan_s"] + scan_s, 4)
                meta["scan_ranges"].append((p0, p1))
            kfin, comp, acc = telemetry.fetch((kf, cmp_, acc))
            kfin = kfin.astype(np.int64)
            comp = comp.astype(np.int64)            # acc: (W, S), gated
            for s in live_seeds:
                sel = np.flatnonzero(acc[:, s])
                sel = sel[:K - int(filled[s])]
                got = sel.size
                lo = int(filled[s])
                T[lo:lo + got, s] = t_host[s, p0 + sel]
                if math:
                    vK[lo:lo + got, s] = v_w[sel, s]
                    gK[lo:lo + got, s] = g_w[sel, s]
                filled[s] += got

        c_prev = c_new
        if (kfin >= K).all():
            if math:
                x = carry[3]
                val, gn = vK, gK
            return comp.astype(np.int64), x, T, val, gn
        # exhaustion: double every budget (the extension draws and
        # scans only the new slots/arrivals); Ringmaster's discard
        # budget grows with the pool so the window can absorb storms
        budgets = budgets * 2
        if ringmaster:
            budget = min(budget * 4, int(budgets.sum()) - K)
    raise RuntimeError(
        f"arrival-scan jax engine could not certify chain coverage "
        f"within its per-worker renewal-chain budgets (max "
        f"{int(budgets.max()) // 2} slots) even after doubling windows "
        f"(extreme speed heterogeneity or a discard storm — max_delay "
        f"far below the typical delay?); pass a larger chain_len to "
        f"simulate_batch_jax or use backend='serial'")


def _arrival_while_run(model, problem, max_delay, delay_adaptive, n, S, K,
                       gamma, seeds):
    """PR 4 reference engine — Async/Ringmaster as an arrival-indexed
    ``lax.while_loop``. NOT routed by :func:`simulate_batch_jax` anymore
    (the renewal-chain arrival scan replaced it); kept callable via
    ``async_engine="while"`` as the benchmark baseline
    (``benchmarks/simbatch_speed.py`` gates the scan's speedup against
    it) and as an independent cross-check of the scan's recursion.

    Each
    iteration pops the earliest pending finish per seed (ties by worker
    index), steps unless the gradient's delay exceeds ``max_delay``
    (discard => recompute at the current iterate), and restarts the
    popped worker. The restart costs ONE keyed draw from the pre-split
    per-(seed, worker) key grid — worker streams are pure functions of
    ``(seed value, worker index)``, independent of arrival order (the
    keyed-draw contract, DESIGN.md §3b) — instead of a full ``(S, n)``
    row per arrival. Per-worker start-iterate snapshots (``xs``)
    evaluate delayed gradients at the iterate they started from, exactly
    like the event engine's snapshot dict. Returns per-step time/value
    buffers."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .time_models import jax_worker_key_grid

    math = problem is not None
    with telemetry.span(ENGINE_PREP):
        keys0, x_init = _keys_and_x(problem, S, n, seeds)
        xs_init = jnp.broadcast_to(x_init[:, None, :],
                                   (S, n) + x_init.shape[1:])

    fixed = isinstance(model, FixedTimes)
    universal = isinstance(model, UniversalModel)
    sampled = not fixed and not universal
    if fixed:
        taus = jnp.asarray(model.taus)
    elif sampled:
        item = model.jax_sampler_item
        if item is None:
            # correct fallback for user models without a single-draw
            # sampler: draw the row, keep one column (~n× draw volume)
            row_sampler = model.jax_sampler

            def item(key, i):
                return row_sampler(key)[i]

    rows = jnp.arange(S)
    widx = jnp.arange(n)
    # Async pops exactly K arrivals. Ringmaster also pays discards, but
    # a worker can only be re-discarded after another step lands, so
    # each worker is discarded at most K+1 times: arrivals are bounded
    # by K accepts + n*(K+1) discards. The cap is that bound plus slack
    # and only guards against a broken recursion — the caller verifies
    # every seed reached K and raises otherwise.
    cap = (K + 1) * (n + 2) + 64

    def cond(carry):
        it, ft, ver, k = carry[0], carry[1], carry[2], carry[3]
        return jnp.any(k < K) & (it < cap)

    def body(carry):
        it, ft, ver, k, comp, x, xs, keys, grid, Tb, vb, gb = carry
        sub = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
        keys = sub[:, 0]
        w = jnp.argmin(ft, axis=1)                # earliest pending pop
        t = ft[rows, w]
        delay = k - ver[rows, w]
        active = k < K
        accept = active & (delay <= max_delay)
        kc = jnp.clip(k, 0, K - 1)
        if math:
            g = jax.vmap(problem.stoch_grad)(xs[rows, w], sub[:, 1])
            # g.dtype, not a hard-coded float32: under x64=True the
            # carry is float64 and a float32 mult would silently down-
            # cast the step (the scan engine already derives its dtype).
            mult = (1.0 / (1.0 + delay.astype(g.dtype) / n)
                    if delay_adaptive else jnp.ones(S, g.dtype))
            x = jnp.where(accept[:, None],
                          x - gamma * mult[:, None] * g, x)
            val = jax.vmap(problem.f)(x)
            gn = jax.vmap(lambda xx: jnp.sum(problem.grad(xx) ** 2))(x)
            vb = vb.at[rows, kc].set(jnp.where(accept, val, vb[rows, kc]))
            gb = gb.at[rows, kc].set(jnp.where(accept, gn, gb[rows, kc]))
        Tb = Tb.at[rows, kc].set(jnp.where(accept, t, Tb[rows, kc]))
        k = k + accept.astype(k.dtype)
        # restart the popped worker: one keyed draw (or inversion)
        if fixed:
            ftw = t + taus[w]
        elif universal:
            ftw = model.finish_times_jax(t, workers=w)
        else:
            kw = jax.vmap(jax.random.split)(grid[rows, w])  # (S, 2, 2)
            grid = grid.at[rows, w].set(kw[:, 0])
            ftw = t + jax.vmap(item)(kw[:, 1], w)
        ft = ft.at[rows, w].set(jnp.where(active, ftw, ft[rows, w]))
        ver = ver.at[rows, w].set(jnp.where(active, k, ver[rows, w]))
        xs = xs.at[rows, w].set(jnp.where(active[:, None], x, xs[rows, w]))
        comp = comp + active.astype(comp.dtype)
        return (it + 1, ft, ver, k, comp, x, xs, keys, grid, Tb, vb, gb)

    @jax.jit
    def run(keys):
        sub = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
        if fixed:
            grid = jnp.zeros((1, 1, 2), jnp.uint32)        # unused
            ft0 = jnp.broadcast_to(taus, (S, n))
        elif universal:
            grid = jnp.zeros((1, 1, 2), jnp.uint32)        # unused
            ft0 = model.finish_times_jax(jnp.zeros((S, n)))
        else:
            grid = jax_worker_key_grid(sub[:, 1], n)       # (S, n, 2)
            kk = jax.vmap(jax.vmap(jax.random.split))(grid)
            grid = kk[:, :, 0]
            ft0 = jax.vmap(jax.vmap(item))(
                kk[:, :, 1], jnp.broadcast_to(widx, (S, n)))
        init = (jnp.zeros((), jnp.int32), ft0,
                jnp.zeros((S, n), jnp.int32), jnp.zeros(S, jnp.int32),
                jnp.zeros(S, jnp.int32), x_init, xs_init, sub[:, 0], grid,
                jnp.zeros((S, K)), jnp.zeros((S, K)), jnp.zeros((S, K)))
        out = lax.while_loop(cond, body, init)
        _, _, _, k, comp, x, _, _, _, Tb, vb, gb = out
        return k, comp, x, Tb.T, vb.T, gb.T      # (K, S) like the scans

    with telemetry.span(ENGINE_DISPATCH):
        out = run(keys0)
    kfin, comp, x, T, val, gn = telemetry.wait(out)
    if int(np.min(telemetry.fetch(kfin))) < K:
        raise RuntimeError(
            f"arrival-indexed jax backend hit its {cap}-arrival cap "
            f"before finishing K={K} iterations (max_delay too tight?); "
            f"use backend='serial'")
    return comp, x, T, val, gn


def simulate_batch_jax(strategy: AggregationStrategy,
                       model,
                       K: int,
                       problem: Optional[JaxProblem] = None,
                       gamma: float = 0.0,
                       seeds: Sequence[int] = (0,),
                       record_every: int = 1,
                       use_pallas: bool = False,
                       malenia_chain: Optional[int] = None,
                       async_chain: Optional[int] = None,
                       async_engine: str = "scan",
                       async_layout: str = "ragged",
                       x64: bool = False) -> List[Trace]:
    """One jitted ``(seeds, ...)`` array program per strategy family
    (m-sync round scan, Rennala/Malenia renewal scans, Async/Ringmaster
    arrival scan); returns the per-seed :class:`Trace` list
    (timing-only traces have empty arrays, like the scalar fast path).

    RNG/backend guarantees: every draw comes from ``jax.random`` keys
    derived from ``PRNGKey(seed)`` — per-seed reproducible, sweep-
    independent, equal in distribution to (never stream-equal with) the
    NumPy engines; deterministic models (FixedTimes, universal) match
    the NumPy engines to float tolerance in generic position, with ties
    broken by worker index. ``malenia_chain`` overrides the Malenia
    engine's per-round renewal-chain length — the default
    ``max(2*ceil(S), ceil(3*spread) + ceil(S), 8)`` scales with the
    model's mean-speed spread ``max(tau)/min(tau)`` (fast workers keep
    arriving while the slowest delivers its first), so strongly
    heterogeneous models allocate ``(seeds, n, L+1)`` chains with large
    ``L``; the engine retries with doubled chains, then raises, if a
    round outruns them. ``async_chain`` is the analogous override for
    the Async/Ringmaster arrival-scan chains (default from
    :func:`_chain_plan_ragged`); ``async_engine="while"`` falls back to
    the PR 4 ``lax.while_loop`` reference engine (benchmarking/
    cross-checks only). ``async_layout`` picks the arrival-scan chain
    layout: ``"ragged"`` (default — per-worker budgets proportional to
    mean rates) or ``"rect"`` (uniform rectangular budgets, the
    pre-windowed baseline); both produce identical results (bitwise
    under x64) since certified arrivals are layout-independent.

    ``x64=True`` runs the whole program in float64 (via
    ``jax.enable_x64``): slower, but gives per-run tie
    parity with the float64 NumPy event heap on adversarially tie-heavy
    instances (flat-power partial participation) where float32
    tie-breaking diverges by whole events.

    The FixedTimes timing-only m-sync case and the timing-only
    arrival-scan programs hit module-level jit caches (no recompile
    across calls of the same shape). The other unsharded m-sync round
    scans are cached per shape, parameters and law, the law keyed by
    value (see :func:`_general_run`), so a fresh model object of the
    same law reuses the program; the other families key their cached
    programs by model and oracle identity, so a new object recompiles
    — fine for sweep-sized S × K, not for tight loops of tiny calls.
    """
    import jax
    import jax.numpy as jnp

    if x64 and not jax.config.jax_enable_x64:
        with jax.enable_x64(True):
            return simulate_batch_jax(
                strategy, model, K, problem=problem, gamma=gamma,
                seeds=seeds, record_every=record_every,
                use_pallas=use_pallas, malenia_chain=malenia_chain,
                async_chain=async_chain, async_engine=async_engine,
                async_layout=async_layout, x64=False)

    strategy.bind(model.n)
    kind = _check_supported(strategy, model, problem)
    n = model.n
    S = len(seeds)
    K = int(K)
    if K <= 0:
        raise ValueError(f"K={K} must be positive for the jax backend")

    if isinstance(model, UniversalModel) and problem is None and S > 1:
        # universal timing-only runs are deterministic (finish-time
        # inversions, no draws): compute one seed, replicate the Trace
        row = simulate_batch_jax(strategy, model, K, problem=None,
                                 gamma=gamma, seeds=[seeds[0]],
                                 record_every=record_every,
                                 use_pallas=use_pallas,
                                 malenia_chain=malenia_chain,
                                 async_chain=async_chain,
                                 async_engine=async_engine,
                                 async_layout=async_layout)
        return [dataclasses.replace(row[0]) for _ in range(S)]

    fixed = isinstance(model, FixedTimes)
    math = problem is not None

    if kind == "msync":
        m = strategy._m
        used = m * K
        if fixed and not math:
            global _fixed_timing_jit
            if _fixed_timing_jit is None:
                _fixed_timing_jit = jax.jit(
                    _fixed_timing_run,
                    static_argnames=("S", "m", "K", "use_pallas"))
            with telemetry.span(ENGINE_DISPATCH):
                out = _fixed_timing_jit(jnp.asarray(model.taus), S=S, m=m,
                                        K=K, use_pallas=use_pallas)
            comp, T = telemetry.wait(out)
            x = val = gn = None
        else:
            comp, x, T, val, gn = _general_run(model, problem, m, n, S, K,
                                               gamma, use_pallas, seeds)
    elif kind == "rennala":
        used = int(strategy.batch) * K
        comp, x, T, val, gn = _rennala_run(model, problem,
                                           int(strategy.batch), n, S, K,
                                           gamma, use_pallas, seeds)
    elif kind == "malenia":
        comp, x, T, val, gn, used = _malenia_run(
            model, problem, float(strategy.S), n, S, K, gamma, seeds,
            chain_len=malenia_chain)
    elif kind == "ringleader":
        comp, x, T, val, gn, used = _ringleader_run(
            model, problem, n, S, K, gamma, seeds, chain_len=async_chain)
    else:
        used = K          # every server step consumes exactly one gradient
        md = (int(strategy.max_delay)
              if kind in ("ringmaster", "optimal_asgd") else K + 1)
        adaptive = bool(getattr(strategy, "delay_adaptive", False))
        if async_engine == "while":               # PR 4 reference engine
            comp, x, T, val, gn = _arrival_while_run(
                model, problem, md, adaptive, n, S, K, gamma, seeds)
        elif async_engine == "scan":
            comp, x, T, val, gn = _chain_scan_run(
                model, problem, kind in ("ringmaster", "optimal_asgd"),
                md, adaptive, n, S, K, gamma, seeds, chain_len=async_chain,
                layout=async_layout)
        else:
            raise ValueError(f"unknown async_engine {async_engine!r}; "
                             "use 'scan' or 'while'")

    return assemble_traces(comp, x, T, val, gn, used, S, K, record_every,
                           problem)


def assemble_traces(comp, x, T, val, gn, used, S, K, record_every,
                    problem) -> List[Trace]:
    """Package raw engine outputs (``comp (S,)``, ``T/val/gn (K, S)``,
    ``x (S, d)``) into the per-seed :class:`Trace` list — shared by
    :func:`simulate_batch_jax` and the sharded sweep backend, so both
    paths produce structurally identical traces from identical arrays.
    Every device array comes to the host in one fetch."""
    import jax.numpy as jnp

    math = problem is not None
    with telemetry.span("repro.batch.assemble"):
        if math:
            x0j = jnp.asarray(problem.x0, dtype=_engine_dtype())
            comp, T, used, val, gn, x, f0, g0 = telemetry.fetch(
                (comp, T, used, val, gn, x, problem.f(x0j),
                 problem.grad(x0j)))
        else:
            comp, T, used = telemetry.fetch((comp, T, used))
        T = np.asarray(T)                         # (K, S)
        used = np.broadcast_to(np.asarray(used), (S,))  # malenia: per seed
        total = T[-1]
        traces: List[Trace] = []
        if math:
            rec = np.arange(record_every, K + 1, record_every)  # steps k
            g0 = np.asarray(g0)
            gn0 = float(np.dot(g0, g0))
            for s in range(S):
                times = np.concatenate([[0.0], T[rec - 1, s]])
                vals = np.concatenate([[float(f0)], val[rec - 1, s]])
                gns = np.concatenate([[gn0], gn[rec - 1, s]])
                traces.append(Trace(times, vals, gns, iterations=K,
                                    total_time=float(total[s]),
                                    gradients_used=int(used[s]),
                                    gradients_computed=int(comp[s]),
                                    x_final=np.asarray(x)[s]))
        else:
            e = np.array([])
            for s in range(S):
                traces.append(Trace(e, e, e, iterations=K,
                                    total_time=float(total[s]),
                                    gradients_used=int(used[s]),
                                    gradients_computed=int(comp[s])))
    return traces
