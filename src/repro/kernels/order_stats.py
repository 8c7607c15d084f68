"""Top-m partial-sort / m-th order statistic kernels.

The batched m-sync simulator (:mod:`repro.core.batch_jax`) needs the m-th
smallest candidate finish time per round — an ``O(m · n)`` partial
selection, not a full sort. XLA's CPU ``lax.top_k``/``sort`` lowerings
are per-round catastrophically slow (~2 ms for ``(32, 1000)``, dominating
the whole scan), so the default path is an *iterative tie-class
extraction* built from elementwise ops only, which XLA fuses into the
surrounding scan body: repeatedly drop the current row minimum's whole
tie class and remember the value once ``m`` elements have been covered.
For ``m = n`` the statistic degenerates to ``max``; for large ``m < n``
we fall back to ``lax.top_k`` (fine on TPU, the intended accelerator).

``mth_smallest_pallas`` is the same selection as a Pallas TPU kernel
(whole block in VMEM, ``fori_loop`` extraction): interpreted on CPU,
compiled on TPU (:mod:`repro.kernels.platform`).

For large ``m`` (``m > _MAX_ITERATIVE_M``, the Rennala/Malenia
``batch >> 64`` pools) the extraction loop's ``O(m · n)`` cost loses, but
``lax.top_k`` still forces the slow XLA sort lowering out of the fused
scan body. ``mth_smallest_counting`` keeps big-batch selection on the
fused path: a value-domain counting bisection (elementwise
``count(x <= mid)`` passes only) narrows an interval around the
statistic, a short snap loop lands on the exact element, and the result
is *verified* by rank counts — the rare unverified row (pathological tie
mass at the row minimum) falls back to ``lax.top_k`` behind a
``lax.cond``, so correctness never depends on the bisection converging.

Tie semantics everywhere: the m-th order statistic counts multiplicity
(``mth_smallest(x, m) == jnp.sort(x)[..., m-1]``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .platform import pallas_call

__all__ = ["mth_smallest", "mth_smallest_iterative", "mth_smallest_counting",
           "mth_smallest_rowwise", "mth_smallest_pallas", "smallest_k"]

# above this m the O(m*n) extraction loop loses to top_k even on CPU
_MAX_ITERATIVE_M = 64

# counting selection: value-bisection passes, then snap-to-element passes
_COUNT_BISECT_ITERS = 26
_COUNT_SNAP_ITERS = 8


def _extract_mth_keep(x: jnp.ndarray, m: int) -> jnp.ndarray:
    """The shared tie-class-extraction loop (plain jax AND Pallas body),
    returning the statistic with a kept trailing axis: ``(..., 1)``.

    Each of the ``m`` iterations removes the entire tie class of the
    running minimum, so duplicated values are counted with multiplicity
    and the loop stops taking values (per row) once ``m`` elements are
    covered. Elementwise ops only — fuses into enclosing scans. The loop
    carry is all ``(..., 1)`` int32/float arrays (no 1-D or bool
    vectors), the form the TPU kernel compiler can carry through an
    ``scf.for``.
    """
    keep = x.shape[:-1] + (1,)

    def body(_, carry):
        rest, killed, val, done = carry
        mn = rest.min(axis=-1, keepdims=True)
        tie = rest == mn
        # explicit int32: under x64 a bool sum defaults to int64, which
        # would promote the carried counter and break the fori_loop carry
        killed = killed + tie.astype(jnp.int32).sum(axis=-1, keepdims=True,
                                                    dtype=jnp.int32)
        hit = (done == 0) & (killed >= m)
        val = jnp.where(hit, mn, val)
        done = jnp.where(hit, 1, done)
        rest = jnp.where(tie, jnp.inf, rest)
        return rest, killed, val, done

    init = (x, jnp.zeros(keep, jnp.int32), jnp.zeros(keep, x.dtype),
            jnp.zeros(keep, jnp.int32))
    _, _, val, _ = lax.fori_loop(0, m, body, init)
    return val


def mth_smallest_iterative(x: jnp.ndarray, m: int) -> jnp.ndarray:
    """m-th smallest along the last axis via tie-class extraction."""
    return _extract_mth_keep(x, m)[..., 0]


def _counting_select(x: jnp.ndarray, m: int):
    """Value-domain counting bisection for the m-th smallest.

    Returns ``(value, verified)``: per-row candidates plus one scalar
    flag that every row's candidate passed the exact rank check
    (``count(x < v) < m <= count(x <= v)``). Elementwise ops only, so
    XLA fuses the whole selection into an enclosing scan body — no
    ``sort``/``top_k`` lowering on the hot path.
    """
    batch = x.shape[:-1]
    # invariants: count(x <= lo) < m (lo below the whole row at start),
    # count(x <= hi) >= m (hi is the row max, count = n >= m)
    lo = x.min(axis=-1) - 1.0
    hi = x.max(axis=-1)

    def bisect(_, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        ge = (x <= mid[..., None]).sum(axis=-1) >= m
        return jnp.where(ge, lo, mid), jnp.where(ge, mid, hi)

    lo, hi = lax.fori_loop(0, _COUNT_BISECT_ITERS, bisect, (lo, hi))

    # snap to the smallest element above lo; while the interval is still
    # wider than the gap between distinct row values, sub-threshold
    # elements can sit in (lo, answer) — advance lo past them (each
    # iteration consumes at least one tie class, and after the bisection
    # above more than one leftover is pathological)
    def cond(c):
        _, _, done, it = c
        return jnp.any(~done) & (it < _COUNT_SNAP_ITERS)

    def body(c):
        lo, val, done, it = c
        cand = jnp.where(x > lo[..., None], x, jnp.inf).min(axis=-1)
        ok = (x <= cand[..., None]).sum(axis=-1) >= m
        val = jnp.where(done, val, cand)
        lo = jnp.where(done | ok, lo, cand)
        return lo, val, done | ok, it + 1

    _, val, done, _ = lax.while_loop(
        cond, body,
        (lo, jnp.zeros(batch, x.dtype), jnp.zeros(batch, bool),
         jnp.zeros((), jnp.int32)))
    exact = ((x < val[..., None]).sum(axis=-1) < m) & done
    return val, jnp.all(exact)


def mth_smallest_counting(x: jnp.ndarray, m: int) -> jnp.ndarray:
    """m-th smallest along the last axis via counting bisection.

    The big-``m`` fused-path selection (``batch >> 64`` Rennala/Malenia
    pools): elementwise counting passes instead of a ``top_k`` sort
    lowering. Self-verifying — rows the bisection cannot certify fall
    back to ``lax.top_k`` behind a ``lax.cond`` (paid only when taken).
    """
    val, ok = _counting_select(x, m)
    return lax.cond(ok, lambda: val,
                    lambda: -lax.top_k(-x, m)[0][..., m - 1])


def mth_smallest_rowwise(x: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """m-th smallest along the last axis with a TRACED per-row ``m``.

    The sharded sweep backend fuses grid points with different ``m``
    into one compiled program, so ``m`` arrives as an ``(rows,)`` int32
    tensor instead of a static Python int. :func:`_counting_select`
    only consumes ``m`` through rank comparisons, so the same
    elementwise bisection works unchanged; the unverified-row fallback
    swaps ``lax.top_k`` (static ``k`` only) for a full-sort gather,
    paid only when the ``lax.cond`` is actually taken. Tie semantics
    are identical to :func:`mth_smallest`: the statistic counts
    multiplicity, so the snapped value equals
    ``jnp.sort(x)[..., m-1]`` bitwise (both select an element of
    ``x``).
    """
    m = jnp.asarray(m, jnp.int32)
    val, ok = _counting_select(x, m)

    def sort_select():
        order = jnp.sort(x, axis=-1)
        return jnp.take_along_axis(order, (m - 1)[..., None],
                                   axis=-1)[..., 0]

    return lax.cond(ok, lambda: val, sort_select)


def smallest_k(x, k: int, *, prefer_host: bool = None):
    """``(values, indices)`` of the ``k`` smallest entries per row in
    ascending order, ties broken by index (stable).

    This is the arrival-scan async engine's ONE-TIME merge of the
    ``(S, n*L)`` renewal-chain pool into global arrival order — it runs
    *between* jitted programs, not inside one, so the backend is free to
    pick the fastest sort for the platform:

    * **host** (default on CPU) — NumPy's stable argsort. XLA's CPU sort
      lowering is catastrophically slow for this shape (~115 ms for
      ``(32, 16000)`` vs ~15 ms in NumPy), the same lowering problem
      that motivated the iterative/counting selections above.
    * **device** (default on accelerators) — ``jnp.argsort`` keeps the
      pool resident; TPU/GPU sorts don't share the CPU lowering cliff.

    The host path is NOT jit-traceable (it materializes ``x``); pass
    ``prefer_host=False`` to force the device sort if you must call this
    under a trace.

    **Tie contract (rectangular AND ragged pools).** Equal values order
    by flat index — a stable sort in both backends. The rectangular
    ``(S, n, L)`` pool flattens worker-major, so ties break by (worker,
    within-worker arrival index); the ragged layout
    (:func:`repro.core.time_models.ragged_layout`) keeps that contract
    *by construction*: its flat buffer is still worker-major (worker
    ``i``'s whole budget precedes worker ``i+1``'s), so
    ``widx[flat_index]`` is nondecreasing and equal arrival times
    resolve to the same (worker, slot) winner as the rectangle would —
    which is why uniform-budget ragged runs are bitwise equal to
    rectangular ones even through tie rounds.

    **Full-merge fast path.** The ragged pool is sized to the arrival
    budget, so the arrival-scan engine routinely asks for ``k == n``
    (merge the ENTIRE pool) where the rectangular layout asked for a
    small prefix of a huge pool. For ``k == n`` the post-sort slice is
    skipped — NumPy's trailing slice would alias anyway, but on device
    the elided slice op lets XLA return the argsort buffer as-is
    instead of staging a copy of the full ``(S, n)`` order.
    """
    n = x.shape[-1]
    if not 1 <= k <= n:
        raise ValueError(f"k={k} out of range [1, {n}]")
    if prefer_host is None:
        prefer_host = jax.default_backend() == "cpu"
    if prefer_host and not isinstance(x, jax.core.Tracer):
        import numpy as np
        xh = np.asarray(x)
        order = np.argsort(xh, axis=-1, kind="stable")
        if k < n:
            order = order[..., :k]
        return (jnp.asarray(np.take_along_axis(xh, order, axis=-1)),
                jnp.asarray(order))
    order = jnp.argsort(x, axis=-1, stable=True)
    if k < n:
        order = order[..., :k]
    return jnp.take_along_axis(x, order, axis=-1), order


def _mth_smallest_kernel(m: int, x_ref, o_ref):
    o_ref[...] = _extract_mth_keep(x_ref[...], m)


def mth_smallest_pallas(x: jnp.ndarray, m: int) -> jnp.ndarray:
    """Pallas top-m partial-sort kernel: ``(S, n) -> (S,)``.

    One VMEM-resident block; the selection loop never leaves on-chip
    memory. Interpreted on CPU, compiled on TPU
    (:func:`repro.kernels.platform.pallas_call`).
    """
    if x.ndim != 2:
        raise ValueError(f"expected (rows, n), got {x.shape}")
    out = pallas_call(
        functools.partial(_mth_smallest_kernel, m),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], 1), x.dtype),
    )(x)
    return out[:, 0]


def mth_smallest(x: jnp.ndarray, m: int, *,
                 use_pallas: bool = False) -> jnp.ndarray:
    """m-th smallest along the last axis, backend chosen by shape/flags."""
    n = x.shape[-1]
    if not 1 <= m <= n:
        raise ValueError(f"m={m} out of range [1, {n}]")
    if use_pallas:
        shape = x.shape
        return mth_smallest_pallas(x.reshape(-1, n), m).reshape(shape[:-1])
    if m == n:
        return x.max(axis=-1)
    if m <= _MAX_ITERATIVE_M:
        return mth_smallest_iterative(x, m)
    return mth_smallest_counting(x, m)
