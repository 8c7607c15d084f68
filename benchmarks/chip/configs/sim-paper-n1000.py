"""Plain reference for the ``sim-paper-n1000`` deployment.

m-Synchronous SGD (Algorithm 3 of arXiv 2602.03802) as a straightforward
event loop over per-worker clocks, written from the algorithm's
definition and importing nothing of ``repro``.

m-sync, round ``k`` (the server holds iterate ``x^k``):

* a worker still computing at an older version is stale: when its result
  arrives (time ``ft``) it is discarded, counted as computed, and the
  worker restarts at ``ft`` on ``x^k``;
* the round ends at ``T_k``, the ``m``-th earliest arrival at version
  ``k`` (ties by worker index); those ``m`` results are used, and their
  workers restart at ``T_k`` on ``x^{k+1}``;
* a version-``k`` result that arrives after ``T_k`` is stale in round
  ``k + 1``.

Draws follow the simulator's keyed streams (``jax.random`` threefry),
generated here from the seed alone: ``carry, k0 = split(PRNGKey(seed))``
gives the first durations ``exponential(k0, (n,)) / lam``; each round
``carry, k1, k2, _ = split(carry, 4)``: ``k1`` for restarts after a
discard during the round, ``k2`` for restarts after the step.

Precision: a worker's clock is a running sum kept in ``clock`` (the
configuration's float32: the clocks fix the event order). The control
passes ``bfloat16``.
"""

from __future__ import annotations

import functools

import ml_dtypes
import numpy as np

DTYPES = {"float64": np.float64, "float32": np.float32,
          "bfloat16": ml_dtypes.bfloat16}


def _rounder(name):
    dt = DTYPES[name]
    if dt is np.float64:
        return lambda a: np.asarray(a, np.float64)
    return lambda a: np.asarray(a, np.float64).astype(dt).astype(np.float64)


# ------------------------------------------------------------------ draws

@functools.lru_cache(maxsize=None)
def _msync_draws_fn(n: int, K: int, lam: float):
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def draws(key):
        carry, k0 = jax.random.split(key, 2)
        d0 = jax.random.exponential(k0, (n,)) / lam

        def body(c, _):
            c, k1, k2, _ = jax.random.split(c, 4)
            return c, jnp.stack([jax.random.exponential(k1, (n,)) / lam,
                                 jax.random.exponential(k2, (n,)) / lam])

        _, d = lax.scan(body, carry, None, length=K)
        return d0, d

    return draws


def _key(seed: int):
    import jax

    return jax.random.PRNGKey(int(seed))


# ----------------------------------------------------------------- m-sync

def msync(seed: int, n: int, K: int, m: int, lam: float,
          clock: str = "float32") -> dict:
    d0, d = _msync_draws_fn(n, K, float(lam))(_key(seed))
    return msync_from_draws(np.asarray(d0), np.asarray(d), K, m, clock)


def msync_from_draws(d0, d, K: int, m: int, clock: str = "float32") -> dict:
    """m-sync over given durations: ``d0`` (n,) first, ``d[k, 0]`` (n,)
    restarts after a discard in round ``k``, ``d[k, 1]`` after its step."""
    c = _rounder(clock)
    d0, d = c(d0), c(d)
    n = d0.shape[0]
    ft = d0                          # finish time of each worker's work
    ver = np.zeros(n, np.int64)      # version each worker computes at
    comp = 0
    T = 0.0
    for k in range(K):
        stale = ver < k
        cand = np.where(stale, c(ft + d[k, 0]), ft)
        T = np.partition(cand, m - 1)[m - 1]
        acc = cand < T
        ties = np.flatnonzero(cand == T)
        acc[ties[:m - int(acc.sum())]] = True
        popped = stale & (ft < T)
        comp += m + int(popped.sum())
        ft = np.where(popped, cand, ft)
        ver = np.where(popped, k, ver)
        ft = np.where(acc, c(T + d[k, 1]), ft)
        ver = np.where(acc, k + 1, ver)
    return {"total_time": float(T), "gradients_computed": comp,
            "gradients_used": K * m}


# ------------------------------------------------------------------ entry

def simulate(strategy: str, params: dict, cfg: dict, seed: int,
             clock: str = "float32") -> dict:
    """One seed of one grid point: total simulated time, gradients
    computed and used."""
    n, K, lam = cfg["n"], cfg["K"], cfg["law"]["lam"]
    if strategy == "msync":
        return msync(seed, n, K, int(params["m"]), lam, clock)
    raise KeyError(f"the sim-paper-n1000 reference has no {strategy!r}")
