"""Computation-time models from the paper.

Implements the paper's four worker-compute assumptions:

* Assumption 2.2 — Fixed computation model: worker ``i`` always takes
  ``tau_i`` seconds per stochastic gradient.
* Assumption 3.1 — Random computation model: worker ``i``'s time is a
  ``(tau_i, R)``-sub-exponential random variable (mean ``tau_i``,
  ``E[exp(|t - tau_i| / R)] <= 2``, nonnegative a.s.).
* Assumption 5.1 — Universal computation model: worker ``i`` has an
  integrable computation *power* ``v_i(t) >= 0`` and computes
  ``N_i(t0, t1) = floor(int_{t0}^{t1} v_i)`` gradients in ``[t0, t1]``.
* Assumption 5.4 — Partial participation: all powers equal ``v`` except an
  (arbitrary, possibly adversarial) set of at most ``p*n`` stragglers at any
  instant.

All models expose a unified event-simulator interface::

    sample_time(i, rng) -> float          # seconds for ONE gradient started now
    sample_times(workers, rng) -> array   # batched draw for many workers
    (Universal models instead expose ``time_for_integral`` /
    ``finish_times(workers, t_start)``.)

``sample_times`` is the engine's hot path: models with closed-form or
vectorizable distributions override it (``FixedTimes`` is a pure gather;
the distribution factories below install NumPy-vectorized samplers), so a
round that restarts many workers costs one vector op instead of ``n``
Python calls. The default falls back to per-worker ``sample_time`` calls
in worker order, which keeps the RNG stream identical to the scalar path.
``sample_times_tensor`` is the multi-seed sweep engine's bulk draw: the
entire ``(seeds, rounds, workers)`` time tensor in one call per model,
either from per-seed Philox counter streams (``rng_scheme="counter"``,
the fast sweep default) or replaying the scalar per-round stream order
(``"stream"``). Every ``SubExponentialTimes`` factory also carries a
``jax_sampler`` for the device-resident ``simulate_batch`` backend, and
``UniversalModel.finish_times`` is a batched closed-form inversion of
the cumulative-power grid (the event engine's universal hot path).

Every random model also reports its ``(tau_i, R)`` sub-exponential
certificate where known, so the theory in :mod:`repro.core.complexity` can be
evaluated against the exact constants used by the simulator.

Device-resident hooks (the ``backend="jax"`` engines in
:mod:`repro.core.batch_jax` consume these):

* ``SubExponentialTimes.jax_sampler(key) -> (n,)`` — one full round of
  per-worker times (every in-tree factory installs it);
* ``SubExponentialTimes.jax_sampler_item(key, i) -> scalar`` — ONE draw
  from worker ``i``'s marginal, for arrival-indexed recursions that
  restart a single worker per event (the keyed Async/Ringmaster path —
  one draw per arrival instead of a full ``(seeds, n)`` row);
* :func:`jax_worker_key_grid` — the pre-split ``(seeds, workers)``
  counter-key grid those keyed draws consume: worker ``i``'s stream
  under seed ``s`` is a pure function of ``(s, i)``, independent of
  arrival order and of which other seeds are in the sweep (the
  ``jax.random`` twin of the ``rng_scheme="counter"`` contract);
* ``UniversalModel.finish_times_jax`` — the jit-compatible twin of
  ``finish_times`` (batched ``searchsorted`` on the cumulative-power
  grid + the same closed-form quadratic segment inversion), which lets
  universal/partial-participation scenarios run inside jitted sweeps.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence

import numpy as np

__all__ = [
    "TimeModel",
    "FixedTimes",
    "SubExponentialTimes",
    "philox_rngs",
    "jax_worker_key_grid",
    "jax_chain_draws",
    "ragged_layout",
    "jax_chain_draws_ragged",
    "truncated_normal_times",
    "exponential_times",
    "shifted_exponential_times",
    "gamma_times",
    "uniform_times",
    "chi2_times",
    "UniversalModel",
    "PartialParticipationModel",
    "PiecewisePower",
    "powers_figure3",
    "powers_figure4",
]


def philox_rngs(seeds: Sequence[int]) -> list:
    """One counter-based generator per seed (Philox, 128-bit spawn key).

    ``philox_rngs([s])[0]`` depends only on the seed *value* ``s`` — not
    on the position of ``s`` in the sweep or on the other seeds — so any
    sweep that includes seed ``s`` draws the same row for it. These are
    the ``rng_scheme="counter"`` streams: independent of (and therefore
    NOT stream-equal to) the ``np.random.default_rng(s)`` streams the
    scalar ``simulate()`` path consumes.
    """
    return [np.random.Generator(np.random.Philox(
        key=np.random.SeedSequence(int(s)).generate_state(2, np.uint64)))
        for s in seeds]


def jax_worker_key_grid(seed_keys, n: int):
    """Pre-split ``(seeds, workers)`` ``jax.random`` key grid.

    ``grid[s, i]`` roots worker ``i``'s independent draw stream under
    seed ``s``: arrival-indexed engines split one fresh subkey off
    ``grid[s, i]`` per arrival of worker ``i``, so a worker's stream is
    a pure function of ``(seed value, worker index)`` — independent of
    the arrival order, of the other workers, and of which other seeds
    are in the sweep. This is the ``jax.random`` counter-key twin of the
    NumPy :func:`philox_rngs` contract (``rng_scheme="counter"``): NOT
    stream-equal to any NumPy path, reproducible per seed value.

    ``seed_keys`` is a sequence of seed ints or an already-built
    ``(seeds, 2)`` raw ``uint32`` key array (e.g. one branch of a
    ``jax.random.split``, to keep the grid disjoint from other streams
    derived from the same seed).
    """
    import jax
    import jax.numpy as jnp

    if getattr(seed_keys, "ndim", None) != 2:
        seed_keys = jnp.stack(
            [jax.random.PRNGKey(int(s)) for s in seed_keys])
    return jax.vmap(lambda k: jax.random.split(k, n))(seed_keys)


def jax_chain_draws(chain_keys, L: int, row_sampler):
    """``(seeds, L, workers)`` renewal-chain duration rows for the
    arrival-scan async engine.

    Row ``(s, j)`` is ``row_sampler(fold_in(chain_keys[s], j))`` — ONE
    vectorized draw of every worker's ``j``-th renewal duration (the
    model's ``jax_sampler``), so the whole chain pool costs ``S * L``
    key derivations instead of ``S * n * L`` per-item draws. Cumulative
    sums along ``j`` turn the rows into each worker's arrival chain.

    Contract (the arrival-scan twin of the :func:`philox_rngs` /
    :func:`jax_worker_key_grid` counter contracts): row ``(s, j)`` is a
    pure function of *(seed key, slot j)* via ``jax.random.fold_in`` —
    independent of ``L`` (**prefix-stable**: growing ``L`` appends rows
    and never reshuffles existing ones, which the engine's
    chain-doubling retries rely on to leave already-certified seeds
    bitwise unchanged), of the sweep composition, and of arrival order.
    Like every ``jax.random`` path it is equal in distribution to — and
    never stream-equal with — the NumPy engines.
    """
    import jax
    import jax.numpy as jnp

    def per_seed(key):
        return jax.vmap(
            lambda j: row_sampler(jax.random.fold_in(key, j)))(
                jnp.arange(L))

    return jax.vmap(per_seed)(chain_keys)


def ragged_layout(budgets, starts=None):
    """Host-side offset/slot-budget layout for ragged per-worker chains.

    ``budgets[i]`` is worker ``i``'s slot count; the flat buffer packs
    the workers' slot runs back to back (worker-major), so flat index
    ``offsets[i] + j`` holds worker ``i``'s ``j``-th slot. Returns
    ``(offsets, widx, gslot, total)``: per-worker start offsets
    ``(n,)``, the flat-index -> worker map ``(total,)``, the
    flat-index -> *global* slot index map ``(total,)`` (``starts[i] +
    j`` — window extensions pass the slots already drawn so the global
    slot index keeps counting across windows), and the flat length.
    Worker-major packing keeps the merged-pool tie contract intact:
    flat-index tie-breaking in :func:`~repro.kernels.order_stats.
    smallest_k` is (worker, global slot) lexicographic order, exactly
    the rectangular pool's documented contract."""
    b = np.asarray(budgets, dtype=np.int64)
    n = b.size
    s0 = (np.zeros(n, np.int64) if starts is None
          else np.asarray(starts, dtype=np.int64))
    if (b < 0).any() or (s0 < 0).any():
        raise ValueError("ragged_layout needs nonnegative budgets/starts")
    offsets = np.concatenate([[0], np.cumsum(b)[:-1]]).astype(np.int64)
    total = int(b.sum())
    widx = np.repeat(np.arange(n, dtype=np.int64), b)
    gslot = (np.arange(total, dtype=np.int64) - np.repeat(offsets, b)
             + np.repeat(s0, b))
    return offsets, widx, gslot, total


def jax_chain_draws_ragged(chain_keys, budgets, row_sampler, starts=None):
    """``(seeds, total)`` flat ragged renewal-duration buffer — the
    per-worker-budgeted twin of :func:`jax_chain_draws`.

    Entry ``(s, offsets[i] + j)`` is bitwise
    ``row_sampler(fold_in(chain_keys[s], starts[i] + j))[i]`` — i.e.
    worker ``i``'s slot at global index ``g = starts[i] + j`` equals
    column ``i`` of the rectangular contract's row ``g``. The fold-in
    keyed prefix-stability contract is therefore preserved exactly:
    growing any worker's budget (or drawing a window extension via
    ``starts``) appends slots and never reshuffles or re-keys existing
    ones, and with uniform budgets and ``starts=None`` the buffer is
    ``jax_chain_draws(chain_keys, L, row_sampler)`` transposed to
    worker-major and flattened, bitwise.

    The buffer is built by ONE short scan over the global slot range
    (``max(starts + budgets) - min(starts)`` steps, each one
    ``row_sampler`` row) that scatters each row's in-budget entries
    through a precomputed destination map (out-of-budget entries drop),
    so no ``(seeds, L_max, n)`` rectangle is ever materialized — under
    skewed rates the flat buffer is up to ``n`` times smaller."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b = np.asarray(budgets, dtype=np.int64)
    n = b.size
    s0 = (np.zeros(n, np.int64) if starts is None
          else np.asarray(starts, dtype=np.int64))
    offsets, _, _, total = ragged_layout(b, s0)
    jmin = int(s0.min()) if n else 0
    jmax = int((s0 + b).max()) if n else 0
    steps = max(jmax - jmin, 0)
    # dest[j - jmin, i]: flat slot of worker i's draw at global slot j,
    # or `total` (out of range -> dropped by the scatter) outside
    # [starts[i], starts[i] + budgets[i])
    jg = np.arange(jmin, jmax, dtype=np.int64)[:, None]
    rel = jg - s0[None, :]
    dest = jnp.asarray(np.where((rel >= 0) & (rel < b[None, :]),
                                offsets[None, :] + rel,
                                total).astype(np.int32))
    probe = jax.eval_shape(row_sampler,
                           jax.ShapeDtypeStruct((2,), jnp.uint32))

    def per_seed(key):
        def body(buf, inp):
            j, d = inp
            row = row_sampler(jax.random.fold_in(key, j))
            return buf.at[d].set(row, mode="drop"), None

        buf0 = jnp.zeros((total,), probe.dtype)
        if steps == 0:
            return buf0
        buf, _ = lax.scan(body, buf0,
                          (jnp.arange(jmin, jmax), dest))
        return buf

    return jax.vmap(per_seed)(chain_keys)


def _as_rng(key, rng_scheme: str):
    if isinstance(key, np.random.Generator):
        return key
    if rng_scheme == "counter":
        return philox_rngs([key])[0]
    return np.random.default_rng(int(key))


class TimeModel:
    """Base class: per-gradient computation-time sampling for ``n`` workers."""

    n: int

    def sample_time(self, i: int, rng: np.random.Generator) -> float:
        raise NotImplementedError

    def sample_times(self, workers: Sequence[int],
                     rng: np.random.Generator) -> np.ndarray:
        """Batched per-gradient times for ``workers`` (engine hot path).

        The fallback draws per worker in order, so it consumes the RNG
        stream exactly like sequential ``sample_time`` calls; subclasses
        override with a single vectorized draw where possible.
        """
        return np.array([self.sample_time(int(i), rng) for i in workers],
                        dtype=float)

    def sample_times_seeds(self, workers: Sequence[int],
                           rngs: Sequence[np.random.Generator]) -> np.ndarray:
        """Multi-seed batched draw: one ``(seeds, workers)`` matrix.

        Row ``s`` consumes ``rngs[s]`` exactly as one :meth:`sample_times`
        call would, so per-seed RNG-stream parity with scalar runs is
        preserved (the seed-batched ``simulate_batch`` engine depends on
        this). Models whose draws are RNG-free (:class:`FixedTimes`)
        override with a pure broadcast.
        """
        return np.stack([np.asarray(self.sample_times(workers, rng),
                                    dtype=float) for rng in rngs])

    def sample_times_tensor(self, workers: Sequence[int], rounds: int,
                            seed_keys: Sequence,
                            rng_scheme: str = "counter") -> np.ndarray:
        """One ``(seeds, rounds, workers)`` tensor of per-gradient times.

        This is the sweep engine's bulk draw: the *entire* time tensor
        for a multi-seed run comes out of one call per model instead of
        ``seeds x rounds`` small draws. ``seed_keys`` are seed ints or
        already-constructed ``np.random.Generator`` instances (stateful —
        successive calls continue each seed's stream, which is how the
        batched engine chunks very long horizons).

        ``rng_scheme`` picks the documented reproducibility contract:

        * ``"counter"`` (default) — one tiled vectorized draw per seed
          from its Philox counter stream (:func:`philox_rngs`). Row ``s``
          is a pure function of the seed value; entry ``[s, r, j]`` is an
          independent draw from worker ``workers[j]``'s marginal.
          Distribution-equal to — but NOT stream-equal with — the scalar
          ``simulate()`` path.
        * ``"stream"`` — row ``[s, r]`` is the ``r``-th successive
          :meth:`sample_times` call on ``np.random.default_rng(s)``, i.e.
          exactly the values a per-round loop would consume.
        """
        if rng_scheme not in ("counter", "stream"):
            raise ValueError(f"unknown rng_scheme {rng_scheme!r}; "
                             "use 'counter' or 'stream'")
        workers = np.asarray(workers, dtype=int)
        W = len(workers)
        out = np.empty((len(seed_keys), int(rounds), W), dtype=float)
        tiled = np.tile(workers, int(rounds))
        for si, key in enumerate(seed_keys):
            rng = _as_rng(key, rng_scheme)
            if rng_scheme == "counter":
                out[si] = np.asarray(self.sample_times(tiled, rng),
                                     dtype=float).reshape(int(rounds), W)
            else:
                for r in range(int(rounds)):
                    out[si, r] = self.sample_times(workers, rng)
        return out

    def mean_times(self) -> np.ndarray:
        """``tau_i = E[time for worker i]``, sorted or not — as configured."""
        raise NotImplementedError

    # Sub-exponential certificate (Assumption 3.1); None => unknown/infinite.
    def sub_exponential_R(self) -> Optional[float]:
        return None

    def faulted(self, *faults) -> "SubExponentialTimes":
        """Wrap this model with fault transformations (``repro.core.faults``).

        ``model.faulted(CrashRestart(p=0.05, mean_downtime=2.0))`` is
        :func:`repro.core.faults.with_faults` as a method; with no
        active faults the wrapper is bitwise a no-op on every backend.
        """
        from .faults import FaultyTimes
        return FaultyTimes(self, faults)


@dataclasses.dataclass
class FixedTimes(TimeModel):
    """Assumption 2.2 — deterministic ``tau_i``."""

    taus: np.ndarray

    def __post_init__(self) -> None:
        self.taus = np.asarray(self.taus, dtype=float)
        if np.any(self.taus <= 0):
            raise ValueError("tau_i must be positive")
        self.n = len(self.taus)

    def sample_time(self, i: int, rng: np.random.Generator) -> float:
        return float(self.taus[i])

    def sample_times(self, workers: Sequence[int],
                     rng: np.random.Generator) -> np.ndarray:
        return self.taus[np.asarray(workers, dtype=int)]

    def sample_times_seeds(self, workers: Sequence[int],
                           rngs: Sequence[np.random.Generator]) -> np.ndarray:
        # deterministic: no RNG consumed, one broadcast for all seeds
        return np.broadcast_to(self.taus[np.asarray(workers, dtype=int)],
                               (len(rngs), len(workers))).copy()

    def sample_times_tensor(self, workers: Sequence[int], rounds: int,
                            seed_keys: Sequence,
                            rng_scheme: str = "counter") -> np.ndarray:
        if rng_scheme not in ("counter", "stream"):
            raise ValueError(f"unknown rng_scheme {rng_scheme!r}; "
                             "use 'counter' or 'stream'")
        return np.broadcast_to(
            self.taus[np.asarray(workers, dtype=int)],
            (len(seed_keys), int(rounds), len(workers))).copy()

    def mean_times(self) -> np.ndarray:
        return self.taus

    def sub_exponential_R(self) -> float:
        return 0.0

    @staticmethod
    def sqrt_law(n: int, tau1: float = 1.0) -> "FixedTimes":
        """tau_i = tau1 * sqrt(i) — the paper's Figure 5 / K.1 setup."""
        return FixedTimes(tau1 * np.sqrt(np.arange(1, n + 1)))

    @staticmethod
    def power_law(n: int, alpha: float, tau1: float = 1.0,
                  delta: Optional[np.ndarray] = None) -> "FixedTimes":
        """tau_m = tau1 * m**alpha + delta_m — eq. (10)."""
        taus = tau1 * np.arange(1, n + 1, dtype=float) ** alpha
        if delta is not None:
            taus = taus + np.asarray(delta, dtype=float)
        return FixedTimes(taus)

    @staticmethod
    def linear(n: int, tau1: float = 1.0) -> "FixedTimes":
        """tau_i = tau1 * i — the log-factor-tight case of Theorem 2.3."""
        return FixedTimes(tau1 * np.arange(1, n + 1, dtype=float))


@dataclasses.dataclass
class SubExponentialTimes(TimeModel):
    """Assumption 3.1 — random per-gradient times, independent across draws.

    ``sampler(i, rng)`` must return a nonnegative float with mean
    ``taus[i]``; ``R`` is the common sub-exponential parameter (may be a
    conservative upper bound). ``batch_sampler(workers, rng)``, when
    provided, draws one vectorized sample per listed worker — the engine
    prefers it for bulk restarts. ``jax_sampler(key) -> (n,)``, when
    provided, draws one full round of per-worker times with ``jax.random``
    — the ``simulate_batch`` JAX backend needs it (distribution-equal to
    the NumPy samplers, not stream-equal). ``jax_sampler_item(key, i)``
    draws ONE sample from worker ``i``'s marginal (``i`` may be traced):
    the keyed Async/Ringmaster arrival loop uses it with a
    :func:`jax_worker_key_grid` so each arrival costs one draw instead
    of a full ``(seeds, n)`` row; when absent, the engine falls back to
    row draws through ``jax_sampler`` (correct, ~n× more draw volume).
    """

    taus: np.ndarray
    sampler: Callable[[int, np.random.Generator], float]
    R: float
    name: str = "subexp"
    batch_sampler: Optional[Callable[[np.ndarray, np.random.Generator],
                                     np.ndarray]] = None
    jax_sampler: Optional[Callable] = None
    jax_sampler_item: Optional[Callable] = None

    def __post_init__(self) -> None:
        self.taus = np.asarray(self.taus, dtype=float)
        self.n = len(self.taus)

    def sample_time(self, i: int, rng: np.random.Generator) -> float:
        t = float(self.sampler(i, rng))
        return max(t, 0.0)

    def sample_times(self, workers: Sequence[int],
                     rng: np.random.Generator) -> np.ndarray:
        workers = np.asarray(workers, dtype=int)
        if self.batch_sampler is None:
            return np.array([max(float(self.sampler(int(i), rng)), 0.0)
                             for i in workers])
        return np.maximum(np.asarray(self.batch_sampler(workers, rng),
                                     dtype=float), 0.0)

    def mean_times(self) -> np.ndarray:
        return self.taus

    def sub_exponential_R(self) -> float:
        return self.R


def truncated_normal_times(mus: Sequence[float], sigma: float
                           ) -> SubExponentialTimes:
    """``tau_i ~ N(mu_i, sigma^2)`` truncated to ``[0, inf)``.

    Sub-exponential with ``R = O(sigma)`` (Barreto et al., 2025). The mean of
    the truncated variable is ``mu + sigma * phi(a)/Phi(-a)`` with
    ``a = -mu/sigma``; we report the exact truncated means.
    """
    mus = np.asarray(mus, dtype=float)

    def _truncated_mean(mu: float) -> float:
        if sigma == 0:
            return max(mu, 0.0)
        a = -mu / sigma
        phi = math.exp(-0.5 * a * a) / math.sqrt(2 * math.pi)
        Phi = 0.5 * math.erfc(a / math.sqrt(2))
        return mu + sigma * phi / max(Phi, 1e-300)

    taus = np.array([_truncated_mean(mu) for mu in mus])

    def sampler(i: int, rng: np.random.Generator) -> float:
        while True:
            t = rng.normal(mus[i], sigma)
            if t >= 0:
                return t

    def batch_sampler(workers: np.ndarray,
                      rng: np.random.Generator) -> np.ndarray:
        out = rng.normal(mus[workers], sigma)
        while True:
            bad = out < 0
            if not bad.any():
                return out
            out[bad] = rng.normal(mus[workers][bad], sigma)

    def jax_sampler(key):
        # exact bounded sampling (no rejection loop): truncate the
        # standard normal to [(0 - mu)/sigma, inf) and rescale —
        # distribution-equal to the NumPy rejection sampler
        import jax
        import jax.numpy as jnp
        if sigma == 0:
            return jnp.maximum(jnp.asarray(mus), 0.0)
        z = jax.random.truncated_normal(key, (0.0 - mus) / sigma, jnp.inf,
                                        mus.shape)
        return mus + sigma * z

    def jax_sampler_item(key, i):
        import jax
        import jax.numpy as jnp
        mu = jnp.asarray(mus)[i]
        if sigma == 0:
            return jnp.maximum(mu, 0.0)
        z = jax.random.truncated_normal(key, (0.0 - mu) / sigma, jnp.inf)
        return mu + sigma * z

    return SubExponentialTimes(taus, sampler, R=float(sigma),
                               name=f"truncnorm(sigma={sigma})",
                               batch_sampler=batch_sampler,
                               jax_sampler=jax_sampler,
                               jax_sampler_item=jax_sampler_item)


def exponential_times(lam: float, n: int) -> SubExponentialTimes:
    """``tau_i ~ Exp(lam)`` for all workers: ``tau_i = R = 1/lam`` (§3)."""
    taus = np.full(n, 1.0 / lam)

    def sampler(i: int, rng: np.random.Generator) -> float:
        return rng.exponential(1.0 / lam)

    def jax_sampler(key):
        import jax
        return jax.random.exponential(key, (n,)) / lam

    def jax_sampler_item(key, i):
        import jax
        return jax.random.exponential(key) / lam

    return SubExponentialTimes(
        taus, sampler, R=1.0 / lam, name=f"exp(lam={lam})",
        batch_sampler=lambda w, rng: rng.exponential(1.0 / lam, size=len(w)),
        jax_sampler=jax_sampler, jax_sampler_item=jax_sampler_item)


def shifted_exponential_times(mus: Sequence[float], lams: Sequence[float]
                              ) -> SubExponentialTimes:
    """``tau_i = mu_i + Exp(lam_i)`` (§D.1): R = max_i 1/lam_i."""
    mus = np.asarray(mus, dtype=float)
    lams = np.asarray(lams, dtype=float)
    taus = mus + 1.0 / lams

    def sampler(i: int, rng: np.random.Generator) -> float:
        return mus[i] + rng.exponential(1.0 / lams[i])

    def jax_sampler(key):
        import jax
        return mus + jax.random.exponential(key, mus.shape) / lams

    def jax_sampler_item(key, i):
        import jax
        import jax.numpy as jnp
        return (jnp.asarray(mus)[i]
                + jax.random.exponential(key) / jnp.asarray(lams)[i])

    return SubExponentialTimes(
        taus, sampler, R=float(np.max(1.0 / lams)), name="shifted-exp",
        batch_sampler=lambda w, rng: mus[w] + rng.exponential(1.0 / lams[w]),
        jax_sampler=jax_sampler, jax_sampler_item=jax_sampler_item)


def gamma_times(means: Sequence[float], var: float) -> SubExponentialTimes:
    """Gamma with per-worker mean ``tau_i`` and common variance (§K.3).

    shape k = tau^2/var, scale theta = var/tau; R = O(max sqrt(k)*theta).
    """
    means = np.asarray(means, dtype=float)
    ks = means ** 2 / var
    thetas = var / means
    R = float(np.max(np.maximum(np.sqrt(ks), 1.0) * thetas))

    def sampler(i: int, rng: np.random.Generator) -> float:
        return rng.gamma(ks[i], thetas[i])

    def jax_sampler(key):
        import jax
        return jax.random.gamma(key, ks) * thetas

    def jax_sampler_item(key, i):
        import jax
        import jax.numpy as jnp
        return (jax.random.gamma(key, jnp.asarray(ks)[i])
                * jnp.asarray(thetas)[i])

    return SubExponentialTimes(
        means, sampler, R=R, name="gamma",
        batch_sampler=lambda w, rng: rng.gamma(ks[w], thetas[w]),
        jax_sampler=jax_sampler, jax_sampler_item=jax_sampler_item)


def uniform_times(means: Sequence[float], half_width: float
                  ) -> SubExponentialTimes:
    """``tau_i ~ Unif(tau_i - w, tau_i + w)`` (§K.3/K.4). Bounded => R=O(w)."""
    means = np.asarray(means, dtype=float)

    def sampler(i: int, rng: np.random.Generator) -> float:
        return rng.uniform(means[i] - half_width, means[i] + half_width)

    def jax_sampler(key):
        import jax
        import jax.numpy as jnp
        u = jax.random.uniform(key, means.shape,
                               minval=-half_width, maxval=half_width)
        # same clamp the engine applies to every NumPy draw via
        # sample_time / sample_times (times are nonnegative a.s.)
        return jnp.maximum(means + u, 0.0)

    def jax_sampler_item(key, i):
        import jax
        import jax.numpy as jnp
        u = jax.random.uniform(key, minval=-half_width, maxval=half_width)
        return jnp.maximum(jnp.asarray(means)[i] + u, 0.0)

    return SubExponentialTimes(
        means, sampler, R=float(half_width), name=f"uniform(w={half_width})",
        batch_sampler=lambda w, rng: rng.uniform(means[w] - half_width,
                                                 means[w] + half_width),
        jax_sampler=jax_sampler, jax_sampler_item=jax_sampler_item)


def chi2_times(dofs: Sequence[int]) -> SubExponentialTimes:
    """``tau_i ~ chi^2_{k_i}`` (§D.1): tau_i = k_i, R = O(max sqrt(k_i))."""
    dofs = np.asarray(dofs, dtype=float)

    def sampler(i: int, rng: np.random.Generator) -> float:
        return rng.chisquare(dofs[i])

    def jax_sampler(key):
        # chi^2_k == Gamma(shape k/2, scale 2)
        import jax
        return 2.0 * jax.random.gamma(key, dofs / 2.0)

    def jax_sampler_item(key, i):
        import jax
        import jax.numpy as jnp
        return 2.0 * jax.random.gamma(key, jnp.asarray(dofs)[i] / 2.0)

    return SubExponentialTimes(dofs.copy(), sampler,
                               R=float(2.0 * np.sqrt(np.max(dofs))),
                               name="chi2",
                               batch_sampler=lambda w, rng:
                                   rng.chisquare(dofs[w]),
                               jax_sampler=jax_sampler,
                               jax_sampler_item=jax_sampler_item)


# ---------------------------------------------------------------------------
# Assumption 5.1 — Universal computation model.
# ---------------------------------------------------------------------------

class UniversalModel:
    """Computation powers ``v_i(t)`` on a uniform grid with linear interp.

    ``N_i(t0, t1) = floor(int_{t0}^{t1} v_i(s) ds)`` — eq. (11). The paper's
    Figures 3/4 define powers exactly this way (grid ``t_k = 0.1 k`` +
    linear interpolation), so a trapezoid cumulative integral on the grid is
    *exact* for these instances.
    """

    def __init__(self, grid: np.ndarray, powers: np.ndarray) -> None:
        # powers: (n, T) nonnegative samples on grid (T,)
        self.grid = np.asarray(grid, dtype=float)
        self.powers = np.maximum(np.asarray(powers, dtype=float), 0.0)
        self.n = self.powers.shape[0]
        dt = np.diff(self.grid)
        mids = 0.5 * (self.powers[:, 1:] + self.powers[:, :-1])
        self.cum = np.concatenate(
            [np.zeros((self.n, 1)), np.cumsum(mids * dt, axis=1)], axis=1)

    def integral(self, i: int, t0: float, t1: float) -> float:
        """``int_{t0}^{t1} v_i`` (exact for piecewise-linear powers)."""
        return self._cum_at(i, t1) - self._cum_at(i, t0)

    def _cum_at(self, i: int, t: float) -> float:
        g = self.grid
        if t <= g[0]:
            return 0.0
        if t >= g[-1]:
            # extrapolate with the final power value (constant tail)
            return float(self.cum[i, -1] + self.powers[i, -1] * (t - g[-1]))
        j = int(np.searchsorted(g, t) - 1)
        dt = t - g[j]
        h = g[j + 1] - g[j]
        v0 = self.powers[i, j]
        v1 = self.powers[i, j + 1]
        vt = v0 + (v1 - v0) * dt / h
        return float(self.cum[i, j] + 0.5 * (v0 + vt) * dt)

    def N(self, i: int, t0: float, t1: float) -> int:
        return int(math.floor(self.integral(i, t0, t1) + 1e-12))

    def time_for_integral(self, i: int, t0: float, target: float) -> float:
        """Smallest ``t >= t0`` with ``int_{t0}^{t} v_i >= target`` (inf if never)."""
        base = self._cum_at(i, t0)
        want = base + target
        if self.cum[i, -1] < want:
            tail_v = self.powers[i, -1]
            if tail_v <= 0:
                return math.inf
            return float(self.grid[-1]
                         + (want - self.cum[i, -1]) / tail_v)
        # binary search on [t0, grid[-1]]
        lo, hi = t0, float(self.grid[-1])
        for _ in range(80):
            mid = 0.5 * (lo + hi)
            if self._cum_at(i, mid) >= want:
                hi = mid
            else:
                lo = mid
        return hi

    def _cum_at_vec(self, idx: np.ndarray, t: np.ndarray) -> np.ndarray:
        """Vectorized :meth:`_cum_at`: cumulative integral of ``v_i`` at
        per-worker times ``t`` (same segment convention as the scalar)."""
        g = self.grid
        t = np.asarray(t, dtype=float)
        tf = np.where(np.isfinite(t), t, g[-1])     # placeholder; masked below
        j = np.clip(np.searchsorted(g, tf, side="left") - 1, 0, len(g) - 2)
        dt = tf - g[j]
        h = g[j + 1] - g[j]
        v0 = self.powers[idx, j]
        v1 = self.powers[idx, j + 1]
        vt = v0 + (v1 - v0) * dt / h
        mid = self.cum[idx, j] + 0.5 * (v0 + vt) * dt
        tail = self.cum[idx, -1] + self.powers[idx, -1] * (tf - g[-1])
        out = np.where(tf <= g[0], 0.0, np.where(tf >= g[-1], tail, mid))
        # t = inf: infinite tail power integral (inf if tail v > 0 else
        # the finite grid total — the 0 * inf nan is never the answer)
        return np.where(np.isfinite(t), out,
                        np.where(self.powers[idx, -1] > 0, np.inf,
                                 self.cum[idx, -1]))

    def finish_times(self, workers: Sequence[int], t0,
                     target: float = 1.0) -> np.ndarray:
        """Batched :meth:`time_for_integral` (the event engine's hot path).

        ``t0`` is a scalar or a per-worker array. Replaces the per-worker
        80-iteration Python bisection with one vectorized inversion:
        a batched binary search over the per-worker cumulative-power grid
        rows finds the crossing segment, then the quadratic
        ``cum(t) = cum_j + v0*dt + 0.5*(v1-v0)/h*dt^2`` (exact for the
        piecewise-linear powers) is solved in closed form. Agrees with
        the scalar bisection to ~1e-12 relative (tested at 1e-9).
        """
        idx = np.asarray(workers, dtype=int)
        t0 = np.broadcast_to(np.asarray(t0, dtype=float), idx.shape).copy()
        g = self.grid
        T = len(g)
        base = self._cum_at_vec(idx, t0)
        want = base + target
        tail_v = self.powers[idx, -1]
        cum_end = self.cum[idx, -1]
        overflow = cum_end < want                    # crossing past the grid
        with np.errstate(divide="ignore", invalid="ignore"):
            t_tail = g[-1] + (want - cum_end) / tail_v
        t_tail = np.where(tail_v > 0, t_tail, np.inf)
        # first grid index with cum >= want (per-row binary search; rows
        # differ so np.searchsorted cannot batch this directly)
        want_in = np.where(overflow, cum_end, want)  # keep the search bounded
        lo = np.zeros(idx.shape, dtype=np.int64)
        hi = np.full(idx.shape, T - 1, dtype=np.int64)
        for _ in range(int(np.ceil(np.log2(max(T, 2)))) + 1):
            mid = (lo + hi) // 2
            ge = self.cum[idx, mid] >= want_in
            hi = np.where(ge, mid, hi)
            lo = np.where(ge, lo, np.minimum(mid + 1, T - 1))
        jj = np.maximum(hi, 1)                       # crossing in [jj-1, jj]
        rem = np.where(overflow, 0.0, want - self.cum[idx, jj - 1])
        v0 = self.powers[idx, jj - 1]
        v1 = self.powers[idx, jj]
        h = g[jj] - g[jj - 1]
        slope = (v1 - v0) / h
        # 0.5*slope*dt^2 + v0*dt = rem, stable root (exact in the linear
        # slope -> 0 limit): dt = 2*rem / (v0 + sqrt(v0^2 + 2*slope*rem))
        with np.errstate(divide="ignore", invalid="ignore"):
            disc = np.maximum(v0 * v0 + 2.0 * slope * rem, 0.0)
            den = v0 + np.sqrt(disc)
            dt = np.where(den > 0, 2.0 * rem / np.where(den > 0, den, 1.0),
                          0.0)
        t_in = g[jj - 1] + np.where(rem > 0, dt, 0.0)
        out = np.where(overflow, t_tail, np.maximum(t_in, t0))
        # never-started computations (t0 = inf) never finish
        return np.where(np.isfinite(t0), out, np.inf)

    # ------------------------------------------------ device-resident twin
    def _jax_arrays(self):
        """(grid, cum, powers) as jnp arrays, cached per x64 mode (the
        cache key matters: tests run the 1e-9 parity check under
        ``jax.enable_x64`` while the engines default to
        float32)."""
        import jax
        import jax.numpy as jnp

        key = bool(jax.config.jax_enable_x64)
        cache = getattr(self, "_jax_cache", None)
        if cache is None:
            cache = self._jax_cache = {}
        if key not in cache:
            # eager even when first touched inside a jit trace: cached
            # constants must not be tracers of the enclosing program
            with jax.ensure_compile_time_eval():
                cache[key] = (jnp.asarray(self.grid),
                              jnp.asarray(self.cum),
                              jnp.asarray(self.powers))
        return cache[key]

    def _cum_at_jax(self, t, idx):
        """jit-compatible :meth:`_cum_at_vec`: cumulative integral of
        ``v_{idx}`` at times ``t`` (``t`` and ``idx`` broadcast)."""
        import jax.numpy as jnp

        g, cum, powers = self._jax_arrays()
        t = jnp.asarray(t)
        tf = jnp.where(jnp.isfinite(t), t, g[-1])
        j = jnp.clip(jnp.searchsorted(g, tf, side="left") - 1, 0,
                     len(self.grid) - 2)
        dt = tf - g[j]
        h = g[j + 1] - g[j]
        v0 = powers[idx, j]
        v1 = powers[idx, j + 1]
        vt = v0 + (v1 - v0) * dt / h
        mid = cum[idx, j] + 0.5 * (v0 + vt) * dt
        tail = cum[idx, -1] + powers[idx, -1] * (tf - g[-1])
        out = jnp.where(tf <= g[0], 0.0,
                        jnp.where(tf >= g[-1], tail, mid))
        return jnp.where(jnp.isfinite(t), out,
                         jnp.where(powers[idx, -1] > 0, jnp.inf,
                                   cum[idx, -1]))

    def finish_times_jax(self, t0, workers=None, target: float = 1.0):
        """jit-compatible :meth:`finish_times` (the ``backend="jax"``
        hot path): smallest ``t >= t0`` with unit power integral.

        ``t0``'s last axis indexes workers ``0..n-1`` unless ``workers``
        (an integer array broadcastable against ``t0``) says otherwise —
        arrival-indexed engines pass the single popped worker per seed.
        A batched ``jnp.searchsorted`` (vmapped over the per-worker
        cumulative-power rows) finds the crossing segment and the same
        closed-form quadratic inversion as the NumPy path solves it —
        deterministic, no RNG. Matches the NumPy ``finish_times`` to
        ~1e-12 relative under x64 (tested at 1e-9 on the Fig 3/4 grids,
        including the constant-tail extrapolation and the ``v = 0``
        never-finishes inf branch); float32 precision under the engine
        default. Like every jax engine draw, NOT part of any NumPy RNG
        stream contract (the inversion is draw-free anyway).
        """
        import jax
        import jax.numpy as jnp

        g, cum, powers = self._jax_arrays()
        t0 = jnp.asarray(t0)
        if workers is None:
            workers = jnp.arange(self.n)
        idx = jnp.broadcast_to(workers, t0.shape)
        base = self._cum_at_jax(t0, idx)
        want = base + target
        tail_v = powers[idx, -1]
        cum_end = cum[idx, -1]
        overflow = cum_end < want                # crossing past the grid
        t_tail = jnp.where(tail_v > 0,
                           g[-1] + (want - cum_end) / jnp.where(
                               tail_v > 0, tail_v, 1.0), jnp.inf)
        want_in = jnp.where(overflow, cum_end, want)
        # first grid index with cum >= want, per (row = worker) pair
        flat_idx = idx.reshape(-1)
        flat_want = want_in.reshape(-1)
        jj = jax.vmap(lambda i, w: jnp.searchsorted(cum[i], w,
                                                    side="left"))(
            flat_idx, flat_want).reshape(idx.shape)
        jj = jnp.clip(jj, 1, len(self.grid) - 1)  # crossing in [jj-1, jj]
        rem = jnp.where(overflow, 0.0, want - cum[idx, jj - 1])
        v0 = powers[idx, jj - 1]
        v1 = powers[idx, jj]
        h = g[jj] - g[jj - 1]
        slope = (v1 - v0) / h
        # 0.5*slope*dt^2 + v0*dt = rem, stable root (exact in the linear
        # slope -> 0 limit): dt = 2*rem / (v0 + sqrt(v0^2 + 2*slope*rem))
        disc = jnp.maximum(v0 * v0 + 2.0 * slope * rem, 0.0)
        den = v0 + jnp.sqrt(disc)
        dt = jnp.where(den > 0, 2.0 * rem / jnp.where(den > 0, den, 1.0),
                       0.0)
        t_in = g[jj - 1] + jnp.where(rem > 0, dt, 0.0)
        out = jnp.where(overflow, t_tail, jnp.maximum(t_in, t0))
        return jnp.where(jnp.isfinite(t0), out, jnp.inf)


@dataclasses.dataclass
class PiecewisePower:
    """Analytic power: constant ``v`` until ``t_switch`` then ``v_after``.

    Used for the §6/§I "worker becomes infinitely fast" example
    (v_after = inf encoded as a huge float).
    """

    v: float
    t_switch: float = math.inf
    v_after: float = math.inf

    def integral(self, t0: float, t1: float) -> float:
        if t1 <= self.t_switch:
            return self.v * (t1 - t0)
        pre = self.v * (max(self.t_switch, t0) - t0) if t0 < self.t_switch else 0.0
        post = self.v_after * (t1 - max(self.t_switch, t0))
        return pre + post


def powers_figure3(n: int = 50, seed: int = 0, t_max: float = 400.0
                   ) -> UniversalModel:
    """Figure 3: ``v_i(t_k) = max(sin(a_i t_k + s_i) + eps, 0)``."""
    rng = np.random.default_rng(seed)
    grid = np.arange(0.0, t_max, 0.1)
    a = rng.uniform(0.5, 1.0, size=n)
    s = rng.uniform(0.0, 2 * np.pi, size=n)
    eps = rng.normal(0.0, 0.1, size=(n, len(grid)))
    powers = np.maximum(np.sin(a[:, None] * grid[None, :] + s[:, None]) + eps,
                        0.0)
    return UniversalModel(grid, powers)


def powers_figure4(n: int = 50, seed: int = 0, t_max: float = 400.0
                   ) -> UniversalModel:
    """Figure 4: ``v_i(t_k) = max(s_i + 3 sin(t_k + phi_i) + eps, 0.1)``."""
    rng = np.random.default_rng(seed)
    grid = np.arange(0.0, t_max, 0.1)
    s = rng.uniform(10.5, 11.0, size=n)
    phi = rng.uniform(0.0, 2 * np.pi, size=n)
    eps = rng.normal(0.0, 0.1, size=(n, len(grid)))
    powers = np.maximum(s[:, None] + 3 * np.sin(grid[None, :] + phi[:, None])
                        + eps, 0.1)
    return UniversalModel(grid, powers)


class PartialParticipationModel(UniversalModel):
    """Assumption 5.4 — equal power ``v`` except ≤ p·n stragglers at any time.

    ``straggler_fn(t) -> set of straggler indices`` may be adversarial; by
    default a rotating window (the worst *stationary* adversary for m-sync:
    it keeps rotating which workers are dead so no fixed subset works).
    """

    def __init__(self, n: int, v: float = 1.0, p: float = 0.1,
                 period: float = 1.0, t_max: float = 400.0,
                 straggler_fn: Optional[Callable[[float], set]] = None,
                 dt: float = 0.05) -> None:
        self.v0 = v
        self.p = p
        k = int(math.floor(p * n))
        grid = np.arange(0.0, t_max, dt)
        powers = np.full((n, len(grid)), float(v))
        if straggler_fn is None:
            def straggler_fn(t: float) -> set:
                start = int(t / period) * k % max(n, 1)
                return {(start + j) % n for j in range(k)}
        for ti, t in enumerate(grid):
            for i in straggler_fn(float(t)):
                powers[i, ti] = 0.0
        super().__init__(grid, powers)
