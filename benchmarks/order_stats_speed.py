"""Pallas top-m partial-sort kernel vs ``lax.top_k`` vs the iterative
tie-class extraction, at the paper-relevant shapes (ISSUE 3 satellite).

The batched simulators need the m-th smallest of ``(S, n)`` candidates
per round. This benchmark times the three lowerings at n ∈ {1e3, 1e5}
and asserts they agree. The Pallas kernel is interpreted on CPU and
compiled on TPU, by the platform (:mod:`repro.kernels.platform`): a CPU
Pallas column times the Python kernel body, NOT TPU performance — the
number that matters on CPU is iterative vs top_k. The kernel holds one
whole ``(S, n)`` block in VMEM, which at ``(32, 1e5)`` exceeds a v5e
core's budget, so the Pallas column covers n=1e3 only.

A second sweep covers the big-``m`` regime (``m > 64`` — the
``batch >> 64`` Rennala/Malenia pools, ISSUE 4): the counting-bisection
selection (``mth_smallest_counting``) vs ``lax.top_k``. Its raw-call
timing on CPU is shape-dependent; the point of the counting path is
that it is *elementwise only*, so inside a jitted ``lax.scan`` body it
fuses instead of forcing the slow sort lowering (the simbatch Rennala
parity tests exercise exactly that).
"""

import time

import numpy as np

from repro.kernels.order_stats import (mth_smallest_counting,
                                       mth_smallest_iterative,
                                       mth_smallest_pallas)


#: the Pallas kernel's whole-block VMEM footprint limits it to this n
_PALLAS_MAX_N = 1_000


def _timed(fn, reps: int = 5) -> float:
    fn()                                     # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(fast: bool = True):
    import jax
    import jax.numpy as jnp
    from jax import lax

    rows = []
    S = 32
    # both sizes even in fast mode — n=1e5 is the whole point (top_k's
    # CPU lowering scales badly); fast mode trims the m sweep instead
    sizes = [1_000, 100_000]
    ms = (10,) if fast else (10, 64)
    interpreted = jax.default_backend() == "cpu"

    topk = jax.jit(lambda x, m: -lax.top_k(-x, m)[0][..., m - 1],
                   static_argnames="m")
    iterative = jax.jit(mth_smallest_iterative, static_argnames="m")

    for n in sizes:
        x = jnp.asarray(np.random.default_rng(0).uniform(0.0, 1.0, (S, n)))
        for m in ms:
            ref = np.sort(np.asarray(x), axis=1)[:, m - 1]
            t_iter = _timed(lambda: jax.block_until_ready(iterative(x, m=m)))
            t_topk = _timed(lambda: jax.block_until_ready(topk(x, m=m)))
            lanes = [("iterative", lambda: iterative(x, m=m)),
                     ("topk", lambda: topk(x, m=m))]
            if n <= _PALLAS_MAX_N:
                lanes.append(("pallas", lambda: mth_smallest_pallas(x, m)))
            for name, fn in lanes:
                np.testing.assert_allclose(np.asarray(fn()), ref,
                                           rtol=1e-6, err_msg=name)
            tag = f"order_stats/n={n}/m={m}"
            rows.append((f"{tag}/iterative_s", t_iter,
                         f"S={S} fused extraction"))
            rows.append((f"{tag}/topk_s", t_topk,
                         f"iter/topk={t_iter / t_topk:.2f}"))
            if n <= _PALLAS_MAX_N:
                t_pal = _timed(lambda: jax.block_until_ready(
                    mth_smallest_pallas(x, m)), reps=2)
                rows.append((f"{tag}/pallas_s", t_pal,
                             "interpreted (CPU)" if interpreted
                             else "compiled"))
    # big-m regime: counting bisection vs top_k (fused-path selection)
    counting = jax.jit(mth_smallest_counting, static_argnames="m")
    for n, m in (((10_000, 256),) if fast
                 else ((10_000, 256), (100_000, 1024))):
        x = jnp.asarray(np.random.default_rng(1).uniform(0.0, 1.0, (S, n)))
        ref = np.sort(np.asarray(x), axis=1)[:, m - 1]
        t_cnt = _timed(lambda: jax.block_until_ready(counting(x, m=m)))
        t_topk = _timed(lambda: jax.block_until_ready(topk(x, m=m)))
        np.testing.assert_allclose(np.asarray(counting(x, m=m)), ref,
                                   rtol=1e-6)
        tag = f"order_stats/bigm/n={n}/m={m}"
        rows.append((f"{tag}/counting_s", t_cnt,
                     f"S={S} elementwise bisection (fuses in scans)"))
        rows.append((f"{tag}/topk_s", t_topk,
                     f"counting/topk={t_cnt / t_topk:.2f}"))
    rows.append(("order_stats/interpret", float(interpreted),
                 f"Pallas kernel on {jax.default_backend()}"))
    return rows


def main():
    for name, val, derived in run():
        print(f"{name},{val},{derived}")


if __name__ == "__main__":
    main()
