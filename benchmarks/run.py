"""Benchmark harness: one module per paper table/figure.

Prints ``name,value,derived`` CSV. ``--slow`` runs the paper-scale
versions (n=1000 etc.); default is the fast CI-friendly scale.
``--seeds N`` overrides the seed-sweep width of the experiment-layer
modules (those whose ``run()`` accepts a ``seeds`` kwarg); ``--json``
additionally writes every row plus per-module timings as a JSON artifact
(uploaded by CI).

Modules:
  fig5_quadratic     Figure 5 (quadratic, n workers, tau=sqrt(i))
  fig8_grid          Figures 8/9 grids (K.1/K.2)
  thm23_logfactor    Theorem 2.3 log-factor table
  thm32_random       Theorem 3.2 E[T_rand] vs bound (random models)
  sec53_gap          §5.3 numerical gap ratios (Figures 3/4) vs paper
  sec6_async_needed  §6/I asynchronicity-needed example
  table_mstar        Propositions 4.1/4.2 m* selection table
  malenia_het        §6 heterogeneous (Malenia) constant-gap table
  sec6_heterogeneous §6 worker-exclusive f_i: m-Sync plateaus, Malenia works
  secj_R_estimation  §J sub-exponential R of real step times
  ablation_m_sweep   measured T(m) vs Theorem 2.3 closed form + Prop 4.1 m*
  thm55_participation  Theorem 5.5 window under the rotating adversary
  simbatch_speed     simulate_batch jax >= 5x / counter >= 4x acceptance
                     smokes; writes the BENCH_simbatch.json perf baseline
  chain_layout       rectangular vs ragged vs windowed renewal pools on
                     the power-law regime (ragged >= 3x fewer elements);
                     merges its lanes into BENCH_simbatch.json
  sweep_scaling      backend="jax_sharded" vs unsharded sweep speedup at
                     forced device counts (subprocess per XLA_FLAGS
                     setting); writes the BENCH_sweep.json perf baseline
  fault_frontier     strategy race across the §3c fault regimes
                     (crash/slowdown/bursts/spikes/mix) vs fault-free;
                     writes BENCH_fault_frontier.json
  atlas              head-to-head time-complexity atlas: sync family vs
                     async rivals (Ringleader, optimal ASGD, ...) across
                     six heterogeneity regimes; writes BENCH_atlas.json
  order_stats_speed  Pallas top-m kernel vs lax.top_k vs iterative
                     extraction at n in {1e3, 1e5}

Simulator-backed modules run through the experiment layer
(``repro.exp.run_experiment``): strategies × scenarios × seed sweeps via
the batched engine, reporting mean ± std across seeds.
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time

from repro.launch.compile_cache import use_compile_cache

from . import (ablation_m_sweep, atlas, chain_layout, fault_frontier,
               fig5_quadratic, fig8_grid, malenia_het, order_stats_speed,
               sec6_async_needed, sec6_heterogeneous, sec53_gap,
               secj_R_estimation, simbatch_speed, sweep_scaling,
               table_mstar, thm23_logfactor, thm32_random,
               thm55_participation)

MODULES = [
    ("fig5_quadratic", fig5_quadratic),
    ("thm23_logfactor", thm23_logfactor),
    ("table_mstar", table_mstar),
    ("sec53_gap", sec53_gap),
    ("thm32_random", thm32_random),
    ("sec6_async_needed", sec6_async_needed),
    ("malenia_het", malenia_het),
    ("fig8_grid", fig8_grid),
    ("secj_R_estimation", secj_R_estimation),
    ("ablation_m_sweep", ablation_m_sweep),
    ("thm55_participation", thm55_participation),
    ("sec6_heterogeneous", sec6_heterogeneous),
    ("fault_frontier", fault_frontier),
    ("atlas", atlas),
    ("simbatch_speed", simbatch_speed),
    ("chain_layout", chain_layout),
    ("order_stats_speed", order_stats_speed),
    ("sweep_scaling", sweep_scaling),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slow", action="store_true",
                    help="paper-scale runs (n=1000, long horizons)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--seeds", type=int, default=None,
                    help="seed-sweep width for experiment-layer modules")
    ap.add_argument("--json", default=None,
                    help="also write rows + timings to this JSON file")
    args = ap.parse_args()
    use_compile_cache()

    print("name,value,derived")
    failures = 0
    all_rows = []
    timings = {}
    for name, mod in MODULES:
        if args.only and args.only not in name:
            continue
        kwargs = {"fast": not args.slow}
        if args.seeds is not None \
                and "seeds" in inspect.signature(mod.run).parameters:
            kwargs["seeds"] = args.seeds
        t0 = time.time()
        try:
            rows = mod.run(**kwargs)
            for rname, val, derived in rows:
                print(f"{rname},{val},{derived}", flush=True)
                all_rows.append({"name": rname, "value": val,
                                 "derived": derived})
            timings[name] = time.time() - t0
            print(f"_timing/{name},{timings[name]:.1f},seconds",
                  flush=True)
        except Exception as e:  # keep the harness going; report at exit
            failures += 1
            print(f"_error/{name},{type(e).__name__},{e}", flush=True)
            all_rows.append({"name": f"_error/{name}",
                             "value": type(e).__name__, "derived": str(e)})
    if args.json:
        from repro.exp.runner import atomic_write_json, sanitize_json
        atomic_write_json(args.json, sanitize_json(
            {"meta": {"slow": args.slow, "seeds": args.seeds,
                      "only": args.only, "failures": failures},
             "timings_s": timings,
             "rows": all_rows}), default=str)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
