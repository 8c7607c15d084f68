"""Run one cell of the chip benchmark and print its result line.

    python3 -m benchmarks.chip.run --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Checks for the chip first and exits non-zero, printing no result, when
JAX finds no TPU or fewer than the cell asks for. Then it turns on the
program's persistent compilation cache with the program's own settings,
builds the cell's work from the configuration and the mix, warms up
(set-up), measures ``--seconds`` and, with ``--trace 1``, traces a short
second window from which the per-layer metrics are read. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``, each number
compared with the reference beside its limit.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from .registry import REPO  # noqa: E402

sys.path.insert(0, str(REPO / "src"))

from . import device, registry  # noqa: E402
from .cell import print_checks, say  # noqa: E402


def _drivers():
    from . import sweep, train

    return {"sweep": sweep, "train": train}


def per_layer_values(bench: dict, cell: dict, obs: dict) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    for m in registry.per_layer(bench, cell):
        v = registry.metric_reader(m["name"]).read(obs)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(bench, cell, run, info, trace_on: bool) -> dict:
    if trace_on:
        metrics = per_layer_values(bench, cell, run.obs)
    else:
        metrics = {}
        for m in registry.end_to_end(bench, cell):
            v = run.setup_s if m["name"] == "setup_s" \
                else run.rates[m["name"]]
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = dict(info, memory_peak_bytes=run.memory_peak_bytes)
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": dev}
    if trace_on:
        dev.update(busy_s=run.busy_s, window_s=run.window_s)
        line["breakdown"] = run.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in run.checks}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    ref = registry.reference(cell["config"])
    driver = _drivers()[mix["kind"]]

    info = device.check_device(cell["chips"])
    say(f"chip found {time.perf_counter() - T0:.2f} s after start")

    from repro.launch.compile_cache import use_compile_cache

    from .clock import CompileClock

    # the program's own cache settings, as its entry points use them: what
    # it compiles anew on every call is compiled in the window too
    cache = use_compile_cache()
    say(f"compile cache: {cache}")
    clock = CompileClock()
    run = driver.run_cell(cell, cfg, mix, ref, args.seed, args.seconds,
                          bool(args.trace), T0, clock)
    if args.trace:
        from . import peaks

        run.obs.update(peaks=peaks.peaks(info["kind"]), chips=cell["chips"],
                       config=cfg, mix=mix)
    line = result_line(bench, cell, run, info, bool(args.trace))
    print_checks(run.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
