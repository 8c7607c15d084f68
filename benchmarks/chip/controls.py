"""Readings that the limits of ``correct`` are set from.

    python3 -m benchmarks.chip.controls --workload <name> --seeds 1 2 3

For each seed, at the cell's own size and on the chip it asks for:

* ``program`` -- the numbers a run compares, from the program itself;
* ``control`` -- the same numbers with the reference, computed one
  precision below the configuration's, put in the program's place
  (sweeps: bfloat16 clocks for float32; training: float8
  e4m3 matrix products for bfloat16);
* ``half_batch`` (training) -- the program with a fault planted: the loss
  of each step taken over the first half of the rows only.

One JSON line per seed and reading. The benchmark's own runs never run
this; the tests run it at a tiny size.
"""

from __future__ import annotations

import argparse
import json
import sys
from contextlib import contextmanager

from .registry import REPO

sys.path.insert(0, str(REPO / "src"))

from . import registry  # noqa: E402
from .cell import rel_gap  # noqa: E402


def sweep_readings(cell, cfg, mix, ref, seed: int) -> dict:
    """Program and control readings on one sweep's seeds (every grid
    point, ``mix['check_seeds']`` seeds)."""
    from . import sweep
    from .cell import WINDOW, rng

    work = sweep.Sweeps(cell, cfg, mix)
    ans = work.run(work.draw(rng(seed, WINDOW)))
    prog, _ = sweep.check(work, [ans], ref, seed, mix["limits"])
    ctrl = {"time_gap": [], "computed_gap": [], "used_gap": []}
    for s in range(min(mix["check_seeds"], work.S)):
        for point in ans["points"]:
            params = dict(work.spec[1], **point)
            want = ref.simulate(work.spec[0], params, cfg, ans["seeds"][s])
            low = ref.simulate(work.spec[0], params, cfg, ans["seeds"][s],
                               clock="bfloat16")
            ctrl["time_gap"].append(rel_gap(low["total_time"],
                                            want["total_time"]))
            ctrl["computed_gap"].append(rel_gap(low["gradients_computed"],
                                                want["gradients_computed"]))
            ctrl["used_gap"].append(abs(low["gradients_used"]
                                        - want["gradients_used"]))
    return {"program": {c.name: c.value for c in prog},
            "control": {k: max(v) for k, v in ctrl.items()}}


@contextmanager
def half_batch_loss():
    """Plant the fault: every loss the model computes is taken over the
    first half of the batch's rows, the mean over those alone."""
    from repro.models import model as model_mod

    real = model_mod.Model.loss

    def loss(self, params, batch, ctx=None, *, example_weights=None, **kw):
        half = batch["tokens"].shape[0] // 2
        batch = {k: v[:half] for k, v in batch.items()}
        if example_weights is not None:
            example_weights = example_weights[:half]
        return real(self, params, batch, ctx,
                    example_weights=example_weights, **kw)

    model_mod.Model.loss = loss
    try:
        yield
    finally:
        model_mod.Model.loss = real


def train_readings(cell, cfg, mix, ref, seed: int) -> dict:
    from . import train

    def program_run():
        work = train.Steps(cell, cfg, mix, seed)
        prog = train.first_steps(work)
        work.state = work.trainer.final_state = None
        return work, prog

    work, prog = program_run()
    p0 = prog.pop("p0")
    want = train.reference_readings(ref, work, p0)
    low = train.reference_readings(ref, work, p0, precision="fp8")
    out = {"program": _named(train.compare(prog, want, mix["limits"])),
           "control": _named(train.compare(low, want, mix["limits"]))}
    with half_batch_loss():
        _, bad = program_run()
    bad.pop("p0")
    out["half_batch"] = _named(train.compare(bad, want, mix["limits"]))
    return out


def _named(checks) -> dict:
    return {c.name: c.value for c in checks}


def readings(workload: str, seed: int) -> dict:
    bench = registry.load_benchmark()
    cell = registry.workload(bench, workload)
    cfg = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    ref = registry.reference(cell["config"])
    fn = sweep_readings if mix["kind"] == "sweep" else train_readings
    return fn(cell, cfg, mix, ref, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from . import device

    cell = registry.workload(registry.load_benchmark(), args.workload)
    device.check_device(cell["chips"])
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    for seed in args.seeds:
        for kind, vals in readings(args.workload, seed).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": kind, **vals}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
