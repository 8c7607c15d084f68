"""A training cell that asks for four chips, at a tiny size on four CPU
devices, in a child process (the device count is fixed when JAX starts):
the mesh takes its size from the cell, and the masked step on the
``("data",)`` mesh agrees with the one-device reference while the
half-batch fault does not."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.chip.registry import REPO, HERE

CHILD = r"""
import json, sys
import jax
sys.path.insert(0, TESTS)
from chipbench_helpers import train_cell
from benchmarks.chip import controls, train

class Patch:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)

cell, cfg, mix, ref = train_cell(Patch(), chips=4)
cfg["batch_per_chip"] = 2
work = train.Steps(cell, cfg, mix, 3)
mesh = jax.tree.leaves(work.state.params)[0].sharding.mesh.shape
r = controls.train_readings(cell, cfg, mix, ref, 3)
print(json.dumps({"limits": mix["limits"], "B": work.B, "mesh": dict(mesh),
                  **r}))
"""


def test_four_device_step_is_correct_and_half_batch_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), str(REPO),
                    os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c",
                        f"TESTS = {str(HERE / 'tests')!r}\n" + CHILD],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    limits = r["limits"]
    assert r["B"] == 8 and r["mesh"] == {"data": 4}, r
    assert all(v <= limits[k] for k, v in r["program"].items()), r
    assert any(v > limits[k] for k, v in r["half_batch"].items()), r
