"""Each cell's control comes out as not correct, and the program as
correct, at a tiny size on the CPU: the reference one precision down
(bfloat16 clocks for the float32 simulator, float8 products for the
bfloat16 model) put in the program's place fails a limit."""

from __future__ import annotations

import pytest

from benchmarks.chip import controls

from chipbench_helpers import sim_cell, train_cell


def _fails(values: dict, limits: dict) -> list:
    return [k for k, v in values.items() if not v <= limits[k]]


@pytest.mark.parametrize("name", ["sim.msync_mgrid.exp"])
def test_sweep_control_fails_and_program_passes(name):
    cell, cfg, mix, ref = sim_cell(name, check_seeds=4)
    r = controls.sweep_readings(cell, cfg, mix, ref, 7)
    assert _fails(r["program"], mix["limits"]) == []
    assert _fails(r["control"], mix["limits"])


def test_train_control_and_half_batch_fail_and_program_passes(monkeypatch):
    cell, cfg, mix, ref = train_cell(monkeypatch)
    r = controls.train_readings(cell, cfg, mix, ref, 1)
    assert _fails(r["program"], mix["limits"]) == []
    assert _fails(r["control"], mix["limits"])
    assert _fails(r["half_batch"], mix["limits"])
