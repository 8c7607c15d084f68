"""What every cell's driver shares: seeds, the run record, the checks.

A driver (:mod:`.sweep`, :mod:`.train`) builds the cell's work from the
configuration and the mix, warms up, measures a window, optionally
traces a second short window, and then compares a sample of what the
window produced with the plain reference. It returns a :class:`CellRun`;
:mod:`.run` turns that into the result line.
"""

from __future__ import annotations

import dataclasses
import math
import sys
from pathlib import Path
from typing import Optional

import numpy as np

from .registry import REPO

#: where runs write what they leave behind (artifacts, traces); git-ignored
OUT = REPO / ".bench_out"

#: seed purposes: each stream is independent of the others
WARMUP, WINDOW, TRACED, CHECK, WEIGHTS, DATA = range(6)


def seed_ints(seed: int, purpose: int, count: int) -> list:
    """``count`` seeds below 2**31 for one purpose, drawn from ``seed``
    (any whole number, however large)."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), purpose])
    rng = np.random.default_rng(ss)
    return [int(x) for x in rng.integers(0, 2**31 - 1, size=count)]


def rng(seed: int, purpose: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), purpose]))


def out_dir(workload: str) -> Path:
    d = OUT / workload
    d.mkdir(parents=True, exist_ok=True)
    return d


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class CellRun:
    setup_s: float
    rates: dict                       # end-to-end metric -> value
    attempted: int
    failed: int
    checks: list                      # [Check]
    memory_peak_bytes: int
    obs: dict = dataclasses.field(default_factory=dict)
    busy_s: Optional[float] = None    # traced runs only
    window_s: Optional[float] = None
    breakdown: Optional[dict] = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks) \
            and self.failed == 0


def rel_gap(got, want) -> float:
    """Largest ``|got - want| / |want|``; ``inf`` if ``got`` is not
    finite or a ``want`` is zero where ``got`` is not."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return math.inf
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(got == want, 0.0, np.abs(got - want) / np.abs(want))
    return float(np.max(r)) if r.size else 0.0


def print_checks(checks) -> None:
    """The compared numbers beside their limits, last on stderr."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()


def say(msg: str) -> None:
    """Progress notes go to stderr; stdout's last line is the result."""
    print(msg, file=sys.stderr, flush=True)

