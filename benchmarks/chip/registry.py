"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

Nothing here knows a particular configuration, mix or metric: a new one
is a new file under ``configs/``, ``traffic/`` or ``metrics/`` plus its
entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
BENCHMARK = REPO / "BENCHMARK.json"

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_benchmark(path: Path = BENCHMARK) -> dict:
    with open(path) as fh:
        return json.load(fh)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{sorted(w['name'] for w in bench['workloads'])}")


def _json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{_checked(name)}.json"
    with open(path) as fh:
        return json.load(fh)


def config(name: str) -> dict:
    """``configs/<name>.json``."""
    return _json("configs", name)


def traffic(name: str) -> dict:
    """``traffic/<name>.json``."""
    return _json("traffic", name)


def _module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip._by_name." + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config_name: str) -> ModuleType:
    """The plain reference beside the configuration: ``configs/<name>.py``."""
    return _module(HERE / "configs" / f"{_checked(config_name)}.py")


def metric_reader(name: str) -> ModuleType:
    """``metrics/<name>.py``, whose ``read(obs)`` gives the number."""
    return _module(HERE / "metrics" / f"{_checked(name)}.py")


def _applies(metric: dict, cell: dict, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def end_to_end(bench: dict, cell: dict) -> list:
    """The end-to-end metrics this cell reports (``--trace 0``)."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def per_layer(bench: dict, cell: dict) -> list:
    """The per-layer metrics this cell reports (``--trace 1``)."""
    e2e = {m["name"] for m in end_to_end(bench, cell)}
    return [m for m in bench["per_layer"] if _applies(m, cell, e2e)]
