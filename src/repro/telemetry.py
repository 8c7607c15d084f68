"""Program spans and the host-sync counter, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: under a running profiler it
lands in the same ``.xplane.pb`` as the device operations, on the same
clock; with the profiler off it costs about a microsecond. The object a
span yields also keeps its host ``perf_counter`` seconds, for callers
that record a duration themselves::

    with telemetry.span("repro.engine.dispatch") as sp:
        out = program(*args)
    meta["exec_s"] = sp.seconds

Spans belong to host code only: inside a traced function they would
fire once, at trace time (repcheck rule JIT006). Device code is named
with ``jax.named_scope`` instead.

``host_syncs`` is the one counter: every call that blocks the host on
device results goes through :func:`wait` (``block_until_ready``),
:func:`fetch` (a host copy) or, where the caller times it, a :func:`sync`
span; each opens a span and counts the call once.

The span names are a contract with the benchmark that reads them
(``PERF.md`` lists which metric reads each):

* experiment: ``repro.exp.run``, ``repro.exp.report``;
* router: ``repro.batch.point``;
* engines: ``repro.engine.prep``, ``repro.engine.dispatch``,
  ``repro.engine.wait``, ``repro.batch.assemble``;
* trainer: ``repro.train.step`` (a step annotation), ``repro.train.feed``,
  ``repro.train.participation``, ``repro.train.put``,
  ``repro.train.dispatch``, ``repro.train.sync``, ``repro.train.log``.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import jax

__all__ = ["Span", "span", "step_span", "count", "counters", "sync",
           "wait", "fetch", "HOST_SYNCS", "ENGINE_PREP", "ENGINE_DISPATCH",
           "ENGINE_WAIT"]

#: the counter of calls that block the host on device results
HOST_SYNCS = "host_syncs"

ENGINE_PREP = "repro.engine.prep"
ENGINE_DISPATCH = "repro.engine.dispatch"
ENGINE_WAIT = "repro.engine.wait"

_COUNTERS: Dict[str, int] = {}


class Span:
    """A profiler annotation that also keeps its host seconds."""

    __slots__ = ("_annotation", "_t0", "seconds")

    def __init__(self, annotation):
        self._annotation = annotation
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        return False

    def annotate(self, **attrs) -> None:
        """Add stats to the open span, for what is known only inside it."""
        self._annotation.set_metadata(**attrs)


def span(name: str, **attrs) -> Span:
    """A host span named ``name``; ``attrs`` become the event's stats."""
    return Span(jax.profiler.TraceAnnotation(name, **attrs))


def step_span(name: str, step_num: int, **attrs) -> Span:
    """A span the profiler knows as training step ``step_num``."""
    return Span(jax.profiler.StepTraceAnnotation(name, step_num=step_num,
                                                 **attrs))


def count(name: str, n: int = 1) -> None:
    _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def counters() -> Dict[str, int]:
    """A copy of every counter's running total in this process."""
    return dict(_COUNTERS)


class _Sync(Span):
    """A span that counts one host sync when it closes."""

    __slots__ = ()

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        count(HOST_SYNCS)
        return False


def sync(name: str = ENGINE_WAIT) -> Span:
    """A span around one call that blocks the host on device results,
    counted as one host sync."""
    return _Sync(jax.profiler.TraceAnnotation(name))


def _on_device(x) -> bool:
    return any(isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(x))


def wait(x: Any, name: str = ENGINE_WAIT) -> Any:
    """``jax.block_until_ready(x)`` inside a :func:`sync` span; ``x``
    without a device array is returned as it is, uncounted."""
    if not _on_device(x):
        return x
    with sync(name):
        return jax.block_until_ready(x)


def fetch(x: Any, name: str = ENGINE_WAIT) -> Any:
    """``jax.device_get(x)`` inside a :func:`sync` span: NumPy copies of
    ``x``'s device arrays, a pytree's leaves fetched together; ``x``
    without a device array is returned as it is, uncounted."""
    if not _on_device(x):
        return x
    with sync(name):
        return jax.device_get(x)
