"""Profiler trace to per-layer numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
:func:`load` reads the newest with ``jax.profiler.ProfileData``. The
reduction works on anything shaped like it (planes with ``name`` and
``lines``, lines with ``name`` and ``events``, events with ``name``,
``start_ns`` and ``duration_ns``), so the tests feed it small synthetic
traces.

* A device is a plane named ``/device:TPU:<i>``; its operations are the
  events of the line named ``XLA Ops``, each named by its HLO instruction
  (``fusion.399``) under the program that ran it (``XLA Modules`` line,
  ``jit_step_fn/fusion.399``).
* Busy time is the union of a device's operation intervals, averaged over
  the devices that ran anything.
* Self time of an operation is its duration less the part that nested
  operations on the same line cover (a loop's events hold its body's).
* An idle gap is a stretch between two busy intervals of the first
  device; it is put to the innermost host event on the benchmark's own
  thread (the one carrying ``bench.`` spans) that covers its midpoint.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import time
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
TOP = 10


def start(trace_dir: str) -> None:
    """Start the profiler with the host's TraceMe events but without the
    Python function tracer, which would slow the host several times over
    and inflate the idle share it is meant to measure."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def traced_window(trace_dir: str, body):
    """Run ``body()`` under the profiler, inside a ``bench.window`` span;
    return the trace's reduction and the window's seconds. The trace is
    deleted once read."""
    import jax

    clear(trace_dir)
    start(trace_dir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        body()
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    red = reduce(load(trace_dir))
    clear(trace_dir)
    return red, window_s


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ProfileData."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(files[-1])


def clear(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


def union(intervals):
    """Sorted, merged ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def self_times(events):
    """``[(name, self_ns)]`` for ``[(name, start, end)]`` on one line."""
    evs = sorted(events, key=lambda t: (t[1], -t[2]))
    stack, out = [], []
    for name, s, e in evs:
        while stack and stack[-1][2] <= s:
            out.append(_close(stack.pop()))
        node = [name, s, e, 0.0]
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append(node)
    while stack:
        out.append(_close(stack.pop()))
    return out


def _close(node):
    name, s, e, child = node
    return name, max(e - s - child, 0.0)


def op_name(hlo: str) -> str:
    """``fusion.399`` from ``%fusion.399 = (f32[...]) fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def module_name(name: str) -> str:
    """``jit_step_fn`` from ``jit_step_fn(7096203147581258495)``."""
    return name.split("(", 1)[0]


def _label_ops(ops, modules):
    """Prefix each op with the program whose interval holds its start."""
    mods = sorted(modules, key=lambda t: t[1])
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda t: t[1]):
        while j + 1 < len(mods) and mods[j + 1][1] <= s:
            j += 1
        if mods and mods[j][1] <= s < mods[j][2]:
            name = f"{mods[j][0]}/{name}"
        out.append((name, s, e))
    return out


def device_ops(prof) -> dict:
    """``{device index: [(program/op, start_ns, end_ns)]}`` of devices
    that ran at least one operation."""
    out = {}
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops.extend((op_name(ev.name), float(ev.start_ns),
                            float(ev.start_ns) + float(ev.duration_ns))
                           for ev in line.events)
            elif line.name == MODULES_LINE:
                mods.extend((module_name(ev.name), float(ev.start_ns),
                             float(ev.start_ns) + float(ev.duration_ns))
                            for ev in line.events)
        if ops:
            out[int(m.group(1))] = _label_ops(ops, mods)
    return out


def host_events(prof):
    """``[(name, start_ns, end_ns)]`` of the host thread that carries the
    benchmark's ``bench.`` spans (empty when there is none)."""
    best, best_n = [], 0
    for plane in prof.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            evs = [(ev.name, float(ev.start_ns),
                    float(ev.start_ns) + float(ev.duration_ns))
                   for ev in line.events if ev.duration_ns > 0]
            n = sum(1 for e in evs if e[0].startswith(SPAN_PREFIX))
            if n > best_n:
                best, best_n = evs, n
    return best


def label_points(host, points):
    """Innermost host event covering each time in ``points`` (a sorted
    list), by one sweep over the events, which nest like calls."""
    evs = sorted(host, key=lambda t: (t[1], -t[2]))
    out, stack, i = [], [], 0
    for t in points:
        while i < len(evs) and evs[i][1] <= t:
            while stack and stack[-1][2] <= evs[i][1]:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        out.append(stack[-1][0] if stack else
                   "outside the benchmark's spans")
    return out


def idle_gaps(busy, host, lo=None, hi=None):
    """``{label: seconds}`` of the idle stretches of a merged busy list
    between ``lo`` and ``hi`` (default: first to last busy instant)."""
    if not busy:
        return {}
    lo = busy[0][0] if lo is None else lo
    hi = busy[-1][1] if hi is None else hi
    inside = [iv for iv in busy if lo <= iv[0] and iv[1] <= hi]
    edges = [(lo, lo)] + inside + [(hi, hi)]
    stretches = [(e0, s1) for (_, e0), (s1, _) in zip(edges, edges[1:])
                 if s1 > e0]
    labels = label_points(host, [0.5 * (a + b) for a, b in stretches])
    gaps = defaultdict(float)
    for (a, b), name in zip(stretches, labels):
        gaps[name] += (b - a) * 1e-9
    return dict(gaps)


def reduce(prof) -> dict:
    """Busy seconds (mean over devices), top operations by self time,
    and idle gaps by host event, all as plain numbers."""
    ops = device_ops(prof)
    if not ops:
        return {"devices": 0, "busy_s": 0.0, "device_ops": [],
                "idle_gaps": []}
    busy = []
    by_name = defaultdict(float)
    for evs in ops.values():
        merged = union((s, e) for _, s, e in evs)
        busy.append(total(merged))
        for name, st in self_times(evs):
            by_name[name] += st
    nd = len(ops)
    first = ops[min(ops)]
    host = host_events(prof)
    spans = [(s, e) for n, s, e in host if n.startswith(SPAN_PREFIX)]
    lo = min((s for s, _ in spans), default=None)
    hi = max((e for _, e in spans), default=None)
    gaps = idle_gaps(union((s, e) for _, s, e in first), host, lo, hi)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": nd,
        "busy_s": sum(busy) / nd * 1e-9,
        "device_ops": [[n, v / nd * 1e-9] for n, v in top],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:TOP],
    }


def describe(prof, limit: int = 5) -> list:
    """A few lines naming each plane and line with its first events, to
    look at a trace by hand."""
    out = []
    for plane in prof.planes:
        for line in plane.lines:
            evs = list(line.events)
            head = ", ".join(f"{e.name}@{e.start_ns:.0f}+{e.duration_ns:.0f}"
                             for e in evs[:limit])
            out.append(f"{plane.name} | {line.name} | {len(evs)} | {head}")
    return out
