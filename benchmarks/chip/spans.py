"""The program's own spans and named scopes, read from a profiler trace.

The program marks its host work with ``repro.`` spans
(:mod:`repro.telemetry`: ``jax.profiler.TraceAnnotation`` events on the
thread that calls it) and names device code with ``jax.named_scope``.
This module reads both from the same trace as :mod:`.trace`:

* :func:`span_table` — per ``repro.`` span name on the benchmark's host
  thread: count, total seconds and self seconds (less the nested
  ``repro.`` spans);
* :func:`scope_busy` — per named scope, the device busy seconds: the
  union of the ``XLA Ops`` intervals whose op path (the ``OP_PATH_STAT``
  stat of the event's metadata, read by :mod:`.xspace`) names the
  scope, mean over the devices that ran;
* :func:`idle_by_span` — each idle gap of the first device put down to
  the innermost ``repro.`` span covering its midpoint, or else to the
  innermost ``bench.`` span;
* :func:`coverage` — the share of a span's time that its nested
  ``repro.`` spans cover.

``python3 -m benchmarks.chip.spans <run.py's arguments>`` runs one cell
as :mod:`.run` does, adds these tables to the traced window's reduction
(``--trace 1``), prints them on stderr and writes them to
``.bench_out/<workload>/spans.json``. The result line is
:mod:`.run`'s.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict

from . import trace as tr
from . import xspace

PROGRAM_PREFIX = "repro."
#: the program's named scopes (``jax.named_scope`` in ``repro``)
SCOPES = ("head", "order_stat")
#: the metadata stat of an ``XLA Ops`` event that carries its op path
#: (``jit(run)/while/body/order_stat/reduce_min:`` on a TPU v5e)
OP_PATH_STAT = "tf_op"
#: the spans whose children's cover is checked
PARENTS = ("repro.exp.run", "repro.train.step")


def _program(host):
    return [ev for ev in host if ev[0].startswith(PROGRAM_PREFIX)]


def span_table(host) -> dict:
    """``{name: {"count", "total_s", "self_s"}}`` of the ``repro.``
    spans among ``host`` events ``[(name, start_ns, end_ns)]``."""
    spans = _program(host)
    out = defaultdict(lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0})
    for name, s, e in spans:
        out[name]["count"] += 1
        out[name]["total_s"] += (e - s) * 1e-9
    for name, st in tr.self_times(spans):
        out[name]["self_s"] += st * 1e-9
    return dict(out)


def _scope_pattern(scope: str):
    # a path component, also inside a transform: ``transpose(jvp(head))``
    return re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[)/]|$)")


def scope_busy(space, scopes=SCOPES) -> dict:
    """``{scope: seconds}``: per named scope, the union of the intervals
    of the device operations whose op path (their metadata's
    ``OP_PATH_STAT``) names it, mean over the devices that ran an
    operation. ``space`` is the trace as :func:`.xspace.load` reads it."""
    pats = {sc: _scope_pattern(sc) for sc in scopes}
    per_dev = []
    for plane in space.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        named = {k: [sc for sc, pat in pats.items() if pat.search(path)]
                 for k, path in xspace.metadata_stat(
                     plane, OP_PATH_STAT).items()}
        ivs, ran = defaultdict(list), False
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            t0 = line.timestamp_ns * 1000
            for ev in line.events:
                ran = True
                for sc in named.get(ev.metadata_id, ()):
                    s = t0 + ev.offset_ps
                    ivs[sc].append((s, s + ev.duration_ps))
        if ran:
            per_dev.append({sc: tr.total(tr.union(ivs[sc])) * 1e-12
                            for sc in scopes})
    if not per_dev:
        return {}
    return {sc: sum(d[sc] for d in per_dev) / len(per_dev)
            for sc in scopes}


def _window(host):
    marks = [(s, e) for n, s, e in host if n.startswith(tr.SPAN_PREFIX)]
    return (min((s for s, _ in marks), default=None),
            max((e for _, e in marks), default=None))


def idle_by_span(prof, host) -> dict:
    """``{span: seconds}``: the first device's idle stretches inside the
    benchmark's spans, each put to the innermost ``repro.`` span covering
    its midpoint, else to the innermost bare ``bench.`` span (one that no
    program span holds)."""
    ops = tr.device_ops(prof)
    if not ops:
        return {}
    program = _program(host)
    bare = [ev for ev in host if ev[0].startswith(tr.SPAN_PREFIX)
            and not any(s <= ev[1] and ev[2] <= e for _, s, e in program)]
    busy = tr.union((s, e) for _, s, e in ops[min(ops)])
    lo, hi = _window(host)
    return tr.idle_gaps(busy, program + bare, lo, hi)


def coverage(host, parent: str):
    """Share of the time of every ``parent`` span that the ``repro.``
    spans nested in it cover (None if there is no such span)."""
    spans = _program(host)
    tops = [(s, e) for n, s, e in spans if n == parent]
    if not tops:
        return None
    covered = 0.0
    for s, e in tops:
        inner = [(max(a, s), min(b, e)) for _, a, b in spans
                 if s <= a < e and (a, b) != (s, e)]
        covered += tr.total(tr.union(iv for iv in inner if iv[1] > iv[0]))
    return covered / sum(e - s for s, e in tops)


def reduce_spans(prof, space=None) -> dict:
    """Every table above for one trace (``prof`` as :func:`.trace.load`
    reads it, ``space`` as :func:`.xspace.load` does), as plain
    numbers."""
    host = tr.host_events(prof)
    idle = idle_by_span(prof, host)
    return {
        "spans": span_table(host),
        "scopes": {} if space is None else scope_busy(space),
        "idle_by_span": sorted(([k, v] for k, v in idle.items()),
                               key=lambda kv: -kv[1]),
        "coverage": {p: coverage(host, p) for p in PARENTS},
    }


def print_tables(red: dict, file=None) -> None:
    file = file or sys.stderr
    spans = red.get("spans", {})
    print("program spans (count, total s, self s):", file=file)
    for name, row in sorted(spans.items(), key=lambda kv: -kv[1]["self_s"]):
        print(f"  {name}: {row['count']} {row['total_s']!r} "
              f"{row['self_s']!r}", file=file)
    print(f"named scopes, device s: {red.get('scopes')}", file=file)
    idle = red.get("idle_by_span", [])
    total = sum(v for _, v in idle) or 1.0
    print("device idle by innermost repro. span, else bench. span "
          "(s, share):", file=file)
    for name, v in idle:
        print(f"  {name}: {v!r} {v / total:.4f}", file=file)
    print(f"children's cover: {red.get('coverage')}", file=file)
    file.flush()


def main(argv=None) -> int:
    from . import run              # puts the program's src/ on the path
    from .cell import out_dir

    from repro.exp.runner import atomic_write_json

    argv = sys.argv[1:] if argv is None else list(argv)
    workload = argv[argv.index("--workload") + 1]
    plain_load, plain_reduce = tr.load, tr.reduce
    spaces = []

    def load_with_space(trace_dir):
        prof = plain_load(trace_dir)
        spaces.append(xspace.load(newest_xplane(trace_dir)))
        return prof

    def reduce_with_spans(prof):
        red = plain_reduce(prof)
        extra = reduce_spans(prof, spaces.pop() if spaces else None)
        print_tables(extra)
        atomic_write_json(str(out_dir(workload) / "spans.json"), extra,
                          indent=1)
        return red

    tr.load, tr.reduce = load_with_space, reduce_with_spans
    try:
        return run.main(argv)
    finally:
        tr.load, tr.reduce = plain_load, plain_reduce


def newest_xplane(trace_dir: str) -> str:
    """The file :func:`.trace.load` reads."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return files[-1]


if __name__ == "__main__":
    sys.exit(main())
