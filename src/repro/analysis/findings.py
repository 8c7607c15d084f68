"""Finding record + ``# repcheck: ignore[...]`` pragma suppression.

A :class:`Finding` is one rule violation at one source location. Every
rule has a stable ID (``RNG001``, ``JIT003``, ``REG002``, ...) so a
violation can be allowlisted in place with a same-line pragma::

    x = jnp.ones(S, jnp.float32)  # repcheck: ignore[JIT005]

Multiple IDs may be listed (``ignore[JIT001,JIT003]``); ``ignore[*]``
suppresses every rule on that line. Pragmas are the escape hatch of last
resort — DESIGN.md "Enforced invariants" requires a justification
comment next to each one.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Set

__all__ = ["Finding", "parse_pragmas", "filter_suppressed", "RULES"]

# Rule ID -> one-line description. The CLI prints this table under
# --list-rules and DESIGN.md "Enforced invariants" mirrors it.
RULES: Dict[str, str] = {
    "RNG001": "literal-constant PRNGKey/key() inside an engine function "
              "body (keys must arrive via split/fold_in/parameters)",
    "RNG002": "syntactically-identical key expression feeds two distinct "
              "jax.random draw sites (stream reuse)",
    "RNG003": "np.random call in a jax-only engine module (host RNG "
              "breaks device-resident reproducibility)",
    "JIT001": "host coercion (float()/int()/.item()/np.asarray) on a "
              "traced value inside a jit/scan/while_loop function",
    "JIT002": "Python `if`/`while` branches on a traced parameter inside "
              "a scan/while_loop body (use lax.cond/jnp.where)",
    "JIT003": "print()/time.time()/time.perf_counter() inside a traced "
              "function (side effect fires at trace time only)",
    "JIT004": "attribute mutation (obj.attr = ...) inside a traced "
              "function (silent trace-time side effect)",
    "JIT005": "hard-coded jnp.float32/float64 dtype inside a scanned "
              "engine body (breaks x64 engine-mode parity; derive the "
              "dtype from a carried array)",
    "JIT006": "telemetry span/counter or profiler TraceAnnotation inside "
              "a traced function (fires once at trace time; use "
              "jax.named_scope for device code)",
    "REG001": "strategy registered in STRATEGIES but missing from the "
              "DESIGN.md §3b coverage matrix",
    "REG002": "DESIGN.md §3b matrix row names a strategy that is not "
              "registered in STRATEGIES",
    "REG003": "scenario registered in SCENARIOS but missing from the "
              "DESIGN.md §3b scenario table",
    "REG004": "DESIGN.md §3b scenario table row names a scenario that "
              "is not registered in SCENARIOS",
    "REG005": "SCENARIOS factory references a time-model factory that "
              "does not exist in repro.core.time_models",
    "REG006": "STRATEGIES entry and the parity-matrix COVERAGE table "
              "(tests/test_strategy_matrix.py) drifted apart — every "
              "registration needs an engine-coverage row and vice versa",
    "REG007": "SHARDED_KINDS (launch/sweep.py) and the DESIGN.md §3b "
              "sharded backend table drifted apart — every natively "
              "sharded engine kind needs a table row and vice versa",
    "ROB001": "bare except / `except Exception: pass` in engine or "
              "launch code silently swallows failures the degradation "
              "ladder should record",
    "ROB002": "non-atomic artifact write: json.dump into "
              "open(path, 'w') (use repro.exp.runner.atomic_write_json "
              "— tmp file + os.replace)",
}

_PRAGMA_RE = re.compile(
    r"#\s*repcheck:\s*ignore\[([A-Za-z0-9_*,\s]+)\]")


@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    """One rule violation at one source location."""
    path: str
    line: int
    rule: str
    message: str
    severity: str = "error"

    def format(self) -> str:
        return (f"{self.path}:{self.line}: {self.rule} "
                f"[{self.severity}] {self.message}")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def parse_pragmas(source: str) -> Dict[int, Set[str]]:
    """Map 1-based line number -> set of suppressed rule IDs ('*' = all)."""
    pragmas: Dict[int, Set[str]] = {}
    for lineno, text in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(text)
        if m:
            ids = {tok.strip() for tok in m.group(1).split(",")
                   if tok.strip()}
            pragmas.setdefault(lineno, set()).update(ids)
    return pragmas


def filter_suppressed(findings: List[Finding],
                      pragmas: Dict[int, Set[str]]) -> List[Finding]:
    """Drop findings whose line carries a matching (or ``*``) pragma."""
    out = []
    for f in findings:
        ids = pragmas.get(f.line, ())
        if f.rule in ids or "*" in ids:
            continue
        out.append(f)
    return out
