"""AST-level contract analyzer for the repo's hand-enforced invariants.

Three pass families (see DESIGN.md "Enforced invariants" for the rule
table and rationale):

* :mod:`repro.analysis.rng` — RNG-stream discipline in the engine
  modules (RNG001–RNG003);
* :mod:`repro.analysis.purity` — jit/scan purity of traced functions
  (JIT001–JIT006);
* :mod:`repro.analysis.registry` — ``STRATEGIES`` / ``SCENARIOS`` /
  time-model / DESIGN.md §3b coverage-matrix / parity-matrix COVERAGE
  lockstep (REG001–REG007);
* :mod:`repro.analysis.robustness` — swallowed exceptions and
  non-atomic artifact writes (ROB001–ROB002).

Stdlib-``ast`` only: the analyzer parses, never imports, so it runs on
a tree whose dependencies are absent (and CI runs it before pytest).
Entry points: ``python -m repro.analysis`` or
:func:`repro.analysis.analyze`. Violations are suppressed in place with
``# repcheck: ignore[RULE]`` pragmas.
"""

from .cli import analyze, main
from .findings import RULES, Finding, filter_suppressed, parse_pragmas
from .passes import ModuleSource, load_module
from .purity import run_purity_pass, traced_functions
from .registry import (collect_registered, collect_sharded_kinds,
                       parse_coverage_table, parse_design_tables,
                       parse_sharded_table, run_registry_pass)
from .rng import run_rng_pass
from .robustness import run_robustness_pass

__all__ = [
    "analyze", "main", "Finding", "RULES", "parse_pragmas",
    "filter_suppressed", "ModuleSource", "load_module",
    "run_rng_pass", "run_purity_pass", "traced_functions",
    "run_registry_pass", "collect_registered", "parse_design_tables",
    "parse_coverage_table", "parse_sharded_table",
    "collect_sharded_kinds", "run_robustness_pass",
]
