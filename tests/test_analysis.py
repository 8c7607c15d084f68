"""ISSUE 6: tests for the ``repro.analysis`` contract analyzer.

Three layers: (1) minimal good/bad fixture snippets per rule — every
rule ID must fire on its bad snippet and stay silent on the good twin;
(2) registry cross-check drift on a miniature strategies/scenarios/
time_models/DESIGN quartet AND on mutated copies of the real repo files
(the acceptance criterion: deleting a §3b matrix row or a STRATEGIES
registration must fail the check); (3) the live repo is finding-free
under the shipped pragma set, which is also what the CI repcheck lane
asserts. The perf-gate failure modes (per-lane diff rows, exit-code
split) ride along at the bottom.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import (RULES, analyze, load_module, main,
                            parse_design_tables, parse_pragmas,
                            run_purity_pass, run_registry_pass,
                            run_rng_pass)

ROOT = Path(__file__).resolve().parents[1]


def _mod(tmp_path, src, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return load_module(p, rel=name)


def _rules(findings):
    return sorted(f.rule for f in findings)


# ------------------------------------------------------------ RNG rules
def test_rng001_literal_prngkey_in_body(tmp_path):
    bad = _mod(tmp_path, """
        import jax

        def engine(n):
            key = jax.random.PRNGKey(0)
            return jax.random.normal(key, (n,))
        """)
    assert _rules(run_rng_pass(bad, jax_only=False)) == ["RNG001"]


def test_rng001_good_twins(tmp_path):
    good = _mod(tmp_path, """
        import jax

        SEED_KEY = jax.random.PRNGKey(0)        # module level: allowed

        def engine(key, s, n):
            k1 = jax.random.fold_in(key, 3)     # derivation: allowed
            root = jax.random.PRNGKey(int(s))   # non-constant: allowed
            return jax.random.normal(k1, (n,)) + jax.random.uniform(
                root, (n,))
        """)
    assert run_rng_pass(good, jax_only=False) == []


def test_rng002_duplicate_key_expression(tmp_path):
    bad = _mod(tmp_path, """
        import jax

        def engine(key, n):
            a = jax.random.normal(key, (n,))
            b = jax.random.uniform(key, (n,))
            return a + b
        """)
    findings = run_rng_pass(bad, jax_only=False)
    assert _rules(findings) == ["RNG002"]
    assert "already feeds the draw" in findings[0].message


def test_rng002_subscript_key_reuse_and_split_ok(tmp_path):
    bad = _mod(tmp_path, """
        import jax

        def engine(key, n):
            sub = jax.random.split(key, 2)
            a = jax.random.normal(sub[0], (n,))
            b = jax.random.uniform(sub[0], (n,))
            return a + b
        """)
    assert _rules(run_rng_pass(bad, jax_only=False)) == ["RNG002"]
    good = _mod(tmp_path, """
        import jax

        def engine(key, n):
            sub = jax.random.split(key, 2)
            return (jax.random.normal(sub[0], (n,))
                    + jax.random.uniform(sub[1], (n,)))
        """, name="good.py")
    assert run_rng_pass(good, jax_only=False) == []


def test_rng002_reassigned_key_not_flagged(tmp_path):
    # the carry idiom: key is split and rebound between the two draws,
    # so the syntactically-equal expressions name different streams
    good = _mod(tmp_path, """
        import jax

        def engine(key, n):
            a = jax.random.normal(key, (n,))
            key, _ = jax.random.split(key)
            b = jax.random.normal(key, (n,))
            return a + b
        """)
    assert run_rng_pass(good, jax_only=False) == []


def test_rng003_host_rng_in_jax_only_module(tmp_path):
    src = """
        import numpy as np

        def engine(n):
            return np.random.default_rng(0).normal(size=n)
        """
    bad = _mod(tmp_path, src)
    assert _rules(run_rng_pass(bad, jax_only=True)) == ["RNG003"]
    # the same code in a NumPy-layer module (time_models) is legitimate
    assert run_rng_pass(_mod(tmp_path, src, name="tm.py"),
                        jax_only=False) == []


# ------------------------------------------------------------ JIT rules
def test_jit001_host_coercion_in_jitted_fn(tmp_path):
    bad = _mod(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return float(x) + 1.0
        """)
    assert _rules(run_purity_pass(bad, x64_strict=False)) == ["JIT001"]


def test_jit001_item_and_np_asarray(tmp_path):
    bad = _mod(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            y = x * 2
            return np.asarray(y), y.item()
        """)
    assert _rules(run_purity_pass(bad, x64_strict=False)) \
        == ["JIT001", "JIT001"]


def test_jit001_static_coercions_allowed(tmp_path):
    good = _mod(tmp_path, """
        import functools
        import jax
        import numpy as np

        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            pad = int(n) + 1          # static arg: host int is fine
            c = np.arange(4)          # closure constant: fine
            return x * pad + c.sum()
        """)
    assert run_purity_pass(good, x64_strict=False) == []


def test_jit002_python_branch_in_loop_body(tmp_path):
    bad = _mod(tmp_path, """
        from jax import lax

        def outer(c0):
            def body(c):
                if c > 0:
                    return c - 1
                return c
            return lax.while_loop(lambda c: c < 10, body, c0)
        """)
    assert _rules(run_purity_pass(bad, x64_strict=False)) == ["JIT002"]


def test_jit002_static_tests_allowed(tmp_path):
    good = _mod(tmp_path, """
        from jax import lax

        def outer(c0, flag=None):
            def body(c):
                if flag is None:          # pytree-structure test: static
                    return c - 1
                return c - 2
            return lax.while_loop(lambda c: c < 10, body, c0)
        """)
    assert run_purity_pass(good, x64_strict=False) == []


def test_jit003_print_in_scan_step(tmp_path):
    bad = _mod(tmp_path, """
        from jax import lax

        def outer(xs, c0):
            def step(c, x):
                print(c)
                return c + x, c
            return lax.scan(step, c0, xs)
        """)
    assert _rules(run_purity_pass(bad, x64_strict=False)) == ["JIT003"]


def test_jit003_time_in_traced_closure(tmp_path):
    # helper called from a jitted fn is traced too (within-module
    # closure resolution): its time.time() fires at trace time only
    bad = _mod(tmp_path, """
        import time
        import jax

        def stamp(x):
            t0 = time.time()
            return x + t0

        @jax.jit
        def f(x):
            return stamp(x)
        """)
    assert _rules(run_purity_pass(bad, x64_strict=False)) == ["JIT003"]


def test_jit006_span_and_counter_in_traced_code(tmp_path):
    # a host span or counter inside a scan step or a jitted closure fires
    # once at trace time: each spelling is a finding
    bad = _mod(tmp_path, """
        import jax
        from jax import lax
        from repro import telemetry

        def outer(xs, c0):
            def step(c, x):
                with telemetry.span("repro.engine.dispatch"):
                    c = c + x
                telemetry.count("host_syncs")
                return c, c
            return lax.scan(step, c0, xs)

        @jax.jit
        def f(x):
            with jax.profiler.TraceAnnotation("repro.x"):
                return x + 1
        """)
    assert _rules(run_purity_pass(bad, x64_strict=False)) \
        == ["JIT006", "JIT006", "JIT006"]


def test_jit006_host_spans_and_named_scopes_allowed(tmp_path):
    # spans around the call of a jitted program are host code; inside it
    # the device side is named with jax.named_scope
    good = _mod(tmp_path, """
        import jax
        from .. import telemetry

        @jax.jit
        def f(x):
            with jax.named_scope("head"):
                return x + 1

        def run(x):
            with telemetry.span("repro.engine.dispatch"):
                y = f(x)
            return telemetry.wait(y)
        """)
    assert run_purity_pass(good, x64_strict=False) == []


def test_jit004_attribute_mutation(tmp_path):
    bad = _mod(tmp_path, """
        import jax

        @jax.jit
        def f(x, box):
            box.cache = x
            return x
        """)
    assert _rules(run_purity_pass(bad, x64_strict=False)) == ["JIT004"]


def test_jit005_hardcoded_dtype_x64_strict_only(tmp_path):
    src = """
        import jax.numpy as jnp
        from jax import lax

        def outer(c0):
            def body(c):
                return c * jnp.ones(3, jnp.float32)
            return lax.while_loop(lambda c: c[0] < 9, body, c0)
        """
    bad = _mod(tmp_path, src)
    assert _rules(run_purity_pass(bad, x64_strict=True)) == ["JIT005"]
    # modules without an x64 engine mode are out of scope for JIT005
    assert run_purity_pass(bad, x64_strict=False) == []


def test_untraced_host_code_not_flagged(tmp_path):
    good = _mod(tmp_path, """
        import time

        def dispatcher(x):
            t0 = time.time()              # host code: fine
            print("running", float(x))    # host code: fine
            return x
        """)
    assert run_purity_pass(good, x64_strict=True) == []


# -------------------------------------------------------------- pragmas
def test_pragma_suppresses_named_rule(tmp_path):
    mod = _mod(tmp_path, """
        import jax

        def engine(n):
            key = jax.random.PRNGKey(0)  # repcheck: ignore[RNG001]
            return jax.random.normal(key, (n,))
        """)
    assert run_rng_pass(mod, jax_only=False) == []


def test_pragma_other_rule_does_not_suppress(tmp_path):
    mod = _mod(tmp_path, """
        import jax

        def engine(n):
            key = jax.random.PRNGKey(0)  # repcheck: ignore[JIT001]
            return jax.random.normal(key, (n,))
        """)
    assert _rules(run_rng_pass(mod, jax_only=False)) == ["RNG001"]


def test_pragma_parsing_star_and_lists():
    pragmas = parse_pragmas(
        "a = 1  # repcheck: ignore[RNG001, JIT003]\n"
        "b = 2\n"
        "c = 3  # repcheck: ignore[*]\n")
    assert pragmas == {1: {"RNG001", "JIT003"}, 3: {"*"}}


# ----------------------------------------------------- registry (fixtures)
_MINI_STRATEGIES = """
STRATEGIES = {}


def register_strategy(name):
    def deco(f):
        STRATEGIES[name] = f
        return f
    return deco


@register_strategy("msync")
def make_msync():
    pass


@register_strategy("malenia")
def make_malenia():
    pass
"""

_MINI_SCENARIOS = """
from repro.core.time_models import FixedTimes, exponential_times

SCENARIOS = {}


def register_scenario(name):
    def deco(f):
        SCENARIOS[name] = f
        return f
    return deco


@register_scenario("fixed_sqrt")
def fixed_sqrt(n):
    return FixedTimes.sqrt_law(n)


@register_scenario("exponential")
def exponential(n):
    return exponential_times(1.0, n)
"""

_MINI_TIME_MODELS = """
class FixedTimes:
    @staticmethod
    def sqrt_law(n):
        return n


def exponential_times(lam, n):
    return n
"""

_MINI_MATRIX = """
COVERAGE = {
    "msync": ("serial",),
    "malenia": ("serial", "jax"),
}
"""

_MINI_SWEEP = """
SHARDED_KINDS = ("msync", "malenia")
"""

_MINI_DESIGN = """# design

## §3b Engine coverage

| strategy \\ model | Fixed |
|------------------|-------|
| msync            | serial |
| malenia          | serial, jax |

| scenario    | family |
|-------------|--------|
| fixed_sqrt  | Fixed  |
| exponential | SubExp |

| sharded kind | engine program |
|--------------|----------------|
| `msync`      | round scan     |
| `malenia`    | renewal rounds |

## §4 Other section

| strategy \\ model | ignored |
|------------------|---------|
| bogus            | table outside §3b |
"""


@pytest.fixture
def mini_repo(tmp_path):
    paths = {
        "strategies": tmp_path / "strategies.py",
        "scenarios": tmp_path / "scenarios.py",
        "time_models": tmp_path / "time_models.py",
        "design": tmp_path / "DESIGN.md",
        "matrix": tmp_path / "test_strategy_matrix.py",
        "sweep": tmp_path / "sweep.py",
    }
    paths["strategies"].write_text(_MINI_STRATEGIES)
    paths["scenarios"].write_text(_MINI_SCENARIOS)
    paths["time_models"].write_text(_MINI_TIME_MODELS)
    paths["design"].write_text(_MINI_DESIGN)
    paths["matrix"].write_text(_MINI_MATRIX)
    paths["sweep"].write_text(_MINI_SWEEP)
    return paths


def _run_mini(paths):
    return run_registry_pass(
        paths["design"].parent,
        strategies_path=paths["strategies"],
        scenarios_path=paths["scenarios"],
        time_models_path=paths["time_models"],
        design_path=paths["design"],
        matrix_test_path=paths["matrix"],
        sweep_path=paths["sweep"])


def test_registry_mini_repo_clean(mini_repo):
    assert _run_mini(mini_repo) == []


def test_reg001_strategy_missing_from_matrix(mini_repo):
    design = mini_repo["design"].read_text()
    mini_repo["design"].write_text(
        "\n".join(l for l in design.splitlines()
                  if not l.startswith("| malenia")))
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG001"]
    assert "malenia" in findings[0].message


def test_reg002_matrix_row_without_registration(mini_repo):
    strat = mini_repo["strategies"].read_text()
    mini_repo["strategies"].write_text(
        strat.replace('@register_strategy("malenia")\n', ""))
    findings = _run_mini(mini_repo)
    # the dropped registration orphans BOTH tables that still name it:
    # the DESIGN matrix row (REG002) and the parity COVERAGE row (REG006)
    assert _rules(findings) == ["REG002", "REG006"]
    assert all("malenia" in f.message for f in findings)


def test_reg003_scenario_missing_from_table(mini_repo):
    design = mini_repo["design"].read_text()
    mini_repo["design"].write_text(
        "\n".join(l for l in design.splitlines()
                  if not l.startswith("| exponential")))
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG003"]


def test_reg004_table_row_without_registration(mini_repo):
    design = mini_repo["design"].read_text()
    mini_repo["design"].write_text(design.replace(
        "| exponential | SubExp |",
        "| exponential | SubExp |\n| ghost_scenario | SubExp |"))
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG004"]
    assert "ghost_scenario" in findings[0].message


def test_reg005_nonexistent_factory(mini_repo):
    scen = mini_repo["scenarios"].read_text()
    mini_repo["scenarios"].write_text(scen.replace(
        "FixedTimes.sqrt_law(n)", "FixedTimes.cube_law(n)"))
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG005"]
    assert "cube_law" in findings[0].message


def test_reg005_import_of_missing_name(mini_repo):
    scen = mini_repo["scenarios"].read_text()
    mini_repo["scenarios"].write_text(scen.replace(
        "FixedTimes, exponential_times",
        "FixedTimes, exponential_times, gamma_times"))
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG005"]
    assert "gamma_times" in findings[0].message


def test_reg006_registration_without_coverage_row(mini_repo):
    """ISSUE 9: a STRATEGIES entry with no parity-matrix COVERAGE row is
    REG006 drift (pointing at the registration line)."""
    matrix = mini_repo["matrix"].read_text()
    mini_repo["matrix"].write_text(
        matrix.replace('    "malenia": ("serial", "jax"),\n', ""))
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG006"]
    assert "malenia" in findings[0].message
    assert findings[0].path == str(mini_repo["strategies"])


def test_reg006_coverage_row_without_registration(mini_repo):
    matrix = mini_repo["matrix"].read_text()
    mini_repo["matrix"].write_text(matrix.replace(
        '"malenia": ("serial", "jax"),',
        '"malenia": ("serial", "jax"),\n    "ghost": ("serial",),'))
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG006"]
    assert "ghost" in findings[0].message
    assert findings[0].path == str(mini_repo["matrix"])


def test_reg006_missing_matrix_test_is_structural(mini_repo):
    mini_repo["matrix"].unlink()
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG006"]
    assert "missing" in findings[0].message


def test_reg006_no_coverage_literal_is_structural(mini_repo):
    mini_repo["matrix"].write_text("COVERAGE = build_coverage()\n")
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG006"]
    assert "dict literal" in findings[0].message


def test_reg007_kind_missing_from_sharded_table(mini_repo):
    """ISSUE 10: a SHARDED_KINDS entry the DESIGN §3b sharded backend
    table does not document is REG007 drift (pointing at the literal)."""
    mini_repo["sweep"].write_text(
        'SHARDED_KINDS = ("msync", "malenia", "ghost_kind")\n')
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG007"]
    assert "ghost_kind" in findings[0].message
    assert findings[0].path == str(mini_repo["sweep"])


def test_reg007_table_row_without_sharded_kind(mini_repo):
    design = mini_repo["design"].read_text()
    mini_repo["design"].write_text(design.replace(
        "| `malenia`    | renewal rounds |",
        "| `malenia`    | renewal rounds |\n| `phantom` | nothing |"))
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG007"]
    assert "phantom" in findings[0].message
    assert "fall back" in findings[0].message
    assert findings[0].path == str(mini_repo["design"])


def test_reg007_missing_sweep_is_structural(mini_repo):
    mini_repo["sweep"].unlink()
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG007"]
    assert "missing" in findings[0].message


def test_reg007_no_kinds_literal_is_structural(mini_repo):
    mini_repo["sweep"].write_text("SHARDED_KINDS = make_kinds()\n")
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG007"]
    assert "literal" in findings[0].message


def test_reg007_no_sharded_table_is_structural(mini_repo):
    design = mini_repo["design"].read_text()
    mini_repo["design"].write_text(design.replace(
        "| sharded kind | engine program |\n"
        "|--------------|----------------|\n"
        "| `msync`      | round scan     |\n"
        "| `malenia`    | renewal rounds |\n", ""))
    findings = _run_mini(mini_repo)
    assert _rules(findings) == ["REG007"]
    assert "sharded" in findings[0].message.lower()


def test_missing_matrix_table_is_structural_finding(mini_repo):
    mini_repo["design"].write_text("# design\n\n## §3b Engines\n\nprose\n")
    rules = _rules(_run_mini(mini_repo))
    assert "REG002" in rules and "REG004" in rules    # tables missing
    assert "REG001" in rules and "REG003" in rules    # all regs unmatched


# ------------------------------------------------- registry (live repo)
def test_live_registry_crosscheck_clean():
    """The plain-pytest spelling of the CI repcheck registry lane:
    STRATEGIES / SCENARIOS / time_models / DESIGN §3b are in lockstep."""
    assert run_registry_pass(ROOT) == []


def test_live_design_tables_cover_all_registrations():
    matrix, scen = parse_design_tables(ROOT / "DESIGN.md")
    assert matrix is not None and scen is not None
    assert set(matrix) == {"sync", "msync", "auto_m", "async", "rennala",
                           "malenia", "ringmaster", "ringleader",
                           "optimal_asgd", "deadline", "dropout"}
    # 16 base regimes (incl. the PR 10 power-law pair) + 6 §3c faults
    assert len(scen) == 22


def test_live_coverage_table_matches_design_matrix():
    """The parity COVERAGE table and the DESIGN §3b matrix name exactly
    the same strategies (the REG006 + REG001/REG002 triangle, spelled
    out directly)."""
    from repro.analysis import parse_coverage_table
    matrix, _ = parse_design_tables(ROOT / "DESIGN.md")
    coverage = parse_coverage_table(ROOT / "tests/test_strategy_matrix.py")
    assert coverage is not None
    assert set(coverage) == set(matrix)


def test_live_sharded_table_matches_sharded_kinds():
    """ISSUE 10: the DESIGN §3b sharded-kind table, the parsed
    SHARDED_KINDS literal, and the imported tuple agree exactly (the
    REG007 lockstep, spelled out directly)."""
    from repro.analysis import collect_sharded_kinds, parse_sharded_table
    from repro.launch.sweep import SHARDED_KINDS
    table = parse_sharded_table(ROOT / "DESIGN.md")
    kinds = collect_sharded_kinds(ROOT / "src/repro/launch/sweep.py")
    assert table is not None and kinds is not None
    assert set(table) == set(kinds) == set(SHARDED_KINDS)


def test_deleting_live_coverage_row_fails_crosscheck(tmp_path):
    """ISSUE 9 acceptance: dropping a COVERAGE row from the live parity
    test breaks the REG006 cross-check."""
    src = (ROOT / "tests/test_strategy_matrix.py").read_text()
    mutated = tmp_path / "test_strategy_matrix.py"
    mutated.write_text(src.replace(
        '    "ringleader": ("serial", "jax"),\n', ""))
    findings = run_registry_pass(ROOT, matrix_test_path=mutated)
    assert any(f.rule == "REG006" and "ringleader" in f.message
               for f in findings)


def test_deleting_live_matrix_row_fails_crosscheck(tmp_path):
    """Acceptance: deleting any §3b matrix row breaks the cross-check."""
    design = (ROOT / "DESIGN.md").read_text()
    mutated = tmp_path / "DESIGN.md"
    mutated.write_text("\n".join(
        l for l in design.splitlines() if not l.startswith("| rennala")))
    findings = run_registry_pass(ROOT, design_path=mutated)
    assert any(f.rule == "REG001" and "rennala" in f.message
               for f in findings)


def test_deleting_live_strategy_registration_fails_crosscheck(tmp_path):
    """Acceptance: dropping a STRATEGIES entry breaks the cross-check."""
    strat = (ROOT / "src/repro/core/strategies.py").read_text()
    mutated = tmp_path / "strategies.py"
    mutated.write_text(
        strat.replace('@register_strategy("ringmaster")\n', ""))
    findings = run_registry_pass(ROOT, strategies_path=mutated)
    assert any(f.rule == "REG002" and "ringmaster" in f.message
               for f in findings)


# ------------------------------------------------------ live repo + CLI
def test_live_repo_is_finding_free():
    """ISSUE 6 acceptance: the analyzer exits clean on the whole tree
    under the shipped pragma set (the CI repcheck lane's assertion)."""
    assert analyze(ROOT) == []


def test_cli_json_on_bad_tree(tmp_path, capsys):
    engine_dir = tmp_path / "kernels"
    engine_dir.mkdir()
    (engine_dir / "bad.py").write_text(textwrap.dedent("""
        import numpy as np

        def engine(n):
            return np.random.normal(size=n)
        """))
    rc = main(["--root", str(tmp_path), "--format", "json",
               str(engine_dir)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["count"] == 1
    assert out["findings"][0]["rule"] == "RNG003"
    assert out["findings"][0]["line"] == 5


def test_cli_text_clean_dir(tmp_path, capsys):
    (tmp_path / "ok.py").write_text("x = 1\n")
    rc = main(["--root", str(tmp_path), str(tmp_path)])
    assert rc == 0
    assert "clean" in capsys.readouterr().out


def test_cli_list_rules_covers_all_ids(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out


def test_cli_subprocess_end_to_end():
    """The exact CI repcheck invocation exits 0 on the real tree."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--format", "json"],
        cwd=ROOT, capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["count"] == 0


# ------------------------------------------------------------ perf gate
def _gate_doc(**sections):
    doc = {"meta": {"n": 4, "K": 10}}
    doc.update(sections)
    return doc


def test_perf_gate_exit_code_split():
    from benchmarks import perf_gate
    base = _gate_doc(speedup_vs_serial={"jax": 5.0},
                     total_time_mean={"async": 1.0})
    ok = perf_gate.compare(base, base, tol=0.3)
    assert ok == [] and perf_gate.exit_code(ok) == perf_gate.EXIT_OK

    slow = _gate_doc(speedup_vs_serial={"jax": 2.0},
                     total_time_mean={"async": 1.0})
    reg = perf_gate.compare(slow, base, tol=0.3)
    assert [f.kind for f in reg] == ["regression"]
    assert perf_gate.exit_code(reg) == perf_gate.EXIT_REGRESSION

    missing = _gate_doc(speedup_vs_serial={"jax": 5.0})
    struct = perf_gate.compare(missing, base, tol=0.3)
    assert any(f.kind == "structural" for f in struct)
    assert perf_gate.exit_code(struct) == perf_gate.EXIT_STRUCTURAL


def test_perf_gate_meta_mismatch_is_structural():
    from benchmarks import perf_gate
    a = _gate_doc(total_time_mean={"async": 1.0})
    b = _gate_doc(total_time_mean={"async": 1.0})
    b["meta"]["n"] = 8
    failures = perf_gate.compare(a, b, tol=0.3)
    assert [f.kind for f in failures] == ["structural"]
    assert "config mismatch" in failures[0].bound


def test_perf_gate_failure_row_is_readable():
    from benchmarks import perf_gate
    base = _gate_doc(speedup_vs_serial={"jax_vs_serial": 5.0})
    slow = _gate_doc(speedup_vs_serial={"jax_vs_serial": 2.0})
    (failure,) = perf_gate.compare(slow, base, tol=0.3)
    row = failure.row()
    assert "speedup_vs_serial.jax_vs_serial" in row
    assert "2" in row and "5" in row and "floor" in failure.bound


def test_perf_gate_cli_exit_codes(tmp_path, capsys):
    from benchmarks import perf_gate
    base = tmp_path / "base.json"
    meas = tmp_path / "meas.json"
    base.write_text(json.dumps(
        _gate_doc(speedup_vs_serial={"jax": 5.0})))
    meas.write_text(json.dumps(
        _gate_doc(speedup_vs_serial={"jax": 2.0})))
    assert perf_gate.main([str(meas), str(base)]) \
        == perf_gate.EXIT_REGRESSION
    out = capsys.readouterr().out
    assert "lane" in out and "measured" in out and "baseline" in out
    assert perf_gate.main([str(meas), str(tmp_path / "absent.json")]) \
        == perf_gate.EXIT_STRUCTURAL
    assert perf_gate.main([str(base), str(base)]) == perf_gate.EXIT_OK
