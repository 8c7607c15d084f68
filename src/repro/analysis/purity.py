"""Jit/scan purity pass: JIT001 – JIT006.

Discovery: a function is *traced* when it is (a) decorated with
``jax.jit`` (bare or under ``functools.partial``), (b) passed to
``jax.jit`` / ``lax.scan`` / ``lax.while_loop`` / ``lax.fori_loop`` /
``lax.cond`` / ``lax.switch`` / ``jax.vmap`` / ``jax.checkpoint``
(lambdas and within-module names both resolve), or (c) called by name
from another traced function defined in the same module (trace-time
closure). Resolution is deliberately *within-module only*: cross-module
call graphs would need imports executed, and the contract modules keep
their scanned code self-contained.

Inside a traced function the pass tracks the *param-derived* name set —
parameters (minus jit ``static_argnames``/``static_argnums``) plus
anything assigned from them, to a fixpoint — and flags:

* JIT001  host coercions (``float()``/``int()``/``bool()``, ``.item()``,
  ``.tolist()``, any ``numpy.*`` call) applied to a param-derived value;
* JIT002  Python ``if``/``while``/ternary branching on a param-derived
  name — only in loop bodies (scan step, while cond/body, fori body,
  cond/switch branches) where parameters are traced by construction;
  ``x is None`` and ``isinstance`` tests are exempt (static pytree
  structure checks);
* JIT003  ``print`` / ``time.time`` / ``time.perf_counter`` /
  ``time.monotonic`` / ``breakpoint`` anywhere in a traced function;
* JIT004  attribute mutation (``obj.attr = ...``) anywhere in a traced
  function;
* JIT005  (x64-strict modules only) a hard-coded ``jnp.float32`` /
  ``jnp.float64`` inside a traced function — the engine dtype must
  derive from a carried array so ``x64=True`` switches the whole
  program;
* JIT006  a host span or counter (``telemetry.span`` / ``step_span`` /
  ``sync`` / ``count`` / ``wait`` / ``fetch``, or a
  ``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation``) anywhere
  in a traced function: it fires once, at trace time, and then times
  or counts nothing (device code is named with ``jax.named_scope``).
"""

from __future__ import annotations

import ast
import dataclasses
import itertools
from typing import Dict, List, Optional, Set, Tuple

from .findings import Finding
from .passes import ModuleSource, assigned_names, call_name, dotted_name

__all__ = ["run_purity_pass", "traced_functions"]

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

# canonical call target -> positions of the function-valued arguments,
# and whether those functions are loop bodies (params traced for sure)
_TRACED_ARG_SLOTS: Dict[str, Tuple[Tuple[int, ...], bool]] = {
    "jax.jit": ((0,), False),
    "jit": ((0,), False),
    "jax.vmap": ((0,), False),
    "jax.checkpoint": ((0,), False),
    "jax.remat": ((0,), False),
    "jax.lax.scan": ((0,), True),
    "lax.scan": ((0,), True),
    "jax.lax.while_loop": ((0, 1), True),
    "lax.while_loop": ((0, 1), True),
    "jax.lax.fori_loop": ((2,), True),
    "lax.fori_loop": ((2,), True),
    "jax.lax.cond": ((1, 2), True),
    "lax.cond": ((1, 2), True),
    "jax.lax.switch": ((1, 2, 3, 4, 5), True),
    "lax.switch": ((1, 2, 3, 4, 5), True),
}

_SIDE_EFFECT_CALLS = {"print", "breakpoint", "time.time",
                      "time.perf_counter", "time.monotonic",
                      "time.sleep"}

_TELEMETRY_CALLS = {"span", "step_span", "sync", "count", "wait", "fetch"}
_PROFILER_SPANS = {"TraceAnnotation", "StepTraceAnnotation"}

_HOST_COERCIONS = {"float", "int", "bool", "complex"}
_HOST_METHODS = {"item", "tolist"}


@dataclasses.dataclass
class TracedFn:
    node: ast.AST                 # FunctionDef / AsyncFunctionDef / Lambda
    kind: str                     # "jit" | "loop" | "closure"
    static_names: Set[str] = dataclasses.field(default_factory=set)

    @property
    def name(self) -> str:
        return getattr(self.node, "name", "<lambda>")


def _scope_body(fn: ast.AST):
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    stack = list(body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, _FUNC_NODES):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _canonical(mod: ModuleSource, name: Optional[str]) -> Optional[str]:
    if name is None:
        return None
    head, _, tail = name.partition(".")
    head = mod.import_aliases.get(head, mod.from_imports.get(head, head))
    return f"{head}.{tail}" if tail else head


def _static_from_jit_call(call: ast.Call) -> Set[str]:
    """Constant ``static_argnames`` from a jit/partial(jit, ...) call."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                out.update(e.value for e in v.elts
                           if isinstance(e, ast.Constant))
    return out


def _unwrap_partial(node: ast.AST, mod: ModuleSource):
    """``partial(jax.jit, ...)`` / ``jax.checkpoint(f)`` -> inner expr."""
    while isinstance(node, ast.Call):
        name = _canonical(mod, dotted_name(node.func))
        if name in ("functools.partial", "partial", "jax.checkpoint",
                    "jax.remat", "jax.jit", "jit") and node.args:
            node = node.args[0]
        else:
            break
    return node


def _name_to_defs(mod: ModuleSource) -> Dict[str, List[ast.AST]]:
    defs: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    return defs


def traced_functions(mod: ModuleSource) -> List[TracedFn]:
    """Discover traced functions: decorator/call roots + module closure."""
    defs = _name_to_defs(mod)
    found: Dict[int, TracedFn] = {}       # id(node) -> TracedFn

    def add(expr: ast.AST, kind: str, static: Set[str]) -> None:
        expr = _unwrap_partial(expr, mod)
        if isinstance(expr, ast.Lambda):
            found.setdefault(id(expr), TracedFn(expr, kind, static))
        elif isinstance(expr, ast.Name):
            for d in defs.get(expr.id, []):
                found.setdefault(id(d), TracedFn(d, kind, static))

    # (a) decorators
    for name, nodes in defs.items():
        for node in nodes:
            for deco in node.decorator_list:
                target = deco.args[0] if (isinstance(deco, ast.Call)
                                          and deco.args) else deco
                cname = _canonical(mod, dotted_name(target))
                if cname in ("jax.jit", "jit", "jax.checkpoint",
                             "jax.remat"):
                    static = (_static_from_jit_call(deco)
                              if isinstance(deco, ast.Call) else set())
                    found.setdefault(id(node),
                                     TracedFn(node, "jit", static))

    # (b) call-site roots
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        cname = _canonical(mod, dotted_name(node.func))
        slots = _TRACED_ARG_SLOTS.get(cname or "")
        if not slots:
            continue
        positions, is_loop = slots
        static = _static_from_jit_call(node) if "jit" in (cname or "") \
            else set()
        for pos in positions:
            if pos < len(node.args):
                add(node.args[pos], "loop" if is_loop else "jit", static)

    # (c) within-module trace-time closure, to a fixpoint
    work = list(found.values())
    while work:
        tf = work.pop()
        for node in _scope_body(tf.node):
            if isinstance(node, ast.Call) and isinstance(node.func,
                                                         ast.Name):
                for d in defs.get(node.func.id, []):
                    if id(d) not in found:
                        nf = TracedFn(d, "closure")
                        found[id(d)] = nf
                        work.append(nf)
    return list(found.values())


def _param_derived(tf: TracedFn) -> Set[str]:
    """Names derived from (non-static) parameters, to a fixpoint."""
    args = getattr(tf.node, "args", None)
    derived: Set[str] = set()
    if args is not None:
        for a in itertools.chain(args.posonlyargs, args.args,
                                 args.kwonlyargs,
                                 filter(None, [args.vararg, args.kwarg])):
            if a.arg not in tf.static_names:
                derived.add(a.arg)
    changed = True
    while changed:
        changed = False
        for node in _scope_body(tf.node):
            targets: List[str] = []
            value: Optional[ast.AST] = None
            if isinstance(node, ast.Assign):
                value = node.value
                for t in node.targets:
                    targets.extend(assigned_names(t))
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                value = node.value
                targets.extend(assigned_names(node.target))
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                value = node.iter
                targets.extend(assigned_names(node.target))
            elif isinstance(node, ast.NamedExpr):
                value = node.value
                targets.extend(assigned_names(node.target))
            if value is None or not targets:
                continue
            if any(isinstance(n, ast.Name) and n.id in derived
                   for n in ast.walk(value)):
                new = set(targets) - derived
                if new:
                    derived.update(new)
                    changed = True
    return derived


def _mentions(node: ast.AST, names: Set[str]) -> bool:
    return any(isinstance(n, ast.Name) and n.id in names
               for n in ast.walk(node))


def _is_static_test(test: ast.AST) -> bool:
    """``x is None`` / ``x is not None`` / ``isinstance(...)`` tests are
    pytree-structure checks, static under tracing."""
    if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
        consts = [test.left, *test.comparators]
        if any(isinstance(c, ast.Constant) and c.value is None
               for c in consts):
            return True
    if isinstance(test, ast.Call) and isinstance(test.func, ast.Name) \
            and test.func.id in ("isinstance", "callable", "hasattr"):
        return True
    if isinstance(test, ast.BoolOp):
        return all(_is_static_test(v) for v in test.values)
    return False


def _host_span(cname: Optional[str]) -> bool:
    """A call to the telemetry module's spans/counters or to a profiler
    annotation, however imported."""
    if not cname:
        return False
    parts = cname.split(".")
    if parts[-1] in _PROFILER_SPANS:
        return len(parts) == 1 or parts[-2] == "profiler"
    return (len(parts) >= 2 and parts[-2] == "telemetry"
            and parts[-1] in _TELEMETRY_CALLS)


def run_purity_pass(mod: ModuleSource, x64_strict: bool) -> List[Finding]:
    findings: List[Finding] = []
    for tf in traced_functions(mod):
        derived = _param_derived(tf)
        label = f"traced function {tf.name!r} ({tf.kind})"
        for node in _scope_body(tf.node):
            # JIT003: trace-time-only side effects
            if isinstance(node, ast.Call):
                cname = _canonical(mod, dotted_name(node.func))
                if cname in _SIDE_EFFECT_CALLS:
                    findings.append(Finding(
                        mod.rel, node.lineno, "JIT003",
                        f"{cname}() inside {label} fires at trace time "
                        f"only (and re-fires on every retrace)"))
                # JIT006: host spans/counters in traced code
                elif _host_span(cname):
                    findings.append(Finding(
                        mod.rel, node.lineno, "JIT006",
                        f"{cname}() inside {label} fires once at trace "
                        f"time and times or counts nothing; name device "
                        f"code with jax.named_scope"))
                # JIT001: host coercions on traced values
                elif cname in _HOST_COERCIONS and node.args \
                        and _mentions(node.args[0], derived):
                    findings.append(Finding(
                        mod.rel, node.lineno, "JIT001",
                        f"{cname}() on param-derived value inside {label} "
                        f"forces a host sync (ConcretizationTypeError "
                        f"under jit)"))
                elif cname and cname.startswith("numpy.") and any(
                        _mentions(a, derived) for a in node.args):
                    findings.append(Finding(
                        mod.rel, node.lineno, "JIT001",
                        f"{cname}() on param-derived value inside {label} "
                        f"pulls the array to host"))
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _HOST_METHODS \
                        and _mentions(node.func.value, derived):
                    findings.append(Finding(
                        mod.rel, node.lineno, "JIT001",
                        f".{node.func.attr}() on param-derived value "
                        f"inside {label} forces a host sync"))
            # JIT002: Python branching on traced values (loop bodies)
            if tf.kind == "loop" and isinstance(
                    node, (ast.If, ast.While, ast.IfExp)):
                if _mentions(node.test, derived) \
                        and not _is_static_test(node.test):
                    findings.append(Finding(
                        mod.rel, node.lineno, "JIT002",
                        f"Python branch on param-derived test inside "
                        f"{label}; use lax.cond/jnp.where"))
            # JIT004: attribute mutation
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Attribute):
                    findings.append(Finding(
                        mod.rel, node.lineno, "JIT004",
                        f"attribute mutation "
                        f"{ast.unparse(t)} = ... inside {label} is a "
                        f"trace-time side effect"))
            # JIT005: hard-coded engine dtype (x64-strict modules)
            if x64_strict and isinstance(node, ast.Attribute):
                cname = _canonical(mod, dotted_name(node))
                if cname in ("jax.numpy.float32", "jax.numpy.float64",
                             "jnp.float32", "jnp.float64"):
                    findings.append(Finding(
                        mod.rel, node.lineno, "JIT005",
                        f"hard-coded {cname} inside {label} pins the "
                        f"engine dtype; derive it from a carried "
                        f"array's .dtype so x64=True switches the whole "
                        f"program"))
    return mod.apply_pragmas(findings)
