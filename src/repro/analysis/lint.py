"""Reservoir-lint: AST-based determinism/JAX static analysis (stdlib only).

Usage::

    python -m repro.analysis.lint src/ [more paths...] [--fail-on=error]

Rule catalogue (see DESIGN.md §Static analysis & sanitizers for the full
rationale and which historical bug each rule would have caught):

D-class — determinism rules (simulator correctness):

* **D001** (error): builtin ``hash()`` call.  ``hash(str)`` is salted per
  *process* (PYTHONHASHSEED), so anything derived from it — seeds, routing,
  bucket choices — differs across invocations and breaks pinned goldens.
  Use ``zlib.crc32(x.encode())`` (the repo idiom since PR 4).
* **D002** (error): wall-clock read (``time.time``/``perf_counter``/
  ``datetime.now``/...) inside a sim-path package (``core/``,
  ``federation/``, ``faults/``, ``serving/``) where only the virtual clock
  (``EventLoop.now``) may be read.  ``launch/`` and ``benchmarks/`` are
  exempt (they measure real wall time by design).
* **D003** (error): unseeded randomness — ``random.Random()`` with no seed,
  module-global ``random.*`` draws, global ``np.random.*`` state, or
  ``np.random.default_rng()`` without a seed.  Every RNG must be seeded
  explicitly or derived from one that is.
* **D004** (warning): iteration over a bare ``set`` (or ``list()``/
  ``tuple()``/``join()`` of one).  Set iteration order is insertion- and
  hash-salt-dependent; when it feeds scheduling or serialization the run
  is irreproducible.  Sort first (``sorted(s)``) or use an ordered
  container.  Heuristic: only names/attributes the linter can locally
  prove set-typed are flagged.

J-class — JAX rules (retrace / host-sync hygiene):

* **J001** (error): ``jax.jit`` / ``pl.pallas_call`` / ``functools.partial(
  jax.jit, ...)`` constructed inside a plain function or loop: each call
  builds a fresh jit wrapper, so every invocation retraces and the
  compile cache is useless.  Hoist to module scope, decorate, or cache the
  wrapper (waive with the cache as the reason).  A ``pallas_call`` inside
  a function that is itself jitted at module scope is the standard idiom
  and is not flagged.
* **J002** (warning): implicit host sync inside a jitted function or
  Pallas kernel body — ``float()``/``int()``/``bool()`` on a traced value,
  ``.item()``, or ``np.asarray``/``np.array`` on device values.  These
  block dispatch (or silently fall back to host math) in the kernel/store
  hot paths.

O-class — observability rules (metrics-registry hygiene):

* **O001** (error): direct subscript mutation of a legacy stats mapping
  (``<obj>.stats[...] += 1`` / ``= ...`` on ``stats`` / ``engine_stats`` /
  ``fault_stats``) inside a sim-path package.  Those mappings are
  ``repro.obs.registry.CounterGroup`` views adopted by the one
  ``MetricsRegistry``; write through ``.inc(key, n)`` so every increment
  goes through the counters' one write path.  Tests and
  benchmarks may still poke the mapping (CounterGroup stays a
  MutableMapping for exactly that reason).

Waivers: append ``# lint: disable=D001(reason)`` to the flagged line (or
put the comment alone on the line directly above).  A reason is mandatory
— a bare waiver is itself a violation (W000) — and a waiver that matches
no violation is reported unused (W001) so stale waivers cannot accumulate.

Exit status: nonzero iff any unwaived violation at or above ``--fail-on``
severity (default ``error``; CI runs ``--fail-on=warning``).
"""
from __future__ import annotations

import ast
import dataclasses
import io
import re
import sys
import tokenize
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

SEVERITIES = ("warning", "error")  # ascending

RULES: Dict[str, Tuple[str, str]] = {
    # code -> (severity, summary)
    "D001": ("error", "process-salted builtin hash(); use zlib.crc32"),
    "D002": ("error", "wall-clock read on the virtual timeline"),
    "D003": ("error", "unseeded / global-state randomness"),
    "D004": ("warning", "order-sensitive iteration over a bare set"),
    "J001": ("error", "jit/pallas_call constructed per call (retrace)"),
    "J002": ("warning", "implicit host sync in jit/kernel scope"),
    "O001": ("error", "direct mutation of a registry-adopted stats map"),
    "W000": ("error", "waiver without a reason"),
    "W001": ("error", "unused waiver"),
}

# legacy stats mappings re-homed into the metrics registry (O001)
REGISTRY_STATS_ATTRS = {"stats", "engine_stats", "fault_stats"}

# packages where only the virtual clock may be read (D002)
SIM_PATH_PACKAGES = {"core", "federation", "faults", "serving"}
# packages exempt from D002 (real wall time is the point there)
WALLCLOCK_EXEMPT = {"launch", "benchmarks"}

WALLCLOCK_CALLS = {
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns", "time.process_time",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "datetime.datetime.today", "datetime.date.today",
}

GLOBAL_RANDOM_DRAWS = {
    "random", "randint", "randrange", "choice", "choices", "sample",
    "shuffle", "uniform", "gauss", "normalvariate", "betavariate",
    "expovariate", "triangular", "vonmisesvariate", "seed", "getrandbits",
}
GLOBAL_NP_RANDOM = {
    "seed", "rand", "randn", "randint", "random", "random_sample", "choice",
    "uniform", "normal", "standard_normal", "shuffle", "permutation",
    "beta", "binomial", "poisson", "exponential", "get_state", "set_state",
}

_WAIVER_RE = re.compile(r"lint:\s*disable=(.+)")
_WAIVER_ITEM_RE = re.compile(r"([A-Z]\d{3})(?:\(([^)]*)\))?")


@dataclasses.dataclass
class Violation:
    rule: str
    path: str
    line: int
    col: int
    message: str
    severity: str = ""
    waived: bool = False
    waive_reason: str = ""

    def __post_init__(self):
        if not self.severity:
            self.severity = RULES[self.rule][0]

    def format(self) -> str:
        tag = f" [waived: {self.waive_reason}]" if self.waived else ""
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.severity}] {self.message}{tag}")


@dataclasses.dataclass
class _Waiver:
    rule: str
    line: int          # line the waiver applies to
    comment_line: int  # line the comment physically sits on
    reason: str
    used: bool = False


def _collect_waivers(source: str) -> List[_Waiver]:
    """Parse ``# lint: disable=CODE(reason)[,CODE(reason)...]`` comments.

    A trailing comment waives its own line; a comment alone on a line
    waives the next line.  Uses ``tokenize`` so string literals containing
    the marker are never mistaken for waivers.
    """
    waivers: List[_Waiver] = []
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _WAIVER_RE.search(tok.string)
            if m is None:
                continue
            line = tok.start[0]
            # comment alone on its line -> applies to the next line
            prefix = source.splitlines()[line - 1][: tok.start[1]]
            target = line + 1 if prefix.strip() == "" else line
            for item in _WAIVER_ITEM_RE.finditer(m.group(1)):
                waivers.append(_Waiver(item.group(1), target, line,
                                       (item.group(2) or "").strip()))
    except tokenize.TokenError:
        pass
    return waivers


# --------------------------------------------------------------------- helpers
def _module_parts(path: Path) -> Tuple[str, ...]:
    """Path components after the last ``repro``/``src`` marker (best effort)."""
    parts = path.parts
    for marker in ("repro", "src"):
        if marker in parts:
            return parts[len(parts) - parts[::-1].index(marker):]
    return parts


def _is_sim_path(path: Path) -> bool:
    parts = _module_parts(path)
    if any(p in WALLCLOCK_EXEMPT for p in parts):
        return False
    return any(p in SIM_PATH_PACKAGES for p in parts)


class _Aliases(ast.NodeVisitor):
    """First pass: import aliases + jit/kernel function marks + set attrs."""

    def __init__(self):
        self.aliases: Dict[str, str] = {}       # local name -> canonical module
        self.from_names: Dict[str, str] = {}    # local name -> canonical dotted
        self.jit_funcs: Set[str] = set()        # function names jitted at def
        self.kernel_funcs: Set[str] = set()     # pallas kernel body functions
        self.set_attrs: Set[str] = set()        # self.<attr> assigned a set

    CANON = {
        "numpy": "numpy", "np": None, "jax": "jax",
    }

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.aliases[a.asname or a.name.split(".")[0]] = (
                a.name if a.asname else a.name.split(".")[0])
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        for a in node.names:
            self.from_names[a.asname or a.name] = f"{mod}.{a.name}"
        self.generic_visit(node)

    # --- function marks ------------------------------------------------
    def _mark_function(self, node) -> None:
        for dec in node.decorator_list:
            if _dotted(dec, self) in ("jax.jit",):
                self.jit_funcs.add(node.name)
            elif isinstance(dec, ast.Call):
                callee = _dotted(dec.func, self)
                if callee == "jax.jit":
                    self.jit_funcs.add(node.name)
                elif callee == "functools.partial" and dec.args and \
                        _dotted(dec.args[0], self) == "jax.jit":
                    self.jit_funcs.add(node.name)
        if node.name.endswith("_kernel"):
            self.kernel_funcs.add(node.name)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._mark_function(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node: ast.Call) -> None:
        # functions passed as a pallas_call kernel body are kernel scope
        if _dotted(node.func, self) == "jax.experimental.pallas.pallas_call" \
                and node.args and isinstance(node.args[0], ast.Name):
            self.kernel_funcs.add(node.args[0].id)
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        if _is_set_expr(node.value, None):
            for tgt in node.targets:
                if (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    self.set_attrs.add(tgt.attr)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        ann = node.annotation
        is_set_ann = (isinstance(ann, ast.Name) and ann.id in ("set", "Set")) \
            or (isinstance(ann, ast.Subscript)
                and _dotted(ann.value, self) in ("set", "Set", "typing.Set",
                                                 "frozenset"))
        if is_set_ann or (node.value is not None
                          and _is_set_expr(node.value, None)):
            tgt = node.target
            if (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"):
                self.set_attrs.add(tgt.attr)
        self.generic_visit(node)


def _dotted(node: ast.AST, info) -> Optional[str]:
    """Resolve an expression to a canonical dotted name, or None.

    ``np.random.seed`` -> ``numpy.random.seed`` given ``import numpy as np``;
    a bare imported name resolves through ``from_names``.
    """
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    base = node.id
    if info is not None:
        if base in info.aliases:
            base = info.aliases[base]
        elif base in info.from_names:
            base = info.from_names[base]
    parts.append(base)
    return ".".join(reversed(parts))


def _is_set_expr(node: ast.AST, scope: Optional["_Scope"]) -> bool:
    """Can ``node`` be locally proven to evaluate to a set?"""
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in ("set", "frozenset"):
        return True
    if scope is not None:
        if isinstance(node, ast.Name) and node.id in scope.set_names:
            return True
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"
                and node.attr in scope.set_attrs):
            return True
    if isinstance(node, ast.BinOp) and isinstance(node.op, (ast.BitOr,
                                                            ast.BitAnd,
                                                            ast.Sub)):
        return (_is_set_expr(node.left, scope)
                and _is_set_expr(node.right, scope))
    return False


@dataclasses.dataclass
class _Scope:
    set_names: Set[str]
    set_attrs: Set[str]


# --------------------------------------------------------------------- checker
class _Checker(ast.NodeVisitor):
    def __init__(self, path: Path, info: _Aliases, sim_path: bool):
        self.path = path
        self.info = info
        self.sim_path = sim_path
        self.violations: List[Violation] = []
        self.func_stack: List[ast.AST] = []   # enclosing FunctionDefs
        self.loop_depth = 0
        self.scopes: List[_Scope] = [_Scope(set(), info.set_attrs)]

    # ------------------------------------------------------------- utils
    def _add(self, rule: str, node: ast.AST, message: str) -> None:
        self.violations.append(Violation(
            rule, str(self.path), node.lineno, node.col_offset, message))

    def _in_jit_scope(self) -> bool:
        return any(
            getattr(f, "name", None) in self.info.jit_funcs
            or getattr(f, "name", None) in self.info.kernel_funcs
            for f in self.func_stack)

    def _enclosing_jitted(self) -> bool:
        """Is any enclosing function itself jit-wrapped (trace-cached)?"""
        return any(getattr(f, "name", None) in self.info.jit_funcs
                   for f in self.func_stack)

    # --------------------------------------------------------- traversal
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        # J001: a jit-decorated def nested inside another function builds a
        # fresh jit wrapper per outer call
        if self.func_stack and node.name in self.info.jit_funcs:
            self._add("J001", node,
                      f"jit-decorated '{node.name}' defined inside a "
                      "function: every outer call builds a fresh jit and "
                      "retraces; hoist to module scope or cache the wrapper")
        self.func_stack.append(node)
        self.scopes.append(_Scope(set(), self.info.set_attrs))
        self.generic_visit(node)
        self.scopes.pop()
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _check_stats_mutation(self, tgt: ast.AST, node: ast.AST) -> None:
        # O001 — <obj>.stats[...] written directly in a sim path
        if not (self.sim_path and isinstance(tgt, ast.Subscript)
                and isinstance(tgt.value, ast.Attribute)
                and tgt.value.attr in REGISTRY_STATS_ATTRS):
            return
        self._add("O001", node,
                  f"direct mutation of '.{tgt.value.attr}[...]': this "
                  "mapping is a CounterGroup adopted by the metrics "
                  "registry; write through .inc(key, n) so the increment "
                  "goes through the counters' one write path")

    def visit_Assign(self, node: ast.Assign) -> None:
        if _is_set_expr(node.value, self.scopes[-1]):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self.scopes[-1].set_names.add(tgt.id)
        else:
            for tgt in node.targets:  # reassignment to non-set clears the mark
                if isinstance(tgt, ast.Name):
                    self.scopes[-1].set_names.discard(tgt.id)
        for tgt in node.targets:
            self._check_stats_mutation(tgt, node)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_stats_mutation(node.target, node)
        self.generic_visit(node)

    def _check_iteration(self, iter_node: ast.AST) -> None:
        if _is_set_expr(iter_node, self.scopes[-1]):
            self._add("D004", iter_node,
                      "iterating a bare set: order is insertion- and "
                      "hash-salt-dependent; sorted() it (or use an ordered "
                      "container) before order feeds scheduling or "
                      "serialization")

    def visit_For(self, node: ast.For) -> None:
        self._check_iteration(node.iter)
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    def visit_While(self, node: ast.While) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    def _visit_comp(self, node) -> None:
        for gen in node.generators:
            self._check_iteration(gen.iter)
        self.generic_visit(node)

    visit_ListComp = visit_SetComp = visit_DictComp = _visit_comp
    visit_GeneratorExp = _visit_comp

    # ------------------------------------------------------------- calls
    def visit_Call(self, node: ast.Call) -> None:
        info = self.info
        # D001 — builtin hash()
        if isinstance(node.func, ast.Name) and node.func.id == "hash" \
                and node.func.id not in info.from_names:
            self._add("D001", node,
                      "builtin hash() is process-salted (PYTHONHASHSEED): "
                      "seeds/routing derived from it differ per invocation "
                      "and break cross-process goldens; use "
                      "zlib.crc32(x.encode())")
        name = _dotted(node.func, info)
        # D002 — wall clock in sim path
        if self.sim_path and name in WALLCLOCK_CALLS:
            self._add("D002", node,
                      f"wall-clock read '{name}' in a sim-path package: "
                      "only the virtual clock (EventLoop.now) may be read "
                      "on the simulated timeline")
        # D003 — unseeded / global-state randomness
        if name == "random.Random" and not node.args and not node.keywords:
            self._add("D003", node,
                      "random.Random() without a seed draws from OS "
                      "entropy: pass an explicit seed")
        elif name == "random.SystemRandom":
            self._add("D003", node,
                      "random.SystemRandom is nondeterministic by "
                      "construction; use a seeded random.Random")
        elif name is not None and name.startswith("random.") \
                and name.split(".", 1)[1] in GLOBAL_RANDOM_DRAWS:
            self._add("D003", node,
                      f"'{name}' draws from the process-global RNG: any "
                      "import-order change reshuffles every stream; use a "
                      "seeded random.Random instance")
        elif name is not None and name.startswith("numpy.random.") \
                and name.rsplit(".", 1)[1] in GLOBAL_NP_RANDOM:
            self._add("D003", node,
                      f"'{name}' uses numpy's global RNG state; use "
                      "np.random.default_rng(seed)")
        elif name == "numpy.random.default_rng" and not node.args \
                and not node.keywords:
            self._add("D003", node,
                      "np.random.default_rng() without a seed is "
                      "entropy-seeded; pass an explicit seed")
        # J001 — jit/pallas_call constructed per call
        if name in ("jax.jit", "jax.experimental.pallas.pallas_call") or (
                name == "functools.partial" and node.args
                and _dotted(node.args[0], info) == "jax.jit"):
            what = "pallas_call" if name and name.endswith("pallas_call") \
                else "jax.jit"
            if self.loop_depth > 0:
                self._add("J001", node,
                          f"{what} constructed inside a loop: each "
                          "iteration builds a fresh traced callable "
                          "(retrace per iteration); hoist it out")
            elif self.func_stack and not self._enclosing_jitted():
                self._add("J001", node,
                          f"{what} constructed inside a function: every "
                          "call builds a fresh jit wrapper and retraces; "
                          "hoist to module scope, decorate, or cache the "
                          "wrapper")
        # D004 — order capture of a set
        if isinstance(node.func, ast.Name) \
                and node.func.id in ("list", "tuple", "iter", "enumerate") \
                and node.args and _is_set_expr(node.args[0], self.scopes[-1]):
            self._add("D004", node,
                      f"{node.func.id}() over a bare set captures "
                      "arbitrary order; use sorted()")
        if isinstance(node.func, ast.Attribute) and node.func.attr == "join" \
                and node.args and _is_set_expr(node.args[0], self.scopes[-1]):
            self._add("D004", node,
                      "join() over a bare set serializes arbitrary order; "
                      "use sorted()")
        # J002 — implicit host sync inside jit/kernel scope
        if self._in_jit_scope():
            if isinstance(node.func, ast.Name) \
                    and node.func.id in ("float", "int", "bool") \
                    and node.args \
                    and not isinstance(node.args[0], ast.Constant):
                self._add("J002", node,
                          f"{node.func.id}() on a traced value forces a "
                          "host sync (or a trace error) inside jit; keep "
                          "it a device array")
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                self._add("J002", node,
                          ".item() forces a device->host sync inside "
                          "jit/kernel scope")
            if name in ("numpy.asarray", "numpy.array"):
                self._add("J002", node,
                          f"'{name}' on a traced value falls back to host "
                          "numpy (blocking transfer) inside jit/kernel "
                          "scope; use jnp")
        self.generic_visit(node)


# ----------------------------------------------------------------------- api
def lint_source(source: str, path: str = "<string>") -> List[Violation]:
    """Lint one source string; returns ALL violations (waived ones marked).

    Unused waivers and reason-less waivers are appended as W-class
    violations so the waiver ledger itself stays honest.
    """
    p = Path(path)
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Violation("W000", str(p), e.lineno or 1, 0,
                          f"syntax error: {e.msg}", severity="error")]
    info = _Aliases()
    info.visit(tree)
    checker = _Checker(p, info, _is_sim_path(p))
    checker.visit(tree)
    violations = checker.violations
    waivers = _collect_waivers(source)
    for v in violations:
        for w in waivers:
            if w.rule == v.rule and w.line == v.line:
                w.used = True
                if not w.reason:
                    continue  # reason-less waivers do not suppress
                v.waived = True
                v.waive_reason = w.reason
    for w in waivers:
        if not w.reason:
            violations.append(Violation(
                "W000", str(p), w.comment_line, 0,
                f"waiver for {w.rule} has no reason: use "
                f"'# lint: disable={w.rule}(why this is safe)'"))
        elif not w.used:
            violations.append(Violation(
                "W001", str(p), w.comment_line, 0,
                f"waiver for {w.rule} matches no violation on line "
                f"{w.line}; delete it"))
    violations.sort(key=lambda v: (v.line, v.col, v.rule))
    return violations


def lint_paths(paths) -> List[Violation]:
    out: List[Violation] = []
    for root in paths:
        root = Path(root)
        files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
        for f in files:
            out.extend(lint_source(f.read_text(), str(f)))
    return out


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    fail_on = "error"
    show_waived = False
    paths: List[str] = []
    for a in argv:
        if a.startswith("--fail-on"):
            fail_on = a.split("=", 1)[1] if "=" in a else "error"
            if fail_on not in SEVERITIES:
                print(f"unknown severity {fail_on!r}; use one of "
                      f"{SEVERITIES}", file=sys.stderr)
                return 2
        elif a == "--show-waived":
            show_waived = True
        elif a == "--list-rules":
            for code, (sev, summary) in sorted(RULES.items()):
                print(f"{code} [{sev}] {summary}")
            return 0
        elif a.startswith("-"):
            print(f"unknown option {a!r}", file=sys.stderr)
            return 2
        else:
            paths.append(a)
    if not paths:
        paths = ["src"]
    violations = lint_paths(paths)
    gate = SEVERITIES.index(fail_on)
    failing = 0
    for v in violations:
        if v.waived:
            if show_waived:
                print(v.format())
            continue
        print(v.format())
        if SEVERITIES.index(v.severity) >= gate:
            failing += 1
    waived = sum(v.waived for v in violations)
    active = sum(not v.waived for v in violations)
    print(f"reservoir-lint: {active} violation(s) "
          f"({failing} at/above '{fail_on}'), {waived} waived",
          file=sys.stderr)
    return 1 if failing else 0


if __name__ == "__main__":
    sys.exit(main())
