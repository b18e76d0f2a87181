"""Repo-specific AST lint for the warm serving path.

Generic linters can't see what breaks *this* codebase: a ``np.`` call on a
traced value aborts tracing, a ``float()`` on a tracer forces a device
sync in the middle of the fused program, a bare ``assert`` on a user path
vanishes under ``python -O``, and an unlocked mutation of the solver's
program cache races the prewarm thread.  Each rule below encodes one of
those invariants; ``tests/test_analysis.py`` keeps every rule live with a
known-bad fixture that must fire exactly once.

Rules
-----
R001  host-library call (``np.`` / ``numpy.`` / ``scipy.``) on a traced
      value inside a traced scope — aborts tracing or silently constant-
      folds.  Shape/dtype-derived statics are fine: ``np.log2(x.shape[0])``
      does not fire.
R002  tracer coercion: ``float()/int()/bool()/complex()`` or
      ``.item()/.tolist()`` on a traced value — forces a blocking
      device→host transfer inside the program.
R003  Python-value branching (``if``/``while``/``assert``) on a traced
      value inside a traced scope — trace-time divergence; use
      ``lax.cond``/``jnp.where``.
R004  bare ``assert`` used for validation in ``repro/core`` or
      ``repro/euler`` — raise a typed error; asserts vanish under ``-O``.
R005  lock discipline: in a class that owns ``self._lock``, any attribute
      that is mutated under the lock somewhere must be mutated under the
      lock everywhere (``__init__`` exempt).
R006  thread contract: every ``threading.Thread(...)`` must pass an
      explicit ``daemon=`` and carry a ``thread-contract:`` comment in the
      comment block above it documenting its join/abandon rules.
R007  orphan timing: a direct ``time.perf_counter()`` /
      ``time.monotonic()`` read in ``repro/core``, ``repro/euler`` or
      ``repro/launch`` whose enclosing function never feeds an
      observability sink (``.span(``/``.observe(``/``.inc(``/…) —
      ad-hoc wall-clock accounting belongs in ``repro.obs`` (DESIGN.md
      §13).  Clock *references* (``clock=time.perf_counter``) are fine;
      so is any function that routes at least one measurement through a
      span or metric.

Traced scopes are discovered, not annotated: a function is traced if its
name is passed to a tracing entry point (``jax.jit``, ``shard_map``,
``lax.scan``, ``pl.pallas_call``, …), if it is decorated with one, or if
an already-traced function references it by name (transitive closure).
``# lint: traced`` on or above a ``def`` force-marks it; ``# lint: ok``
on an offending line suppresses that line.

Run: ``python -m repro.analysis.lint [paths...]`` (default: the repo's
``src/`` tree; exit 1 iff findings).
"""
from __future__ import annotations

import ast
import dataclasses
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# Call targets (matched on the trailing attribute name) whose function
# arguments are traced by JAX.
TRACER_ENTRIES = {
    "jit", "shard_map", "vmap", "pmap", "scan", "while_loop", "fori_loop",
    "cond", "switch", "pallas_call", "associative_scan", "checkpoint",
    "remat", "make_jaxpr", "grad", "value_and_grad", "custom_jvp",
    "custom_vjp", "eval_shape",
}

# Roots of host-library attribute chains (R001).
HOST_LIB_ROOTS = {"np", "numpy", "scipy", "sp"}

# Attribute reads that yield static (trace-time) values from a tracer.
STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding", "itemsize"}

# Builtins whose result is static even on tracer input.
STATIC_CALLS = {"len", "isinstance", "type", "range", "enumerate", "id",
                "repr", "str", "getattr", "hasattr"}

COERCIONS = {"float", "int", "bool", "complex"}
COERCION_METHODS = {"item", "tolist", "__bool__", "__float__", "__int__"}

# Mutating method names for R005 (containers the solver caches live in).
MUTATOR_METHODS = {"pop", "popitem", "setdefault", "update", "clear",
                   "move_to_end", "append", "extend", "add", "remove",
                   "discard", "insert"}

# R004 applies only to these path fragments (POSIX-normalized).
ASSERT_SCOPES = ("repro/core/", "repro/euler/")

# R007 applies only to these path fragments (POSIX-normalized).
TIMING_SCOPES = ("repro/core/", "repro/euler/", "repro/launch/")

# Wall-clock reads R007 polices when *called* (references are fine).
TIMING_CALLS = {"perf_counter", "monotonic"}

# Attribute-call names that count as an observability sink: the obs
# instrument/span surface plus the generic record/event verbs.
OBS_SINKS = {"observe", "span", "inc", "set", "add", "record", "event"}

SUPPRESS_MARK = "lint: ok"
TRACED_MARK = "lint: traced"


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: " \
               f"{self.rule} {self.message}"


def _tail_name(func: ast.expr) -> Optional[str]:
    """`jax.lax.scan` → 'scan'; `jit` → 'jit'."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _root_name(node: ast.expr) -> Optional[str]:
    """Leftmost Name of an attribute/subscript chain, else None."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _func_arg_names(call: ast.Call) -> List[str]:
    """Names passed (directly or via functools.partial) as positional
    arguments of a call — candidates for 'this function gets traced'."""
    names: List[str] = []
    for a in call.args:
        if isinstance(a, ast.Name):
            names.append(a.id)
        elif isinstance(a, ast.Call):
            # functools.partial(fn, ...) / jax.jit(fn) nested in a call
            tail = _tail_name(a.func)
            if tail in ({"partial"} | TRACER_ENTRIES):
                for inner in a.args:
                    if isinstance(inner, ast.Name):
                        names.append(inner.id)
    for kw in call.keywords:
        if isinstance(kw.value, ast.Name) and kw.arg in (
                "f", "fn", "fun", "func", "body_fun", "cond_fun", "kernel"):
            names.append(kw.value.id)
    return names


def _decorated_traced(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        if _tail_name(target) in TRACER_ENTRIES:
            return True
        # functools.partial(jax.jit, ...) as a decorator
        if isinstance(dec, ast.Call) and _tail_name(dec.func) == "partial":
            for a in dec.args:
                if _tail_name(a) in TRACER_ENTRIES:
                    return True
    return False


class _Taint:
    """Forward taint over one traced function body.

    Parameters without a default are tracers; parameters *with* a default
    are treated as static configuration (the engine threads e.g.
    ``splice_rounds=64``/``gather_circuit=True`` through traced helpers,
    and branching on those is legitimate trace-time specialization), as are
    parameters annotated with a static type (``cap: int``,
    ``cfg: LMConfig`` — jit static_argnames / closure-config idiom).
    Shape/dtype access, identity tests and static builtins launder taint
    away.
    """

    STATIC_ANN = {"int", "bool", "str", "float"}
    STATIC_ANN_SUFFIXES = ("Config", "Cfg", "Caps", "Key", "Mesh", "Tree")

    @classmethod
    def _static_annotation(cls, ann: Optional[ast.expr]) -> bool:
        tail = _tail_name(ann) if ann is not None else None
        return tail is not None and (
            tail in cls.STATIC_ANN or
            tail.endswith(cls.STATIC_ANN_SUFFIXES))

    def __init__(self, fn: ast.AST):
        self.tainted: Set[str] = set()
        args = fn.args
        pos = list(args.posonlyargs) + list(args.args)
        n_defaults = len(args.defaults)
        required = pos[:len(pos) - n_defaults] if n_defaults else pos
        for a in required:
            if a.arg not in ("self", "cls") and \
                    not self._static_annotation(a.annotation):
                self.tainted.add(a.arg)
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is None:
                self.tainted.add(a.arg)
        if args.vararg:
            self.tainted.add(args.vararg.arg)

    def expr(self, node: Optional[ast.expr]) -> bool:
        """Is the value of this expression (possibly) a tracer?"""
        if node is None or isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False
            return self.expr(node.value)
        if isinstance(node, ast.Call):
            tail = _tail_name(node.func)
            if tail in STATIC_CALLS:
                return False
            if self.expr(node.func):
                return True
            return any(self.expr(a) for a in node.args) or \
                any(self.expr(kw.value) for kw in node.keywords)
        if isinstance(node, ast.Subscript):
            return self.expr(node.value) or self.expr(node.slice)
        if isinstance(node, ast.BinOp):
            return self.expr(node.left) or self.expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # identity tests (`x is None`) inspect the Python object, not
            # the traced value — static even on tracers
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return self.expr(node.left) or \
                any(self.expr(c) for c in node.comparators)
        if isinstance(node, ast.IfExp):
            return self.expr(node.body) or self.expr(node.orelse) or \
                self.expr(node.test)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self.expr(v) for v in node.values if v is not None)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            return any(self.expr(g.iter) for g in node.generators)
        if isinstance(node, ast.Slice):
            return any(self.expr(p) for p in
                       (node.lower, node.upper, node.step))
        if isinstance(node, ast.Lambda):
            return False
        if isinstance(node, (ast.JoinedStr, ast.FormattedValue)):
            return False
        return False   # unknown node kinds assumed static

    def _bind(self, target: ast.expr, hot: bool) -> None:
        if isinstance(target, ast.Name):
            if hot:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, hot)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, hot)

    def stmt(self, node: ast.stmt) -> None:
        """Propagate taint through one (possibly compound) statement."""
        if isinstance(node, ast.Assign):
            hot = self.expr(node.value)
            for t in node.targets:
                self._bind(t, hot)
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            self._bind(node.target, self.expr(node.value))
        elif isinstance(node, ast.AugAssign):
            if isinstance(node.target, ast.Name):
                if self.expr(node.value) or self.expr(node.target):
                    self.tainted.add(node.target.id)
        elif isinstance(node, ast.For):
            self._bind(node.target, self.expr(node.iter))
        elif isinstance(node, ast.With):
            for item in node.items:
                if item.optional_vars is not None:
                    self._bind(item.optional_vars,
                               self.expr(item.context_expr))


class _FileLint:
    def __init__(self, src: str, path: str):
        self.src = src
        self.path = path
        self.posix = Path(path).as_posix()
        self.lines = src.splitlines()
        self.tree = ast.parse(src, filename=path)
        self.findings: List[Finding] = []

    # -------------------------------------------------- infrastructure
    def _line(self, i: int) -> str:
        return self.lines[i - 1] if 1 <= i <= len(self.lines) else ""

    def _suppressed(self, line: int) -> bool:
        return SUPPRESS_MARK in self._line(line)

    def _emit(self, node: ast.AST, rule: str, message: str) -> None:
        line = getattr(node, "lineno", 0)
        if self._suppressed(line):
            return
        self.findings.append(Finding(self.path, line,
                                     getattr(node, "col_offset", 0) + 1,
                                     rule, message))

    # -------------------------------------------------- traced scopes
    def _traced_defs(self) -> List[ast.AST]:
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        traced: Set[int] = set()

        def mark(name: str) -> None:
            for d in defs.get(name, []):
                traced.add(id(d))

        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and \
                    _tail_name(node.func) in TRACER_ENTRIES:
                for name in _func_arg_names(node):
                    mark(name)
        for group in defs.values():
            for d in group:
                if _decorated_traced(d):
                    traced.add(id(d))
                header = self._line(d.lineno)
                above = self._line(d.lineno - 1)
                for dec in getattr(d, "decorator_list", []):
                    above = self._line(dec.lineno - 1)
                    break
                if TRACED_MARK in header or TRACED_MARK in above:
                    traced.add(id(d))

        # Transitive closure: names referenced from a traced body are
        # traced too (covers `core` passed into lax.scan via a closure
        # in another function, helpers called from kernels, etc.).
        changed = True
        while changed:
            changed = False
            for group in defs.values():
                for d in group:
                    if id(d) not in traced:
                        continue
                    for sub in ast.walk(d):
                        if isinstance(sub, ast.Name) and \
                                isinstance(sub.ctx, ast.Load) and \
                                sub.id in defs:
                            for tgt in defs[sub.id]:
                                if id(tgt) not in traced:
                                    traced.add(id(tgt))
                                    changed = True
        out = []
        for group in defs.values():
            out.extend(d for d in group if id(d) in traced)
        return out

    def _body_stmts(self, fn: ast.AST) -> Iterable[ast.stmt]:
        """Statements of fn in source order, not descending into nested
        defs (each traced nested def is analyzed on its own)."""
        stack: List[ast.stmt] = list(reversed(fn.body))
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Lambda)):
                continue
            children = []
            for name in ("body", "orelse", "finalbody"):
                children.extend(getattr(node, name, []) or [])
            for h in getattr(node, "handlers", []) or []:
                children.extend(h.body)
            stack.extend(reversed(children))

    # -------------------------------------------------- R001-R003
    def _check_traced_bodies(self) -> None:
        for fn in self._traced_defs():
            taint = _Taint(fn)
            for stmt in self._body_stmts(fn):
                # branching checks before taint update (test uses the
                # pre-statement environment)
                if isinstance(stmt, (ast.If, ast.While)):
                    if taint.expr(stmt.test):
                        self._emit(
                            stmt, "R003",
                            f"Python `{type(stmt).__name__.lower()}` on a "
                            f"traced value in traced scope "
                            f"`{fn.name}` — use lax.cond/jnp.where")
                elif isinstance(stmt, ast.Assert):
                    if taint.expr(stmt.test):
                        self._emit(
                            stmt, "R003",
                            f"`assert` on a traced value in traced "
                            f"scope `{fn.name}` — use "
                            f"checkify/typed errors")
                self._check_calls_in(stmt, taint, fn.name)
                taint.stmt(stmt)

    def _check_calls_in(self, stmt: ast.stmt, taint: _Taint,
                        scope: str) -> None:
        # Only the statement's own expressions — nested statements are
        # visited by _body_stmts with an up-to-date taint environment.
        exprs: List[ast.expr] = []
        for field, value in ast.iter_fields(stmt):
            if field in ("body", "orelse", "finalbody", "handlers"):
                continue
            if isinstance(value, ast.expr):
                exprs.append(value)
            elif isinstance(value, list):
                exprs.extend(v for v in value if isinstance(v, ast.expr))
            elif field == "items":     # With
                for item in value:
                    exprs.append(item.context_expr)
        for expr in exprs:
            for node in ast.walk(expr):
                if not isinstance(node, ast.Call):
                    continue
                args_hot = any(taint.expr(a) for a in node.args) or \
                    any(taint.expr(kw.value) for kw in node.keywords)
                tail = _tail_name(node.func)
                root = _root_name(node.func) \
                    if isinstance(node.func, ast.Attribute) else None
                if root in HOST_LIB_ROOTS and args_hot:
                    self._emit(
                        node, "R001",
                        f"`{root}.{tail}` called on a traced value in "
                        f"traced scope `{scope}` — use jnp/lax")
                if isinstance(node.func, ast.Name) and \
                        tail in COERCIONS and args_hot:
                    self._emit(
                        node, "R002",
                        f"`{tail}()` coerces a traced value in traced "
                        f"scope `{scope}` — forces a device sync")
                if isinstance(node.func, ast.Attribute) and \
                        tail in COERCION_METHODS and \
                        taint.expr(node.func.value):
                    self._emit(
                        node, "R002",
                        f"`.{tail}()` on a traced value in traced scope "
                        f"`{scope}` — forces a device sync")

    # -------------------------------------------------- R004
    def _in_assert_scope(self) -> bool:
        return any(frag in self.posix for frag in ASSERT_SCOPES)

    def _check_asserts(self) -> None:
        if not self._in_assert_scope():
            return
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assert):
                self._emit(node, "R004",
                           "bare `assert` used for validation — raise "
                           "ValueError/RuntimeError (asserts vanish "
                           "under python -O)")

    # -------------------------------------------------- R005
    @staticmethod
    def _self_attr(node: ast.expr) -> Optional[str]:
        """`self.x`, `self.x[...]`, `self.x.y...` → 'x'."""
        while isinstance(node, (ast.Attribute, ast.Subscript)):
            if isinstance(node, ast.Attribute) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == "self":
                return node.attr
            node = node.value
        return None

    def _mutations(self, method: ast.AST) -> List[Tuple[str, ast.AST, bool]]:
        """(attr, node, deep) mutation sites of self.<attr> in a method.
        deep=True means container/field mutation (self.x[k]=, self.x.y=,
        self.x.pop(...)); deep=False is plain rebinding self.x = v."""
        out: List[Tuple[str, ast.AST, bool]] = []
        for node in ast.walk(method):
            targets: List[ast.expr] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = node.targets
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in MUTATOR_METHODS:
                attr = self._self_attr(node.func.value)
                if attr is not None:
                    out.append((attr, node, True))
                continue
            for t in targets:
                attr = self._self_attr(t)
                if attr is None:
                    continue
                deep = not (isinstance(t, ast.Attribute) and
                            isinstance(t.value, ast.Name) and
                            t.value.id == "self")
                out.append((attr, t, deep))
        return out

    def _under_lock(self, cls: ast.ClassDef, node: ast.AST) -> bool:
        """Is `node` lexically inside a `with self._lock:` in cls?"""
        target = getattr(node, "lineno", -1), getattr(node, "col_offset", -1)
        for w in ast.walk(cls):
            if not isinstance(w, ast.With):
                continue
            if not any(self._self_attr(i.context_expr) == "_lock"
                       for i in w.items):
                continue
            if w.lineno <= target[0] <= (w.end_lineno or w.lineno):
                return True
        return False

    def _check_locks(self) -> None:
        for cls in ast.walk(self.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            owns_lock = any(
                isinstance(n, ast.Assign) and any(
                    self._self_attr(t) == "_lock" for t in n.targets)
                for n in ast.walk(cls))
            if not owns_lock:
                continue
            methods = [n for n in cls.body if isinstance(
                n, (ast.FunctionDef, ast.AsyncFunctionDef))]
            sites: List[Tuple[str, ast.AST, bool, str]] = []
            for m in methods:
                for attr, node, deep in self._mutations(m):
                    sites.append((attr, node, deep, m.name))
            guarded = {attr for attr, node, deep, mname in sites
                       if deep and self._under_lock(cls, node)}
            for attr, node, deep, mname in sites:
                if attr in guarded and mname != "__init__" and \
                        not self._under_lock(cls, node):
                    self._emit(
                        node, "R005",
                        f"`self.{attr}` is lock-guarded elsewhere in "
                        f"`{cls.name}` but mutated here ({mname}) "
                        f"outside `with self._lock`")

    # -------------------------------------------------- R006
    def _check_threads(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            if _tail_name(node.func) != "Thread":
                continue
            problems = []
            if not any(kw.arg == "daemon" for kw in node.keywords):
                problems.append("no explicit daemon= kwarg")
            # Marker on the call line or anywhere in the contiguous
            # comment block immediately above it.
            window = [self._line(node.lineno)]
            i = node.lineno - 1
            while i > 0 and self._line(i).strip().startswith("#"):
                window.append(self._line(i))
                i -= 1
            if not any("thread-contract:" in ln for ln in window):
                problems.append("no `# thread-contract:` comment above "
                                "documenting join/abandon rules")
            if problems:
                self._emit(node, "R006",
                           "threading.Thread: " + "; ".join(problems))

    # -------------------------------------------------- R007
    def _shallow_nodes(self, fn: ast.AST) -> Iterable[ast.AST]:
        """Every AST node lexically inside ``fn`` without descending into
        nested def/class scopes (each def is checked on its own; lambdas
        belong to their enclosing function)."""
        stack: List[ast.AST] = list(fn.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    @staticmethod
    def _is_timing_call(node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        if isinstance(f, ast.Attribute):
            return f.attr in TIMING_CALLS and \
                isinstance(f.value, ast.Name) and f.value.id == "time"
        return isinstance(f, ast.Name) and f.id in TIMING_CALLS

    def _check_timing(self) -> None:
        if not any(frag in self.posix for frag in TIMING_SCOPES):
            return
        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            reads: List[ast.AST] = []
            has_sink = False
            for node in self._shallow_nodes(fn):
                if self._is_timing_call(node):
                    reads.append(node)
                elif isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr in OBS_SINKS:
                    has_sink = True
            if has_sink:
                continue
            for node in reads:
                self._emit(
                    node, "R007",
                    f"wall-clock read in `{fn.name}` never reaches an "
                    f"observability sink — route it through a repro.obs "
                    f"span/metric (DESIGN.md §13)")

    # -------------------------------------------------- driver
    def run(self) -> List[Finding]:
        self._check_traced_bodies()
        self._check_asserts()
        self._check_locks()
        self._check_threads()
        self._check_timing()
        # An assert on a tracer in core/euler would fire R003 and R004 on
        # the same line; keep the more actionable R004 only.
        r4 = {(f.path, f.line) for f in self.findings if f.rule == "R004"}
        self.findings = [f for f in self.findings
                         if not (f.rule == "R003" and
                                 (f.path, f.line) in r4)]
        self.findings.sort(key=lambda f: (f.path, f.line, f.rule))
        return self.findings


def check_source(src: str, path: str = "<string>") -> List[Finding]:
    """Lint one source string (the unit used by the fixture tests)."""
    return _FileLint(src, path).run()


def _iter_py(paths: Sequence[str]) -> Iterable[Path]:
    for p in paths:
        path = Path(p)
        if path.is_dir():
            yield from sorted(path.rglob("*.py"))
        elif path.suffix == ".py":
            yield path


def check_paths(paths: Sequence[str]) -> List[Finding]:
    findings: List[Finding] = []
    for f in _iter_py(paths):
        findings.extend(check_source(f.read_text(), str(f)))
    return findings


def default_target() -> str:
    """The repo's ``src`` tree, resolved relative to this file."""
    return str(Path(__file__).resolve().parents[2])


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    paths = argv or [default_target()]
    findings = check_paths(paths)
    for f in findings:
        print(f)
    n_files = sum(1 for _ in _iter_py(paths))
    print(f"repro.analysis.lint: {len(findings)} finding(s) "
          f"in {n_files} file(s)", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
