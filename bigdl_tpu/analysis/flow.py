"""Interprocedural flow analysis for graftlint v2 (docs/static-analysis.md).

This module turns the per-file AST walker of PR 12 into a project-wide
engine: a symbol table over every module in the package, a call graph
with enough receiver-type inference to resolve ``self.method(...)`` and
``self.attr.method(...)`` calls, per-function summaries computed to a
fixpoint (may-raise, returns-a-page-ref, captures-param, blocking), a
path-sensitive liveness interpreter for PagePool reference obligations,
and a held-lock-set propagation pass that builds the lock-order graph.

Everything here is plain ``ast`` — no jax, no imports of the analyzed
code.  The whole-project pass parses ~120 files in well under a second;
results are cached per root so the N file-level checks that consume a
:class:`Project` pay for it once.

Fixture support: ``bigdl_tpu.analysis.core.lint_text`` feeds synthetic
sources whose ``rel`` may shadow a real file.  :func:`project_for`
detects that (source text differs from the file on disk) and analyzes
the fixture as a single-file overlay on top of the cached real project,
so unit tests get interprocedural context without re-parsing the tree.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

# ---------------------------------------------------------------------------
# Name heuristics shared by the summaries.
#
# Attribute calls we cannot resolve to a function in the project are
# normally assumed pure (neither raising nor blocking): the engine is
# full of jitted callables and numpy ops, and treating every unknown
# call as a potential raise would flag half the codebase.  Two curated
# lists carve out the exceptions.

#: Unresolvable attribute calls with these names are treated as
#: may-raise: durable-storage writes and host<->device transfers are the
#: fault points the injection framework (faults.py) arms, so a page ref
#: live across one of them is live across a real-world failure.
KNOWN_RAISERS = frozenset({
    "write", "flush", "fsync", "load", "save", "open",
    "device_get", "device_put", "block_until_ready",
})

#: Unresolvable attribute calls with these names are treated as
#: blocking (for LCK102: no blocking work under a hot lock).
KNOWN_BLOCKERS = frozenset({
    "flush", "fsync", "sleep", "join", "wait",
    "device_get", "device_put", "block_until_ready",
    "recv", "send", "connect", "accept",
})

#: PagePool refcount primitives: a raise inside these is already a
#: double-release assertion, so calls to them never create exception
#: edges in the liveness interpreter (otherwise every rollback loop
#: would flag itself).
_REFCOUNT_NAMES = frozenset({"alloc", "incref", "decref"})

#: Attribute names that smell like a lock guarding serving hot paths.
#: LCK102 only fires for blocking calls under these (the journal's own
#: lock intentionally serializes its fsync; that is its job).
HOT_LOCK_ATTRS = frozenset({"_stat_lock", "_admission_lock"})

_MAX_STATES = 32        # path explosion cap per function (then we merge)
_MAX_HELD = 4           # held-lock set size cap during propagation
_MAX_CHAIN = 6          # witness call-chain length cap


def _call_attr(node: ast.AST) -> Optional[str]:
    """Attribute name of a Call like ``<expr>.name(...)``, else None."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def _is_self_attr(node: ast.AST) -> Optional[str]:
    """``self.X`` -> ``X``, else None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _is_alloc_name(attr: Optional[str]) -> bool:
    """Page-allocator naming convention: ``pool.alloc()``, the ladder
    around it (``PageTable.alloc``, serving/pages.py) and the
    ``self._alloc_page*`` / injected ``self._alloc`` wrappers around that.
    Name-based so callable attributes (AdapterPager's ``_alloc`` is a
    constructor-injected closure) count even when unresolvable."""
    return attr is not None and (attr == "alloc" or attr.startswith("_alloc"))


# ---------------------------------------------------------------------------
# Constant evaluation (DSP checks).


def eval_const(node: ast.AST, env: Optional[Dict[str, object]] = None):
    """Evaluate a literal/constant-arithmetic expression, else raise.

    Supports int/float/str/bool constants, tuples, names bound in *env*,
    unary minus, and + - * // % ** << binary ops.  Deliberately no
    attribute access, calls, or true division (float creep).
    """
    if isinstance(node, ast.Constant):
        return node.value
    if isinstance(node, ast.Tuple):
        return tuple(eval_const(e, env) for e in node.elts)
    if isinstance(node, ast.Name):
        if env is not None and node.id in env:
            return env[node.id]
        raise ValueError("unbound name %s" % node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -eval_const(node.operand, env)
    if isinstance(node, ast.BinOp):
        left = eval_const(node.left, env)
        right = eval_const(node.right, env)
        op = node.op
        if isinstance(op, ast.Add):
            return left + right
        if isinstance(op, ast.Sub):
            return left - right
        if isinstance(op, ast.Mult):
            return left * right
        if isinstance(op, ast.FloorDiv):
            return left // right
        if isinstance(op, ast.Mod):
            return left % right
        if isinstance(op, ast.Pow):
            return left ** right
        if isinstance(op, ast.LShift):
            return left << right
        raise ValueError("unsupported binop")
    raise ValueError("not a constant expression")


def module_consts(tree: ast.Module) -> Dict[str, object]:
    """Top-level ``NAME = <const expr>`` bindings of a module."""
    env: Dict[str, object] = {}
    for stmt in tree.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)):
            try:
                env[stmt.targets[0].id] = eval_const(stmt.value, env)
            except ValueError:
                pass
    return env


# ---------------------------------------------------------------------------
# Symbol table.


class FuncInfo:
    """One function or method, with its resolution context."""

    __slots__ = ("qualname", "rel", "node", "cls", "module")

    def __init__(self, qualname, rel, node, cls, module):
        self.qualname = qualname          # "rel::Class.meth" or "rel::fn"
        self.rel = rel
        self.node = node                  # ast.FunctionDef
        self.cls = cls                    # ClassInfo or None
        self.module = module              # ModuleInfo


class ClassInfo:
    __slots__ = ("name", "rel", "node", "methods", "attr_types", "lock_attrs",
                 "module")

    def __init__(self, name, rel, node, module):
        self.name = name
        self.rel = rel
        self.node = node
        self.module = module
        self.methods: Dict[str, FuncInfo] = {}
        # attr -> set of class names this attr may hold (from
        # ``self.x = ClassName(...)`` in any method, incl. inside
        # BoolOp/IfExp operands, and from annotations).
        self.attr_types: Dict[str, Set[str]] = {}
        # attr -> "Lock" | "RLock" for ``self.x = threading.Lock()``.
        self.lock_attrs: Dict[str, str] = {}


class ModuleInfo:
    __slots__ = ("rel", "src", "tree", "classes", "functions", "imports")

    def __init__(self, rel, src, tree):
        self.rel = rel
        self.src = src
        self.tree = tree
        self.classes: Dict[str, ClassInfo] = {}
        self.functions: Dict[str, FuncInfo] = {}
        # local name -> dotted module path it refers to ("from X import
        # Y" maps Y -> "X.Y"; "import X.Y as Z" maps Z -> "X.Y").
        self.imports: Dict[str, str] = {}


def _scan_attr_types(cls: ClassInfo) -> None:
    """Infer ``self.attr`` class types from constructor-call assignments."""

    def record(attr: str, value: ast.AST) -> None:
        # Unwrap conditional forms: ``a if c else b``, ``a or b``.
        candidates: List[ast.AST] = [value]
        if isinstance(value, ast.IfExp):
            candidates = [value.body, value.orelse]
        elif isinstance(value, ast.BoolOp):
            candidates = list(value.values)
        for v in candidates:
            if isinstance(v, ast.Call):
                fn = v.func
                name = None
                if isinstance(fn, ast.Name):
                    name = fn.id
                elif isinstance(fn, ast.Attribute):
                    name = fn.attr
                if name:
                    if name in ("Lock", "RLock"):
                        cls.lock_attrs.setdefault(attr, name)
                    elif name[:1].isupper():
                        cls.attr_types.setdefault(attr, set()).add(name)

    for node in ast.walk(cls.node):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                attr = _is_self_attr(tgt)
                if attr:
                    record(attr, node.value)
        elif isinstance(node, ast.AnnAssign) and node.target is not None:
            attr = _is_self_attr(node.target)
            if attr and isinstance(node.annotation, ast.Name):
                ann = node.annotation.id
                if ann[:1].isupper():
                    cls.attr_types.setdefault(attr, set()).add(ann)
            if attr and node.value is not None:
                record(attr, node.value)


def _build_module(rel: str, src: str, tree: ast.Module) -> ModuleInfo:
    mod = ModuleInfo(rel, src, tree)
    for stmt in tree.body:
        if isinstance(stmt, ast.Import):
            for alias in stmt.names:
                mod.imports[alias.asname or alias.name.split(".")[0]] = \
                    alias.name
        elif isinstance(stmt, ast.ImportFrom) and stmt.module:
            for alias in stmt.names:
                mod.imports[alias.asname or alias.name] = \
                    stmt.module + "." + alias.name
        elif isinstance(stmt, ast.ClassDef):
            cls = ClassInfo(stmt.name, rel, stmt, mod)
            mod.classes[stmt.name] = cls
            for item in stmt.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qn = "%s::%s.%s" % (rel, stmt.name, item.name)
                    cls.methods[item.name] = FuncInfo(qn, rel, item, cls, mod)
            _scan_attr_types(cls)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            qn = "%s::%s" % (rel, stmt.name)
            mod.functions[stmt.name] = FuncInfo(qn, rel, stmt, None, mod)
    return mod


class Project:
    """Symbol table + call resolution + memoized summaries for one tree."""

    def __init__(self, root: str):
        self.root = root
        self.modules: Dict[str, ModuleInfo] = {}
        # class name -> [ClassInfo] (names are unique enough in practice;
        # resolution fans out over all same-named classes).
        self.class_index: Dict[str, List[ClassInfo]] = {}
        # method/function simple name -> [FuncInfo] for last-resort
        # unique-name resolution.
        self._summaries: Dict[Tuple[str, str], object] = {}

    # -- construction -------------------------------------------------------

    @classmethod
    def load(cls, root: str) -> "Project":
        proj = cls(root)
        pkg = os.path.join(root, "bigdl_tpu")
        if os.path.isdir(pkg):
            for dirpath, dirnames, filenames in os.walk(pkg):
                dirnames[:] = sorted(
                    d for d in dirnames
                    if d != "__pycache__" and not d.startswith("."))
                for fn in sorted(filenames):
                    if not fn.endswith(".py"):
                        continue
                    path = os.path.join(dirpath, fn)
                    rel = os.path.relpath(path, root).replace(os.sep, "/")
                    try:
                        with open(path, "r", encoding="utf-8") as f:
                            src = f.read()
                        tree = ast.parse(src)
                    except (OSError, SyntaxError):
                        continue
                    proj._add_module(rel, src, tree)
        proj._reindex()
        return proj

    def _add_module(self, rel: str, src: str, tree: ast.Module) -> None:
        self.modules[rel] = _build_module(rel, src, tree)

    def _reindex(self) -> None:
        self.class_index = {}
        for mod in self.modules.values():
            for cls in mod.classes.values():
                self.class_index.setdefault(cls.name, []).append(cls)

    def overlay(self, rel: str, src: str, tree: ast.Module) -> "Project":
        """A copy of this project with *rel* replaced by fixture source."""
        proj = Project(self.root)
        proj.modules = dict(self.modules)
        proj._add_module(rel, src, tree)
        proj._reindex()
        return proj

    def src_of(self, rel: str) -> Optional[str]:
        mod = self.modules.get(rel)
        return mod.src if mod is not None else None

    # -- call resolution ----------------------------------------------------

    def _classes_named(self, name: str) -> List[ClassInfo]:
        return self.class_index.get(name, [])

    def resolve_call(self, call: ast.Call, scope: FuncInfo) -> List[FuncInfo]:
        """Possible callees of *call* evaluated inside *scope*.

        Best-effort: an empty list means "unknown receiver", not "no
        callee".  Checks treat unknown calls per the KNOWN_* heuristics.
        """
        fn = call.func
        out: List[FuncInfo] = []
        if isinstance(fn, ast.Name):
            name = fn.id
            mod = scope.module
            if name in mod.functions:
                return [mod.functions[name]]
            # Constructor: Class(...) resolves to Class.__init__.
            for cls in self._classes_named(name):
                init = cls.methods.get("__init__")
                if init is not None:
                    out.append(init)
            if out:
                return out
            # from X import f
            dotted = mod.imports.get(name)
            if dotted:
                return self._resolve_dotted(dotted)
            return []
        if isinstance(fn, ast.Attribute):
            meth = fn.attr
            recv = fn.value
            # self.meth(...)
            if isinstance(recv, ast.Name) and recv.id == "self" and scope.cls:
                m = scope.cls.methods.get(meth)
                if m is not None:
                    return [m]
                return []
            # self.attr.meth(...) via inferred attr types
            attr = _is_self_attr(recv)
            if attr and scope.cls:
                for tname in sorted(scope.cls.attr_types.get(attr, ())):
                    for cls in self._classes_named(tname):
                        m = cls.methods.get(meth)
                        if m is not None:
                            out.append(m)
                return out
            # module.f(...)
            if isinstance(recv, ast.Name):
                dotted = scope.module.imports.get(recv.id)
                if dotted:
                    return self._resolve_dotted(dotted + "." + meth)
                # local var with inferred class type
                for tname in sorted(
                        self._local_types(scope).get(recv.id, ())):
                    for cls in self._classes_named(tname):
                        m = cls.methods.get(meth)
                        if m is not None:
                            out.append(m)
                return out
        return out

    def _resolve_dotted(self, dotted: str) -> List[FuncInfo]:
        """Resolve "pkg.mod.fn" / "pkg.mod.Class" to FuncInfos."""
        parts = dotted.split(".")
        for split in range(len(parts) - 1, 0, -1):
            rel = "/".join(parts[:split]) + ".py"
            mod = self.modules.get(rel)
            if mod is None:
                continue
            tail = parts[split:]
            if len(tail) == 1:
                f = mod.functions.get(tail[0])
                if f is not None:
                    return [f]
                cls = mod.classes.get(tail[0])
                if cls is not None and "__init__" in cls.methods:
                    return [cls.methods["__init__"]]
            elif len(tail) == 2:
                cls = mod.classes.get(tail[0])
                if cls is not None:
                    m = cls.methods.get(tail[1])
                    if m is not None:
                        return [m]
        return []

    def _local_types(self, scope: FuncInfo) -> Dict[str, Set[str]]:
        """``x = ClassName(...)`` local bindings inside *scope*."""
        key = ("localtypes", scope.qualname)
        cached = self._summaries.get(key)
        if cached is not None:
            return cached  # type: ignore[return-value]
        out: Dict[str, Set[str]] = {}
        for node in ast.walk(scope.node):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Name)
                    and node.value.func.id[:1].isupper()):
                out.setdefault(node.targets[0].id, set()).add(
                    node.value.func.id)
        self._summaries[key] = out
        return out

    def all_functions(self) -> List[FuncInfo]:
        out = []
        for mod in self.modules.values():
            out.extend(mod.functions.values())
            for cls in mod.classes.values():
                out.extend(cls.methods.values())
        return out

    # -- summaries ----------------------------------------------------------

    def may_raise(self, fi: FuncInfo, _depth: int = 0) -> bool:
        """Whether calling *fi* can plausibly raise on a real fault path.

        Explicit ``raise`` in the body counts unless it sits inside a
        ``try`` of the same function (assumed handled).  Transitively,
        resolved callees are consulted up to depth 2; unresolved
        attribute calls count only when named like I/O (KNOWN_RAISERS).
        Refcount primitives never count (their raise is a double-release
        assertion, itself a bug this checker exists to prevent).
        """
        key = ("may_raise", fi.qualname)
        cached = self._summaries.get(key)
        if cached is not None:
            return cached  # type: ignore[return-value]
        self._summaries[key] = False        # recursion guard: optimistic
        result = self._may_raise_uncached(fi, _depth)
        self._summaries[key] = result
        return result

    def _may_raise_uncached(self, fi: FuncInfo, depth: int) -> bool:
        guarded = _try_guarded_lines(fi.node)
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Raise) and node.lineno not in guarded:
                return True
            if depth >= 2 or not isinstance(node, ast.Call):
                continue
            attr = _call_attr(node)
            if attr in _REFCOUNT_NAMES:
                continue
            callees = self.resolve_call(node, fi)
            if callees:
                if any(self.may_raise(c, depth + 1) for c in callees):
                    return True
            elif attr in KNOWN_RAISERS and node.lineno not in guarded:
                return True
        return False

    def is_blocking(self, fi: FuncInfo, _depth: int = 0) -> bool:
        """Whether *fi* transitively performs blocking I/O (for LCK102)."""
        key = ("blocking", fi.qualname)
        cached = self._summaries.get(key)
        if cached is not None:
            return cached  # type: ignore[return-value]
        self._summaries[key] = False
        result = False
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            attr = _call_attr(node)
            if attr in KNOWN_BLOCKERS:
                result = True
                break
            if isinstance(node.func, ast.Name) and node.func.id == "sleep":
                result = True
                break
            if _depth < 3:
                callees = self.resolve_call(node, fi)
                if any(self.is_blocking(c, _depth + 1) for c in callees):
                    result = True
                    break
        self._summaries[key] = result
        return result

    def returns_ref(self, fi: FuncInfo) -> bool:
        """Whether *fi* returns a freshly-acquired page ref to its caller.

        Fixpoint over "returns a var assigned from ``.alloc()`` or from
        a returns_ref callee" (covers PageTable.alloc and the engine's
        preempting wrapper around it without hand-listing either).
        """
        self._compute_returns_ref()
        return bool(self._summaries.get(("returns_ref", fi.qualname)))

    def _compute_returns_ref(self) -> None:
        if self._summaries.get(("returns_ref_done", "")):
            return
        funcs = self.all_functions()
        flagged: Set[str] = set()
        changed = True
        rounds = 0
        while changed and rounds < 5:
            changed = False
            rounds += 1
            for fi in funcs:
                if fi.qualname in flagged:
                    continue
                if self._returns_ref_once(fi, flagged):
                    flagged.add(fi.qualname)
                    changed = True
        for qn in flagged:
            self._summaries[("returns_ref", qn)] = True
        self._summaries[("returns_ref_done", "")] = True

    def _returns_ref_once(self, fi: FuncInfo, flagged: Set[str]) -> bool:
        ref_vars: Set[str] = set()
        for node in ast.walk(fi.node):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                val = node.value
                if isinstance(val, ast.Call) and \
                        _is_alloc_name(_call_attr(val)):
                    ref_vars.add(node.targets[0].id)
                elif isinstance(val, ast.Call):
                    callees = self.resolve_call(val, fi)
                    if any(c.qualname in flagged for c in callees):
                        ref_vars.add(node.targets[0].id)
        if not ref_vars:
            return False
        for node in ast.walk(fi.node):
            if (isinstance(node, ast.Return) and isinstance(node.value, ast.Name)
                    and node.value.id in ref_vars):
                return True
        return False

    def captured_params(self, fi: FuncInfo) -> Set[str]:
        """Params of *fi* stored into ``self`` (ownership transferred in).

        ``def __init__(self, pages): self.pages = pages`` captures
        "pages": a caller passing a live ref there has handed it over.
        Also covers ``self.x.append(p)`` and ``self.x[k] = p``.
        """
        key = ("captures", fi.qualname)
        cached = self._summaries.get(key)
        if cached is not None:
            return cached  # type: ignore[return-value]
        params = {a.arg for a in fi.node.args.args if a.arg != "self"}
        out: Set[str] = set()
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Assign):
                names = {v.id for v in ast.walk(node.value)
                         if isinstance(v, ast.Name)} & params
                if not names:
                    continue
                for tgt in node.targets:
                    base = tgt
                    while isinstance(base, ast.Subscript):
                        base = base.value
                    if isinstance(base, ast.Attribute):
                        out |= names
            elif (isinstance(node, ast.Call)
                    and _call_attr(node) == "append"
                    and isinstance(node.func, ast.Attribute)  # noqa: SIM102
                    and isinstance(node.func.value, (ast.Attribute,
                                                     ast.Subscript))):
                out |= {a.id for a in node.args
                        if isinstance(a, ast.Name)} & params
        self._summaries[key] = out
        return out


def _try_guarded_lines(fn: ast.AST) -> FrozenSet[int]:
    """Line numbers inside any ``try`` body of *fn* (handlers excluded)."""
    lines: Set[int] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Try) and (node.handlers or node.finalbody):
            for stmt in node.body:
                end = getattr(stmt, "end_lineno", stmt.lineno)
                lines.update(range(stmt.lineno, end + 1))
    return frozenset(lines)


# ---------------------------------------------------------------------------
# Project cache / fixture overlay.

_PROJECT_CACHE: Dict[str, Project] = {}
_OVERLAY_CACHE: Dict[Tuple[str, str, int], Project] = {}


def project_for(ctx) -> Project:
    """The Project for a FileContext — cached, fixture-aware.

    If *ctx*'s source matches the file on disk (normal tree lint) the
    shared per-root project is returned.  Otherwise the source is a
    synthetic fixture (lint_text in tests): a single-file overlay is
    built on top of the cached project so interprocedural context (the
    real qtype registry, lock declarations, ...) stays available.
    """
    base = _PROJECT_CACHE.get(ctx.root)
    if base is None:
        base = Project.load(ctx.root)
        _PROJECT_CACHE[ctx.root] = base
    if base.src_of(ctx.rel) == ctx.src:
        return base
    key = (ctx.root, ctx.rel, hash(ctx.src))
    proj = _OVERLAY_CACHE.get(key)
    if proj is None:
        if len(_OVERLAY_CACHE) > 64:
            _OVERLAY_CACHE.clear()
        proj = base.overlay(ctx.rel, ctx.src, ctx.tree)
        _OVERLAY_CACHE[key] = proj
    return proj


def invalidate_cache() -> None:
    """Drop cached projects (tests that rewrite tree files call this)."""
    _PROJECT_CACHE.clear()
    _OVERLAY_CACHE.clear()


# ---------------------------------------------------------------------------
# PAGE liveness interpreter.


class PageLeak:
    __slots__ = ("rule", "line", "var", "acquired_line", "detail")

    def __init__(self, rule, line, var, acquired_line, detail):
        self.rule = rule
        self.line = line
        self.var = var
        self.acquired_line = acquired_line
        self.detail = detail


class _State:
    """One abstract execution path: live refs + escaped names."""

    __slots__ = ("live", "escaped")

    def __init__(self, live=None, escaped=None):
        self.live: Dict[str, int] = dict(live or {})
        self.escaped: Set[str] = set(escaped or ())

    def copy(self) -> "_State":
        return _State(self.live, self.escaped)

    def key(self):
        return (frozenset(self.live.items()), frozenset(self.escaped))


def _merge_states(states: List[_State]) -> List[_State]:
    seen = {}
    for s in states:
        seen.setdefault(s.key(), s)
    out = list(seen.values())
    if len(out) <= _MAX_STATES:
        return out
    # Path explosion: collapse to one may-be-live union state.
    union = _State()
    for s in out:
        for v, ln in s.live.items():
            union.live.setdefault(v, ln)
        union.escaped |= s.escaped
    return [union]


class _PageInterp:
    """Path-sensitive page-ref liveness over one function body.

    Acquire events: ``x = <e>.alloc()``, ``<e>.incref(x)`` (unless x
    already escaped to a container/object), ``x = f(...)`` where f's
    summary says returns_ref, and ``for p in xs: <e>.incref(p)`` which
    acquires the iterable as a unit.  Release/transfer events: decref
    (incl. the loop form), append into a local list (moves the ref),
    assignment into self/attrs/subscripts (ownership transfer), return
    of the live name (transfer to caller), and passing the name to a
    callee whose summary captures that parameter.

    ``x is None`` tests refine paths: on the branch where x is None the
    obligation dies (alloc returned None — nothing was acquired).
    """

    def __init__(self, project: Project, fi: FuncInfo):
        self.project = project
        self.fi = fi
        self.leaks: List[PageLeak] = []
        self.guarded = _try_guarded_lines(fi.node)
        self._reported: Set[Tuple[str, int]] = set()
        # loop-var substitution: {loopvar: iterable_name}
        self.subst: Dict[str, str] = {}

    # -- entry --------------------------------------------------------------

    def run(self) -> List[PageLeak]:
        states = self._exec_block(self.fi.node.body, [_State()])
        end = getattr(self.fi.node, "end_lineno", self.fi.node.lineno)
        for s in states:
            self._report_exit(s, end, "falls off the end of the function")
        return self.leaks

    # -- helpers ------------------------------------------------------------

    def _name(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name):
            return self.subst.get(node.id, node.id)
        return None

    def _report_exit(self, s: _State, line: int, how: str) -> None:
        for var, acq in sorted(s.live.items()):
            if (var, acq) in self._reported:
                continue
            self._reported.add((var, acq))
            self.leaks.append(PageLeak(
                "PAGE001", line, var, acq,
                "page ref held by '%s' (acquired line %d) %s without "
                "decref or ownership transfer" % (var, acq, how)))

    def _kill_live_in_expr(self, s: _State, expr: ast.AST) -> None:
        for node in ast.walk(expr):
            nm = self._name(node)
            if nm and nm in s.live:
                del s.live[nm]
                s.escaped.add(nm)

    # -- statement dispatch --------------------------------------------------

    def _exec_block(self, body: Sequence[ast.stmt],
                    states: List[_State]) -> List[_State]:
        for stmt in body:
            if not states:
                return states
            states = self._exec_stmt(stmt, states)
            states = _merge_states(states)
        return states

    def _exec_stmt(self, stmt: ast.stmt,
                   states: List[_State]) -> List[_State]:
        if isinstance(stmt, ast.Assign):
            return [self._do_assign(stmt, s) for s in states]
        if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            fake = ast.Assign(targets=[stmt.target], value=stmt.value)
            ast.copy_location(fake, stmt)
            return [self._do_assign(fake, s) for s in states]
        if isinstance(stmt, ast.AugAssign):
            for s in states:
                self._scan_calls(stmt, s)
            return states
        if isinstance(stmt, ast.Expr):
            for s in states:
                self._do_call_effects(stmt.value, s)
                self._check_may_raise(stmt, s)
            return states
        if isinstance(stmt, ast.Return):
            out: List[_State] = []
            for s in states:
                if stmt.value is not None:
                    self._do_call_effects(stmt.value, s)
                    self._kill_live_in_expr(s, stmt.value)
                self._report_exit(s, stmt.lineno, "leaks on this return")
            return out
        if isinstance(stmt, ast.Raise):
            for s in states:
                if stmt.lineno not in self.guarded:
                    self._report_exit(s, stmt.lineno, "leaks on this raise")
            return []
        if isinstance(stmt, ast.If):
            return self._do_if(stmt, states)
        if isinstance(stmt, (ast.While,)):
            return self._do_while(stmt, states)
        if isinstance(stmt, ast.For):
            return self._do_for(stmt, states)
        if isinstance(stmt, ast.With):
            for s in states:
                self._check_may_raise(stmt, s, items_only=True)
            return self._exec_block(stmt.body, states)
        if isinstance(stmt, ast.Try):
            return self._do_try(stmt, states)
        if isinstance(stmt, (ast.Break, ast.Continue)):
            # Approximate: carry the state through to after the loop.
            return states
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Import, ast.ImportFrom,
                             ast.Global, ast.Nonlocal, ast.Pass)):
            return states
        if isinstance(stmt, ast.Assert):
            return states
        if isinstance(stmt, ast.Delete):
            for s in states:
                for tgt in stmt.targets:
                    nm = self._name(tgt)
                    if nm:
                        s.live.pop(nm, None)
            return states
        # Anything else: conservatively scan for call effects.
        for s in states:
            self._scan_calls(stmt, s)
        return states

    # -- assignment ----------------------------------------------------------

    def _do_assign(self, stmt: ast.Assign, s: _State) -> _State:
        s = s.copy()
        val = stmt.value
        self._do_call_effects(val, s)
        self._check_may_raise(stmt, s)
        tgt = stmt.targets[0] if len(stmt.targets) == 1 else None
        tname = self._name(tgt) if tgt is not None else None

        acquires = False
        if isinstance(val, ast.Call):
            if _is_alloc_name(_call_attr(val)):
                acquires = True
            else:
                callees = self.project.resolve_call(val, self.fi)
                if callees and any(self.project.returns_ref(c)
                                   for c in callees):
                    acquires = True

        if tname is not None and isinstance(tgt, ast.Name):
            # Rebinding a name drops its old obligation only if moved.
            if acquires:
                s.live[tname] = stmt.lineno
            else:
                # x = y / x = a + b: obligation moves to x.
                moved = False
                for node in ast.walk(val):
                    nm = self._name(node)
                    if nm and nm in s.live:
                        acq = s.live.pop(nm)
                        s.live[tname] = min(acq, s.live.get(tname, acq))
                        moved = True
                if not moved:
                    s.live.pop(tname, None)
        else:
            # Store into self.x / obj[k] / tuple target: ownership
            # transfers out of the frame for every live name used.
            self._kill_live_in_expr(s, val)
        return s

    # -- calls ---------------------------------------------------------------

    def _do_call_effects(self, expr: ast.AST, s: _State) -> None:
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            attr = _call_attr(node)
            if attr == "incref" and len(node.args) == 1:
                nm = self._name(node.args[0])
                if nm and nm not in s.escaped and nm not in s.live:
                    s.live[nm] = node.lineno
            elif attr == "decref" and len(node.args) == 1:
                nm = self._name(node.args[0])
                if nm:
                    s.live.pop(nm, None)
            elif attr == "append" and len(node.args) == 1:
                nm = self._name(node.args[0])
                if nm and nm in s.live:
                    recv = node.func.value  # type: ignore[union-attr]
                    rname = self._name(recv)
                    acq = s.live.pop(nm)
                    if rname is not None:
                        # Moves into a local list: list now owns it.
                        s.live[rname] = min(acq, s.live.get(rname, acq))
                    else:
                        # self.slot_pages[slot].append(pg): transferred.
                        s.escaped.add(nm)
            else:
                # Passing a name to a callee that captures it transfers
                # ownership (if live) and marks it escaped either way —
                # a later incref on an escaped name is the *container's*
                # hold (e.g. RadixNode stores the page, then insert
                # increfs on the node's behalf), not a new obligation
                # of this frame.
                named_args = [(i, self._name(a)) for i, a in
                              enumerate(node.args)]
                named_args = [(i, nm) for i, nm in named_args if nm]
                if not named_args:
                    continue
                for callee in self.project.resolve_call(node, self.fi):
                    captured = self.project.captured_params(callee)
                    if not captured:
                        continue
                    params = [a.arg for a in callee.node.args.args]
                    if params and params[0] == "self":
                        params = params[1:]
                    for i, nm in named_args:
                        if i < len(params) and params[i] in captured:
                            s.live.pop(nm, None)
                            s.escaped.add(nm)

    def _scan_calls(self, stmt: ast.stmt, s: _State) -> None:
        self._do_call_effects(stmt, s)
        self._check_may_raise(stmt, s)

    def _check_may_raise(self, stmt: ast.stmt, s: _State,
                         items_only: bool = False) -> None:
        """PAGE002: a may-raise call with refs live and no enclosing try."""
        if not s.live or stmt.lineno in self.guarded:
            return
        nodes = stmt.items if items_only and isinstance(stmt, ast.With) \
            else [stmt]
        for top in nodes:
            for node in ast.walk(top):
                if not isinstance(node, ast.Call):
                    continue
                attr = _call_attr(node)
                if attr in _REFCOUNT_NAMES or attr == "append":
                    continue
                raises = False
                callees = self.project.resolve_call(node, self.fi)
                if callees:
                    raises = any(self.project.may_raise(c) for c in callees)
                elif attr in KNOWN_RAISERS:
                    raises = True
                if not raises:
                    continue
                key = ("PAGE002", node.lineno)
                if key in self._reported:
                    continue
                self._reported.add(key)
                held = ", ".join(
                    "'%s' (line %d)" % (v, ln)
                    for v, ln in sorted(s.live.items()))
                self.leaks.append(PageLeak(
                    "PAGE002", node.lineno, next(iter(sorted(s.live))),
                    min(s.live.values()),
                    "call may raise while page refs %s are held with no "
                    "enclosing try to roll them back" % held))

    # -- control flow --------------------------------------------------------

    def _refine(self, test: ast.AST, s: _State, branch: bool) -> _State:
        """Kill obligations proven None on this branch of *test*."""
        s = s.copy()

        def none_vars(t: ast.AST, when: bool) -> Set[str]:
            # Vars known None when `t` evaluates to `when`.
            if isinstance(t, ast.Compare) and len(t.ops) == 1 \
                    and isinstance(t.comparators[0], ast.Constant) \
                    and t.comparators[0].value is None:
                nm = self._name(t.left)
                if nm:
                    if isinstance(t.ops[0], ast.Is) and when:
                        return {nm}
                    if isinstance(t.ops[0], ast.IsNot) and not when:
                        return {nm}
                return set()
            if isinstance(t, ast.UnaryOp) and isinstance(t.op, ast.Not):
                return none_vars(t.operand, not when)
            if isinstance(t, ast.BoolOp):
                if isinstance(t.op, ast.Or) and not when:
                    # (a or b) false => every operand false.
                    out: Set[str] = set()
                    for v in t.values:
                        out |= none_vars(v, False)
                    return out
                if isinstance(t.op, ast.And) and when:
                    out = set()
                    for v in t.values:
                        out |= none_vars(v, True)
                    return out
            return set()

        for nm in none_vars(test, branch):
            s.live.pop(nm, None)
        return s

    def _do_if(self, stmt: ast.If, states: List[_State]) -> List[_State]:
        for s in states:
            self._do_call_effects(stmt.test, s)
            self._check_may_raise(ast.Expr(value=stmt.test, lineno=stmt.lineno,
                                           col_offset=0), s)
        then_in = [self._refine(stmt.test, s, True) for s in states]
        else_in = [self._refine(stmt.test, s, False) for s in states]
        out = self._exec_block(stmt.body, then_in)
        out += self._exec_block(stmt.orelse, else_in)
        return out

    def _do_while(self, stmt: ast.While,
                  states: List[_State]) -> List[_State]:
        # Abstract: body runs 0 or 1 times; obligations created in the
        # body must resolve within it (merge catches carried liveness).
        body_in = [self._refine(stmt.test, s, True) for s in states]
        after_body = self._exec_block(stmt.body, body_in)
        exits = states + after_body
        return [self._refine(stmt.test, s, False) for s in exits]

    def _do_for(self, stmt: ast.For, states: List[_State]) -> List[_State]:
        # Loop-var substitution: incref/decref/append on the loop var
        # apply to the iterable as a unit ("for p in pages: decref(p)"
        # releases `pages`).
        loopvar = stmt.target.id if isinstance(stmt.target, ast.Name) else None
        itername = self._name(stmt.iter)
        pushed = False
        if loopvar and itername:
            self.subst[loopvar] = itername
            pushed = True
        try:
            after_body = self._exec_block(stmt.body, [s.copy() for s in states])
            if pushed:
                # Acquire/release loops over a tracked container run
                # "exactly once" abstractly: a zero-iteration release
                # loop only happens when the container is empty, i.e.
                # the obligation was vacuous to begin with.
                return after_body
            zero_iter = self._exec_block(stmt.orelse, states) \
                if stmt.orelse else states
            return zero_iter + after_body
        finally:
            if pushed:
                del self.subst[loopvar]

    def _do_try(self, stmt: ast.Try, states: List[_State]) -> List[_State]:
        body_out = self._exec_block(stmt.body, [s.copy() for s in states])
        # Handlers see the union of entry and post-body states (a raise
        # can interrupt anywhere; entry state is the conservative floor).
        handler_in = _merge_states(
            [s.copy() for s in states] + [s.copy() for s in body_out])
        out = list(body_out)
        for handler in stmt.handlers:
            out += self._exec_block(handler.body, [s.copy()
                                                   for s in handler_in])
        if stmt.orelse:
            out = self._exec_block(stmt.orelse, out)
        if stmt.finalbody:
            out = self._exec_block(stmt.finalbody, out)
        return out


def _has_page_ops(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        attr = _call_attr(node)
        if attr == "incref" or _is_alloc_name(attr):
            return True
    return False


def page_leaks_in(project: Project, fi: FuncInfo) -> List[PageLeak]:
    """PAGE findings for one function (empty unless it acquires refs)."""
    if fi.node.name == "__init__":
        # Constructors store what they're given; captured params are the
        # caller's transfer, not an acquisition here.
        return []
    if not _has_page_ops(fi.node):
        return []
    interp = _PageInterp(project, fi)
    return interp.run()


def page_leaks_for_module(project: Project,
                          rel: str) -> List[Tuple[FuncInfo, PageLeak]]:
    """All PAGE findings in one module — cached (PAGE001 and PAGE002
    share one interpreter run per file)."""
    key = ("page_leaks", rel)
    cached = project._summaries.get(key)
    if cached is not None:
        return cached  # type: ignore[return-value]
    out: List[Tuple[FuncInfo, PageLeak]] = []
    mod = project.modules.get(rel)
    if mod is not None:
        funcs = list(mod.functions.values())
        for cls in mod.classes.values():
            funcs.extend(cls.methods.values())
        for fi in funcs:
            for leak in page_leaks_in(project, fi):
                out.append((fi, leak))
    project._summaries[key] = out
    return out


# ---------------------------------------------------------------------------
# Lock analysis.


class LockSite:
    __slots__ = ("lock", "rel", "line", "func")

    def __init__(self, lock, rel, line, func):
        self.lock = lock
        self.rel = rel
        self.line = line
        self.func = func


class LockEdge:
    __slots__ = ("held", "acquired", "witness")

    def __init__(self, held, acquired, witness):
        self.held = held              # lock id
        self.acquired = acquired      # lock id
        self.witness = witness        # "f -> g -> h acquires X at rel:line"


class LockReport:
    def __init__(self):
        self.locks: Dict[str, str] = {}          # lock id -> kind
        self.edges: Dict[Tuple[str, str], LockEdge] = {}
        self.self_deadlocks: List[LockSite] = []  # plain Lock re-acquired
        self.blocking_under_hot: List[Tuple[LockSite, str]] = []
        self.cycles: List[List[LockEdge]] = []


def _lock_attr_index(project: Project) -> Dict[str, List[str]]:
    """attr name -> [lock ids] across every class (for unique-name use)."""
    idx: Dict[str, List[str]] = {}
    for mod in project.modules.values():
        for cls in mod.classes.values():
            for attr, kind in cls.lock_attrs.items():
                idx.setdefault(attr, []).append("%s.%s" % (cls.name, attr))
    return idx


class _LockWalker:
    """Propagates held-lock sets through the call graph."""

    def __init__(self, project: Project):
        self.project = project
        self.report = LockReport()
        self.attr_index = _lock_attr_index(project)
        for mod in project.modules.values():
            for cls in mod.classes.values():
                for attr, kind in cls.lock_attrs.items():
                    self.report.locks["%s.%s" % (cls.name, attr)] = kind
        self._seen: Set[Tuple[str, FrozenSet[str]]] = set()

    def resolve_lock(self, expr: ast.AST, scope: FuncInfo) -> Optional[str]:
        """``with <expr>:`` -> lock id, or None if not a known lock."""
        if isinstance(expr, ast.Attribute):
            attr = expr.attr
            # self.X
            if isinstance(expr.value, ast.Name) and expr.value.id == "self" \
                    and scope.cls and attr in scope.cls.lock_attrs:
                return "%s.%s" % (scope.cls.name, attr)
            # self.a.X / obj.X: attr-type inference, else unique name.
            base_attr = _is_self_attr(expr.value)
            if base_attr and scope.cls:
                for tname in sorted(scope.cls.attr_types.get(base_attr, ())):
                    for cls in self.project._classes_named(tname):
                        if attr in cls.lock_attrs:
                            return "%s.%s" % (cls.name, attr)
            ids = self.attr_index.get(attr, [])
            if len(ids) == 1:
                return ids[0]
        return None

    def run(self) -> LockReport:
        for fi in self.project.all_functions():
            self._visit_func(fi, frozenset(), ())
        self._find_cycles()
        return self.report

    def _visit_func(self, fi: FuncInfo, held: FrozenSet[str],
                    chain: Tuple[str, ...]) -> None:
        key = (fi.qualname, held)
        if key in self._seen or len(held) > _MAX_HELD \
                or len(chain) > _MAX_CHAIN:
            return
        self._seen.add(key)
        # `local` = locks acquired lexically in THIS function: LCK102
        # findings anchor there (the frame that took the lock owns the
        # fix); inherited holds still propagate for ordering edges.
        self._visit_body(fi.node.body, fi, held, frozenset(), chain)

    def _visit_body(self, body, fi: FuncInfo, held: FrozenSet[str],
                    local: FrozenSet[str], chain: Tuple[str, ...]) -> None:
        for stmt in body:
            self._visit_stmt(stmt, fi, held, local, chain)

    def _visit_stmt(self, stmt, fi: FuncInfo, held: FrozenSet[str],
                    local: FrozenSet[str], chain: Tuple[str, ...]) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs run later, with nothing held
        if isinstance(stmt, ast.With):
            acquired: List[Tuple[str, int]] = []
            for item in stmt.items:
                lock = self.resolve_lock(item.context_expr, fi)
                if lock is None:
                    continue
                site = LockSite(lock, fi.rel, stmt.lineno, fi.qualname)
                kind = self.report.locks.get(lock, "Lock")
                if lock in held:
                    if kind != "RLock":
                        self.report.self_deadlocks.append(site)
                    continue  # re-entry adds no ordering edge
                for h in sorted(held):
                    ekey = (h, lock)
                    if ekey not in self.report.edges:
                        witness = " -> ".join(chain + (fi.qualname,)) + \
                            " acquires %s at %s:%d (holding %s)" % (
                                lock, fi.rel, stmt.lineno, h)
                        self.report.edges[ekey] = LockEdge(h, lock, witness)
                acquired.append((lock, stmt.lineno))
            news = {l for l, _ in acquired}
            self._visit_body(stmt.body, fi, held | news, local | news, chain)
            return
        # Compound statements: recurse into bodies (held set unchanged),
        # visiting calls only in the header expression here so nested
        # With blocks are not double-walked.
        if isinstance(stmt, ast.If):
            for n in ast.walk(stmt.test):
                self._visit_call(n, fi, held, local, chain)
            self._visit_body(stmt.body, fi, held, local, chain)
            self._visit_body(stmt.orelse, fi, held, local, chain)
            return
        if isinstance(stmt, (ast.For, ast.While)):
            header = stmt.iter if isinstance(stmt, ast.For) else stmt.test
            for n in ast.walk(header):
                self._visit_call(n, fi, held, local, chain)
            self._visit_body(stmt.body, fi, held, local, chain)
            self._visit_body(stmt.orelse, fi, held, local, chain)
            return
        if isinstance(stmt, ast.Try):
            self._visit_body(stmt.body, fi, held, local, chain)
            for handler in stmt.handlers:
                self._visit_body(handler.body, fi, held, local, chain)
            self._visit_body(stmt.orelse, fi, held, local, chain)
            self._visit_body(stmt.finalbody, fi, held, local, chain)
            return
        # Simple statement: every call in it runs with `held` held.
        for node in ast.walk(stmt):
            self._visit_call(node, fi, held, local, chain)

    def _visit_call(self, node, fi: FuncInfo, held: FrozenSet[str],
                    local: FrozenSet[str], chain: Tuple[str, ...]) -> None:
        if not isinstance(node, ast.Call):
            return
        callees = self.project.resolve_call(node, fi)
        if local:
            hot = sorted(h for h in local
                         if h.split(".", 1)[-1] in HOT_LOCK_ATTRS)
            if hot:
                attr = _call_attr(node)
                blocking = attr in KNOWN_BLOCKERS or (
                    isinstance(node.func, ast.Name)
                    and node.func.id == "sleep")
                if not blocking and callees:
                    blocking = any(self.project.is_blocking(c)
                                   for c in callees)
                if blocking:
                    site = LockSite(hot[0], fi.rel, node.lineno, fi.qualname)
                    desc = attr or (node.func.id if isinstance(
                        node.func, ast.Name) else "<call>")
                    self.report.blocking_under_hot.append((site, desc))
        for callee in callees:
            self._visit_func(callee, held, chain + (fi.qualname,))

    def _find_cycles(self) -> None:
        graph: Dict[str, List[str]] = {}
        for (h, a) in self.report.edges:
            graph.setdefault(h, []).append(a)
        seen_cycles: Set[FrozenSet[str]] = set()
        # For each node, BFS for the shortest path back to itself; a
        # cycle is recorded once, keyed by its node set.
        for start in sorted(graph):
            parent: Dict[str, str] = {}
            queue = [start]
            found = None
            while queue and found is None:
                cur = queue.pop(0)
                for nxt in sorted(graph.get(cur, ())):
                    if nxt == start:
                        found = cur
                        break
                    if nxt not in parent:
                        parent[nxt] = cur
                        queue.append(nxt)
            if found is None:
                continue
            path = [found]
            while path[-1] != start:
                path.append(parent[path[-1]])
            path.reverse()            # start .. found
            cyc = path + [start]      # start .. found -> start
            key = frozenset(path)
            if key in seen_cycles:
                continue
            seen_cycles.add(key)
            edges = [self.report.edges[(cyc[i], cyc[i + 1])]
                     for i in range(len(cyc) - 1)]
            self.report.cycles.append(edges)
        # Deterministic order for stable output.
        self.report.cycles.sort(
            key=lambda es: tuple(e.acquired for e in es))


def lock_report(project: Project) -> LockReport:
    """The (cached) whole-project lock analysis."""
    cached = project._summaries.get(("lock_report", ""))
    if cached is None:
        cached = _LockWalker(project).run()
        project._summaries[("lock_report", "")] = cached
    return cached  # type: ignore[return-value]
