"""graftlint rules: the codebase's serving/training contracts as AST
checks (rule table + rationale in docs/static-analysis.md).

==========  ===============================================================
rule        invariant
==========  ===============================================================
``WCT001``  no wall-clock *calls* in serving/, obs/, sim/,
            train/supervisor.py, parallel/health.py — timestamps flow
            through the injectable ``clock=`` (PR 11; sim/ added by
            ISSUE 13: the simulator must be wall-clock-free or its
            reports stop being reproducible); referencing ``time.time``
            as a default clock implementation is fine, *calling* it is
            not
``ATW001``  no bare ``open(..., "w"/"wb")`` anywhere in bigdl_tpu/ —
            artifacts commit via ``utils/durability.atomic_write`` (PR 7);
            append-mode logs are exempt (append-only is its own protocol)
``FLT001``  every ``.fire("p")`` / ``.arm("p")`` names a point declared in
            the scoped injector registry (serving/faults.POINTS,
            train/supervisor.POINTS, utils/diskfaults.DISK_POINTS)
``LCK001``  attributes carrying a ``# guarded-by: <lock>`` annotation are
            only touched inside ``with self.<lock>:`` (outside the
            constructor) — the kv_pool_utilization scrape-500 bug class
``MET001``  serving/metrics.py family names reconciled two-way against the
            ``expected_families`` registry tuples, statically (no jax)
``DON001``  a variable passed at a donating jit call site
            (``donate_argnums``/``donate_argnames``) is not read again
            afterwards in the same function without rebinding
``CRC001``  JSONL journal/event-log lines (``.write`` of a ``json.dumps``)
            go through ``serving/journal.crc_line``
==========  ===============================================================
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterable, Optional

from bigdl_tpu.analysis.core import (
    Check, FileContext, Finding, const_str, docstring_nodes, dotted_name,
)


# ---------------------------------------------------------------------------
# WCT001 — wall-clock ban
# ---------------------------------------------------------------------------

class WallClockBan(Check):
    rule = "WCT001"
    description = (
        "wall-clock calls in clock-injected subsystems (serving/, obs/, "
        "train/supervisor.py, parallel/health.py, "
        "parallel/qcollectives.py)"
    )

    SCOPES = (
        "bigdl_tpu/serving/",
        "bigdl_tpu/obs/",
        "bigdl_tpu/sim/",  # the simulator IS the fake-clock domain: one
        # wall-clock call would silently re-couple reports to the host
        "bigdl_tpu/train/supervisor.py",
        "bigdl_tpu/parallel/health.py",
        # collectives run inside jit traces priced by roofline/sim
        # models — any host-clock call there is a trace-time landmine
        "bigdl_tpu/parallel/qcollectives.py",
    )
    BANNED = {
        "time.time", "time.time_ns", "time.monotonic",
        "time.monotonic_ns", "time.perf_counter", "time.perf_counter_ns",
        "datetime.now", "datetime.utcnow", "datetime.datetime.now",
        "datetime.datetime.utcnow", "datetime.date.today",
    }

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if not any(ctx.rel.startswith(s) or ctx.rel == s.rstrip("/")
                   for s in self.SCOPES):
            return
        # `from time import monotonic [as m]` / `from datetime import
        # datetime as dt` would otherwise bypass the dotted-name match:
        # map the local alias back to its fully-qualified spelling
        aliased: dict = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module in (
                    "time", "datetime"):
                for a in node.names:
                    aliased[a.asname or a.name] = f"{node.module}.{a.name}"
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name:
                head, _, rest = name.partition(".")
                if head in aliased:
                    name = aliased[head] + (f".{rest}" if rest else "")
            if name in self.BANNED:
                yield Finding(
                    self.rule, ctx.rel, node.lineno,
                    f"wall-clock call {name}() in a clock-injected "
                    "subsystem",
                    hint="route the timestamp through the injectable "
                         "clock= (engine/ApiServer/TraceRecorder ctor "
                         "arg); keep wall-clock references only as "
                         "default clock implementations",
                )


# ---------------------------------------------------------------------------
# ATW001 — non-atomic writes
# ---------------------------------------------------------------------------

class AtomicWriteBan(Check):
    rule = "ATW001"
    description = (
        "bare write-mode open() outside utils/durability.py's atomic "
        "protocol"
    )

    EXEMPT_FILES = ("bigdl_tpu/utils/durability.py",)

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.rel in self.EXEMPT_FILES:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) not in ("open", "io.open"):
                continue
            mode = None
            if len(node.args) >= 2:
                mode = const_str(node.args[1])
            for kw in node.keywords:
                if kw.arg == "mode":
                    mode = const_str(kw.value)
            if mode is None:
                continue  # default "r", or dynamic (can't tell statically)
            if "w" in mode or "x" in mode:
                yield Finding(
                    self.rule, ctx.rel, node.lineno,
                    f"non-atomic write-mode open(..., {mode!r}) — a kill "
                    "mid-write leaves a torn artifact",
                    hint="commit through utils/durability.atomic_write"
                         "(path, writer) (tmp + fsync + rename); append-"
                         "mode journals are exempt by design",
                )


# ---------------------------------------------------------------------------
# FLT001 — fault-point validity
# ---------------------------------------------------------------------------

class FaultPointValidity(Check):
    rule = "FLT001"
    description = (
        ".fire()/.arm() strings must be declared injector points "
        "(serving/faults, train/supervisor, utils/diskfaults registries)"
    )

    #: registry source file -> module-level tuple constant holding the
    #: declared points
    REGISTRIES = (
        ("serving", "bigdl_tpu/serving/faults.py", "POINTS"),
        ("train", "bigdl_tpu/train/supervisor.py", "POINTS"),
        ("disk", "bigdl_tpu/utils/diskfaults.py", "DISK_POINTS"),
    )

    def __init__(self):
        # one registry parse per scan root, not per linted file — the
        # three source files would otherwise be re-parsed ~100x per run
        self._reg_cache: dict = {}

    def _load_registries(self, root: str) -> dict:
        if root in self._reg_cache:
            return self._reg_cache[root]
        regs: dict = {}
        for key, rel, const in self.REGISTRIES:
            path = os.path.join(root, rel.replace("/", os.sep))
            if not os.path.exists(path):
                continue
            try:
                with open(path, encoding="utf-8") as f:
                    tree = ast.parse(f.read())
            except (OSError, SyntaxError):
                continue
            for node in tree.body:
                if (isinstance(node, ast.Assign) and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and node.targets[0].id == const):
                    try:
                        val = ast.literal_eval(node.value)
                    except ValueError:
                        continue
                    if isinstance(val, (tuple, list)) and all(
                            isinstance(v, str) for v in val):
                        regs[key] = set(val)
        self._reg_cache[root] = regs
        return regs

    def _scope(self, rel: str, regs: dict) -> tuple:
        """(scope label, allowed point set) for a file. parallel/ rides
        the train registry: health.py fires the supervisor's rank_drop."""
        if (rel.startswith("bigdl_tpu/serving/")
                or rel.startswith("bigdl_tpu/sim/")):
            # sim/ composes the SERVING injector (chaos traces arm
            # slow_step/alloc_page against the simulated engine), so its
            # fault points are checked against the serving registry
            return "serving", regs.get("serving", set())
        if (rel.startswith("bigdl_tpu/train/")
                or rel.startswith("bigdl_tpu/parallel/")):
            return "train", regs.get("train", set())
        if rel.startswith("bigdl_tpu/utils/"):
            return "disk", regs.get("disk", set())
        union: set = set()
        for s in regs.values():
            union |= s
        return "any", union

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        regs = self._load_registries(ctx.root)
        if not regs:
            return
        scope, allowed = self._scope(ctx.rel, regs)
        if not allowed:
            return
        for node in ast.walk(ctx.tree):
            if (not isinstance(node, ast.Call)
                    or not isinstance(node.func, ast.Attribute)
                    or node.func.attr not in ("fire", "arm")
                    or not node.args):
                continue
            point = const_str(node.args[0])
            if point is None or point in allowed:
                continue
            yield Finding(
                self.rule, ctx.rel, node.lineno,
                f".{node.func.attr}({point!r}) names no declared "
                f"injection point of the {scope} registry",
                hint=f"declare it in the injector's points tuple or use "
                     f"one of: {', '.join(sorted(allowed))}",
            )


# ---------------------------------------------------------------------------
# LCK001 — lock discipline
# ---------------------------------------------------------------------------

_GUARD_RE = re.compile(r"#\s*guarded-by:\s*(\w+)")


class LockDiscipline(Check):
    rule = "LCK001"
    description = (
        "# guarded-by: <lock> annotated attributes accessed outside "
        "`with self.<lock>:` (outside the constructor)"
    )

    @staticmethod
    def _guarded_attrs(ctx: FileContext, cls: ast.ClassDef) -> dict:
        """{attr: lock} from guarded-by comments attached to self.attr
        assignments in this class (trailing comment on the assignment
        line, or a comment on the line directly above it)."""
        assigns: list = []  # (lineno, end_lineno, attr, fn_name)
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                targets = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, ast.AnnAssign):
                    targets = [node.target]
                for t in targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        assigns.append((node.lineno,
                                        node.end_lineno or node.lineno,
                                        t.attr, fn.name))
        guarded: dict = {}
        for i, text in enumerate(ctx.lines, start=1):
            m = _GUARD_RE.search(text)
            if not m:
                continue
            lock = m.group(1)
            # trailing comment on the assignment's own line(s) wins; the
            # comment-above form applies only when the annotation line
            # holds no assignment itself (else a trailing annotation
            # would also leak onto the NEXT attribute)
            on_line = [(a, f) for lo, hi, a, f in assigns if lo <= i <= hi]
            if on_line:
                for attr, fn_name in on_line:
                    guarded[attr] = (lock, fn_name)
                continue
            for lo, _hi, attr, fn_name in assigns:
                if lo == i + 1:
                    guarded[attr] = (lock, fn_name)
        return guarded

    def _visit(self, node, guarded: dict, ctx: FileContext,
               held: frozenset, out: list) -> None:
        """Recursive walk tracking which `self.<lock>`s are held."""
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            # a nested def may run long after the enclosing with exits:
            # its body is scanned as holding nothing
            for child in ast.iter_child_nodes(node):
                self._visit(child, guarded, ctx, frozenset(), out)
            return
        if isinstance(node, ast.With):
            locks = set()
            for item in node.items:
                ce = item.context_expr
                if (isinstance(ce, ast.Attribute)
                        and isinstance(ce.value, ast.Name)
                        and ce.value.id == "self"):
                    locks.add(ce.attr)
                # the header expressions themselves evaluate unlocked
                self._visit(ce, guarded, ctx, held, out)
            for stmt in node.body:
                self._visit(stmt, guarded, ctx, held | frozenset(locks),
                            out)
            return
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self" and node.attr in guarded):
            lock, _ = guarded[node.attr]
            if lock not in held:
                out.append(Finding(
                    self.rule, ctx.rel, node.lineno,
                    f"self.{node.attr} is annotated guarded-by {lock} "
                    f"but accessed outside `with self.{lock}:`",
                    hint=f"take `with self.{lock}:` around the access "
                         "(or move it into the guarded helper)",
                ))
        for child in ast.iter_child_nodes(node):
            self._visit(child, guarded, ctx, held, out)

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if "guarded-by:" not in ctx.src:
            return
        for cls in ast.walk(ctx.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            guarded = self._guarded_attrs(ctx, cls)
            if not guarded:
                continue
            init_fns = {fn for (_, fn) in guarded.values()}
            for fn in cls.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                if fn.name in init_fns:
                    # the annotating constructor runs before any other
                    # thread can exist — bare init writes are the point
                    continue
                out: list = []
                for stmt in fn.body:
                    self._visit(stmt, guarded, ctx, frozenset(), out)
                yield from out


# ---------------------------------------------------------------------------
# MET001 — static metrics drift
# ---------------------------------------------------------------------------

class MetricsDrift(Check):
    rule = "MET001"
    description = (
        "serving/metrics.py family names reconciled against the "
        "expected_families registry tuples, two-way, without importing jax"
    )

    TARGET = "bigdl_tpu/serving/metrics.py"
    REGISTRY_NAMES = ("_PROCESS_FAMILIES", "_ENGINE_FAMILIES",
                      "_PAGED_FAMILIES", "_STATE_FAMILIES", "_LATENT_FAMILIES",
                      "_WINDOW_FAMILIES",
                      "_MOE_FAMILIES", "_SPEC_FAMILIES", "_ADAPTER_FAMILIES")
    _TYPE_RE = re.compile(r"# TYPE (bigdl_tpu_\w+) ")
    _FAMILY_RE = re.compile(r"^(bigdl_tpu_\w+)(?:$|[\s{])")

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.rel != self.TARGET:
            return
        registry: dict = {}  # family -> lineno
        registry_spans: list = []
        for node in ctx.tree.body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id in self.REGISTRY_NAMES):
                registry_spans.append(
                    (node.lineno, node.end_lineno or node.lineno))
                try:
                    for fam in ast.literal_eval(node.value):
                        registry.setdefault(fam, node.lineno)
                except ValueError:
                    yield Finding(
                        self.rule, ctx.rel, node.lineno,
                        f"{node.targets[0].id} is not a literal tuple of "
                        "strings — the registry must be statically "
                        "readable",
                    )
        docstrings = docstring_nodes(ctx.tree)
        rendered: dict = {}  # family -> lineno
        for node in ast.walk(ctx.tree):
            if (not isinstance(node, ast.Constant)
                    or not isinstance(node.value, str)
                    or id(node) in docstrings):
                continue
            if any(lo <= node.lineno <= hi for lo, hi in registry_spans):
                continue
            for fam in self._TYPE_RE.findall(node.value):
                rendered.setdefault(fam, node.lineno)
            m = self._FAMILY_RE.match(node.value)
            if m:
                rendered.setdefault(m.group(1), node.lineno)
        for fam in sorted(set(rendered) - set(registry)):
            yield Finding(
                self.rule, ctx.rel, rendered[fam],
                f"family {fam} is rendered but absent from the "
                "expected_families registry",
                hint="add it to the matching _*_FAMILIES tuple (the "
                     "runtime drift gate in ci --core enforces the same "
                     "invariant dynamically)",
            )
        for fam in sorted(set(registry) - set(rendered)):
            yield Finding(
                self.rule, ctx.rel, registry[fam],
                f"family {fam} is registered in expected_families but "
                "never constructed by render()",
                hint="render it or drop the registry entry",
            )


# ---------------------------------------------------------------------------
# DON001 — donation hazard
# ---------------------------------------------------------------------------

class DonationHazard(Check):
    rule = "DON001"
    description = (
        "a variable passed at a donating jit call site is read again in "
        "the same function without rebinding (its buffer is gone)"
    )

    @staticmethod
    def _donation(call: ast.Call) -> Optional[tuple]:
        """(argnums, argnames) when ``call`` is a jax.jit/pjit with
        donation; None otherwise."""
        name = dotted_name(call.func)
        if name not in ("jax.jit", "jit", "jax.pjit", "pjit"):
            return None
        nums: list = []
        names: list = []
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                try:
                    v = ast.literal_eval(kw.value)
                except ValueError:
                    continue
                nums = [v] if isinstance(v, int) else list(v)
            elif kw.arg == "donate_argnames":
                try:
                    v = ast.literal_eval(kw.value)
                except ValueError:
                    continue
                names = [v] if isinstance(v, str) else list(v)
        if not nums and not names:
            return None
        return nums, names

    @staticmethod
    def _walk_local(fn) -> Iterable[ast.AST]:
        """fn's own nodes only — nested defs/lambdas have their own
        scopes (and their own _scan_function pass), so a same-named
        parameter or local inside one is a different variable."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._scan_function(fn, ctx)

    def _scan_function(self, fn, ctx: FileContext) -> Iterable[Finding]:
        # 1. locals bound to a donating jit
        jitted: dict = {}  # local name -> (argnums, argnames)
        calls: list = []  # (call node, argnums, argnames)
        local_nodes = list(self._walk_local(fn))
        for node in local_nodes:
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call):
                don = self._donation(node.value)
                if don and len(node.targets) == 1 and isinstance(
                        node.targets[0], ast.Name):
                    jitted[node.targets[0].id] = don
        for node in local_nodes:
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Name) and \
                        node.func.id in jitted:
                    calls.append((node, *jitted[node.func.id]))
                elif isinstance(node.func, ast.Call):
                    # direct jax.jit(f, donate_*=...)(args)
                    don = self._donation(node.func)
                    if don:
                        calls.append((node, *don))
        if not calls:
            return
        # 2. per call: donated plain-Name arguments
        events: list = []  # (lineno, col, kind, name) kind: load|store
        for node in self._walk_local(fn):
            if isinstance(node, ast.Name):
                kind = ("store" if isinstance(node.ctx, (ast.Store,
                                                         ast.Del))
                        else "load")
                events.append((node.lineno, node.col_offset, kind,
                               node.id))
        events.sort()
        for call, nums, names in calls:
            donated: list = []  # (var, spelled)
            for i in nums:
                if 0 <= i < len(call.args) and isinstance(
                        call.args[i], ast.Name):
                    donated.append((call.args[i].id, f"argnum {i}"))
            for kw in call.keywords:
                if kw.arg in names and isinstance(kw.value, ast.Name):
                    donated.append((kw.value.id, f"argname {kw.arg!r}"))
            end = call.end_lineno or call.lineno
            for var, spelled in donated:
                for lineno, _col, kind, name in events:
                    if name != var or lineno < call.lineno:
                        continue
                    if kind == "store":
                        # rebound — including the canonical
                        # `x = g(x)` pattern, whose Store target sorts
                        # before the call's own argument Load — so the
                        # stale buffer is unreachable from here on
                        break
                    if lineno <= end:
                        continue  # the donated argument itself
                    yield Finding(
                        self.rule, ctx.rel, lineno,
                        f"{var!r} was donated at the jit call on line "
                        f"{call.lineno} ({spelled}) and read again here "
                        "— its buffer is deleted after the call",
                        hint="rebind the result over the donated name "
                             f"({var} = f({var}, ...)) or drop the "
                             "donation",
                    )
                    break  # one finding per donated var is enough


# ---------------------------------------------------------------------------
# CRC001 — journal-line discipline
# ---------------------------------------------------------------------------

class JournalLineDiscipline(Check):
    rule = "CRC001"
    description = (
        "JSONL journal/event-log writes (.write of a json.dumps line) "
        "must go through serving/journal.crc_line"
    )

    @classmethod
    def _trailing_const(cls, node):
        """Rightmost constant of a concat chain / f-string — the line
        terminator a JSONL write appends. None = not statically
        determinable (or no trailing literal at all)."""
        while True:
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
                node = node.right
                continue
            if isinstance(node, ast.JoinedStr) and node.values:
                node = node.values[-1]
                continue
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "encode"):
                node = node.func.value
                continue
            break
        if isinstance(node, ast.Constant):
            v = node.value
            if isinstance(v, bytes):
                v = v.decode("latin-1")
            if isinstance(v, str):
                return v
        return None

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if (not isinstance(node, ast.Call)
                    or not isinstance(node.func, ast.Attribute)
                    or node.func.attr != "write" or not node.args):
                continue
            arg = node.args[0]
            has_dumps = any(
                isinstance(s, ast.Call)
                and (dotted_name(s.func) or "").endswith("dumps")
                for s in ast.walk(arg)
            )
            if not has_dumps:
                continue
            # only JSONL *lines* are in scope: the payload must end with
            # exactly one newline. Whole-document JSON (config files,
            # trace exports) and wire protocols (SSE "data: ...\n\n",
            # FastChat's NUL-delimited stream) are different contracts.
            tail = self._trailing_const(arg)
            if tail is None or not tail.endswith("\n") \
                    or tail.endswith("\n\n"):
                continue
            has_crc = any(
                isinstance(s, ast.Call)
                and (dotted_name(s.func) or "").endswith("crc_line")
                for s in ast.walk(arg)
            )
            if has_crc:
                continue
            yield Finding(
                self.rule, ctx.rel, node.lineno,
                "JSONL record written without the crc-suffix line "
                "discipline — interior rot in this log would be "
                "undetectable",
                hint="wrap the body: f.write(journal.crc_line("
                     "json.dumps(rec)) + '\\n') (serving/journal.py)",
            )


from .interproc import INTERPROC_CHECKS  # noqa: E402 (checks need the
# Check/Finding definitions above via core; interproc imports from core
# directly so this late import only avoids a cosmetic cycle)

ALL_CHECKS = (
    WallClockBan,
    AtomicWriteBan,
    FaultPointValidity,
    LockDiscipline,
    MetricsDrift,
    DonationHazard,
    JournalLineDiscipline,
) + INTERPROC_CHECKS
