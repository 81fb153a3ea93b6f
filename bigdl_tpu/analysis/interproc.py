"""graftlint v2 rule families: interprocedural PAGE / LCK / DSP checks.

These checks consume :mod:`bigdl_tpu.analysis.flow`'s project-wide
symbol table, call graph, and summaries instead of a single file's AST.
Each still implements the plain :class:`~bigdl_tpu.analysis.core.Check`
protocol — ``run(ctx)`` emits findings for *ctx*'s file only — so the
suppression/baseline/CLI machinery from PR 12 applies unchanged.  The
project analysis is computed once per root and cached (flow.py), so the
per-file cost is a dictionary lookup plus this file's share of results.

Rule map (details + examples in docs/static-analysis.md):

- PAGE001  page ref leaks on a normal exit (return / fall-off)
- PAGE002  page refs live across a may-raise call with no enclosing try
- LCK101   lock-order cycle (two witness call paths reported)
- LCK102   blocking call (fsync/flush/sleep/host transfer) under a hot
           lock (``_stat_lock`` / ``_admission_lock``)
- DSP001   registered qtype missing from the GEMV dispatch table (or a
           dispatch key naming an unregistered qtype)
- DSP002   ``from bigdl_tpu.ops.pallas import X`` where X is not
           exported by the kernel package
- DSP003   dispatch k_multiple incompatible with the qtype's
           block/superblock geometry; DecodeSpec storage not covered
- DSP004   VMEM-budget magic number drifted from tiling.py's constants
- DSP005   tiling.py budget invariants (caps, lane alignment) violated
- DSP006   attention epilogue decodes K/V tiles inline instead of
           through the shared qdecode.decode_kv body
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import Check, FileContext, Finding
from . import flow

# ---------------------------------------------------------------------------
# PAGE family.


class PageLeakOnExit(Check):
    rule = "PAGE001"
    description = (
        "page ref acquired (PagePool.alloc/incref) but not released or "
        "ownership-transferred on every normal exit path"
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if "alloc(" not in ctx.src and ".incref(" not in ctx.src:
            return
        project = flow.project_for(ctx)
        for fi, leak in flow.page_leaks_for_module(project, ctx.rel):
            if leak.rule != self.rule:
                continue
            yield Finding(
                rule=self.rule, path=ctx.rel, line=leak.line,
                message="in %s: %s" % (fi.node.name, leak.detail),
                hint="decref on this path, append into the owning "
                     "table/list before exiting, or return the ref "
                     "to the caller",
            )


class PageLeakOnRaise(Check):
    rule = "PAGE002"
    description = (
        "page refs live across a may-raise call (storage write, host "
        "transfer, raising callee) with no enclosing try to roll back"
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if "alloc(" not in ctx.src and ".incref(" not in ctx.src:
            return
        project = flow.project_for(ctx)
        for fi, leak in flow.page_leaks_for_module(project, ctx.rel):
            if leak.rule != self.rule:
                continue
            yield Finding(
                rule=self.rule, path=ctx.rel, line=leak.line,
                message="in %s: %s" % (fi.node.name, leak.detail),
                hint="wrap the faultable call in try/except, decref "
                     "the held refs in the handler, and re-raise",
            )


# ---------------------------------------------------------------------------
# LCK family.
#
# The lock analysis is whole-project; each check filters the shared
# report down to sites in ctx's file so findings stay file-anchored
# (and suppressions / baseline entries work per-site as usual).


class LockOrderCycle(Check):
    rule = "LCK101"
    description = (
        "lock-order cycle: two call paths acquire the same locks in "
        "opposite order (deadlock when the threads interleave)"
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if "Lock(" not in ctx.src and "RLock(" not in ctx.src \
                and "with self." not in ctx.src:
            return
        project = flow.project_for(ctx)
        report = flow.lock_report(project)
        for site in report.self_deadlocks:
            if site.rel != ctx.rel:
                continue
            yield Finding(
                rule=self.rule, path=ctx.rel, line=site.line,
                message="re-acquisition of non-reentrant lock %s in %s "
                        "(already held on this call path) deadlocks"
                        % (site.lock, site.func),
                hint="make the inner call a _locked variant, or declare "
                     "the lock RLock if re-entry is intended",
            )
        for edges in report.cycles:
            # Anchor the cycle at each in-file witness edge (usually
            # one); the message carries every witness path.
            witnesses = "; ".join(e.witness for e in edges)
            order = " -> ".join([edges[0].held] +
                                [e.acquired for e in edges])
            for e in edges:
                rel, line = _witness_site(e)
                if rel != ctx.rel:
                    continue
                yield Finding(
                    rule=self.rule, path=ctx.rel, line=line,
                    message="lock-order cycle %s; witnesses: %s"
                            % (order, witnesses),
                    hint="pick one global order for these locks and "
                         "restructure the call path that violates it "
                         "(move work outside the outer lock)",
                )


def _witness_site(edge: "flow.LockEdge") -> Tuple[str, int]:
    # witness text ends with "... at rel:line (holding X)"
    try:
        at = edge.witness.rsplit(" at ", 1)[1]
        loc = at.split(" ", 1)[0]
        rel, line = loc.rsplit(":", 1)
        return rel, int(line)
    except (IndexError, ValueError):  # pragma: no cover - defensive
        return "", 0


class BlockingUnderHotLock(Check):
    rule = "LCK102"
    description = (
        "blocking call (fsync/flush/sleep/host transfer, or a callee "
        "that transitively blocks) made while holding a hot serving "
        "lock (_stat_lock/_admission_lock)"
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if "_stat_lock" not in ctx.src and "_admission_lock" not in ctx.src:
            return
        project = flow.project_for(ctx)
        report = flow.lock_report(project)
        seen: Set[Tuple[int, str]] = set()
        for site, desc in report.blocking_under_hot:
            if site.rel != ctx.rel or (site.line, desc) in seen:
                continue
            seen.add((site.line, desc))
            yield Finding(
                rule=self.rule, path=ctx.rel, line=site.line,
                message="blocking call '%s' under hot lock %s (in %s): "
                        "every scrape/submit convoys behind it"
                        % (desc, site.lock, site.func),
                hint="snapshot state under the lock, do the blocking "
                     "work after releasing it",
            )


# ---------------------------------------------------------------------------
# DSP family.

_QTYPES_REL = "bigdl_tpu/quant/qtypes.py"
_LINEAR_REL = "bigdl_tpu/ops/linear.py"
_TILING_REL = "bigdl_tpu/ops/pallas/tiling.py"
_QDECODE_REL = "bigdl_tpu/ops/pallas/qdecode.py"
_PALLAS_INIT_REL = "bigdl_tpu/ops/pallas/__init__.py"
_QMATMUL_REL = "bigdl_tpu/ops/pallas/qmatmul.py"


def _registry_specs(project: "flow.Project") -> Dict[str, Dict[str, object]]:
    """qtype name -> literal QTypeSpec kwargs, from qtypes.py's
    ``_register(QTypeSpec(...))`` calls."""
    mod = project.modules.get(_QTYPES_REL)
    out: Dict[str, Dict[str, object]] = {}
    if mod is None:
        return out
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "_register" and node.args):
            continue
        spec = node.args[0]
        if not (isinstance(spec, ast.Call)
                and isinstance(spec.func, ast.Name)
                and spec.func.id == "QTypeSpec"):
            continue
        kwargs: Dict[str, object] = {
            "bits": None, "block_size": None, "storage": "packed_u8",
            "planes": (), "superblock": 0, "line": spec.lineno,
        }
        pos_names = ("name", "bits", "block_size")
        for i, arg in enumerate(spec.args[:3]):
            try:
                kwargs[pos_names[i]] = flow.eval_const(arg)
            except ValueError:
                pass
        for kw in spec.keywords:
            if kw.arg is None:
                continue
            try:
                kwargs[kw.arg] = flow.eval_const(kw.value)
            except ValueError:
                pass
        name = kwargs.get("name")
        if isinstance(name, str):
            out[name] = kwargs
    return out


def _gemv_table(tree: ast.Module) -> Tuple[Optional[int],
                                           Dict[str, Tuple[int, int]]]:
    """(dict lineno, {qtype: (k_multiple, entry lineno)}) from the
    ``_QGEMV_QTYPES = {...}`` literal in linear.py."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "_QGEMV_QTYPES"
                and isinstance(node.value, ast.Dict)):
            table: Dict[str, Tuple[int, int]] = {}
            for k, v in zip(node.value.keys, node.value.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, str)):
                    continue
                try:
                    k_multiple = int(flow.eval_const(v))
                except (ValueError, TypeError):
                    k_multiple = -1
                table[k.value] = (k_multiple, k.lineno)
            return node.lineno, table
    return None, {}


class DispatchCoverage(Check):
    rule = "DSP001"
    description = (
        "every non-dense registered qtype needs a _QGEMV_QTYPES entry "
        "(or the table names a qtype that is not registered)"
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.rel != _LINEAR_REL:
            return
        project = flow.project_for(ctx)
        specs = _registry_specs(project)
        if not specs:
            return
        lineno, table = _gemv_table(ctx.tree)
        if lineno is None:
            return
        for name, spec in sorted(specs.items()):
            if spec.get("storage") == "dense":
                continue  # bf16/fp16 pass-through: no kernel needed
            if name not in table:
                yield Finding(
                    rule=self.rule, path=ctx.rel, line=lineno,
                    message="registered qtype '%s' (qtypes.py:%s) has no "
                            "_QGEMV_QTYPES entry — it would silently fall "
                            "back to dequant-matmul on the decode path"
                            % (name, spec.get("line")),
                    hint="add a _QGEMV_QTYPES entry (its k_multiple) and "
                         "a qdecode.spec_for branch",
                )
        for name, (_, line) in sorted(table.items()):
            if name not in specs:
                yield Finding(
                    rule=self.rule, path=ctx.rel, line=line,
                    message="_QGEMV_QTYPES entry '%s' names a qtype that "
                            "is not registered in quant/qtypes.py" % name,
                    hint="remove the stale entry or register the qtype",
                )


class KernelExportConsistency(Check):
    rule = "DSP002"
    description = (
        "`from bigdl_tpu.ops.pallas import X` where X is not exported "
        "by the kernel package (lazy imports fail only at dispatch time)"
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if "bigdl_tpu.ops.pallas" not in ctx.src:
            return
        project = flow.project_for(ctx)
        exported = _pallas_exports(project)
        if not exported:
            return
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.ImportFrom)
                    and node.module == "bigdl_tpu.ops.pallas"):
                continue
            for alias in node.names:
                if alias.name not in exported:
                    yield Finding(
                        rule=self.rule, path=ctx.rel, line=node.lineno,
                        message="'%s' is not exported by "
                                "bigdl_tpu.ops.pallas — this lazy import "
                                "raises at first dispatch, not at "
                                "module import" % alias.name,
                        hint="export it from ops/pallas/__init__.py or "
                             "fix the symbol name",
                    )


def _pallas_exports(project: "flow.Project") -> Set[str]:
    mod = project.modules.get(_PALLAS_INIT_REL)
    if mod is None:
        return set()
    names: Set[str] = set(mod.functions) | set(mod.classes)
    names |= set(mod.imports)  # from .qmatmul import qmatmul, ...
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
        elif isinstance(node, ast.ImportFrom):
            # relative `from .qmatmul import X` bindings land in the
            # package namespace too (ModuleInfo.imports only records
            # absolute-module froms).
            for alias in node.names:
                names.add(alias.asname or alias.name)
    names.discard("__all__")
    # submodules are importable from the package too (qmatmul.py does
    # `from bigdl_tpu.ops.pallas import qdecode`)
    pkg = _PALLAS_INIT_REL.rsplit("/", 1)[0] + "/"
    for rel in project.modules:
        if rel.startswith(pkg):
            names.add(rel[len(pkg):-len(".py")])
    return names


class DispatchGeometry(Check):
    rule = "DSP003"
    description = (
        "dispatch k_multiple must be divisible by the qtype's block "
        "(and superblock) size; DecodeSpec storage dispatch must cover "
        "every registered storage or have an explicit default"
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.rel == _LINEAR_REL:
            yield from self._check_k_multiples(ctx)
        elif ctx.rel == _QDECODE_REL:
            yield from self._check_storage_coverage(ctx)

    def _check_k_multiples(self, ctx: FileContext) -> Iterable[Finding]:
        project = flow.project_for(ctx)
        specs = _registry_specs(project)
        _, table = _gemv_table(ctx.tree)
        for name, (k_multiple, line) in sorted(table.items()):
            spec = specs.get(name)
            if spec is None or k_multiple <= 0:
                continue
            block = spec.get("block_size")
            if isinstance(block, int) and block > 0 \
                    and k_multiple % block != 0:
                yield Finding(
                    rule=self.rule, path=ctx.rel, line=line,
                    message="'%s' k_multiple %d is not a multiple of its "
                            "quant block_size %d — the kernel's K grid "
                            "would split blocks" % (name, k_multiple, block),
                    hint="round k_multiple up to lcm(block_size, lane "
                         "tiling)",
                )
            sb = spec.get("superblock")
            if isinstance(sb, int) and sb > 0 and k_multiple % sb != 0:
                yield Finding(
                    rule=self.rule, path=ctx.rel, line=line,
                    message="'%s' k_multiple %d is not a multiple of its "
                            "superblock %d (k-quant scale hierarchy "
                            "would straddle tiles)" % (name, k_multiple, sb),
                    hint="use a k_multiple that is a multiple of the "
                         "superblock",
                )
            if spec.get("storage") == "packed_planes" \
                    and not spec.get("planes"):
                yield Finding(
                    rule=self.rule, path=ctx.rel, line=line,
                    message="'%s' uses packed_planes storage but declares "
                            "no planes tuple" % name,
                    hint="declare the per-plane bit widths in QTypeSpec",
                )

    def _check_storage_coverage(self, ctx: FileContext) -> Iterable[Finding]:
        project = flow.project_for(ctx)
        specs = _registry_specs(project)
        storages = {s.get("storage") for s in specs.values()}
        storages.discard("dense")  # dense never reaches DecodeSpec
        fn = None
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.FunctionDef) and node.name == "spec_for":
                fn = node
                break
        if fn is None:
            return
        covered, has_default = _storage_branches(fn)
        if has_default:
            return
        for storage in sorted(s for s in storages
                              if isinstance(s, str) and s not in covered):
            yield Finding(
                rule=self.rule, path=ctx.rel, line=fn.lineno,
                message="spec_for() has no branch for storage '%s' and "
                        "no default — decode dispatch would fall through"
                        % storage,
                hint="add an explicit branch or a default return",
            )


def _storage_branches(fn: ast.FunctionDef) -> Tuple[Set[str], bool]:
    """Storage string literals compared in *fn*, and whether the
    function has an unconditional (default) exit."""
    covered: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Compare):
            for comp in node.comparators:
                if isinstance(comp, ast.Constant) \
                        and isinstance(comp.value, str):
                    covered.add(comp.value)
    # Default exit: a top-level return/raise, or an if/elif chain whose
    # final `else:` exists (every storage falls somewhere).
    has_default = False
    for stmt in fn.body:
        if isinstance(stmt, (ast.Return, ast.Raise)):
            has_default = True
        elif isinstance(stmt, ast.If):
            tail = stmt
            while tail.orelse and len(tail.orelse) == 1 \
                    and isinstance(tail.orelse[0], ast.If):
                tail = tail.orelse[0]
            if tail.orelse:
                has_default = True
    return covered, has_default


#: VMEM-budget names in tiling.py whose values (and half-values) other
#: ops/ files must derive, not restate as literals.
_BUDGET_NAMES = ("VMEM_BUDGET", "LORA_VMEM_CAP", "_X_SLAB_BYTES")


class VmemLiteralDrift(Check):
    rule = "DSP004"
    description = (
        "MiB-scale literal in ops/ equal to a tiling.py VMEM budget "
        "constant (or half of one) — derive it, don't restate it"
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.rel.startswith("bigdl_tpu/ops/") \
                or ctx.rel == _TILING_REL:
            return
        project = flow.project_for(ctx)
        tiling = project.modules.get(_TILING_REL)
        if tiling is None:
            return
        env = flow.module_consts(tiling.tree)
        budget_values: Dict[int, str] = {}
        for name in _BUDGET_NAMES:
            v = env.get(name)
            if isinstance(v, int):
                budget_values.setdefault(v, name)
                budget_values.setdefault(v // 2, name + " // 2")
        if not budget_values:
            return
        for node, value in _toplevel_literal_ints(ctx.tree):
            if value < (1 << 20):
                continue
            name = budget_values.get(value)
            if name is None:
                continue
            yield Finding(
                rule=self.rule, path=ctx.rel, line=node.lineno,
                message="literal %d restates tiling.py's %s — when the "
                        "budget moves, this site silently diverges"
                        % (value, name),
                hint="import the constant from ops/pallas/tiling.py "
                     "(lazily, next to the kernel import) and derive it",
            )


def _toplevel_literal_ints(tree: ast.Module):
    """(node, value) for maximal pure-literal int expressions."""
    out = []

    def visit(node: ast.AST) -> None:
        try:
            value = flow.eval_const(node)
        except ValueError:
            for child in ast.iter_child_nodes(node):
                visit(child)
            return
        if isinstance(value, int) and not isinstance(value, bool):
            out.append((node, value))

    visit(tree)
    return out


class TilingBudgetInvariants(Check):
    rule = "DSP005"
    description = (
        "tiling.py budget invariants: slabs fit the VMEM budget, the "
        "LoRA cap leaves headroom, flash blocks are lane-aligned"
    )

    #: (required names, predicate over env, message, hint)
    INVARIANTS = (
        (("LORA_VMEM_CAP", "VMEM_BUDGET"),
         lambda e: e["LORA_VMEM_CAP"] <= e["VMEM_BUDGET"] // 2,
         "LORA_VMEM_CAP exceeds half the VMEM budget — the fused LoRA "
         "epilogue would starve the base-kernel slabs",
         "keep the LoRA operand cap <= VMEM_BUDGET // 2"),
        (("_X_SLAB_BYTES", "VMEM_BUDGET"),
         lambda e: e["_X_SLAB_BYTES"] < e["VMEM_BUDGET"],
         "_X_SLAB_BYTES does not fit inside VMEM_BUDGET",
         "shrink the activation slab or raise the budget"),
        (("FLASH_BLOCK_Q", "MOSAIC_LANES"),
         lambda e: e["FLASH_BLOCK_Q"] % e["MOSAIC_LANES"] == 0,
         "FLASH_BLOCK_Q is not a multiple of MOSAIC_LANES",
         "flash attention block shapes must be lane-aligned"),
        (("FLASH_BLOCK_K", "MOSAIC_LANES"),
         lambda e: e["FLASH_BLOCK_K"] % e["MOSAIC_LANES"] == 0,
         "FLASH_BLOCK_K is not a multiple of MOSAIC_LANES",
         "flash attention block shapes must be lane-aligned"),
        (("VMEM_BUDGET",),
         lambda e: e["VMEM_BUDGET"] <= 16 * 1024 * 1024,
         "VMEM_BUDGET exceeds the 16 MiB per-core scoped-vmem ceiling",
         "the budget must leave room for Mosaic's own scratch"),
        (("_DX_SLAB_BYTES", "VMEM_BUDGET"),
         lambda e: e["_DX_SLAB_BYTES"] < e["VMEM_BUDGET"],
         "_DX_SLAB_BYTES does not fit inside VMEM_BUDGET — the dx "
         "accumulator slab would leave no room for the chunk loop",
         "shrink the backward accumulator slab or raise the budget"),
        (("WORDS_VMEM_BYTES", "VMEM_LIMIT_BYTES"),
         lambda e: e["WORDS_VMEM_BYTES"] <= e["VMEM_LIMIT_BYTES"] * 3 // 4,
         "WORDS_VMEM_BYTES leaves the word path's chunk loop under a "
         "quarter of the scoped-VMEM limit — its 6 to 8 MiB of "
         "temporaries and the x / output blocks would not fit",
         "keep WORDS_VMEM_BYTES <= 3/4 of VMEM_LIMIT_BYTES"),
        (("WORD_BLOCK_O", "WORD_ROWS", "MOSAIC_LANES"),
         lambda e: e["WORD_BLOCK_O"] % (e["WORD_ROWS"] * e["MOSAIC_LANES"])
         == 0,
         "WORD_BLOCK_O / WORD_ROWS is not a multiple of MOSAIC_LANES — "
         "the word tile would not transpose in whole 128-lane pieces",
         "the word path's O tile must be WORD_ROWS x 128 x n rows"),
        (("DX_ACC_BPE",),
         lambda e: e["DX_ACC_BPE"] >= 6,
         "DX_ACC_BPE under-prices the dx row tile (f32 accumulator + "
         "bf16 output block is 6 B/element minimum)",
         "keep DX_ACC_BPE >= 6 so pick_block_m_dx cannot overcommit "
         "VMEM"),
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.rel != _TILING_REL:
            return
        env = flow.module_consts(ctx.tree)
        lines = {name: line for name, line in _const_lines(ctx.tree)}
        for names, pred, message, hint in self.INVARIANTS:
            if not all(isinstance(env.get(n), int) for n in names):
                continue
            if pred(env):
                continue
            yield Finding(
                rule=self.rule, path=ctx.rel,
                line=lines.get(names[0], 1),
                message=message, hint=hint,
            )


def _const_lines(tree: ast.Module):
    for stmt in tree.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)):
            yield stmt.targets[0].id, stmt.lineno


#: the attention kernel files whose K/V loads must decode through the
#: one shared body in qdecode.decode_kv (the fp8-KV epilogues)
_ATTN_EPILOGUE_RELS = (
    "bigdl_tpu/ops/pallas/flash_attention.py",
    "bigdl_tpu/ops/pallas/paged_attention.py",
    "bigdl_tpu/ops/pallas/flash_backward.py",
)


class AttentionDecoderUnification(Check):
    rule = "DSP006"
    description = (
        "attention epilogues must decode K/V tiles through "
        "qdecode.decode_kv — an inlined astype/bit-decode is the "
        "three-copies-of-the-decoder drift this family exists to stop"
    )

    def run(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.rel not in _ATTN_EPILOGUE_RELS:
            return
        uses_decode_kv = False
        touches_kv = False
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Name) and node.id in ("k_ref", "v_ref"):
                touches_kv = True
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            callee = (f.attr if isinstance(f, ast.Attribute)
                      else f.id if isinstance(f, ast.Name) else None)
            if callee == "decode_kv":
                uses_decode_kv = True
            elif callee == "decode_values":
                yield Finding(
                    rule=self.rule, path=ctx.rel, line=node.lineno,
                    message="decode_values called directly — the bit "
                            "decoder's body belongs to qdecode; the "
                            "attention epilogues call the decode_kv "
                            "wrapper so fp8-KV and the GEMM weights "
                            "cannot drift apart",
                    hint="use qdecode.decode_kv",
                )
            elif (callee == "astype"
                    and isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Subscript)
                    and isinstance(f.value.value, ast.Name)
                    and f.value.value.id in ("k_ref", "v_ref")):
                yield Finding(
                    rule=self.rule, path=ctx.rel, line=node.lineno,
                    message="K/V tile decoded inline (%s[...].astype) — "
                            "this is the duplicated-decoder pattern "
                            "decode_kv replaced" % f.value.value.id,
                    hint="load through qdecode.decode_kv (scale=None "
                         "for the bf16 passthrough arm)",
                )
        if touches_kv and not uses_decode_kv:
            yield Finding(
                rule=self.rule, path=ctx.rel, line=1,
                message="file reads k_ref/v_ref but never calls "
                        "qdecode.decode_kv — the shared-decoder "
                        "unification has regressed",
                hint="route every K/V tile load through "
                     "qdecode.decode_kv",
            )


INTERPROC_CHECKS = (
    PageLeakOnExit, PageLeakOnRaise,
    LockOrderCycle, BlockingUnderHotLock,
    DispatchCoverage, KernelExportConsistency, DispatchGeometry,
    VmemLiteralDrift, TilingBudgetInvariants,
    AttentionDecoderUnification,
)
