"""AST checkers over ``harp_tpu/`` — the lexical half of jaxlint.

Codes:
  JL101 collective-divergence  collective call inside a branch conditioned on
                               rank / process_index / worker id — one member
                               enters the collective, the rest don't: the
                               gang deadlocks (DrJAX arXiv:2403.07128 makes
                               the static-checkability argument).
  JL102 axis-name              collective ``axis_name`` literal that no mesh /
                               shard_map / canonical axis constant declares —
                               an unbound axis fails only at trace time, a
                               *misbound* one (typo'd "worker") fails at 3am
                               on the gang.
  JL103 retrace-hazard         jit/spmd wrappers rebuilt per call (immediately
                               invoked, or constructed inside a loop without a
                               cache guard), mutable default args on jitted
                               functions, jitted closures over ``global``
                               state — each retraces or shares state silently.
  JL104 host-sync-hot-loop     ``.item()`` / ``block_until_ready`` /
                               ``np.asarray`` inside a Python loop in a
                               fit/train path — a device→host sync per
                               iteration serializes the dispatch pipeline.
  JL105 broad-except           ``except Exception``/bare except without a
                               justified allowlist entry — swallows the
                               KeyboardInterrupt-adjacent world and hides
                               gang member death behind a warning.
  JL106 scatter                ``.at[...].add/.set`` in the device hot trees
                               (folded from r6 tools/lint_scatter.py — XLA
                               lowers these to the serializing TPU scatter
                               unit, measured 8.8x slower than the
                               one-hot-GEMM form; route via ops/lane_pack).
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Set

from tools.jaxlint.core import Finding, FuncStackVisitor

# --------------------------------------------------------------------------
# collective-call recognition (shared by JL101/JL102)
# --------------------------------------------------------------------------

# Distinctive collective names — unambiguous from any call shape.
_COLLECTIVE_ANY = {
    "psum", "psum_like", "psum_scatter", "pmean", "pmax", "pmin",
    "all_gather", "ppermute", "pshuffle", "all_to_all", "reduce_scatter",
    "allreduce", "allgather", "rotate_map", "send_recv",
    "broadcast_one_to_all", "process_allgather", "sync_global_devices",
    "rotate_scan", "pipelined_rotation",
}
# Generic words that are collectives only when called on a known module.
_COLLECTIVE_SCOPED = {"broadcast", "reduce", "gather", "push", "pull",
                      "rotate", "regroup", "barrier"}
_COLLECTIVE_MODULES = {"lax_ops", "table_ops", "rotation", "multihost_utils"}


def _call_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def collective_call_name(node: ast.Call) -> Optional[str]:
    """Name of the collective this call performs, or None."""
    name = _call_name(node.func)
    if name is None:
        return None
    if name in _COLLECTIVE_ANY:
        return name
    if name in _COLLECTIVE_SCOPED and isinstance(node.func, ast.Attribute):
        base = node.func.value
        if isinstance(base, ast.Name) and base.id in _COLLECTIVE_MODULES:
            return name
        if isinstance(base, ast.Attribute) and base.attr in _COLLECTIVE_MODULES:
            return name
    return None


# --------------------------------------------------------------------------
# JL101 collective-divergence
# --------------------------------------------------------------------------

_RANK_CALLS = {"process_index", "worker_id", "axis_index", "getSelfID"}
_RANK_ATTRS = {"process_index", "master_id", "is_master"}
_RANK_NAMES = {"rank", "wid", "worker_id", "my_rank", "self_id", "proc_rank"}


def _mentions_rank(test: ast.AST) -> bool:
    for node in ast.walk(test):
        if isinstance(node, ast.Call) and _call_name(node.func) in _RANK_CALLS:
            return True
        if isinstance(node, ast.Attribute) and node.attr in _RANK_ATTRS:
            return True
        if isinstance(node, ast.Name) and node.id in _RANK_NAMES:
            return True
    return False


def check_collective_divergence(mod: ast.AST, rel: str, src: str
                                ) -> List[Finding]:
    class V(FuncStackVisitor):
        def __init__(self, rel_path):
            super().__init__(rel_path)
            self.rank_branch: List[int] = []   # lineno of rank-If being walked

        def _walk_branch(self, stmts):
            for stmt in stmts:
                self.visit(stmt)

        def visit_If(self, node):
            self.visit(node.test)
            if _mentions_rank(node.test):
                self.rank_branch.append(node.lineno)
                self._walk_branch(node.body)
                self._walk_branch(node.orelse)
                self.rank_branch.pop()
            else:
                self._walk_branch(node.body)
                self._walk_branch(node.orelse)

        def visit_IfExp(self, node):
            self.visit(node.test)
            if _mentions_rank(node.test):
                self.rank_branch.append(node.lineno)
                self.visit(node.body)
                self.visit(node.orelse)
                self.rank_branch.pop()
            else:
                self.visit(node.body)
                self.visit(node.orelse)

        def visit_Call(self, node):
            if self.rank_branch:
                cname = collective_call_name(node)
                if cname is not None:
                    self.emit(
                        "JL101", "collective-divergence", node,
                        f"collective {cname}() inside a rank-conditional "
                        f"branch (if at line {self.rank_branch[-1]}) — only "
                        f"some gang members reach it; the rest wait forever. "
                        f"Hoist the collective out of the branch and mask "
                        f"its CONTRIBUTION instead (lax_ops.broadcast/"
                        f"reduce show the masked-psum idiom)")
            self.generic_visit(node)

    v = V(rel)
    v.visit(mod)
    return v.findings


# --------------------------------------------------------------------------
# JL102 axis-name
# --------------------------------------------------------------------------

# Canonical axes declared by harp_tpu.parallel.mesh (WORKERS/MODEL). Parsed
# from that module at scan time by gather_canonical_axes(); this fallback
# keeps fixture-level checking working standalone.
_FALLBACK_AXES = {"workers", "model"}

_AXIS_DECL_CALLS = {"Mesh", "make_mesh", "shard_map", "P", "PartitionSpec",
                    "AxisName"}


def gather_canonical_axes(repo_root: str) -> Set[str]:
    """Axis-name constants declared module-level in parallel/mesh.py."""
    path = os.path.join(repo_root, "harp_tpu", "parallel", "mesh.py")
    axes: Set[str] = set()
    try:
        with open(path, encoding="utf-8") as f:
            mod = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return set(_FALLBACK_AXES)
    for stmt in mod.body:
        if (isinstance(stmt, ast.Assign)
                and isinstance(stmt.value, ast.Constant)
                and isinstance(stmt.value.value, str)):
            for tgt in stmt.targets:
                if isinstance(tgt, ast.Name) and tgt.id.isupper():
                    axes.add(stmt.value.value)
    return axes or set(_FALLBACK_AXES)


def _module_declared_axes(mod: ast.AST) -> Set[str]:
    """String literals this module itself binds as axes: ALL_CAPS string
    constants, and literals inside Mesh/shard_map/P(...) declarations."""
    declared: Set[str] = set()
    for node in ast.walk(mod):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
                and any(isinstance(t, ast.Name) and t.id.isupper()
                        for t in node.targets)):
            declared.add(node.value.value)
        if (isinstance(node, ast.Call)
                and _call_name(node.func) in _AXIS_DECL_CALLS):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Constant) and isinstance(sub.value,
                                                                str):
                    declared.add(sub.value)
    return declared


# collectives taking axis_name positionally right after the operand
_AXIS_POS1 = {"psum", "pmean", "pmax", "pmin", "all_gather", "ppermute",
              "pshuffle", "all_to_all", "psum_scatter", "axis_index",
              "psum_like"}


def make_axis_name_checker(canonical_axes: Optional[Set[str]] = None):
    axes_base = set(canonical_axes) if canonical_axes else set(_FALLBACK_AXES)

    def check_axis_name(mod: ast.AST, rel: str, src: str) -> List[Finding]:
        known = axes_base | _module_declared_axes(mod)

        class V(FuncStackVisitor):
            def visit_Call(self, node):
                cname = collective_call_name(node)
                if cname is None and _call_name(node.func) != "axis_index":
                    self.generic_visit(node)
                    return
                lit = None
                for kw in node.keywords:
                    if kw.arg == "axis_name" and isinstance(
                            kw.value, ast.Constant) and isinstance(
                            kw.value.value, str):
                        lit = kw.value.value
                name = cname or "axis_index"
                if (lit is None and name in _AXIS_POS1
                        and len(node.args) > (0 if name == "axis_index"
                                              else 1)):
                    pos = node.args[0 if name == "axis_index" else 1]
                    if isinstance(pos, ast.Constant) and isinstance(
                            pos.value, str):
                        lit = pos.value
                if lit is not None and lit not in known:
                    self.emit(
                        "JL102", "axis-name", node,
                        f"collective {name}() names axis {lit!r}, which no "
                        f"enclosing mesh/shard_map declaration or canonical "
                        f"axis constant ({sorted(known)}) binds — use "
                        f"mesh.WORKERS/lax_ops' axis_name default, or "
                        f"declare the axis in this module")
                self.generic_visit(node)

        v = V(rel)
        v.visit(mod)
        return v.findings

    return check_axis_name


check_axis_name = make_axis_name_checker()   # standalone/fixture default


# --------------------------------------------------------------------------
# JL103 retrace-hazard
# --------------------------------------------------------------------------

def _is_jit_like(node: ast.Call) -> Optional[str]:
    """'jit' / 'spmd' / 'pjit' if this call constructs a compiled wrapper."""
    name = _call_name(node.func)
    if name in {"jit", "pjit", "spmd"}:
        return name
    return None


def _decorated_jit(fn: ast.AST) -> bool:
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call):
            name = _call_name(dec.func)
            if name in {"jit", "pjit"}:
                return True
            if name == "partial" and dec.args and _call_name(
                    dec.args[0]) in {"jit", "pjit"}:
                return True
        elif _call_name(dec) in {"jit", "pjit"}:
            return True
    return False


_MUTABLE_DEFAULT = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                    ast.SetComp)


def check_retrace_hazard(mod: ast.AST, rel: str, src: str) -> List[Finding]:
    class V(FuncStackVisitor):
        def __init__(self, rel_path):
            super().__init__(rel_path)
            self.loop_depth = 0
            self.cached_nodes: set = set()   # id() of jit calls whose
            #   result is stored into a container (cache[key] = jit(...))

        def enter_function(self, node):
            if not _decorated_jit(node):
                return
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                if isinstance(d, _MUTABLE_DEFAULT) or (
                        isinstance(d, ast.Call) and _call_name(d.func)
                        in {"list", "dict", "set"}):
                    self.emit(
                        "JL103", "retrace-hazard", d,
                        f"jitted {node.name}() has a mutable default "
                        f"argument — defaults are captured at trace time "
                        f"and shared across calls; pass it explicitly or "
                        f"mark it static", func=node.name)
            for stmt in ast.walk(node):
                if isinstance(stmt, ast.Global):
                    self.emit(
                        "JL103", "retrace-hazard", stmt,
                        f"jitted {node.name}() closes over `global` state — "
                        f"the traced program bakes in the value at trace "
                        f"time and never sees updates (silent staleness, "
                        f"not a retrace)", func=node.name)

        def _visit_loop(self, node):
            self.loop_depth += 1
            self.generic_visit(node)
            self.loop_depth -= 1

        visit_For = _visit_loop
        visit_While = _visit_loop

        def visit_Assign(self, node):
            # the sanctioned in-loop compile idiom stores the wrapper in a
            # container keyed on shape/config (`self._fns[key] = jit(...)`)
            # — the subscript target IS the cache, so the wrapper survives
            # the iteration. A plain-name bind (`f = jit(...)`) in a loop
            # does not, whatever `if ... not in ...` guards surround it.
            if (isinstance(node.value, ast.Call) and _is_jit_like(node.value)
                    and any(isinstance(t, ast.Subscript)
                            for t in node.targets)):
                self.cached_nodes.add(id(node.value))
            self.generic_visit(node)

        def visit_Call(self, node):
            inner = node.func
            if isinstance(inner, ast.Call) and _is_jit_like(inner):
                self.emit(
                    "JL103", "retrace-hazard", node,
                    f"{_is_jit_like(inner)}(...) built and invoked in one "
                    f"expression — the wrapper (and its trace cache) is "
                    f"discarded after the call, so every invocation "
                    f"retraces; bind the compiled callable once (the "
                    f"`self._fns[key]` idiom) or use session.run for "
                    f"documented one-shots")
            elif (_is_jit_like(node) and self.loop_depth > 0
                    and id(node) not in self.cached_nodes):
                self.emit(
                    "JL103", "retrace-hazard", node,
                    f"{_is_jit_like(node)}(...) constructed inside a loop "
                    f"and not stored into a cache container — a fresh "
                    f"wrapper per iteration retraces every time; hoist it "
                    f"or bind it `cache[key] = ...` keyed on the "
                    f"shape/config")
            self.generic_visit(node)

    v = V(rel)
    v.visit(mod)
    return v.findings


# --------------------------------------------------------------------------
# JL104 host-sync-hot-loop
# --------------------------------------------------------------------------

_HOT_FUNC_PREFIXES = ("fit", "train")


def check_host_sync(mod: ast.AST, rel: str, src: str) -> List[Finding]:
    class V(FuncStackVisitor):
        def __init__(self, rel_path):
            super().__init__(rel_path)
            self.loop_depth = 0

        def _visit_loop(self, node):
            self.loop_depth += 1
            self.generic_visit(node)
            self.loop_depth -= 1

        visit_For = _visit_loop
        visit_While = _visit_loop

        def _in_hot_fit(self) -> bool:
            return (self.loop_depth > 0
                    and any(f.startswith(_HOT_FUNC_PREFIXES)
                            for f in self.func_stack))

        def visit_Call(self, node):
            if self._in_hot_fit():
                f = node.func
                sync = None
                if isinstance(f, ast.Attribute):
                    if f.attr == "item" and not node.args:
                        sync = ".item()"
                    elif f.attr == "block_until_ready":
                        sync = "block_until_ready()"
                    elif (f.attr == "asarray"
                          and isinstance(f.value, ast.Name)
                          and f.value.id in {"np", "numpy", "onp"}):
                        sync = "np.asarray()"
                if sync:
                    self.emit(
                        "JL104", "host-sync-hot-loop", node,
                        f"{sync} inside a Python loop in "
                        f"{'/'.join(self.func_stack)} — a device→host sync "
                        f"per iteration stalls the dispatch pipeline; keep "
                        f"device values on device until after the loop")
            self.generic_visit(node)

    v = V(rel)
    v.visit(mod)
    return v.findings


# --------------------------------------------------------------------------
# JL105 broad-except
# --------------------------------------------------------------------------

def check_broad_except(mod: ast.AST, rel: str, src: str) -> List[Finding]:
    class V(FuncStackVisitor):
        def visit_ExceptHandler(self, node):
            broad = None
            t = node.type
            if t is None:
                broad = "bare except:"
            else:
                names = [n for n in (t.elts if isinstance(t, ast.Tuple)
                                     else [t])]
                for n in names:
                    nm = n.id if isinstance(n, ast.Name) else (
                        n.attr if isinstance(n, ast.Attribute) else None)
                    if nm in {"Exception", "BaseException"}:
                        broad = f"except {nm}"
            if broad:
                self.emit(
                    "JL105", "broad-except", node,
                    f"{broad} — narrow it to the failures this site can "
                    f"actually handle (ImportError for optional deps, "
                    f"TypeError for hashability probes, ...), or allowlist "
                    f"it with the reason the blast radius must stay wide")
            self.generic_visit(node)

    v = V(rel)
    v.visit(mod)
    return v.findings


# --------------------------------------------------------------------------
# JL106 scatter (folded from tools/lint_scatter.py, r6)
# --------------------------------------------------------------------------

_SCATTER_METHODS = {"add", "set", "mul", "divide", "min", "max", "power",
                    "apply"}
HOT_TREES = ("harp_tpu/models/", "harp_tpu/ops/")


def is_at_indexed_update(node: ast.Call) -> Optional[str]:
    """Matches ``<expr>.at[<idx>].<method>(...)``; returns the method name."""
    f = node.func
    if not (isinstance(f, ast.Attribute) and f.attr in _SCATTER_METHODS):
        return None
    sub = f.value
    if not isinstance(sub, ast.Subscript):
        return None
    base = sub.value
    if isinstance(base, ast.Attribute) and base.attr == "at":
        return f.attr
    return None


def check_scatter(mod: ast.AST, rel: str, src: str) -> List[Finding]:
    if not rel.startswith(HOT_TREES):
        return []

    class V(FuncStackVisitor):
        def visit_Call(self, node):
            m = is_at_indexed_update(node)
            if m is not None:
                self.emit(
                    "JL106", "scatter", node,
                    f".at[...].{m} — XLA lowers indexed updates to the "
                    f"serializing TPU scatter unit (8.8x slower than the "
                    f"one-hot-GEMM form, PERF.md r4/r5); route through "
                    f"ops/lane_pack (gemm_scatter/densify_rows) or "
                    f"allowlist with a reason")
            self.generic_visit(node)

    v = V(rel)
    v.visit(mod)
    return v.findings


# Registry (axis-name is instantiated per-repo-root by the runner so it can
# parse the canonical axes; this module-level list is the fixture default).
AST_CHECKERS = [
    check_collective_divergence,
    check_axis_name,
    check_retrace_hazard,
    check_host_sync,
    check_broad_except,
    check_scatter,
]


def ast_checkers_for_repo(repo_root: str):
    # the JL3xx concurrency engine rides the same registry: one walk of the
    # tree serves the lexical checkers and the thread-domain inference
    from tools.jaxlint.checkers_threads import check_concurrency

    return [
        check_collective_divergence,
        make_axis_name_checker(gather_canonical_axes(repo_root)),
        check_retrace_hazard,
        check_host_sync,
        check_broad_except,
        check_scatter,
        check_concurrency,
    ]
