"""jaxpr checkers — the traced half of jaxlint.

Codes:
  JL201 collective-budget   traced collective counts/kinds for a model step
                            program drifted from the committed manifest
                            ``tools/collective_budget.json`` (regenerate
                            deliberately with ``--update-budget`` — the diff
                            IS the review surface, exactly like check_claims
                            pins bench numbers).
  JL202 dtype-policy        a traced program binds a float64/complex128
                            value (tier-1 runs x64-disabled; an f64 that
                            appears under x64 would double every collective
                            payload), or runs a bf16×bf16 dot_general that
                            ACCUMULATES in bf16 — the repo-wide policy
                            (ops/lane_pack's exactness contract) is bf16
                            operands with f32 accumulation
                            (preferred_element_type), never bf16 sums.
  JL203 byte-budget         traced collective OPERAND BYTES per step drifted
                            from the manifest's ``bytes_per_step`` /
                            ``bytes_by_kind``. Counts alone miss comm-VOLUME
                            regressions: the same one ppermute per hop can
                            silently grow 4x when a quantized path falls
                            back to f32 (the dtype changes, the count does
                            not) or when an operand shape balloons. Bytes
                            are summed over the collective equations'
                            operand avals at tier-1 shapes — per STEP, same
                            scan-body-counts-once convention as JL201.

Everything here uses ``jax.make_jaxpr`` only: programs are traced, never
executed, so the whole budget check runs in tier-1 on the virtual CPU mesh.
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Dict, List, Optional, Tuple

from tools.jaxlint.core import Finding

BUDGET_FILE = os.path.join("tools", "collective_budget.json")

# jaxpr primitive names that move bytes across the worker axis. axis_index
# is deliberately excluded: it reads the device grid, it does not
# communicate, so it is not part of the budget contract.
COLLECTIVE_PRIMS = {
    "psum", "pmin", "pmax", "all_gather", "all_to_all", "reduce_scatter",
    "psum_scatter", "ppermute", "pshuffle", "pbroadcast", "pgather",
}

# Fused ring-DMA hops (r10): on TPU these are in-kernel
# `make_async_remote_copy`s with NO collective primitive in the jaxpr; on
# the CPU tracing mesh the engine lowers them through a jit tagged with
# this name (must equal harp_tpu.ops.ring_dma.FUSED_HOP_NAME — tier-1
# asserts the two constants agree). The walker books a tagged call's
# operand bytes as the synthetic kind "fused_dma" and does NOT recurse into
# it — the inner ppermute is the transport the tag REPLACES, so counting
# both would double-charge, and counting only the ppermute would let a
# silent revert to a bare permute keep the same byte row. The manifest pins
# the kind per target (plus the explicit `fused_dma_bytes_per_step` field),
# so a fused schedule quietly degrading to ppermute moves bytes BETWEEN
# kinds and fails JL201/JL203.
FUSED_HOP_PREFIX = "ring_dma_fused_hop"


def _subjaxprs(eqn):
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if hasattr(item, "eqns"):
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr


def _aval_bytes(var) -> int:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for s in shape:
        n *= int(s)
    return n * dtype.itemsize


def _walk(jaxpr, counts: Dict[str, int], dtype_bad: List[str],
          nbytes: Dict[str, int]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if (name == "jit"     # jax 0.9.0's name for the jit primitive
                and str(eqn.params.get("name", "")).startswith(
                    FUSED_HOP_PREFIX)):
            counts["fused_dma"] = counts.get("fused_dma", 0) + 1
            nbytes["fused_dma"] = nbytes.get("fused_dma", 0) + sum(
                _aval_bytes(v) for v in eqn.invars)
            continue     # no recursion: the tag REPLACES the inner permute
        if name in COLLECTIVE_PRIMS:
            counts[name] = counts.get(name, 0) + 1
            # operand bytes = what the collective puts on the wire at tier-1
            # shapes (per-worker, inside shard_map). Summed over invars so a
            # multi-operand psum charges every payload.
            nbytes[name] = nbytes.get(name, 0) + sum(
                _aval_bytes(v) for v in eqn.invars)
        # dtype policy: no f64/c128 anywhere; bf16 dots must accumulate f32
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in ("float64", "complex128"):
                dtype_bad.append(f"{name} binds a {dt} value")
        if name == "dot_general":
            in_dts = [str(getattr(getattr(v, "aval", None), "dtype", ""))
                      for v in eqn.invars]
            out_dts = [str(getattr(getattr(v, "aval", None), "dtype", ""))
                       for v in eqn.outvars]
            if (in_dts and all(d == "bfloat16" for d in in_dts)
                    and all(d == "bfloat16" for d in out_dts)):
                dtype_bad.append(
                    "bf16 x bf16 dot_general accumulating in bf16 — pass "
                    "preferred_element_type=jnp.float32 (lane_pack "
                    "exactness contract: bf16 operands, f32 sums)")
        for sub in _subjaxprs(eqn):
            _walk(sub, counts, dtype_bad, nbytes)


# One make_jaxpr per (registry, target) per process: the collective
# engines, the gang engine, AND the memory engine (checkers_memory, ISSUE
# 19) all analyze the same traced program, so the trace itself is cached —
# the memory pass costs no extra tracing when it follows a budget pass.
# Values are (ClosedJaxpr, placed args, workers-axis link class at trace
# time); tier-1 shapes keep the held arrays tiny.
_TRACE_CACHE: Dict[Tuple[str, str], tuple] = {}


def traced_target(name: str, gang: bool = False) -> tuple:
    """The cached ``(closed_jaxpr, args, link_class)`` of one registry
    target, tracing it on first use (gang targets trace under the DCN
    hint — see :func:`trace_gang_target`)."""
    key = ("gang" if gang else "single", name)
    if key not in _TRACE_CACHE:
        import jax

        from tools.jaxlint import trace_targets

        if gang:
            from harp_tpu.parallel import mesh as mesh_lib

            with _gang_link_hint("dcn"):
                fn, args = trace_targets.GANG_TARGETS[name]()
                closed = jax.make_jaxpr(fn)(*args)
                link = mesh_lib.axis_link_class(mesh_lib.WORKERS)
        else:
            fn, args = trace_targets.TARGETS[name]()
            closed = jax.make_jaxpr(fn)(*args)
            link = None
        _TRACE_CACHE[key] = (closed, args, link)
    return _TRACE_CACHE[key]


def trace_target(name: str) -> Tuple[Dict[str, int], List[str],
                                     Dict[str, int]]:
    """Trace one registry target; returns (collective counts, dtype issues,
    collective operand bytes by kind).

    Counts/bytes are STATIC occurrences in the traced program. The hot loop
    of every target is a ``lax.scan`` over iterations, so a collective in
    the scan body counts once — i.e. the manifest records collectives **per
    step**, not per run (iteration counts are config, not contract).
    """
    closed, _args, _link = traced_target(name)
    counts: Dict[str, int] = {}
    dtype_bad: List[str] = []
    nbytes: Dict[str, int] = {}
    _walk(closed.jaxpr, counts, dtype_bad, nbytes)
    return counts, dtype_bad, nbytes


def trace_all() -> Dict[str, Tuple[Dict[str, int], List[str],
                                   Dict[str, int]]]:
    from tools.jaxlint import trace_targets

    trace_targets.ensure_cpu_mesh()
    return {name: trace_target(name)
            for name in sorted(trace_targets.TARGETS)}


# --------------------------------------------------------------------------
# gang mode (ISSUE 13): per-process shard shapes + DCN/ICI byte split
# --------------------------------------------------------------------------
#
# Wire model for the link split (EQuARX-style accounting, arXiv:2506.17615,
# applied to the DCN/ICI boundary that DrJAX-style multi-mesh programs make
# first-class, arXiv:2403.07128). The gang lays the workers axis out
# contiguously per process (make_mesh over distributed.initialize's device
# order — mp_smoke's layout), so on the W-worker ring exactly P of the W
# hop edges cross a process (= host = DCN) boundary:
#
# * ring-scheduled kinds (ppermute and the pshuffle permutation, the fused
#   ring-DMA hops, and the reduction/gather family XLA lowers to ring
#   schedules on a 1-D axis): DCN share = P / W of the operand bytes.
# * all_to_all: every worker exchanges with W-1 peers, of which W - D sit
#   on other hosts: DCN share = (W - D) / (W - 1).
#
# Shares are integer floor (DCN rounds down, ICI takes the remainder), so
# the split is deterministic and sums exactly to bytes_by_kind. The split
# only applies when the workers axis is hinted "dcn"
# (mesh.set_axis_link_class — gang launchers do this at bootstrap; a
# single-pod gang's hint stays "ici" and every byte books as ICI).

_ALL_TO_ALL_KINDS = {"all_to_all"}     # pshuffle is a permutation — ring
#                                        model, like ppermute


def split_bytes_by_link(nbytes: Dict[str, int], *, world: int,
                        processes: int, devices_per_process: int,
                        link_class: str) -> Dict[str, Dict[str, int]]:
    """``bytes_by_kind`` split into ``{"dcn": {...}, "ici": {...}}``."""
    dcn: Dict[str, int] = {}
    ici: Dict[str, int] = {}
    for kind, b in sorted(nbytes.items()):
        if link_class != "dcn" or processes <= 1 or world <= 1:
            num, den = 0, 1
        elif kind in _ALL_TO_ALL_KINDS:
            num, den = world - devices_per_process, world - 1
        else:
            num, den = processes, world
        d = b * num // den
        dcn[kind] = d
        ici[kind] = b - d
    return {"dcn": dcn, "ici": ici}


def per_process_shard_shapes(args, devices_per_process: int) -> List[list]:
    """The per-PROCESS block shape of every traced program input.

    A replicated dim keeps its global extent; a dim sharded over the
    workers axis scales the per-device shard by the process's local device
    count. This is the layout each host actually materializes — the
    resharding contract the fleet item moves against (arXiv:2112.01075)."""
    import jax

    shapes: List[list] = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            continue
        shape = tuple(int(s) for s in shape)
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            shapes.append(list(shape))        # host array: fully replicated
            continue
        try:
            shard = tuple(int(s) for s in sharding.shard_shape(shape))
        except (TypeError, ValueError):
            shapes.append(list(shape))
            continue
        shapes.append([g if s == g else min(g, s * devices_per_process)
                       for g, s in zip(shape, shard)])
    return shapes


@contextlib.contextmanager
def _gang_link_hint(link_class: str):
    """Hint the workers axis for the duration of one gang trace, restoring
    the ambient hint after (the hint is process-global mesh state)."""
    from harp_tpu.parallel import mesh as mesh_lib

    prev = mesh_lib.axis_link_class(mesh_lib.WORKERS)
    mesh_lib.set_axis_link_class(mesh_lib.WORKERS, link_class)
    try:
        yield
    finally:
        mesh_lib.set_axis_link_class(mesh_lib.WORKERS, prev)


def trace_gang_target(name: str) -> dict:
    """Trace one gang-mode target under the DCN hint; returns the full
    manifest-row dict (counts, dtype issues, bytes, shard shapes, link
    split).

    The DCN hint is live DURING tracing, so link-aware code paths (the
    rotation pipeline's DCN chunking) trace their actual cross-pod
    program — the gang row pins the program a real 2-host gang runs, not
    the single-pod one retitled.
    """
    from tools.jaxlint import trace_targets

    P = trace_targets.GANG_PROCESSES
    D = trace_targets.GANG_DEVICES_PER_PROCESS
    closed, args, link = traced_target(name, gang=True)
    counts: Dict[str, int] = {}
    dtype_bad: List[str] = []
    nbytes: Dict[str, int] = {}
    _walk(closed.jaxpr, counts, dtype_bad, nbytes)
    by_link = split_bytes_by_link(
        nbytes, world=trace_targets.NUM_WORKERS, processes=P,
        devices_per_process=D, link_class=link)
    shard_shapes = per_process_shard_shapes(args, D)
    return {
        "processes": P,
        "devices_per_process": D,
        "collectives": dict(sorted(counts.items())),
        "per_process_shard_shapes": shard_shapes,
        "bytes_per_step": sum(nbytes.values()),
        "bytes_by_kind": dict(sorted(nbytes.items())),
        "bytes_by_link": by_link,
        "dcn_bytes_per_step": sum(by_link["dcn"].values()),
        "_dtype_bad": dtype_bad,     # stripped before the manifest write
    }


def trace_gang_all() -> Dict[str, dict]:
    from tools.jaxlint import trace_targets

    trace_targets.ensure_cpu_mesh()
    return {name: trace_gang_target(name)
            for name in sorted(trace_targets.GANG_TARGETS)}


def load_budget(repo_root: str) -> Optional[dict]:
    path = os.path.join(repo_root, BUDGET_FILE)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_budget(repo_root: str, traced, gang=None, memory=None,
                 hlo=None) -> str:
    """Rewrite the manifest from ``traced`` (and ``gang``, the gang-mode
    rows from :func:`trace_gang_all`; ``memory``, the static memory rows
    from ``checkers_memory.trace_memory_all``; ``hlo``, the compiled-HLO
    section from ``checkers_hlo.build_hlo_section``. None carries the
    committed rows of that section forward unchanged so a single-engine
    regenerate can't silently drop another engine's contract)."""
    import jax

    if gang is None:
        existing = load_budget(repo_root) or {}
        gang_rows = existing.get("gang_targets", {})
    else:
        gang_rows = {name: {k: v for k, v in row.items()
                            if not k.startswith("_")}
                     for name, row in sorted(gang.items())}
    if memory is None:
        existing = load_budget(repo_root) or {}
        memory_rows = existing.get("memory", {})
    else:
        memory_rows = {name: dict(row)
                       for name, row in sorted(memory.items())}
    if hlo is None:
        existing = load_budget(repo_root) or {}
        hlo_section = existing.get("hlo", {})
    else:
        hlo_section = dict(hlo)
    path = os.path.join(repo_root, BUDGET_FILE)
    doc = {
        "_contract": (
            "Collectives-per-step manifest: static collective-primitive "
            "counts AND operand bytes in each model's traced step program "
            "at tier-1 shapes (tools/jaxlint/trace_targets.py). Tier-1 "
            "fails on ANY drift — an extra psum per step is a perf "
            "regression, a changed kind is a changed comm algorithm, and "
            "changed bytes at the same counts is a comm-VOLUME regression "
            "(e.g. a quantized path silently falling back to f32); "
            "regenerate deliberately with `python -m tools.jaxlint "
            "--update-budget` and review the diff. Counts/bytes are per "
            "STEP (scan bodies count once). fused_dma_bytes_per_step pins "
            "the bytes that move via in-kernel ring DMA "
            "(ops/ring_dma fused hops — tagged jits on the tracing mesh): "
            "a fused schedule silently reverting to bare ppermute moves "
            "these bytes between kinds and fails the gate. gang_targets "
            "pin the dryrun_multichip GANG-MODE step programs: the same "
            "step traced under the declared processes x devices_per_process "
            "topology with the workers axis hinted DCN — each row adds "
            "per_process_shard_shapes (what every HOST holds; drift is a "
            "partitioning-contract break, JL201) and bytes_by_link "
            "(bytes_by_kind split DCN vs ICI by the ring-edge/peer model "
            "in checkers_jaxpr.split_bytes_by_link; grown DCN bytes at "
            "fixed counts is the cross-pod regression single-process rows "
            "cannot see, JL203). memory pins the STATIC memory rows "
            "(ISSUE 19, checkers_memory/static_memory): resident_arg_bytes "
            "(input + closed-over-constant footprint), peak_live_bytes "
            "(liveness peak over the traced program, sub-jaxprs "
            "recursively), and transient_peak_ratio (peak/resident, "
            "rounded) per target across BOTH registries — a grown peak is "
            "a memory regression that otherwise ships invisibly until an "
            "OOM on real HBM, and the resident rows are the model mall's "
            "planning input (JL401). hlo pins the POST-SPMD compiled "
            "contract (ISSUE 20, checkers_hlo/hlo_audit): every target "
            "lowered through jax.jit(...).lower().compile() — compilation "
            "only, never execution — with per-target compiler-emitted "
            "collective counts + result-shape bytes, instruction count, "
            "and while-body count (JL502; the layer GSPMD is free to "
            "rewrite AFTER tracing, so a jaxpr-clean program can still "
            "grow wire traffic only this section sees), plus "
            "device_kinds: the 6 pinned serving dispatches lowered per "
            "reachable device kind (JL504 — cpu always; TPU kinds pin "
            "when lint runs there, and sessions that cannot reach a "
            "pinned kind carry its matrix forward, never stale). Rows "
            "are exact per lowered_with_jax version; a different jax "
            "re-pins with ONE finding."),
        "traced_with_jax": jax.__version__,
        "targets": {
            name: {
                "collectives": dict(sorted(counts.items())),
                "bytes_per_step": sum(nbytes.values()),
                "bytes_by_kind": dict(sorted(nbytes.items())),
                "fused_dma_bytes_per_step": nbytes.get("fused_dma", 0),
            }
            for name, (counts, _bad, nbytes) in sorted(traced.items())},
        "gang_targets": gang_rows,
        "memory": memory_rows,
        "hlo": hlo_section,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")
    return path


def check_budget(repo_root: str, traced=None) -> List[Finding]:
    """JL201/JL202/JL203 findings for the whole trace registry."""
    if traced is None:
        traced = trace_all()
    findings: List[Finding] = []

    def emit(code, checker, target, msg):
        findings.append(Finding(
            code=code, checker=checker, path=BUDGET_FILE, line=1,
            func=target, message=msg))

    budget = load_budget(repo_root)
    if budget is None:
        emit("JL201", "collective-budget", "<manifest>",
             f"{BUDGET_FILE} is missing — generate it with "
             f"`python -m tools.jaxlint --update-budget` and commit it")
        budget_targets = {}
    else:
        budget_targets = budget.get("targets", {})

    for name, (counts, dtype_bad, nbytes) in sorted(traced.items()):
        for issue in dtype_bad:
            emit("JL202", "dtype-policy", name, issue)
        if budget is None:
            continue
        if name not in budget_targets:
            emit("JL201", "collective-budget", name,
                 f"traced target {name!r} has no manifest entry — run "
                 f"--update-budget and review the new row")
            continue
        pinned = budget_targets[name].get("collectives", {})
        if dict(counts) != dict(pinned):
            drift = []
            for kind in sorted(set(counts) | set(pinned)):
                got, want = counts.get(kind, 0), pinned.get(kind, 0)
                if got != want:
                    drift.append(f"{kind}: traced {got} vs pinned {want}")
            emit("JL201", "collective-budget", name,
                 f"collective budget drift ({'; '.join(drift)}) — if "
                 f"intentional, regenerate with --update-budget and review "
                 f"the diff; if not, a step gained/lost communication")
        # JL203: comm volume. A manifest row predating byte budgets (no
        # bytes_per_step key) is itself a finding — the byte contract must
        # cover every target.
        pinned_total = budget_targets[name].get("bytes_per_step")
        pinned_kinds = budget_targets[name].get("bytes_by_kind", {})
        total = sum(nbytes.values())
        if pinned_total is None:
            emit("JL203", "byte-budget", name,
                 f"manifest entry {name!r} has no bytes_per_step — "
                 f"regenerate with --update-budget so the byte contract "
                 f"covers it")
        elif total != pinned_total or dict(nbytes) != dict(pinned_kinds):
            drift = []
            for kind in sorted(set(nbytes) | set(pinned_kinds)):
                got, want = nbytes.get(kind, 0), pinned_kinds.get(kind, 0)
                if got != want:
                    drift.append(f"{kind}: traced {got} B vs pinned {want} B")
            if total != pinned_total:
                drift.append(f"total: traced {total} B vs pinned "
                             f"{pinned_total} B")
            emit("JL203", "byte-budget", name,
                 f"collective byte-budget drift ({'; '.join(drift)}) — "
                 f"comm VOLUME changed at tier-1 shapes (same-count dtype "
                 f"widening, e.g. a quantized path silently reverting to "
                 f"f32, lands here); if intentional, --update-budget and "
                 f"review the diff")
        # fused ring-DMA contract: the explicit fused_dma_bytes_per_step
        # row must exist for any target whose trace moves bytes via the
        # fused engine, and must agree with the by-kind row (a fused target
        # silently reverting to ppermute already failed the kind drift
        # above — fused_dma bytes collapse to 0 and ppermute grows).
        traced_fused = nbytes.get("fused_dma", 0)
        pinned_fused = budget_targets[name].get("fused_dma_bytes_per_step")
        if traced_fused and pinned_fused is None:
            emit("JL203", "byte-budget", name,
                 f"target {name!r} moves {traced_fused} B/step via fused "
                 f"ring DMA but the manifest row has no "
                 f"fused_dma_bytes_per_step — regenerate with "
                 f"--update-budget so the fused contract covers it")
        elif (pinned_fused is not None
              and pinned_fused != pinned_kinds.get("fused_dma", 0)):
            emit("JL203", "byte-budget", name,
                 f"manifest inconsistency for {name!r}: "
                 f"fused_dma_bytes_per_step={pinned_fused} disagrees with "
                 f"bytes_by_kind fused_dma="
                 f"{pinned_kinds.get('fused_dma', 0)} — hand-edited row? "
                 f"regenerate with --update-budget")
    for name in sorted(set(budget_targets) - set(traced)):
        emit("JL201", "collective-budget", name,
             f"manifest entry {name!r} matches no trace target — stale row "
             f"(target renamed/removed); regenerate with --update-budget")
    return findings


def check_gang_budget(repo_root: str, gang=None) -> List[Finding]:
    """JL201/JL202/JL203 for the gang-mode rows (module docstring: the
    gang split of counts, per-process shard shapes, and DCN/ICI bytes)."""
    if gang is None:
        gang = trace_gang_all()
    findings: List[Finding] = []

    def emit(code, checker, target, msg):
        findings.append(Finding(
            code=code, checker=checker, path=BUDGET_FILE, line=1,
            func=target, message=msg))

    budget = load_budget(repo_root)
    pinned_rows = (budget or {}).get("gang_targets", {})
    if budget is not None and not pinned_rows and gang:
        emit("JL201", "gang-budget", "<manifest>",
             f"{BUDGET_FILE} has no gang_targets section but "
             f"{len(gang)} gang-mode targets trace — regenerate with "
             f"`python -m tools.jaxlint --update-budget` and commit the "
             f"gang rows")
    for name, row in sorted(gang.items()):
        for issue in row.get("_dtype_bad", []):
            emit("JL202", "dtype-policy", name, issue)
        if budget is None or name not in pinned_rows:
            if budget is not None and pinned_rows:
                emit("JL201", "gang-budget", name,
                     f"gang-mode target {name!r} has no manifest row — "
                     f"run --update-budget and review the new row")
            continue
        pinned = pinned_rows[name]
        # topology + counts + per-process shard shapes: JL201 (a changed
        # shard shape means each host holds a different block — the
        # partitioning contract moved, not just its cost)
        for key, label in (("processes", "process count"),
                           ("devices_per_process", "devices per process"),
                           ("collectives", "collective counts"),
                           ("per_process_shard_shapes",
                            "per-process shard shapes")):
            if row.get(key) != pinned.get(key):
                emit("JL201", "gang-budget", name,
                     f"gang-mode {label} drift: traced {row.get(key)} vs "
                     f"pinned {pinned.get(key)} — if intentional, "
                     f"regenerate with --update-budget and review the "
                     f"diff; if not, the gang step program (or its "
                     f"per-host partitioning) changed")
        # bytes: JL203, with the DCN split called out separately — DCN is
        # the scarce link, so its growth is the headline even when totals
        # barely move
        traced_link = row.get("bytes_by_link", {})
        pinned_link = pinned.get("bytes_by_link", {})
        if pinned.get("bytes_per_step") is None:
            emit("JL203", "gang-budget", name,
                 f"gang manifest row {name!r} has no bytes_per_step — "
                 f"regenerate with --update-budget so the gang byte "
                 f"contract covers it")
        elif (row.get("bytes_per_step") != pinned.get("bytes_per_step")
              or row.get("bytes_by_kind") != pinned.get("bytes_by_kind")
              or traced_link != pinned_link):
            drift = []
            for link in ("dcn", "ici"):
                got_k = traced_link.get(link, {})
                want_k = pinned_link.get(link, {})
                for kind in sorted(set(got_k) | set(want_k)):
                    g, w = got_k.get(kind, 0), want_k.get(kind, 0)
                    if g != w:
                        drift.append(f"{link}/{kind}: traced {g} B vs "
                                     f"pinned {w} B")
            if row.get("bytes_per_step") != pinned.get("bytes_per_step"):
                drift.append(f"total: traced {row.get('bytes_per_step')} B "
                             f"vs pinned {pinned.get('bytes_per_step')} B")
            dcn_got = row.get("dcn_bytes_per_step", 0)
            dcn_want = pinned.get("dcn_bytes_per_step", 0)
            headline = (f"DCN bytes {dcn_got} vs pinned {dcn_want} — "
                        if dcn_got != dcn_want else "")
            emit("JL203", "gang-budget", name,
                 f"gang-mode byte-budget drift ({headline}"
                 f"{'; '.join(drift) or 'kind-level split moved'}) — "
                 f"cross-pod comm volume changed at tier-1 shapes; if "
                 f"intentional, --update-budget and review the diff")
        elif (pinned.get("dcn_bytes_per_step") is not None
              and pinned["dcn_bytes_per_step"]
              != sum(pinned_link.get("dcn", {}).values())):
            emit("JL203", "gang-budget", name,
                 f"gang manifest inconsistency for {name!r}: "
                 f"dcn_bytes_per_step={pinned['dcn_bytes_per_step']} "
                 f"disagrees with its bytes_by_link dcn sum — hand-edited "
                 f"row? regenerate with --update-budget")
    for name in sorted(set(pinned_rows) - set(gang)):
        emit("JL201", "gang-budget", name,
             f"gang manifest row {name!r} matches no gang-mode trace "
             f"target — stale row; regenerate with --update-budget")
    return findings
