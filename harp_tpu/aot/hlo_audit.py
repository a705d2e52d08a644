"""Post-SPMD lowered-HLO audit — what the compiler actually emits.

Every byte contract the repo enforces (the JL2xx collective budgets, the
JL4xx memory rows) is pinned at the **jaxpr** level: `jax.make_jaxpr`
records the collectives the PROGRAM asked for. But the XLA SPMD
partitioner is free to insert all-gathers, reshards, and full replication
*after* tracing — EQuARX (arXiv:2506.17615) shows the real wire behavior
of XLA collectives is decided exactly at this layer. A program whose
jaxpr is budget-clean can still compile into one that all-gathers a whole
factor table per step, and nothing in the traced contract would notice.

This module closes that gap statically (ISSUE 20): it lowers an
already-traced program through ``jax.jit(...).lower(...).compile()`` —
compilation only, **no execution** — and parses the post-partitioning
optimized HLO module text for

* **compiler-emitted collectives** (``all-gather`` / ``all-reduce`` /
  ``collective-permute`` / ``all-to-all`` / ``reduce-scatter``): counts,
  result-shape bytes, and the shapes themselves, per op kind;
* **cost-row scalars**: total instruction count and while-body count —
  the coarse "did the compiled program grow an op / a loop" signal the
  artifact-manifest hash flags without explaining;
* **entry-parameter shapes**: the per-device blocks the partitioner
  actually compiled each input to — an operand DECLARED sharded that
  compiles at its GLOBAL shape was silently replicated (the static
  signature of a full broadcast).

Conventions: HLO collective bytes are the op's RESULT shape bytes (what
the op materializes — for all-reduce/collective-permute/all-to-all this
equals the operand payload; for all-gather it is the gathered result, for
reduce-scatter the scattered one). This deliberately differs from the
jaxpr engine's operand-bytes convention: the two sections pin different
layers and are never diffed number-for-number — JL501 diffs *kinds*, and
JL502 pins the compiled rows against themselves over time.

Used by ``tools/jaxlint/checkers_hlo.py`` (the JL5xx engine) and by the
AOT store (per-artifact ``hlo`` meta rows — metadata, never a key axis,
exactly like the r20 ``memory`` rows).
"""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Tuple

# the HLO ops that move bytes between devices post-partitioning. The
# -start/-done async split (TPU) books the op once, at its -start.
HLO_COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "all-to-all", "collective-permute",
    "reduce-scatter", "collective-broadcast",
)

# jaxpr collective primitive -> the HLO op kinds it legitimately lowers
# to. An HLO collective kind in the compiled module with NO traced jaxpr
# primitive mapping to it is COMPILER-INSERTED (JL501): the partitioner
# added communication the traced contract never showed.
JAXPR_TO_HLO: Dict[str, Tuple[str, ...]] = {
    # deliberately sharp: a psum maps to all-reduce ONLY. A backend that
    # decomposes it into reduce-scatter + all-gather changed the wire
    # pattern, and that is exactly what JL501 exists to surface — every
    # committed target compiles its psums to plain all-reduce (verified
    # over both registries), so the sharp mapping costs nothing here and
    # catches the decomposition the day a backend introduces it.
    "psum": ("all-reduce",),
    "pmin": ("all-reduce",),
    "pmax": ("all-reduce",),
    "all_gather": ("all-gather",),
    "all_to_all": ("all-to-all",),
    "reduce_scatter": ("reduce-scatter",),
    "psum_scatter": ("reduce-scatter",),
    "ppermute": ("collective-permute",),
    "pshuffle": ("collective-permute",),
    "pbroadcast": ("collective-broadcast", "all-gather"),
    "pgather": ("all-gather",),
    # fused ring-DMA hops: on the CPU tracing mesh the engine lowers them
    # through lax_ops.rotate (ops/ring_dma fallback), i.e. ppermute
    "fused_dma": ("collective-permute",),
}

# why would the partitioner insert this op kind? The inferred cause a
# JL501 finding carries — the three GSPMD insertion families.
INSERTED_CAUSE = {
    "all-gather": "a sharded operand was resharded to REPLICATED (the "
                  "silent full-broadcast signature — GSPMD gathers the "
                  "whole array onto every device)",
    "all-reduce": "partial-sum completion: an unreduced partial result "
                  "crossed a sharding boundary and the partitioner "
                  "finished the reduction itself",
    "collective-permute": "a resharding between mismatched shardings "
                          "(shard rotation / halo exchange inserted by "
                          "the partitioner)",
    "all-to-all": "a sharded-axis transpose resharding (the partitioned "
                  "dim moved to a different axis)",
    "reduce-scatter": "a reduce+reshard combination the partitioner "
                      "fused in place of the traced pattern",
    "collective-broadcast": "a single-device value was broadcast to the "
                            "full mesh by the partitioner",
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

# numpy/jax dtype name -> HLO dtype token (for matching declared arg
# shardings against compiled entry parameters)
_NP_TO_HLO = {
    "float32": "f32", "float64": "f64", "bfloat16": "bf16",
    "float16": "f16", "int32": "s32", "int64": "s64", "int16": "s16",
    "int8": "s8", "uint8": "u8", "uint16": "u16", "uint32": "u32",
    "uint64": "u64", "bool": "pred", "complex64": "c64",
    "complex128": "c128",
}

# one HLO instruction line: `  %name.1 = <shape> op-name(...)` — shape is
# a typed array (`f32[8,2]{1,0}`) or a tuple of them
_SHAPE_RE = r"(?:\([^)]*\)|[\w\[\],]+(?:\{[^}]*\})?)"
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(" + _SHAPE_RE + r")\s+"
    r"([\w\-]+)\(", re.MULTILINE)
_ARRAY_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")
# layout annotations. The TPU compiler writes tiled layouts with
# parentheses inside them (`f32[128,256]{1,0:T(8,128)S(1)}`), which end a
# tuple type early for _SHAPE_RE: read as written, the compiled 4-chip NN
# step showed 0 collectives and 0 whiles where it has 1 and 2 (PR 21 chip
# run). Layouts carry no bytes, so they are dropped before matching.
_LAYOUT_RE = re.compile(r"\{[^{}]*\}")


class HloShape(NamedTuple):
    dtype: str                  # HLO dtype token ("f32", "s32", ...)
    dims: Tuple[int, ...]

    @property
    def nbytes(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n * _DTYPE_BYTES.get(self.dtype, 0)

    def __str__(self) -> str:
        return f"{self.dtype}[{','.join(str(d) for d in self.dims)}]"


def parse_shapes(text: str) -> List[HloShape]:
    """Every array shape in one HLO type string (a tuple type yields each
    element; tokens and opaque types yield nothing)."""
    out = []
    for m in _ARRAY_SHAPE_RE.finditer(text):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue              # token[] / opaque[] carry no bytes
        out.append(HloShape(
            dtype, tuple(int(d) for d in dims.split(",") if d)))
    return out


def shape_bytes(text: str) -> int:
    return sum(s.nbytes for s in parse_shapes(text))


def iter_instructions(hlo_text: str):
    """(result-type text, op name) for every instruction in the module,
    async ``-start``/``-done`` pairs normalized: the ``-start`` books the
    op under its base name, the ``-done`` is skipped (one transfer, one
    count)."""
    for m in _INSTR_RE.finditer(_LAYOUT_RE.sub("", hlo_text)):
        shape_txt, op = m.group(1), m.group(2)
        if op.endswith("-done"):
            continue
        if op.endswith("-start"):
            op = op[: -len("-start")]
        yield shape_txt, op


def collective_stats(hlo_text: str) -> Dict[str, dict]:
    """``{op: {"count", "bytes", "shapes"}}`` over the compiled module —
    bytes are result-shape bytes (module docstring's convention)."""
    out: Dict[str, dict] = {}
    for shape_txt, op in iter_instructions(hlo_text):
        if op not in HLO_COLLECTIVE_OPS:
            continue
        row = out.setdefault(op, {"count": 0, "bytes": 0, "shapes": []})
        row["count"] += 1
        row["bytes"] += shape_bytes(shape_txt)
        row["shapes"].append(
            "+".join(str(s) for s in parse_shapes(shape_txt)) or "()")
    return out


def instruction_count(hlo_text: str) -> int:
    return sum(1 for _ in iter_instructions(hlo_text))


def while_count(hlo_text: str) -> int:
    return sum(1 for _shape, op in iter_instructions(hlo_text)
               if op == "while")


def hlo_row(hlo_text: str) -> dict:
    """The pinned manifest/artifact row for one compiled module: per-kind
    collective counts and bytes, total collective bytes, instruction
    count, while-body count (JL502's contract — exact equality, like the
    jaxpr byte rows)."""
    stats = collective_stats(hlo_text)
    return {
        "collectives": {op: s["count"] for op, s in sorted(stats.items())},
        "collective_bytes": {op: s["bytes"]
                             for op, s in sorted(stats.items())},
        "collective_bytes_total": sum(s["bytes"] for s in stats.values()),
        "instruction_count": instruction_count(hlo_text),
        "while_count": while_count(hlo_text),
    }


# -- lowering ---------------------------------------------------------------


def lower_closed(closed, args):
    """Compile one already-traced ``ClosedJaxpr`` at its placed args —
    the post-SPMD module for a program the trace cache already holds.
    Compilation only: nothing executes, no output buffer is ever
    materialized."""
    import jax
    from jax.extend.core import jaxpr_as_fun

    # jaxpr_as_fun takes the FLAT invars; the cached args are the original
    # pytrees (make_jaxpr flattened them in tree-leaf order)
    flat = jax.tree_util.tree_leaves(args)
    fn = jaxpr_as_fun(closed)
    return jax.jit(fn).lower(*flat).compile()


def compiled_text(compiled) -> str:
    return compiled.as_text()


def lower_fn_text(fn, args) -> str:
    """Post-SPMD module text for a live callable (the AOT export path:
    the endpoint's compiled dispatch is already a jit)."""
    import jax

    lowered = (fn.lower(*args) if hasattr(fn, "lower")
               else jax.jit(fn).lower(*args))
    return lowered.compile().as_text()


def hlo_row_for(fn, args) -> dict:
    """``hlo_row`` of a live callable — the per-artifact meta row the AOT
    store records (metadata, never a key axis)."""
    return hlo_row(lower_fn_text(fn, args))


# -- JL501: compiler-inserted collectives -----------------------------------


class InsertedCollective(NamedTuple):
    op: str                     # HLO op kind
    count: int
    bytes: int
    shapes: Tuple[str, ...]
    cause: str                  # inferred GSPMD insertion family


def expected_hlo_kinds(jaxpr_counts: Dict[str, int]) -> set:
    """The HLO collective kinds the traced jaxpr accounts for."""
    kinds = set()
    for prim, n in jaxpr_counts.items():
        if n:
            kinds.update(JAXPR_TO_HLO.get(prim, ()))
    return kinds


def inserted_collectives(hlo_text: str, jaxpr_counts: Dict[str, int],
                         ) -> List[InsertedCollective]:
    """Compiled collective kinds the traced program never asked for —
    each one is communication the SPMD partitioner inserted after
    tracing, invisible to every jaxpr-level budget (JL501)."""
    allowed = expected_hlo_kinds(jaxpr_counts)
    out = []
    for op, s in sorted(collective_stats(hlo_text).items()):
        if op in allowed:
            continue
        out.append(InsertedCollective(
            op, s["count"], s["bytes"], tuple(s["shapes"][:4]),
            INSERTED_CAUSE.get(op, "unmapped compiler-side insertion")))
    return out


# -- JL503: sharding-propagation audit --------------------------------------


_ENTRY_RE = re.compile(r"^ENTRY\s+%?[\w.\-]+\s*\((.*?)\)\s*->",
                       re.MULTILINE | re.DOTALL)
_PARAM_RE = re.compile(r"[\w.\-]+:\s*([a-z]\w*\[[\d,]*\](?:\{[^}]*\})?)")


def entry_param_shapes(hlo_text: str) -> List[HloShape]:
    """The compiled entry computation's parameter shapes — per-DEVICE
    blocks after partitioning (what each device actually holds)."""
    m = _ENTRY_RE.search(hlo_text)
    if m is None:
        return []
    return [s for p in _PARAM_RE.finditer(m.group(1))
            for s in parse_shapes(p.group(1))]


class ReplicatedOperand(NamedTuple):
    dtype: str
    global_shape: Tuple[int, ...]
    declared_shard: Tuple[int, ...]
    nbytes: int                 # the global (replicated) footprint


def declared_shard_shapes(args) -> List[Tuple[str, Tuple[int, ...],
                                              Tuple[int, ...]]]:
    """``(hlo dtype, global shape, declared per-device shard shape)`` for
    every placed argument leaf (host arrays count as replicated)."""
    import jax

    out = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        shape = tuple(int(s) for s in shape)
        hlo_dt = _NP_TO_HLO.get(str(dtype), str(dtype))
        sharding = getattr(leaf, "sharding", None)
        shard = shape
        if sharding is not None:
            try:
                shard = tuple(int(s) for s in sharding.shard_shape(shape))
            except (TypeError, ValueError):
                shard = shape
        out.append((hlo_dt, shape, shard))
    return out


def replicated_where_sharded(hlo_text: str, args,
                             ) -> List[ReplicatedOperand]:
    """Operands DECLARED sharded that the partitioner compiled at their
    GLOBAL shape (JL503): the entry parameter carries the full array on
    every device — a silent full replication that multiplies the operand's
    HBM footprint by the mesh width and usually rides an inserted
    all-gather on the wire.

    Matching is by (dtype, shape) MULTISET, not position — the compiled
    entry's parameter order is not the argument order. A declared shard
    shape missing from the compiled parameters while the same operand's
    GLOBAL shape shows up in the surplus is the replication signature;
    any other mismatch (a const-folded parameter the compiler dropped) is
    conservatively ignored."""
    from collections import Counter

    declared = declared_shard_shapes(args)
    got = Counter((s.dtype, s.dims) for s in entry_param_shapes(hlo_text))
    expect = Counter((dt, shard) for dt, _g, shard in declared)
    missing = expect - got
    surplus = got - expect
    out = []
    for dt, gshape, shard in declared:
        if shard == gshape:
            continue                       # declared replicated: fine
        if missing.get((dt, shard), 0) <= 0:
            continue                       # compiled at its shard shape
        if surplus.get((dt, gshape), 0) <= 0:
            continue                       # dropped/reshaped, not gathered
        missing[(dt, shard)] -= 1
        surplus[(dt, gshape)] -= 1
        n = 1
        for d in gshape:
            n *= d
        out.append(ReplicatedOperand(
            dt, gshape, shard, n * _DTYPE_BYTES.get(dt, 0)))
    return out
