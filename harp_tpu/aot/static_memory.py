"""Static memory estimator — liveness analysis over traced jaxprs.

The model-mall planning input (ISSUE 19): ``serve.resident_bytes`` is a
runtime gauge, and donation is a comment-level promise jax silently drops
on any aliasing mismatch. This module computes, from a ``jax.make_jaxpr``
trace alone (no execution, no compile), the numbers a multi-tenant mall
must reason about BEFORE placing a program:

* ``resident_arg_bytes`` — the input footprint: every program argument and
  closed-over constant, summed over abstract values. For a serving
  dispatch this is exactly ``Endpoint.resident_bytes()`` plus the placed
  query buffer (tier-1 cross-checks the two).
* ``peak_live_bytes`` — the liveness peak: each variable is live from its
  defining equation to its last use (program inputs from equation 0,
  program outputs to the end), and the peak is the largest byte sum of any
  equation's live set, recursively including sub-jaxpr interiors (scan /
  while / cond / jit bodies contribute ``max(0, sub peak − sub args)`` on
  top of the enclosing live set — branches of one cond never coexist, so
  subprograms take a max, not a sum).
* ``transient_peak_ratio`` — ``peak / resident``, the static twin of the
  reshard engine's chunk budget: an accidental full-gather/broadcast
  materialization shows up as this ratio exploding long before it OOMs on
  real HBM.

This is a static MODEL, not an XLA allocator simulation: XLA may fuse away
intermediates the model charges, and buffer assignment may hold inputs the
model retires early. What matters for the gate is that the model is
deterministic for a given jaxpr — the pinned rows move exactly when the
traced program moves, which is the same contract the collective-budget
rows already enforce for wire bytes.

The donation audit rides the same trace: a ``jit`` equation's
``donated_invars`` mark buffers the caller promised to XLA, but XLA only
honors a donation whose aval (shape + dtype) matches an output's — an
unmatched donation is SILENTLY dropped (jax emits only a warning), and the
"reused" buffer quietly doubles. :func:`dropped_donations` reproduces the
lowering's greedy aval match and returns every donation that cannot alias
any output.

Used by the AOT store (per-artifact memory rows in the meta — metadata,
never a key axis) and by ``tools/jaxlint/checkers_memory.py`` (the JL4xx
engine that pins the rows in ``tools/collective_budget.json``).
"""

from __future__ import annotations

from typing import Dict, Iterator, List, NamedTuple, Tuple

RATIO_DIGITS = 4     # manifest rows round the ratio so exact-equality
#                      drift checks are stable across float printers


def aval_bytes(aval) -> int:
    """Bytes of one abstract value (0 for tokens/opaque avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for s in shape:
        n *= int(s)
    return n * dtype.itemsize


def _var_bytes(var) -> int:
    return aval_bytes(getattr(var, "aval", None))


def _subjaxprs(eqn) -> Iterator:
    """Raw sub-jaxprs of one equation (ClosedJaxpr params unwrap to their
    inner jaxpr; consts are handled by the caller via ``_sub_consts``)."""
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr
            elif hasattr(item, "eqns") and not hasattr(item, "jaxpr"):
                yield item


def _sub_closed(eqn) -> Iterator[Tuple[object, list]]:
    """(jaxpr, consts) pairs for one equation's sub-programs."""
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr, list(getattr(item, "consts", []))
            elif hasattr(item, "eqns") and not hasattr(item, "jaxpr"):
                yield item, []


class Liveness(NamedTuple):
    peak_live_bytes: int
    resident_arg_bytes: int
    peak_eqn_index: int          # -1 when the peak IS the argument set
    peak_eqn_primitive: str      # "" when peak_eqn_index == -1


def analyze_liveness(jaxpr) -> Liveness:
    """Liveness over one (raw) jaxpr — module docstring's model."""
    invars = list(jaxpr.constvars) + list(jaxpr.invars)
    resident = sum(_var_bytes(v) for v in invars)
    n = len(jaxpr.eqns)
    # last use per var: program outputs live to the end; a defined-but-
    # unused result is still materialized AT its defining equation
    last: Dict[object, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if hasattr(v, "aval") and not hasattr(v, "val"):
                last[v] = i
    for v in jaxpr.outvars:
        if hasattr(v, "aval") and not hasattr(v, "val"):
            last[v] = n
    spans: List[Tuple[int, int, int]] = []     # (start, end, bytes)
    for v in invars:
        spans.append((0, last.get(v, -1), _var_bytes(v)))
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            spans.append((i, max(last.get(v, i), i), _var_bytes(v)))
    # prefix-sum the live bytes per equation index
    delta = [0] * (n + 2)
    for start, end, b in spans:
        if end < start or b == 0:
            continue
        delta[start] += b
        delta[end + 1] -= b
    live = [0] * max(n, 1)
    acc = 0
    for i in range(n):
        acc += delta[i]
        live[i] = acc
    peak, peak_i, peak_prim = resident, -1, ""
    for i, eqn in enumerate(jaxpr.eqns):
        extra = 0
        for sub in _subjaxprs(eqn):
            sub_res = analyze_liveness(sub)
            sub_args = sum(_var_bytes(v) for v in
                           list(sub.constvars) + list(sub.invars))
            # interior headroom beyond what the enclosing live set already
            # charges for the operands; max across subs — cond branches /
            # while phases never coexist
            extra = max(extra, max(0, sub_res.peak_live_bytes - sub_args))
        if live[i] + extra > peak:
            peak, peak_i = live[i] + extra, i
            peak_prim = eqn.primitive.name
    return Liveness(peak, resident, peak_i, peak_prim)


def memory_row(closed) -> dict:
    """The manifest/artifact row for one ``ClosedJaxpr``: resident bytes,
    peak live bytes, and the rounded transient ratio."""
    res = analyze_liveness(closed.jaxpr)
    # closed-over consts are resident too — they are baked into the
    # program's HBM footprint exactly like arguments (for a make_jaxpr
    # trace they surface as constvars, already counted; top-level consts
    # carried on the ClosedJaxpr are the same vars, so nothing is added
    # twice — constvars and consts are index-aligned)
    peak = res.peak_live_bytes
    resident = res.resident_arg_bytes
    ratio = round(peak / resident, RATIO_DIGITS) if resident else 0.0
    return {
        "resident_arg_bytes": resident,
        "peak_live_bytes": peak,
        "transient_peak_ratio": ratio,
    }


class CapturedConst(NamedTuple):
    nbytes: int
    shape: Tuple[int, ...]
    dtype: str


def captured_consts(closed) -> List[CapturedConst]:
    """Every closed-over constant baked into the traced program,
    recursively (top-level ClosedJaxpr consts plus inner jit/closed-call
    consts) — the JL403 surface: each one is duplicated HBM per program
    AND a retrace hazard (a new closure constant is a new program)."""
    out: List[CapturedConst] = []

    def note(consts):
        for c in consts:
            # size x itemsize, not .nbytes: jax 0.9.0 hands captured numpy
            # constants over as TypedNdArray, which has no nbytes
            dt = getattr(c, "dtype", None)
            b = (int(getattr(c, "size", 0)) * dt.itemsize
                 if dt is not None else 0)
            if b:
                out.append(CapturedConst(
                    b, tuple(int(s) for s in getattr(c, "shape", ())),
                    str(getattr(c, "dtype", ""))))

    def walk(jaxpr, consts):
        note(consts)
        for eqn in jaxpr.eqns:
            for sub, sub_consts in _sub_closed(eqn):
                walk(sub, sub_consts)

    walk(closed.jaxpr, list(closed.consts))
    return out


class DroppedDonation(NamedTuple):
    jit_name: str        # the jit eqn's `name` param (the traced fn's name)
    aval: str            # the donated-but-unaliasable buffer's aval
    nbytes: int


def dropped_donations(closed) -> List[DroppedDonation]:
    """Donated buffers that cannot alias ANY output (module docstring):
    walks every jit equation, greedily matches each output aval
    (shape + dtype, in output order — the lowering's own matching) against
    the still-unclaimed donated inputs, and returns the leftovers. A
    non-empty result means XLA drops those donations with only a warning:
    the caller believes the buffer is reused; it is actually doubled."""
    out: List[DroppedDonation] = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "jit":    # jax 0.9.0 (was "pjit")
                don = eqn.params.get("donated_invars") or ()
                if any(don):
                    unmatched = [v.aval for v, d in zip(eqn.invars, don)
                                 if d]
                    for o in eqn.outvars:
                        oa = o.aval
                        for di in unmatched:
                            if (di.shape == oa.shape
                                    and di.dtype == oa.dtype):
                                unmatched.remove(di)
                                break
                    name = str(eqn.params.get("name", "<jit>"))
                    for u in unmatched:
                        out.append(DroppedDonation(
                            name, str(u), aval_bytes(u)))
            for sub in _subjaxprs(eqn):
                walk(sub)

    walk(closed.jaxpr)
    return out
