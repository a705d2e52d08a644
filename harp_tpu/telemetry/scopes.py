"""Device scopes — the names the step programs give their own kernels.

XLA numbers what it compiles (``fusion.19``, ``copy.36``) and renumbers it
whenever the program changes, so a device trace read under those names cannot
be laid beside the trace of the commit before. The program therefore names
its kernels itself: every listed scope is a ``jax.named_scope`` on the
training path, which is metadata only (no operation is added: the jaxpr's
equations, the collective budget, the AOT content hashes and the persistent
cache key are what they were) and survives fusion in the compiled text's
``op_name``.

:data:`SCOPES` is the whole list; :func:`scoped` is how a module takes a name
from it. The two readers are shared by the operator and the benchmark:
:func:`scope_map` reads a compiled program's text into ``{instruction:
scope}``, and :func:`device_time_by_scope` reduces a profiler trace's
``XLA Ops`` line to self time per scope through such a map::

    python -m harp_tpu.telemetry.scopes <trace dir> <hlo text>

A fusion that spans two scopes has one ``op_name``, the one XLA kept for it
(its root's): that scope takes the fusion's whole time.

Names are compile-time metadata, and jax leaves metadata out of the persistent
compile cache's key: an executable loaded from an entry that an earlier build
compiled carries *that* build's names (none, if it predates them). Where a
text shows no listed scope, compile once past the stale entry
(``JAX_ENABLE_COMPILATION_CACHE=false``, or jax's
``JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=1``) and take text and trace
from that process: the text gains the scopes.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import sys
from typing import Callable, Dict, Optional

import jax

def scoped(name: str) -> Callable:
    """Decorator: run the function under the listed scope ``name``.

    Stands ABOVE the list: a Pallas kernel traced under a scoped function
    carries this wrapper's source line in its payload, and the compile
    cache's key with it, so a line added above ``inner`` compiles every
    such step anew. A name added to :data:`SCOPES` below moves nothing
    (``tests/test_scopes.py`` holds the order)."""
    if name not in _LISTED:
        raise ValueError(f"{name!r} is not in telemetry.scopes.SCOPES")

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


_TABLE_OPS = ("allreduce", "reduce", "broadcast", "regroup", "allgather",
              "aggregate", "rotate", "rotate_with_map", "push", "pull",
              "gather", "join", "group_by_key", "bucket_route", "route_back",
              "group_by_key_sharded")
_LAX_OPS = ("barrier", "allreduce", "reduce", "broadcast", "allgather",
            "gather", "reduce_scatter", "rotate", "rotate_map", "all_to_all",
            "send_recv")

SCOPES = (
    "kmeans.norms",     # the hoisted sum of squared norms
    "kmeans.scores",    # the score GEMM with the mask, argmin and min
    "kmeans.stats",     # the one-hot stats product: one_hot, sums GEMM, counts
    "kmeans.update",    # M-step arithmetic: average, the cost psum
    *(f"table.{op}" for op in _TABLE_OPS),   # collectives/table_ops.py
    *(f"lax.{op}" for op in _LAX_OPS),       # collectives/lax_ops.py
    "rotation.hop",     # the ring hop of a model block
    "sgdmf.select",     # picking the resident bucket of the slab
    "sgdmf.stripes",    # the masked stripe update (XLA, Pallas or sparse)
    "sgdmf.rmse",       # per-epoch quality: two psums and a square root
    "als.outer",        # the factor's row-wise outer products, packed: P a row
    "als.gram",         # the weights, the plane GEMM (P, rows) and V'V
    "als.rhs",          # the right-hand sides: weights x factors
    "als.solve",        # the batched K x K SPD solve and the block's way out
    "als.monitor",      # per-iteration quality over the observed cells
    "ccd.sweep",        # one fused pass over a side's plane: both row sums
    "ccd.column",       # column t picked, the closed form, row t written back
    "ccd.monitor",      # per-epoch RMSE over the observed cells
    "mds.anneal",       # the schedule's loop: the temperature, the carry, the curve
    "mds.bc",           # one fused pass over delta and w: B(X)X and the stress
    "mds.cg",           # the Guttman solve: w's matvecs and the CG arithmetic
    "kmeans.estep",     # the fused E-step: scores, argmin, one-hot, stats, norms
    "em.factor",        # Cholesky, the whitening A_k and b_k, log det, constants
    "em.estep",         # one fused pass: whitened product, log-sum-exp, moments
    "em.update",        # the statistics' psum, the M-step arithmetic, the quality
)
_LISTED = frozenset(SCOPES)


# -- readers ----------------------------------------------------------------- #

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*)\s+=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([A-Za-z_][\w.\-]*)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r"(?<=\s)([a-z][a-z0-9\-]*)\(")
_REFERENCE = re.compile(r"%([A-Za-z_][\w.\-]*)")
_CALLED = re.compile(r"(?:body|condition|calls)=%([A-Za-z_][\w.\-]*)")
_CONTAINERS = ("while", "conditional", "call")


def scope_of(op_name: str) -> Optional[str]:
    """The deepest listed scope on an ``op_name`` path, or None."""
    for part in reversed(op_name.split("/")):
        if part in _LISTED:
            return part
    return None


def scope_map(hlo_text: str) -> Dict[str, Optional[str]]:
    """``{instruction name: scope}`` over every instruction of a compiled
    program's text (``compiled.as_text()``).

    An instruction the program wrote carries an ``op_name`` path
    (``jit(fit_fn)/.../kmeans.stats/dot_general``): its scope is the deepest
    listed scope on that path, None where the path has none. An instruction
    the compiler made itself (a layout copy, a collective it rewrote, a
    tuple, the relayout of an argument) carries none, or a parameter's bare
    ``args[0]``: it takes the scope of the nearest instruction with a path
    that uses it, else of the nearest that it reads, looking through other
    such instructions; else the scope of the loop or fusion whose body it
    stands in. None where all of that finds nothing."""
    named: Dict[str, Optional[str]] = {}     # the program's: from op_name
    made: Dict[str, str] = {}                # the compiler's: its computation
    reads: Dict[str, list] = {}
    used_by: Dict[str, list] = {}
    caller: Dict[str, str] = {}              # computation -> who runs it
    computation = ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(1)
        body, _, meta = line[m.end():].partition("metadata={")
        op_name = _OP_NAME.search(meta)
        # a path is the program's; a bare ``args[0]`` is XLA's own name for
        # a parameter, which the copies it makes of one inherit
        if op_name is not None and "/" in op_name.group(1):
            named[name] = scope_of(op_name.group(1))
        else:
            made[name] = computation
        reads[name] = _REFERENCE.findall(body)
        for other in reads[name]:
            used_by.setdefault(other, []).append(name)
        for called in _CALLED.findall(body):
            caller[called] = name

    def nearest(name: str, links: Dict[str, list]) -> Optional[str]:
        seen, stack = {name}, list(reversed(links.get(name, ())))
        while stack:
            other = stack.pop()
            if other in seen:
                continue
            seen.add(other)
            if named.get(other) is not None:
                return named[other]
            if other in made:
                stack.extend(reversed(links.get(other, ())))
        return None

    out = dict(named)
    for name in made:
        out[name] = nearest(name, used_by) or nearest(name, reads)
    for name, computation in made.items():     # callers are resolved by now
        if out[name] is None:
            out[name] = out.get(caller.get(computation))
    return out


def _instruction(event_name: str) -> tuple:
    """``(name, opcode)`` of an ``XLA Ops`` event, which is named by its
    instruction's text (``%fusion.19 = f32[...] fusion(...), kind=...``)."""
    lhs, _, rhs = event_name.partition(" = ")
    name = lhs.lstrip("%").strip()
    m = _OPCODE.search(" " + rhs)
    return name, m.group(1) if m else name.split(".")[0]


def device_time_by_scope(xplane_path: str, scopes: Dict[str, Optional[str]],
                         device: int = 0) -> Dict[Optional[str], float]:
    """Seconds of device self time per scope over one chip's ``XLA Ops``.

    An instruction's self time is its event's duration less what the events
    nested in it cover, so a ``while`` is not counted again for its body; the
    containers' own remainder is loop overhead and is left out. Instructions
    the map does not know, or knows under no scope, are summed under None.
    """
    from jax.profiler import ProfileData

    planes = sorted((p for p in ProfileData.from_file(xplane_path).planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
    if device >= len(planes):
        return {}
    events = sorted(
        ((e.start_ns, e.start_ns + e.duration_ns, e.name)
         for line in planes[device].lines if line.name == "XLA Ops"
         for e in line.events), key=lambda e: (e[0], -e[1]))
    out: Dict[Optional[str], float] = {}
    stack: list = []                     # [start, end, name, covered]

    def close(start, end, name, covered):
        name, opcode = _instruction(name)
        if opcode in _CONTAINERS:
            return
        scope = scopes.get(name)
        out[scope] = out.get(scope, 0.0) + (end - start - covered) * 1e-9

    for start, end, name in events:
        while stack and stack[-1][1] <= start:
            close(*stack.pop())
        if stack:
            stack[-1][3] += min(end, stack[-1][1]) - start
        stack.append([start, end, name, 0])
    while stack:
        close(*stack.pop())
    return out


# -- idle time by host phase -------------------------------------------------- #
#
# The other half of a trace, the time in which the device ran nothing (told
# here and not in the module's docstring: a line above ``scoped`` is a line
# above every kernel a scoped function calls)::
#
#     python -m harp_tpu.telemetry.scopes <trace dir> --idle [--span window]
#         [--also call,fetch_quality]

USAGE = ("python -m harp_tpu.telemetry.scopes <trace dir> <hlo text>",
         "python -m harp_tpu.telemetry.scopes <trace dir> --idle "
         "[--span <annotation>] [--also <annotation>,...]")
OTHER = "host_other"         # idle time that no listed annotation covers


def idle_by_phase(xplane_path: str, device: int = 0, *, span: str = "",
                  also=()) -> Optional[dict]:
    """Where one chip's idle time of a trace went, by host phase.

    The traced span runs from the first ``step.dispatch`` to the end of the
    last step program (or, with ``span``, over the longest host annotation of
    that name: the benchmark's ``window``). Idle is the span less the union
    of the chip's ``XLA Ops``. Every idle interval is cut wherever an
    annotation named in ``host_spans.PHASES`` (or in ``also``: a harness's
    own spans) begins or ends, and each piece goes to the innermost such
    annotation that covers it (the one that began last), ``host_other``
    where none does: a gap is split, never given whole to one name.

    The device's clock runs some hundreds of microseconds off the host's:
    the device's events are shifted by the least amount that starts every
    step program (an ``XLA Modules`` event) at or after the start of the
    ``step.dispatch`` that launched it (the last one that began before the
    program did, give or take the shift).

    Returns ``{"span_s", "idle_s", "shift_s", "by_phase": {name: seconds},
    "calls": [{"launch_s", "step_s", "completion_s"}, ...]}``: per step
    program the launch latency (its ``step.dispatch``'s start to the
    program's start), its time on the device, and the completion latency
    (the program's end to the end of the fetch that waited for it: the first
    listed annotation with ``fetch`` in its name that was open or began
    after the program did; None where there is none). None where the trace
    has no such device plane, no step program or no ``step.dispatch``.
    """
    import bisect

    from jax.profiler import ProfileData

    from harp_tpu.telemetry import host_spans

    listed = set(host_spans.PHASES) | set(also) | ({span} if span else set())
    data = ProfileData.from_file(xplane_path)
    planes = sorted((p for p in data.planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
    if device >= len(planes):
        return None
    lines = {line.name: line for line in planes[device].lines}
    ns = 1e-9
    ops = [(e.start_ns * ns, (e.start_ns + e.duration_ns) * ns)
           for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ())]
    modules = sorted(
        (e.start_ns * ns, (e.start_ns + e.duration_ns) * ns)
        for e in (lines["XLA Modules"].events if "XLA Modules" in lines
                  else ()))
    notes = sorted(       # the listed host annotations: (start, end, name)
        (e.start_ns * ns, (e.start_ns + e.duration_ns) * ns, e.name)
        for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events if e.name in listed)
    dispatches = [n for n in notes if n[2] == "step.dispatch"]
    if not modules or not dispatches:
        return None

    # each program's dispatch: the last that began before the program did.
    # The clocks differ by far less than two dispatches lie apart, so half
    # the least distance between two dispatches (5 ms at most) is the slack
    starts = [d[0] for d in dispatches]
    slack = min([5e-3] + [0.5 * (b - a) for a, b in zip(starts, starts[1:])])
    paired = []
    for m in modules:
        i = bisect.bisect_right(starts, m[0] + slack) - 1
        if i >= 0:
            paired.append((m, dispatches[i]))
    if not paired:
        return None
    shift = max(0.0, max(d[0] - m[0] for m, d in paired))

    if span:
        whole = [n for n in notes if n[2] == span]
        if not whole:
            return None
        lo, hi, _ = max(whole, key=lambda n: n[1] - n[0])
    else:
        lo, hi = starts[0], max(m[1] for m in modules) + shift
    gaps, cur = [], lo          # the span less the union of the operations
    for a, b in sorted((a + shift, b + shift) for a, b in ops):
        if a >= hi:
            break
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))

    covers = [n for n in notes if n[2] != span]
    cover_starts = [n[0] for n in covers]
    longest = max((n[1] - n[0] for n in covers), default=0.0)
    by_phase: Dict[str, float] = {}
    for g_lo, g_hi in gaps:
        near = [n for n in covers[bisect.bisect_left(cover_starts,
                                                     g_lo - longest):
                                  bisect.bisect_left(cover_starts, g_hi)]
                if n[1] > g_lo]
        cuts = sorted({g_lo, g_hi, *(t for n in near for t in n[:2]
                                     if g_lo < t < g_hi)})
        for a, b in zip(cuts, cuts[1:]):
            inside = [n for n in near if n[0] <= a and n[1] >= b]
            name = (max(inside, key=lambda n: (n[0], -n[1]))[2]   # began last
                    if inside else OTHER)
            by_phase[name] = by_phase.get(name, 0.0) + b - a

    fetches = [n for n in notes if "fetch" in n[2]]
    calls = []
    for (m_lo, m_hi), d in paired:
        m_lo, m_hi = m_lo + shift, m_hi + shift
        waited = next((f for f in fetches if f[1] >= m_hi and f[1] > d[0]),
                      None)
        calls.append({
            "launch_s": m_lo - d[0], "step_s": m_hi - m_lo,
            "completion_s": None if waited is None else waited[1] - m_hi})
    return {"span_s": hi - lo, "idle_s": sum(b - a for a, b in gaps),
            "shift_s": shift, "by_phase": by_phase, "calls": calls}


def _print_idle(report: dict) -> None:
    print(f"device events shifted by {1e6 * report['shift_s']:.1f} us; span "
          f"{report['span_s']:.6f} s, idle {report['idle_s']:.6f} s "
          f"({100.0 * report['idle_s'] / report['span_s']:.2f} %)")
    idle = report["idle_s"]
    for name, seconds in sorted(report["by_phase"].items(),
                                key=lambda kv: -kv[1]):
        print(f"{name:24s} {seconds:12.6f} s "
              f"{100.0 * seconds / idle if idle else 0.0:6.2f} %")
    calls = report["calls"]
    print(f"{len(calls)} step programs: launch, device and completion "
          "milliseconds of each")
    for i, c in enumerate(calls):
        done = ("     -" if c["completion_s"] is None
                else f"{1e3 * c['completion_s']:10.3f}")
        print(f"{i:6d} {1e3 * c['launch_s']:10.3f} {1e3 * c['step_s']:12.3f} "
              f"{done}")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="python -m harp_tpu.telemetry.scopes",
                                 usage="\n       ".join(USAGE))
    ap.add_argument("trace")
    ap.add_argument("hlo", nargs="?")
    ap.add_argument("--idle", action="store_true")
    ap.add_argument("--span", default="")
    ap.add_argument("--also", default="")
    try:
        args = ap.parse_args(argv)
        if args.idle == (args.hlo is not None):
            ap.error("give the step's hlo text, or --idle")
    except SystemExit:
        return 2
    trace_dir = args.trace
    traces = ([trace_dir] if os.path.isfile(trace_dir) else sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)))
    if not traces:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    if args.idle:
        report = idle_by_phase(
            traces[-1], span=args.span,
            also=tuple(n for n in args.also.split(",") if n))
        if report is None:
            print(f"{traces[-1]} holds no step program under a "
                  "step.dispatch to read", file=sys.stderr)
            return 1
        _print_idle(report)
        return 0
    hlo_path = args.hlo
    with open(hlo_path) as fh:
        mapped = scope_map(fh.read())
    if not any(mapped.values()):
        print(f"{hlo_path} names no listed scope: the executable was loaded "
              "from a compile-cache entry an earlier build compiled (module "
              "docstring)", file=sys.stderr)
    by_scope = device_time_by_scope(traces[-1], mapped)
    whole = sum(by_scope.values())
    for scope, seconds in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        print(f"{scope or '(no scope)':24s} {seconds:12.6f} s "
              f"{100.0 * seconds / whole if whole else 0.0:6.2f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main())
