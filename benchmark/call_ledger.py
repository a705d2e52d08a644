"""The call ledger: every training call of the traced window accounted for
three ways, paired in order, and the compile path's records of the set-up.

The harness deletes the profiler's trace before the readers run; what is left
of it is ``ctx.trace.step_s``, the device's time of each step program. The
three lists of one window are

* the harness's own: ``ctx.window.call_s[i]`` and its ``call`` and
  ``fetch_quality`` spans (``ctx.spans.records``, on ``time.perf_counter()``);
* the program's: the ``<model>.call`` root of each call with its
  ``step.dispatch`` and, where the model fetches its own quality,
  ``step.fetch`` (``program_spans.window_phases``, the same clock);
* the device's: ``ctx.trace.step_s[i]``.

Every cell's driver runs exactly one program a call and none at a job's
reset, so the three are equally long. Where they are not, where the program's
ring let go of a record of this run, where there is no trace, or where the
trace cannot be this window's (a program that took longer on the device than
the call that waited for it), :func:`calls` is ``None`` and every reader of
the window's calls reports nothing, never a number. The set-up's records need
no trace and no pairing: only that the ring kept the run's.

One call, from the start of the harness's ``call`` span to the start of the
next one (the window's end for the last): ``period = dispatch + wait + host``,
where ``wait`` is the program's ``step.fetch`` or, where it has none (K-means
returns at the enqueue), the harness's ``fetch_quality`` of the same call, and
``host`` is all the rest: the ``<model>.call``'s self time and the harness's
loop. With the device's ``step`` of that call: ``overhead = period - step``
(the device's idle time a call in a closed loop) ``= roundtrip + host``,
``roundtrip = dispatch + wait - step`` (launch and completion latency).

The functions at the end are the readers of six per-layer metrics, named as
the metrics. No entry of ``BENCHMARK.json`` lists them yet: an entry appended
to ``per_layer`` fails accepted tests that only a ``benchmark`` PR may edit
(``PERF.md`` section 7, row 10 (h)), which then adds, for each, the entry and
``benchmark/metrics/<name>.py`` with
``from benchmark.call_ledger import <name> as read``.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from benchmark import program_spans

CALL, FETCH_QUALITY = "call", "fetch_quality"      # the harness's spans
FETCH = "step.fetch"
# a call this much longer than the window's median call is a stall
# (PERF.md section 2: stalls are 20-370 ms)
STALL_S = 0.020
# a step program cannot outlast the call that waited for it; the clocks of
# host and device agree far better than this
_SLACK_S = 1e-3


@dataclasses.dataclass
class Call:
    call_s: float          # the harness's: dispatch to the fetched quality
    period_s: float        # start of this call to the start of the next
    step_s: float          # the device's time of the call's step program
    dispatch_s: float      # the program's step.dispatch
    wait_s: float          # step.fetch, or the harness's fetch_quality

    @property
    def overhead_s(self) -> float:
        return self.period_s - self.step_s

    @property
    def roundtrip_s(self) -> float:
        return self.dispatch_s + self.wait_s - self.step_s

    @property
    def host_s(self) -> float:
        return self.period_s - self.dispatch_s - self.wait_s


def _host_spans():
    """The program's ring module, or ``None`` where the checkout has none."""
    try:
        from harp_tpu.telemetry import host_spans
    except ImportError:
        return None
    return host_spans


def _ring_kept_this_run(ctx) -> bool:
    """Whether every record the program left since the run's start is still
    in its ring: nothing was dropped, or the oldest record kept is older than
    the run (the ring lets go of the oldest first)."""
    ring = _host_spans()
    if ring is None:
        return False
    if not ring.dropped():
        return True
    kept = ring.phases()
    return bool(kept) and kept[0].end < ctx.spans.records[0][1]


def calls(ctx) -> Optional[List[Call]]:
    """The window's calls, paired (module docstring), or ``None``."""
    if ctx.trace is None or not ctx.spans.records:
        return None
    inside = program_spans.window_phases(ctx)
    if inside is None or not _ring_kept_this_run(ctx):
        return None
    win = ctx.window
    spans = {name: sorted((lo, hi) for n, lo, hi in ctx.spans.records
                          if n == name and lo >= win.start and hi <= win.end)
             for name in (CALL, FETCH_QUALITY)}
    roots = sorted((r for r in inside
                    if r.parent is None and r.name.endswith(".call")),
                   key=lambda r: r.start)
    steps = list(ctx.trace.step_s)
    n = len(win.call_s)
    if not n or {len(spans[CALL]), len(spans[FETCH_QUALITY]), len(roots),
                 len(steps)} != {n}:
        return None
    children = {}
    for r in inside:
        if r.name in (program_spans.DISPATCH, FETCH):
            children.setdefault(r.parent, {}).setdefault(r.name, []).append(r)
    starts = [lo for lo, _ in spans[CALL]] + [win.end]
    out = []
    for i, root in enumerate(roots):
        mine = children.get(root.id, {})
        if len(mine.get(program_spans.DISPATCH, ())) != 1 \
                or len(mine.get(FETCH, ())) > 1:
            return None
        c_lo, c_hi = spans[CALL][i]
        if not c_lo <= root.start <= root.end <= c_hi:
            return None
        (dispatch,) = mine[program_spans.DISPATCH]
        dispatch_s = dispatch.end - dispatch.start
        fetch_s = sum(f.end - f.start for f in mine.get(FETCH, ()))
        f_lo, f_hi = spans[FETCH_QUALITY][i]
        if steps[i] > win.call_s[i] + _SLACK_S:
            return None                  # not this window's trace
        out.append(Call(
            call_s=win.call_s[i], period_s=starts[i + 1] - starts[i],
            step_s=steps[i], dispatch_s=dispatch_s,
            wait_s=fetch_s if FETCH in mine else f_hi - f_lo))
    return out


def _median_ms(ctx, of) -> Optional[float]:
    """1e3 x the median over the window's calls of ``of(call)``."""
    import numpy as np

    ledger = calls(ctx)
    if ledger is None:
        return None
    return 1e3 * float(np.median([of(c) for c in ledger]))


def stall(ledger: List[Call]) -> Optional[Call]:
    """The ledger's longest call where it took :data:`STALL_S` or more over
    the median call, else ``None``."""
    import numpy as np

    longest = max(ledger, key=lambda c: c.call_s)
    if longest.call_s - float(np.median([c.call_s for c in ledger])) < STALL_S:
        return None
    return longest


def setup_union_s(ctx, name: str) -> Optional[float]:
    """Seconds the program's records called ``name`` (``program.lower``,
    ``program.compile``, ``program.cache_load``) cover together from the
    run's start to the window's start: the union, because jax reports a
    nested trace inside its caller's. Needs no trace. ``None`` where the
    program keeps no such records (the commit before them), where its ring
    let go of a record of this run, and where no record of the name fell
    there (a cold start loads nothing from the cache)."""
    ring = _host_spans()
    if name not in getattr(ring, "PHASES", ()) or not ctx.spans.records \
            or not _ring_kept_this_run(ctx):
        return None
    records = program_spans.setup_phases(ctx)
    if not any(r.name == name for r in records or ()):
        return None
    return ring.union_seconds(records, name)


# --------------------------------------------------------------------------- #
# the readers (module docstring), each ``read(ctx)`` of the metric of its name
# --------------------------------------------------------------------------- #

def program_lower_s(ctx) -> Optional[float]:
    """Set-up seconds inside jax's tracing and lowering (a Pallas kernel's
    Mosaic lowering included), which a persistent compile cache does not
    shorten: jax traces and lowers before it can ask the cache. Layer:
    compile cache / AOT; moves ``setup_s``; ``program_span``."""
    return setup_union_s(ctx, "program.lower")


def program_cache_load_s(ctx) -> Optional[float]:
    """Set-up seconds inside retrievals from the persistent compile cache;
    nothing where none was loaded. Layer: compile cache / AOT; moves
    ``setup_s``; ``program_span``."""
    return setup_union_s(ctx, "program.cache_load")


def call_overhead_ms(ctx) -> Optional[float]:
    """The device's idle time a call in a closed loop: the median of the
    call's period less its step program. Layer: launcher / session; moves
    ``samples_per_s``; ``device_trace``."""
    return _median_ms(ctx, lambda c: c.overhead_s)


def call_roundtrip_ms(ctx) -> Optional[float]:
    """Launch plus completion latency, the runtime's share of the overhead:
    the median of ``step.dispatch`` + wait - step. Layer: launcher / session;
    moves ``samples_per_s``; ``device_trace``."""
    return _median_ms(ctx, lambda c: c.roundtrip_s)


def call_host_ms(ctx) -> Optional[float]:
    """The host's own work a call while the device idles: the median of the
    period less dispatch and wait, which is the ``<model>.call``'s self time
    (the program's epilogue) plus the harness's loop. Layer: launcher /
    session; moves ``samples_per_s``; ``program_span``."""
    return _median_ms(ctx, lambda c: c.host_s)


def stall_host_ms(ctx) -> Optional[float]:
    """What the window's longest call spent outside the device, only where
    that call stalled (:func:`stall`), else nothing. Near
    ``call_overhead_ms``: the device itself ran long. Near the call's excess:
    the completion was told late, or the host was not running us. Layer:
    launcher / session; moves ``call_ms_p95``; ``device_trace``."""
    ledger = calls(ctx)
    stalled = stall(ledger) if ledger else None
    return None if stalled is None else 1e3 * stalled.overhead_s
