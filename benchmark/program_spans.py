"""The program's own host phases (``harp_tpu.telemetry.phase``) of *this run*,
for the per-layer readers that take their numbers from them.

The program keeps its phases in a ring on ``time.perf_counter()``, the clock
the harness's spans are on, so a run's phases are those between the start of
the harness's first span and the end of its window: a process that ran another
cell before (a test process does) must not count that cell's phases. A program
that keeps no such ring (the commit before the phases existed) gives ``None``,
and every reader then reports nothing.
"""

from __future__ import annotations

from typing import List, Optional

PLACE = "session.place"
RUN = "session.run"
DISPATCH = "step.dispatch"
TRACE_MARK = "program.trace"


def _telemetry():
    try:
        from harp_tpu import telemetry
    except ImportError:
        return None
    return telemetry if hasattr(telemetry, "phases") else None


def _between(ctx, since, until) -> Optional[list]:
    tele = _telemetry()
    if tele is None or not ctx.spans.records:
        return None
    run_start = ctx.spans.records[0][1]      # the harness's first span
    return tele.phases(run_start if since is None else since, until)


def setup_phases(ctx) -> Optional[list]:
    """The phases from the run's start to the start of its window."""
    return _between(ctx, None, ctx.window.start)


def window_phases(ctx) -> Optional[list]:
    """The phases inside the measured window."""
    return _between(ctx, ctx.window.start, ctx.window.end)


def run_phases(ctx) -> Optional[list]:
    """The phases from the run's start to the end of its window."""
    return _between(ctx, None, ctx.window.end)


def prepare_roots(records: list) -> List:
    """The models' ``<model>.prepare`` phases (roots: nothing encloses them)."""
    return [r for r in records
            if r.parent is None and r.name.endswith(".prepare")]


def prepare_children_s(ctx, name: str) -> Optional[float]:
    """Seconds of the phases called ``name`` directly under ``*.prepare``."""
    records = setup_phases(ctx)
    roots = {r.id for r in prepare_roots(records or [])}
    if not roots:
        return None
    return sum(r.end - r.start for r in records
               if r.name == name and r.parent in roots)


def prepare_self_s(ctx) -> Optional[float]:
    """Seconds of ``*.prepare`` outside its children: the host's own work."""
    records = setup_phases(ctx)
    roots = prepare_roots(records or [])
    if not roots:
        return None
    tele = _telemetry()
    return sum(tele.self_seconds(records, name)
               for name in {r.name for r in roots})
