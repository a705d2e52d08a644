"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

Read with ``jax.profiler.ProfileData`` alone. What a TPU trace holds (looked
at by hand on a v5e, PR 24): one plane ``/device:TPU:<n>`` per chip with the
lines ``XLA Modules`` (one event per execution of a compiled program),
``XLA Ops`` (every HLO instruction under XLA's own name, a ``while`` with its
body's instructions nested inside it) and ``Async XLA Ops`` (copies and
collectives from their ``-start`` to their ``-done``); and one plane
``/host:CPU`` whose thread lines carry the harness's ``TraceAnnotation``
spans. Times are nanoseconds on one axis, but the device's clock runs
some hundreds of microseconds off the host's: :func:`reduce` shifts the
device events by the least amount that puts every program's start inside the
host span that dispatched it.

No kernel of the program has a stable name yet, so the unit of device time is
the whole step program (an ``XLA Modules`` event); per-kernel rooflines wait
for named scopes (``PERF.md``, Open questions).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end) in seconds

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
_CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"(?<=\s)([a-z][a-z0-9\-]*)\(")
_NS = 1e-9


def opcode(hlo: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event name (the instruction text)."""
    _, _, rhs = hlo.partition(" = ")
    m = _OPCODE.search(" " + (rhs or hlo))
    return m.group(1) if m else ""


def short_name(hlo: str) -> str:
    return hlo.partition(" = ")[0].lstrip("%").strip()


def collective_kind(op: str) -> Optional[str]:
    for kind in COLLECTIVES:
        if op == kind or op == kind + "-start":
            return kind
    return None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of the union ``a`` that no interval of the union ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


@dataclasses.dataclass
class Event:
    name: str
    start: float        # seconds
    end: float


@dataclasses.dataclass
class DevicePlane:
    name: str
    modules: List[Event]
    ops: List[Event]
    async_ops: List[Event]


@dataclasses.dataclass
class Summary:
    """What the per-layer readers and the result line take from a trace."""
    window_s: float
    devices: int
    busy_s: float                         # mean over devices
    busy_s_fullest: float                 # the device with the most busy time
    step_s: List[float]                   # device time of each step program
    collective_s: Dict[str, float]        # by kind, mean over devices
    collective_exposed_s: float           # mean over devices
    device_ops: List[Tuple[str, float]]   # self time by name, longest first
    idle_gaps: List[Tuple[str, float]]    # idle seconds by host span


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns * _NS,
                  (e.start_ns + e.duration_ns) * _NS) for e in line.events]


def read_planes(path: str):
    """``(device planes, host spans by name)`` of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            devices.append(DevicePlane(
                plane.name,
                _events(lines["XLA Modules"]) if "XLA Modules" in lines else [],
                _events(lines["XLA Ops"]) if "XLA Ops" in lines else [],
                _events(lines["Async XLA Ops"])
                if "Async XLA Ops" in lines else []))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in _events(line):
                    host.setdefault(ev.name, []).append(ev)
    devices.sort(key=lambda d: d.name)
    return devices, host


def _self_times(ops: Sequence[Event]) -> Dict[str, float]:
    """Time of each instruction outside the instructions nested in it."""
    out: Dict[str, float] = {}
    stack: List[list] = []               # [event, time covered by children]
    for ev in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= ev.start:
            done, covered = stack.pop()
            name = short_name(done.name)
            out[name] = out.get(name, 0.0) + (done.end - done.start) - covered
        if stack:
            stack[-1][1] += min(ev.end, stack[-1][0].end) - ev.start
        stack.append([ev, 0.0])
    for done, covered in stack:
        name = short_name(done.name)
        out[name] = out.get(name, 0.0) + (done.end - done.start) - covered
    return out


def _clock_skew(modules: Sequence[Event], calls: Sequence[Event]) -> float:
    """Least shift of the device's events that starts every step program
    inside the host span that dispatched it (0 where they cannot be paired)."""
    if not modules or len(modules) != len(calls):
        return 0.0
    pairs = zip(sorted(modules, key=lambda e: e.start),
                sorted(calls, key=lambda e: e.start))
    return max(0.0, max(c.start - m.start for m, c in pairs))


def reduce(path: str, *, window: str = "window", dispatch: str = "call",
           spans: Sequence[str] = ()) -> Optional[Summary]:
    """Reduce one trace. ``window`` names the host span that brackets the
    measured window, ``dispatch`` the span inside which each step program is
    dispatched, ``spans`` the host spans idle gaps are attributed to (the
    innermost listed span that covers most of the gap; ``host_other`` where
    none does). Returns ``None`` when the trace has no device plane or no
    window span: there is nothing to read."""
    devices, host = read_planes(path)
    return summarise(devices, host, window=window, dispatch=dispatch,
                     spans=spans)


def summarise(devices: Sequence[DevicePlane], host: Dict[str, List[Event]], *,
              window: str = "window", dispatch: str = "call",
              spans: Sequence[str] = ()) -> Optional[Summary]:
    """:func:`reduce` on planes that are already read."""
    if not devices or window not in host:
        return None
    win = max(host[window], key=lambda e: e.end - e.start)
    lo, hi = win.start, win.end
    skew = _clock_skew(devices[0].modules, host.get(dispatch, []))

    def shifted(events):
        return [Event(e.name, e.start + skew, e.end + skew) for e in events]

    busy, exposed, steps = [], [], []
    kinds: Dict[str, float] = {}
    self_times: Dict[str, float] = {}
    gaps_by_span: Dict[str, float] = {}
    for n, dev in enumerate(devices):
        ops = shifted(dev.ops)
        codes = [opcode(e.name) for e in ops]
        op_union = clip(union((e.start, e.end) for e in ops), lo, hi)
        busy.append(total(op_union))
        compute = clip(union(
            (e.start, e.end) for e, c in zip(ops, codes)
            if c not in _CONTAINERS and collective_kind(c) is None
            and not any(c == k + "-done" for k in COLLECTIVES)), lo, hi)
        coll: List[Interval] = []
        for e in shifted(dev.async_ops) + [
                e for e, c in zip(ops, codes) if c in COLLECTIVES]:
            kind = collective_kind(opcode(e.name))
            if kind is None:
                continue
            span = clip([(e.start, e.end)], lo, hi)
            kinds[kind] = kinds.get(kind, 0.0) + total(span) / len(devices)
            coll += span
        exposed.append(total(subtract(union(coll), compute)))
        if n == 0:
            steps = [m.end - m.start for m in shifted(dev.modules)
                     if m.start >= lo and m.end <= hi]
            inside = [Event(e.name, max(e.start, lo), min(e.end, hi))
                      for e in ops if e.end > lo and e.start < hi]
            self_times = _self_times(inside)
            named = [e for name in spans for e in host.get(name, [])]
            for g_lo, g_hi in subtract([(lo, hi)], op_union):
                best, best_cover = "host_other", 0.0
                for e in named:
                    cover = min(e.end, g_hi) - max(e.start, g_lo)
                    if cover > best_cover:
                        best, best_cover = e.name, cover
                gaps_by_span[best] = gaps_by_span.get(best, 0.0) + g_hi - g_lo
    ranked = sorted(self_times.items(), key=lambda kv: -kv[1])
    return Summary(
        window_s=hi - lo, devices=len(devices),
        busy_s=sum(busy) / len(busy), busy_s_fullest=max(busy),
        step_s=steps, collective_s=kinds,
        collective_exposed_s=sum(exposed) / len(exposed),
        device_ops=[(k, v) for k, v in ranked if v > 0.0],
        idle_gaps=sorted(gaps_by_span.items(), key=lambda kv: -kv[1]))
