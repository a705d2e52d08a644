"""The reduction from a profiler trace to numbers: interval arithmetic on
made-up planes, and the numbers of one small trace recorded on a v5e chip
(``data/kmeans_v5e_1chip.xplane.pb``: the K-means cell, one job, PR 24)."""

import os

import pytest

from benchmark import trace_reduce as tr
from tests.benchmark import tiny

Event, Plane = tr.Event, tr.DevicePlane


def test_union_subtract_clip():
    u = tr.union([(3, 4), (0, 1), (0.5, 2), (5, 5)])
    assert u == [(0, 2), (3, 4)] and tr.total(u) == 3
    assert tr.clip(u, 1, 3.5) == [(1, 2), (3, 3.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]
    assert tr.subtract([(0, 1)], [(0, 1)]) == []


@pytest.mark.parametrize("text, op, name", [
    ("%multiply_reduce_fusion.1 = (f32[]{:T(128)}, bf16[8000000,128]{1,0:T(8,128)(2,1)}) fusion(f32[8000000,128] %p), kind=kLoop",
     "fusion", "multiply_reduce_fusion.1"),
    ("%copy-start = (f32[128,128]{1,0:T(8,128)S(1)}, f32[128,128]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(f32[128,128]{1,0:T(8,128)} %get-tuple-element",
     "copy-start", "copy-start"),
    ("%collective-permute-start = (f32[10681,100]{0,1:T(8,128)S(1)}, u32[]{:S(2)}) collective-permute-start(f32[10681,100] %while",
     "collective-permute-start", "collective-permute-start"),
    ("%all-reduce.3 = f32[]{:T(128)} all-reduce(f32[] %x), replica_groups={}",
     "all-reduce", "all-reduce.3"),
    ("%while = (s32[]{:T(128)}, f32[5]{0:T(128)}) while((s32[], f32[5]) %tuple), condition=%c",
     "while", "while"),
])
def test_opcode_and_name_of_an_xla_op_event(text, op, name):
    assert tr.opcode(text) == op and tr.short_name(text) == name


def test_collective_kinds():
    assert tr.collective_kind("all-reduce") == "all-reduce"
    assert tr.collective_kind("collective-permute-start") == "collective-permute"
    assert tr.collective_kind("collective-permute-done") is None
    assert tr.collective_kind("fusion") is None


def _op(name, code, start, end):
    return Event(f"%{name} = f32[8]{{0}} {code}(f32[8] %x)", start, end)


def test_self_time_leaves_out_nested_instructions():
    ops = [_op("while", "while", 0, 10), _op("a", "fusion", 1, 4),
           _op("b", "fusion", 5, 9), _op("c", "fusion", 12, 13)]
    assert tr._self_times(ops) == {"while": 3, "a": 3, "b": 4, "c": 1}


def _planes():
    """Two chips, one step each from 1 s to 9.5 s: compute 1..4 and 6..9, a
    collective-permute in flight 3..6 (hidden to 4, exposed 4..6) and an
    all-reduce 9..9.5 with nothing beside it."""
    def plane(n):
        return Plane(f"/device:TPU:{n}",
                     [Event("jit_step(1)", 1.0, 9.5)],
                     [_op("while", "while", 1, 9.5), _op("f1", "fusion", 1, 3),
                      _op("cps", "collective-permute-start", 3, 3.001),
                      _op("f1b", "fusion", 3.001, 4),
                      _op("cpd", "collective-permute-done", 4, 6),
                      _op("f2", "fusion", 6, 9),
                      _op("ar", "all-reduce", 9, 9.5)],
                     [_op("cps", "collective-permute-start", 3, 6)])
    host = {"window": [Event("window", 0.0, 12.0)],
            "call": [Event("call", 0.5, 0.9)],
            "fetch_quality": [Event("fetch_quality", 0.9, 10.0)],
            "job_reset": [Event("job_reset", 10.0, 11.5)]}
    return [plane(0), plane(1)], host


def test_summary_of_made_up_planes():
    devices, host = _planes()
    s = tr.summarise(devices, host,
                     spans=("call", "fetch_quality", "job_reset"))
    assert s.devices == 2 and s.window_s == 12.0
    assert s.busy_s == pytest.approx(8.5) == s.busy_s_fullest
    assert s.step_s == [pytest.approx(8.5)]
    assert s.collective_s == {"collective-permute": pytest.approx(3.0),
                              "all-reduce": pytest.approx(0.5)}
    # exposed: its own issue (1 ms), 4..6 of the permute, all the all-reduce
    assert s.collective_exposed_s == pytest.approx(2.501)
    assert dict(s.device_ops)["f1"] == pytest.approx(2.0)
    assert "while" not in dict(s.device_ops)     # all of it is its body
    assert dict(s.device_ops)["cpd"] == pytest.approx(2.0)
    # idle: 0..1 (the dispatch covers most of it), 9.5..12 (mostly job_reset)
    assert dict(s.idle_gaps) == {"call": pytest.approx(1.0),
                                 "job_reset": pytest.approx(2.5)}


def test_device_clock_is_shifted_to_start_each_step_inside_its_dispatch():
    devices, host = _planes()
    host["call"] = [Event("call", 1.25, 1.3)]     # the device seems early
    s = tr.summarise(devices, host, spans=("call",))
    assert s.step_s == [pytest.approx(8.5)]
    assert s.busy_s == pytest.approx(8.5)
    # everything moved 0.25 s later: the leading gap is now 1.25 s
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(12.0 - 8.5)


def test_nothing_to_read_is_none():
    devices, host = _planes()
    assert tr.summarise([], host) is None
    assert tr.summarise(devices, {"call": host["call"]}) is None


@pytest.fixture(scope="module")
def recorded():
    assert os.path.getsize(tiny.TRACE) < 2 ** 20
    return tr.reduce(tiny.TRACE, spans=("call", "fetch_quality", "job_reset"))


def test_recorded_trace_pins_its_numbers(recorded):
    s = recorded
    assert s.devices == 1
    assert 0.0 < s.busy_s <= s.window_s
    assert s.busy_s == s.busy_s_fullest
    assert s.collective_s == {} and s.collective_exposed_s == 0.0
    assert len(s.step_s) == PINNED["steps"]
    assert s.window_s == pytest.approx(PINNED["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(PINNED["busy_s"], rel=1e-9)
    assert sum(s.step_s) == pytest.approx(PINNED["step_sum_s"], rel=1e-9)
    assert s.device_ops[0][0] == PINNED["top_op"]
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert {k for k, _ in s.idle_gaps} <= {"call", "fetch_quality",
                                           "job_reset", "host_other"}


PINNED = {"steps": 8, "window_s": 1.133600063, "busy_s": 1.1169545619999994,
          "step_sum_s": 1.1169569970000004, "top_op": "fusion.19"}
