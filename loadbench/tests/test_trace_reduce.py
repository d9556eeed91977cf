"""The trace reduction on synthetic intervals and on a small trace
recorded on the CPU (tests/data/cpu_window.xplane.pb, made by
make_trace.py)."""

import os

import pytest

from loadbench import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cpu_window.xplane.pb")


def _ops(*spans, dev=0):
    return [(s, e, f"op{i}", "m", dev) for i, (s, e) in enumerate(spans)]


def test_overlapping_ops_count_once():
    ann = [(0, 100, "window")]
    r = T.reduce(_ops((10, 30), (20, 40), (35, 50), (60, 70)), 1, ann)
    assert r["busy_ns"] == 50           # [10, 50) + [60, 70)
    assert sum(r["op_ns"].values()) == 20 + 20 + 15 + 10


def test_ops_clipped_to_the_window():
    ann = [(100, 200, "window")]
    r = T.reduce(_ops((50, 150), (190, 260)), 1, ann)
    assert r["busy_ns"] == 60
    assert r["window_ns"] == 100


def test_empty_window_is_all_idle():
    ann = [(0, 100, "window"), (0, 100, "svc.tick")]
    r = T.reduce(_ops((200, 300)), 1, ann)
    assert r["busy_ns"] == 0 and r["op_ns"] == {}
    assert r["idle_gaps"] == [("svc.tick", 100)]


def test_busy_is_averaged_over_devices():
    ann = [(0, 100, "window")]
    ops = _ops((0, 100), dev=0) + _ops((0, 50), dev=1)
    assert T.reduce(ops, 2, ann)["busy_ns"] == 75


def test_gaps_go_to_the_innermost_enclosing_phase():
    ann = [(0, 100, "window"), (0, 60, "svc.tick"), (60, 100, "pump"),
           (20, 40, "inject")]
    r = T.reduce(_ops((0, 10), (45, 50)), 1, ann)
    gaps = dict((n, ns) for n, ns in r["idle_gaps"])
    assert r["idle_gaps"][0] == ("pump", 50)       # [50, 100): longest
    assert gaps["inject"] == 35                     # [10, 45) mid 27
    assert len(r["idle_gaps"]) == 2


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        T.reduce(_ops((0, 1)), 1, [(0, 5, "pump")])


def test_recorded_cpu_trace():
    ops, n_dev, ann = T.read(DATA)
    names = {a[2] for a in ann}
    assert {"window", "inject", "pump", "svc.tick"} <= names
    assert ops and n_dev == 1
    r = T.reduce(ops, n_dev, ann)
    assert 0 < r["busy_ns"] <= r["window_ns"]
    assert r["busy_ns"] <= sum(r["op_ns"].values())
    # the 50 ms host sleep is the longest gap, inside `pump`
    name, ns = r["idle_gaps"][0]
    assert name == "pump" and ns >= 40_000_000
    assert r["device_ops"][0][0] == "jit__lambda/dot_general.1"
    assert set(r["module_ns"]) == {"jit__lambda"}


class _Ev:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, lines):
        self.lines = lines


def test_device_ops_are_named_by_their_enclosing_program():
    plane = _Plane([
        _Line("XLA Modules", [_Ev("jit_merge(12)", 0, 100),
                              _Ev("jit_pull(13)", 200, 50)]),
        _Line("XLA Ops", [_Ev("%fusion.3 = s32[8] fusion(...)", 10, 20),
                          _Ev("%copy.1 = s32[8] copy(...)", 210, 5),
                          _Ev("%sort = s32[8] sort(...)", 150, 5)]),
        _Line("Steps", [_Ev("0", 0, 300)])])
    ops = T._device_ops(plane, 0)
    assert [(n, m) for _s, _e, n, m, _d in ops] == [
        ("fusion.3", "jit_merge"), ("copy.1", "jit_pull"), ("sort", None)]
    r = T.reduce(ops, 1, [(0, 300, "window")])
    assert r["module_ns"] == {"jit_merge": 20, "jit_pull": 5}
    assert r["op_ns"]["jit_merge/fusion.3"] == 20 and r["op_ns"]["sort"] == 5
