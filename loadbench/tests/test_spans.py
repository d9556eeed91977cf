"""The program-span side of a traced run (``loadbench/spans.py``): the
window the readers take from the obs ring, the five span readers on a
synthetic ring, the clock map and the idle-gap naming."""

import pytest

from automerge_tpu import obs
from loadbench import spans, spec

MS = 1_000_000


def _ring(records):
    """A fresh obs ring holding exactly ``records``, tracing off."""
    obs.enable(capacity=1 << 12)
    obs.disable()
    rec = obs.recorder()
    rec.clear()
    for r in records:
        rec.emit(r)
    return rec


def _tick(t0, n, tid=1):
    """One 100 ms tick at t0 ms: admit 10, a 40 ms deliver with a 10 ms
    gc inside, sessions 20, flush 15, lag 10; plus an inbox wait of
    ``n`` ms that ends at the admission."""
    t = t0 * MS
    return [
        (t, 100 * MS, "svc", "tick", tid, {"tick": t0}),
        (t, 10 * MS, "svc", "admit", tid, None),
        (t + 10 * MS, 40 * MS, "svc", "deliver", tid, {"room": "r"}),
        (t + 20 * MS, 10 * MS, "host", "gc", tid, {"generation": 0}),
        (t + 50 * MS, 20 * MS, "svc", "sessions", tid, None),
        (t + 70 * MS, 15 * MS, "svc", "flush", tid, None),
        (t + 85 * MS, 10 * MS, "svc", "lag", tid, None),
        (t + 10 * MS - n * MS, n * MS, "svc", "inbox_wait", tid,
         {"room": "r", "tick": t0}),
    ]


def _read(metric, ctx):
    return spec.reader(metric).read(ctx)


@pytest.fixture
def two_ticks():
    # a gc before the window, during start_trace, is left out
    _ring([(1 * MS, 5 * MS, "host", "gc", 1, {"generation": 2})]
          + _tick(1000, 30) + _tick(1100, 50))
    yield
    obs.recorder().clear()


@pytest.mark.parametrize("metric,value", [
    ("inbox_wait_p99_ms.rooms", 50.0),
    ("apply_us_per_op.rooms", 80 * MS / 1e3 / 40),
    ("fanout_us_per_op.rooms", 30 * MS / 1e3 / 40),
    ("session_pass_ms_per_tick.rooms", 30.0),
    ("gc_ms_per_s.rooms", 20.0 / 0.2),
])
def test_span_readers(two_ticks, metric, value):
    assert _read(metric, {"admitted_ops": 40}) == pytest.approx(value)


@pytest.mark.parametrize("metric", [
    "inbox_wait_p99_ms.rooms", "apply_us_per_op.rooms",
    "fanout_us_per_op.rooms", "session_pass_ms_per_tick.rooms",
    "gc_ms_per_s.rooms"])
def test_span_readers_read_nothing_without_the_spans(metric):
    """A program whose tick has no child spans (only ``svc/tick``) and a
    ring that wrapped both give no reading, and raise nothing."""
    _ring([(0, 100 * MS, "svc", "tick", 1, None),
           (0, 10 * MS, "plan", "decode", 1, None)])
    assert _read(metric, {"admitted_ops": 40}) is None
    obs.enable(capacity=16)
    obs.disable()
    rec = obs.recorder()
    for r in _tick(0, 5) * 3:
        rec.emit(r)
    assert rec.n_emitted > rec.n_retained
    assert _read(metric, {"admitted_ops": 40}) is None


def test_window_is_the_ticks_extent(two_ticks):
    win = spans.window()
    assert (win.lo, win.hi, win.ticks) == (1000 * MS, 1200 * MS, 2)
    assert win.seconds == pytest.approx(0.2)


def test_clock_map_is_linear_through_the_anchors():
    clock = spans.to_trace([(1_000, 50_000), (2_001_000, 2_050_000)])
    assert clock(50_000) == 1_000 and clock(1_050_000) == 1_001_000
    shifted = spans.to_trace([(10, 110)])
    assert shifted(200) == 100
    with pytest.raises(ValueError):
        spans.to_trace([])


def test_gaps_named_by_the_span_holding_most_of_them():
    """A gap inside a harness phase goes to the innermost program span
    that holds most of it: not to the phase, and not to a short gc inside
    the patch that holds less of the gap than the patch's own time."""
    phases = [(0, 1000, "svc.tick"), (1000, 1100, "pump")]
    program = [(5, 995, "svc/tick"), (5, 400, "svc/deliver"),
               (60, 300, "frontend/patch"), (100, 160, "host/gc"),
               (400, 900, "svc/flush"), (410, 890, "hub/flush")]
    idle = [(50, 350),      # inside deliver: patch holds 240 of 300
            (380, 980),     # flush holds 500 of 600, deliver 20
            (995, 1100),    # the tick's tail and the pump
            (2000, 2100)]   # outside every span
    names = spans.name_gaps(idle, phases + program)
    assert names == [("frontend/patch", 300), ("hub/flush", 600),
                     ("pump", 105), ("other", 100)]


def test_equal_spans_do_not_loop():
    same = [(0, 10, "svc.tick"), (0, 10, "svc/tick"), (2, 8, "svc/lag")]
    assert spans.name_gaps([(1, 9)], same) == [("svc/lag", 8)]


def test_trace_spans_name_compiles_and_leave_out_waits():
    rec = (0, 5, "device", "compile", 1, {"kernel": "materialize/plain"})
    assert spans.span_name(rec) == "device/compile:materialize/plain"
    wait = (0, 9, "svc", "inbox_wait", 1, {"tick": 3})
    assert spans.on_trace([rec, (0, -1, "svc", "shed", 1, None), wait],
                          lambda t: t + 7) == [
        (7, 12, "device/compile:materialize/plain")]
