"""The two readers of the text patch emission's ``backend/diff`` spans
(``diff_us_per_op``, ``diff_touched_share``) on a synthetic obs ring, and
their silence on a program that has no such span."""

import pytest

from automerge_tpu import obs
from loadbench import spec

MS = 1_000_000


def _ring(records):
    """A fresh obs ring holding exactly ``records``, tracing off."""
    obs.enable(capacity=1 << 12)
    obs.disable()
    rec = obs.recorder()
    rec.clear()
    for r in records:
        rec.emit(r)


def _tick(t0, diffs):
    """One 100 ms tick at t0 ms with one 40 ms room apply; inside it one
    ``backend/diff`` span of 2 ms per (mode) in ``diffs``."""
    t = t0 * MS
    out = [(t, 100 * MS, "svc", "tick", 1, {"tick": t0}),
           (t, 10 * MS, "svc", "admit", 1, None),
           (t + 10 * MS, 40 * MS, "svc", "deliver", 1, {"room": "r"})]
    for i, mode in enumerate(diffs):
        out.append((t + (12 + 3 * i) * MS, 2 * MS, "backend", "diff", 1,
                    {"mode": mode, "k": 2}))
    return out


def _read(metric):
    return spec.reader(metric).read({"admitted_ops": 40})


@pytest.fixture
def ring_cleared():
    yield
    obs.recorder().clear()


@pytest.mark.parametrize("metric,value", [
    ("diff_us_per_op.rooms", 8 * 2 * MS / 1e3 / 40),
    ("diff_touched_share.rooms", 7 / 8),
])
def test_diff_readers(ring_cleared, metric, value):
    _ring(_tick(1000, ["touched"] * 4)
          + _tick(1100, ["touched", "full", "touched", "touched"]))
    assert _read(metric) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["diff_us_per_op.rooms",
                                    "diff_touched_share.rooms"])
def test_diff_readers_read_nothing_without_the_span(ring_cleared, metric):
    """A program without the ``backend/diff`` span, as before the
    touched-slot emission, gives no reading and raises nothing."""
    _ring(_tick(1000, []) + _tick(1100, []))
    assert _read(metric) is None
