"""The two readers of when a room's fan-out leaves within the tick
(``flush_wait_p50_ms``, ``early_flush_share``) on a synthetic obs ring:
ticks that flush every room at the tick's end, ticks that flush each
room right after its last group, a room with two groups, a flush after
clock reveals only, and flushes that sent nothing (``peers`` 0), which
neither reader counts."""

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


def _flush(t, rooms, args):
    """A ``svc/flush`` span at ``t`` holding one 2 ms ``hub/flush`` per
    (room, peers) in ``rooms``; returns (records, end)."""
    out = []
    for i, (room, peers) in enumerate(rooms):
        out.append((t + 2 * i * MS, 2 * MS, "hub", "flush", 1,
                    {"room": room, "peers": peers, "frames": 1,
                     "bytes": 100}))
    dur = max(1, 2 * len(rooms)) * MS
    return [(t, dur, "svc", "flush", 1, args)] + out, t + dur


def _tick(t0, groups, early, tail=()):
    """One tick at ``t0`` ms: a 10 ms admission, one ``svc/deliver`` per
    (room, ms, peers) in ``groups``, 10 ms of sessions, the tick-end
    flush, 5 ms of lag. ``early``: each room flushes right after its
    last group, in a ``svc/flush`` of phase ``deliver`` (the tick-end
    one has phase ``tick``), else every room flushes at the tick's end
    in a ``svc/flush`` with no args. ``tail``: (room, peers) flushes at
    the tick's end with no group (clock reveals only)."""
    t = t0 * MS
    out = [(t, 10 * MS, "svc", "admit", 1, None)]
    now = t + 10 * MS
    last = {room: i for i, (room, _ms, _peers) in enumerate(groups)}
    pending = []
    for i, (room, ms, peers) in enumerate(groups):
        out.append((now, ms * MS, "svc", "deliver", 1, {"room": room}))
        now += ms * MS
        if last[room] != i:
            continue
        if early:
            recs, now = _flush(now, [(room, peers)],
                               {"room": room, "phase": "deliver"})
            out += recs
        else:
            pending.append((room, peers))
    out.append((now, 10 * MS, "svc", "sessions", 1, None))
    recs, now = _flush(now + 10 * MS, pending + list(tail),
                       {"phase": "tick"} if early else None)
    out += recs
    out.append((now, 5 * MS, "svc", "lag", 1, None))
    now += 5 * MS
    return [(t, now - t, "svc", "tick", 1, {"tick": t0})] + out


def _two_ticks(groups, early, tail=()):
    return _tick(1000, groups, early, tail) + _tick(1200, groups, early, tail)


THREE = [("a", 20, 2), ("b", 20, 2), ("c", 20, 0)]
TWO_GROUPS = [("a", 20, 2), ("b", 20, 2), ("a", 20, 2)]

CASES = {
    # a waits 80 - 30, b 82 - 50; c sent nothing
    "tick_end": (_two_ticks(THREE, early=False), 41.0, 0.0),
    "in_delivery": (_two_ticks(THREE, early=True), 0.0, 1.0),
    # a's latest group ends at 70: a waits 80 - 70, b 82 - 50
    "two_groups_tick_end": (_two_ticks(TWO_GROUPS, early=False), 21.0, 0.0),
    "two_groups_in_delivery": (_two_ticks(TWO_GROUPS, early=True), 0.0, 1.0),
    # d's flush has no group in its tick: no wait, but a late fan-out
    "reveal_only": (_two_ticks([("a", 20, 2)], early=True,
                               tail=[("d", 1)]), 0.0, 0.5),
    "sent_nothing": (_two_ticks([("a", 20, 0)], early=False), None, None),
}


@pytest.fixture
def ring_cleared():
    yield
    obs.recorder().clear()


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("metric", ["flush_wait_p50_ms.rooms",
                                    "early_flush_share.rooms"])
def test_flush_readers(ring_cleared, case, metric):
    records, wait_ms, share = CASES[case]
    _ring(records)
    want = wait_ms if metric.startswith("flush_wait") else share
    got = spec.reader(metric).read({"admitted_ops": 40})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
