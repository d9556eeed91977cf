"""The typing generator: the same work from seed to seed, drawn from the
seed."""

import numpy as np

from loadbench.traffic.open_typing import OpenTyping

ROOMS = {"rooms": 16, "peers_per_room": 2, "base_chars": 100}
TYPING = {"rate_changes_per_s": 40, "zipf_constant": 0.99,
          "burst": {"on_s": 1.0, "off_s": 1.0}, "warmup_s": 2,
          "drain_s": 1}


def _cycle_gaps(t, warmup=2.0, cycle=2.0):
    """Per on/off cycle, the sorted gaps from the cycle's start."""
    out = {}
    for due, _p, _c in t.schedule:
        c = int((due + warmup) // cycle)
        out.setdefault(c, []).append(due)
    gaps = {}
    for c, ds in out.items():
        start = -warmup + c * cycle
        gaps[c] = np.sort(np.diff([start] + sorted(ds)))
    return gaps


def test_typing_work_is_the_same_for_every_seed():
    a = OpenTyping(ROOMS, TYPING, 1, window_s=6)
    b = OpenTyping(ROOMS, TYPING, 2**33 + 5, window_s=6)
    assert len(a.schedule) == len(b.schedule) == 40 * 2 * 4
    assert [d for d, _p, _c in a.schedule] != \
        [d for d, _p, _c in b.schedule]
    ga, gb = _cycle_gaps(a), _cycle_gaps(b)
    assert ga.keys() == gb.keys()
    for c in ga:
        assert np.allclose(ga[c], gb[c], atol=1e-12)


def test_every_seed_loads_the_rooms_alike():
    def loads(seed):
        t = OpenTyping(ROOMS, TYPING, seed, window_s=6)
        rooms = [t.peers[p]["room_index"] for _d, p, _c in t.schedule]
        return rooms, sorted(np.bincount(rooms, minlength=16).tolist())
    (ra, la), (rb, lb) = loads(3), loads(2**35 + 9)
    assert la == lb
    assert ra != rb


def test_typing_is_seeded():
    a = OpenTyping(ROOMS, TYPING, 7, window_s=4)
    b = OpenTyping(ROOMS, TYPING, 7, window_s=4)
    assert a.schedule == b.schedule
    assert a.rooms[3]["seed"] == b.rooms[3]["seed"]


def test_max_room_inserts_counts_the_busiest_room():
    t = OpenTyping(ROOMS, TYPING, 11, window_s=6)
    counts = {}
    for _due, p, _c in t.schedule:
        r = t.peers[p]["room_index"]
        counts[r] = counts.get(r, 0) + 1
    assert t.max_room_inserts() == max(counts.values())


def test_a_cell_metric_is_read_by_its_base_name():
    from loadbench import spec
    a = spec.reader("plan_us_per_op.rooms")
    b = spec.reader("plan_us_per_op.another-cell")
    assert a.__file__ == b.__file__
    assert a.__file__.endswith("metrics/plan_us_per_op.py")
