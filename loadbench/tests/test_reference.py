"""The plain reference agrees with the program's op-set oracle on
concurrent typing histories of the typing mix's shape, and the control
(siblings in arrival order) does not."""

import numpy as np
import pytest

from loadbench import reference
from loadbench.population import seed_change
from loadbench.traffic.open_typing import OpenTyping


def _oracle(changes, obj):
    from automerge_tpu.backend import facade
    state, _ = facade.apply_changes(facade.init(), changes)
    items = list(state.read_index().list_iterator(
        obj, lambda op: op["value"]))
    items = [it for it in items if "index" in it]
    return [it["elemId"] for it in items], [it["value"] for it in items]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_typing_rooms_match_oracle(seed):
    config = {"rooms": 3, "peers_per_room": 2, "base_chars": 40}
    mix = {"rate_changes_per_s": 30, "zipf_constant": 0.99,
           "burst": {"on_s": 1.0, "off_s": 1.0}, "warmup_s": 0,
           "drain_s": 1}
    t = OpenTyping(config, mix, seed, window_s=4)
    for _due, p, change in t.schedule:
        t.offers.add(change, p, 0.0, 0.0)
    for r, changes in t.room_changes().items():
        obj = t.rooms[r]["obj"]
        assert reference.order(changes, obj) == _oracle(changes, obj)
        if len(changes) > 2:
            assert reference.order(changes, obj, "arrival") \
                != reference.order(changes, obj)


def test_delivery_order_does_not_matter():
    rng = np.random.default_rng(9)
    base = seed_change("base", "t", 10, rng)
    a = [{"actor": "a", "seq": s, "deps": {"base": 1} if s == 1 else {},
          "ops": [{"action": "ins", "obj": "t", "key": f"base:{s}",
                   "elem": 10 + s},
                  {"action": "set", "obj": "t", "key": f"a:{10 + s}",
                   "value": "A"}]} for s in (1, 2)]
    b = [{"actor": "b", "seq": 1, "deps": {"base": 1},
          "ops": [{"action": "ins", "obj": "t", "key": "base:1",
                   "elem": 11},
                  {"action": "set", "obj": "t", "key": "b:11",
                   "value": "B"}]}]
    one = reference.order([base] + a + b, "t")
    two = reference.order([base] + b + a, "t")
    assert one == two == _oracle([base] + a + b, "t")
    assert reference.order([base] + a + b, "t", "arrival") \
        != reference.order([base] + b + a, "t", "arrival")
