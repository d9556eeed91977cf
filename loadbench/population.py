"""Seed documents and the shared bookkeeping of offered changes.

A document is seeded with ONE bulk change typing its base text (the
program's bulk path, never one keystroke at a time). Offered changes are
recorded once, in offer order, so that the harness can time them and the
check can hand the reference exactly what was sent.
"""

from __future__ import annotations

import numpy as np

ROOT_OBJ = "00000000-0000-0000-0000-000000000000"
LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))


def seed_change(actor: str, obj_id: str, n_chars: int, rng,
                link_key: str = "text") -> dict:
    """One change that makes a text object, types ``n_chars`` random
    letters into it as one chained run, and links it under the root map
    — the shape a frontend emits for ``doc[link_key] = Text(...)``."""
    chars = LETTERS[rng.integers(0, 26, n_chars)].tolist()
    keys = [f"{actor}:{c}" for c in range(1, n_chars + 1)]
    ops = [{"action": "makeText", "obj": obj_id}]
    prev = "_head"
    for c, (key, ch) in enumerate(zip(keys, chars), start=1):
        ops.append({"action": "ins", "obj": obj_id, "key": prev, "elem": c})
        ops.append({"action": "set", "obj": obj_id, "key": key,
                    "value": ch})
        prev = key
    ops.append({"action": "link", "obj": ROOT_OBJ, "key": link_key,
                "value": obj_id})
    return {"actor": actor, "seq": 1, "deps": {}, "ops": ops}


class Offers:
    """Every change offered to the service, in offer order: its sender
    peer, wire ops, due time and the time it was handed to the
    transport. Times are ``time.perf_counter()`` seconds."""

    def __init__(self):
        self.changes: list = []     # the wire dicts, as sent
        self.sender: list = []      # peer index
        self.nops: list = []
        self.due: list = []
        self.injected: list = []

    def add(self, change: dict, sender: int, due: float, injected: float):
        self.changes.append(change)
        self.sender.append(sender)
        self.nops.append(len(change["ops"]))
        self.due.append(due)
        self.injected.append(injected)

    def __len__(self):
        return len(self.changes)


def set_values(change: dict) -> str:
    """The values a change's ``set`` ops assign, joined."""
    return "".join(op["value"] for op in change["ops"]
                   if op["action"] == "set")
