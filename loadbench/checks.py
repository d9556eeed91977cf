"""Reduce a run to its end-to-end metrics and decide ``correct``.

Visibility of a change: the arrival, at the transport of the LAST other
online peer of its room, of the first message carrying it, minus the
change's due time. A change that never arrives misses every limit.

``correct`` compares, against limits of 0 (the comparisons are exact):

- ``docs_mismatched``: documents whose element ids and values, as the
  device engine holds them, differ from the plain reference fed the
  changes the server's clock says it applied (every document the window
  touched, or a seed-drawn sample of them with the hottest included);
- ``fanout_missing``: (online peer, change) pairs where a change offered
  in its room by another peer never reached the peer by the end of the
  drain;
- ``fanout_altered``: received changes that differ from what was
  offered: the values of every received change, and every change whole
  on the dict wire and in a seed-drawn sample of the frames.
"""

from __future__ import annotations

import math

import numpy as np

from loadbench import reference
from loadbench.population import set_values


def percentile(values, p: float) -> float:
    """Nearest-rank percentile (the smallest value covering at least a
    share ``p`` of the samples)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(0, math.ceil(p * len(xs)) - 1)]


def visibility(traffic, first):
    """Per offered change: the time it became visible to every other
    online peer of its room (None if it never did)."""
    peers = traffic.peers
    by_room: dict = {}
    for i, spec in enumerate(peers):
        if spec["online"]:
            by_room.setdefault(spec["room_index"], []).append(i)
    offers = traffic.offers
    vis = []
    for ch, s in zip(offers.changes, offers.sender):
        key = (ch["actor"], ch["seq"])
        t_last = -math.inf
        for p in by_room.get(peers[s]["room_index"], ()):
            if p == s:
                continue
            t = first[p].get(key)
            if t is None:
                t_last = None
                break
            t_last = max(t_last, t)
        vis.append(None if t_last is None or t_last == -math.inf
                   else t_last)
    return vis


def end_to_end(traffic, vis, w0: float, w1: float, t_drained: float):
    """Window statistics. A change missing at the end of the drain counts
    at (end of drain - due), a lower bound of its visibility."""
    offers = traffic.offers
    in_win = [i for i, d in enumerate(offers.due) if w0 <= d < w1]
    lat_ms = []
    failed = 0
    for i in in_win:
        t = vis[i]
        if t is None:
            failed += 1
            t = t_drained
        lat_ms.append((t - offers.due[i]) * 1e3)
    done_ops = sum(n for n, t in zip(offers.nops, vis)
                   if t is not None and w0 <= t <= w1)
    return {"attempted": len(in_win), "failed": failed,
            "ops_per_s": done_ops / (w1 - w0),
            "visibility_p50_ms": percentile(lat_ms, 0.50),
            "visibility_p99_ms": percentile(lat_ms, 0.99)}


def fanout(traffic, first, values, dict_changes, frames, rng,
           frames_checked: int):
    """(missing pairs, altered changes) of the fan-out. Every received
    change's values are compared with what was offered; every dict-wire
    change, and every change of a seed-drawn sample of frames, whole."""
    peers = traffic.peers
    offered = {(c["actor"], c["seq"]): c for c in traffic.offers.changes}
    by_room: dict = {}
    for i, spec in enumerate(peers):
        if spec["online"]:
            by_room.setdefault(spec["room_index"], []).append(i)
    missing = 0
    for ch, s in zip(traffic.offers.changes, traffic.offers.sender):
        key = (ch["actor"], ch["seq"])
        for p in by_room.get(peers[s]["room_index"], ()):
            if p != s and key not in first[p]:
                missing += 1
    want = {}
    altered = 0
    for key, got in values:
        if key not in want:
            ch = offered.get(key)
            want[key] = None if ch is None else set_values(ch)
        if got != want[key]:
            altered += 1
    whole = [ch for _, ch in dict_changes]
    if frames:
        pick = rng.choice(len(frames), min(frames_checked, len(frames)),
                          replace=False)
        for k in sorted(pick.tolist()):
            whole.extend(frames[k][1].changes())
    altered += sum(1 for ch in whole
                   if _norm(ch) != _norm(offered.get((ch["actor"],
                                                      ch["seq"]))))
    return missing, altered


def _norm(ch):
    if ch is None:
        return None
    return (ch["actor"], ch["seq"], sorted(ch.get("deps", {}).items()),
            [tuple(sorted(op.items())) for op in ch["ops"]])


def pick_docs(traffic, rng, n_max: int, w0: float, w1: float) -> list:
    """Room indices to compare: every room the window touched, or a
    seed-drawn sample of ``n_max`` of them with the most-edited room
    always in it."""
    counts: dict = {}
    for s, due in zip(traffic.offers.sender, traffic.offers.due):
        if not w0 <= due < w1:
            continue
        r = traffic.peers[s]["room_index"]
        counts[r] = counts.get(r, 0) + 1
    touched = sorted(counts)
    if len(touched) <= n_max:
        return touched
    hottest = max(touched, key=lambda r: counts[r])
    rest = [r for r in touched if r != hottest]
    pick = rng.choice(len(rest), n_max - 1, replace=False)
    return sorted([hottest] + [rest[k] for k in pick.tolist()])


def docs_mismatched(traffic, engine: dict, room_changes: dict,
                    sibling_order: str = "rga") -> int:
    """Documents whose engine state differs from the reference fed the
    changes the server's clock covers. With ``sibling_order="arrival"``
    the control is put in the engine's place instead."""
    bad = 0
    for r, (clock, ids, vals) in engine.items():
        spec = traffic.rooms[r]
        applied = [c for c in room_changes[r]
                   if c["seq"] <= clock.get(c["actor"], 0)]
        known = {}
        for c in room_changes[r]:
            known[c["actor"]] = max(known.get(c["actor"], 0), c["seq"])
        if any(s > known.get(a, 0) for a, s in clock.items()):
            bad += 1            # the server holds changes nobody sent
            continue
        ref_ids, ref_vals = reference.order(applied, spec["obj"])
        if sibling_order != "rga":
            ids, vals = reference.order(applied, spec["obj"],
                                        sibling_order)
        if ids != ref_ids or vals != ref_vals:
            bad += 1
    return bad


def seeded_rng(seed: int, salt: int):
    return np.random.default_rng([seed, salt])
