"""Median wait between a room's apply and its fan-out, in ms: for each
of the program's ``hub/flush`` spans in the traced window that sent to
a peer (``peers`` > 0), its start less the end of the same room's latest
``svc/deliver`` that ended before it within the same ``svc/tick``. A
flush with no such delivery in its tick (one after clock reveals only)
is left out. Nothing is read when the span ring wrapped, the program has
no tick child spans or no flush follows a delivery."""

import bisect
import statistics

from loadbench.spans import window


def read(ctx):
    win = window()
    if win is None:
        return None
    ticks = sorted((r[0], r[0] + r[1]) for r in win.spans("svc", "tick"))
    tick_starts = [lo for lo, _hi in ticks]
    ends: dict = {}
    for r in win.spans("svc", "deliver"):
        ends.setdefault((r[5] or {}).get("room"), []).append(r[0] + r[1])
    for room_ends in ends.values():
        room_ends.sort()
    waits = []
    for ts, _dur, _cat, _name, _tid, args in win.spans("hub", "flush"):
        args = args or {}
        if not args.get("peers"):
            continue
        i = bisect.bisect_right(tick_starts, ts) - 1
        if i < 0 or ts > ticks[i][1]:
            continue
        room_ends = ends.get(args.get("room"), [])
        j = bisect.bisect_right(room_ends, ts) - 1
        if j < 0 or room_ends[j] < ticks[i][0]:
            continue
        waits.append(ts - room_ends[j])
    return statistics.median(waits) / 1e6 if waits else None
