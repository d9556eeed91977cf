"""Share of the fan-outs in the traced window that left during the
tick's delivery phase: the program's ``hub/flush`` spans that sent to a
peer (``peers`` > 0) and lie inside a ``svc/flush`` span whose ``phase``
arg is ``deliver`` (a room's flush right after its own apply), over all
such ``hub/flush`` spans, as a fraction. A program that flushes every
room at the tick's end only reads 0. Nothing is read when the span ring
wrapped, the program has no tick child spans or no flush sent."""

import bisect

from loadbench.spans import window


def read(ctx):
    win = window()
    sent = [] if win is None else [r for r in win.spans("hub", "flush")
                                   if (r[5] or {}).get("peers")]
    if not sent:
        return None
    early = sorted((r[0], r[0] + r[1]) for r in win.spans("svc", "flush")
                   if (r[5] or {}).get("phase") == "deliver")
    starts = [lo for lo, _hi in early]
    inside = 0
    for ts, dur, *_rest in sent:
        i = bisect.bisect_right(starts, ts) - 1
        inside += i >= 0 and ts + dur <= early[i][1]
    return inside / len(sent)
