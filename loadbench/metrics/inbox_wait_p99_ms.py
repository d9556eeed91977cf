"""p99 of the program's ``svc/inbox_wait`` spans in the traced window, in
ms: how long a change message waited in its tenant's inbox, from its
arrival at the server to the tick that admitted it. Nothing is read when
the span ring wrapped or the program has no such spans."""

from loadbench.checks import percentile
from loadbench.spans import window


def read(ctx):
    win = window()
    waits = [] if win is None else win.durations("svc", "inbox_wait")
    return percentile(waits, 0.99) / 1e6 if waits else None
