"""Garbage-collection pauses of the serving process, in ms per second of
the traced window: the program's ``host/gc`` spans (one per collection,
every generation) clipped to the window, over its length. Nothing is
read when the span ring wrapped or the program has no tick child spans
(such a program records no collection either)."""

from loadbench.spans import window


def read(ctx):
    win = window()
    if win is None or not win.seconds:
        return None
    return win.busy_ns("host", "gc") / 1e6 / win.seconds
