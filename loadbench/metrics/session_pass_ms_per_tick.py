"""Time a tick spends in its passes over every session and room, in ms
per tick: the program's ``svc/sessions`` (retransmit and health passes,
evictions) and ``svc/lag`` (bounds and lag probe) spans in the traced
window, over the window's ``svc/tick`` spans. This work grows with the
sessions served, not with the changes. Nothing is read when the span
ring wrapped or the program has no tick child spans."""

from loadbench.spans import window


def read(ctx):
    win = window()
    if win is None:
        return None
    ns = win.busy_ns("svc", "sessions") + win.busy_ns("svc", "lag")
    return ns / 1e6 / win.ticks
