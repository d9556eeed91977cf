"""p99 of the duration of one ``svc.tick`` call, over the ticks that
started inside the traced span (host clock around each call): the long
ticks that every change in a room waits out. Under more load a tick
takes more changes and lasts longer."""

from loadbench.checks import percentile


def read(ctx):
    ticks = ctx["tick_ms"]
    return percentile(ticks, 0.99) if ticks else None
