"""Room apply time per admitted wire op, in microseconds: the union per
thread of the program's ``svc/deliver`` spans in the traced window (one
per (room, doc) group of a tick: the gate, the backend apply with its
planning and device round, the frontend patch, the change handlers),
over the ops the service admitted. Nothing is read when the span ring
wrapped, the program has no tick child spans or no op was admitted."""

from loadbench.spans import window


def read(ctx):
    win = window()
    if win is None or not ctx["admitted_ops"]:
        return None
    return win.busy_ns("svc", "deliver") / 1e3 / ctx["admitted_ops"]
