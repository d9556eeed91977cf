"""Fan-out time per admitted wire op, in microseconds: the union of the
program's ``svc/flush`` spans in the traced window (each tick's deferred
hub flush of every touched room: change extraction, frame encode, the
sends), over the ops the service admitted. Nothing is read when the span
ring wrapped, the program has no tick child spans or no op was
admitted."""

from loadbench.spans import window


def read(ctx):
    win = window()
    if win is None or not ctx["admitted_ops"]:
        return None
    return win.busy_ns("svc", "flush") / 1e3 / ctx["admitted_ops"]
