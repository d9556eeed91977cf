"""Text patch emission time per admitted wire op, in microseconds: the
union per thread of the program's ``backend/diff`` spans in the traced
window (one per text or list object a room apply diffed, inside
``backend/apply``), over the ops the service admitted. Nothing is read
when the span ring wrapped, the program has no tick child spans or no
``backend/diff`` span, or no op was admitted."""

from loadbench.spans import window


def read(ctx):
    win = window()
    if win is None or not ctx["admitted_ops"]:
        return None
    if not win.spans("backend", "diff"):
        return None
    return win.busy_ns("backend", "diff") / 1e3 / ctx["admitted_ops"]
