"""Host planning time per admitted wire op, in microseconds: the union
per thread of the program's ``plan/*`` spans inside the window (nested
spans counted once), over the ops the service admitted. Nothing is read
when the span ring wrapped or no op was admitted."""


def read(ctx):
    if ctx["plan_ns"] is None or not ctx["admitted_ops"]:
        return None
    return ctx["plan_ns"] / 1e3 / ctx["admitted_ops"]
