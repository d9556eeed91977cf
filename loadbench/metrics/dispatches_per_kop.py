"""Device program launches (``engine/accounting.py``) per 1,000 wire ops
admitted in the window."""


def read(ctx):
    if not ctx["admitted_ops"]:
        return None
    return 1e3 * ctx["dispatches"] / ctx["admitted_ops"]
