"""Share of the traced window in which no operation ran on the device,
in percent, from the profiler trace (``trace_reduce.py``)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["window_ns"]:
        return None
    return 100.0 * (1.0 - tr["busy_ns"] / tr["window_ns"])
