"""Programs compiled inside the window (``obs/device_truth.py``
registry)."""


def read(ctx):
    return ctx["compiles"]
