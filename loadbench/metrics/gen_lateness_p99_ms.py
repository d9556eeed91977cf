"""p99 of (inject time - due time) over the changes handed to the
transport inside the traced span: how late the load generator ran (host
clock)."""

from loadbench.checks import percentile


def read(ctx):
    late = ctx["lateness_ms"]
    return percentile(late, 0.99) if late else None
