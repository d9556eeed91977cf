"""Channel bytes sent in both directions (every ``ResilientChannel``'s
``bytes_sent``, server and clients) per wire op admitted in the
window."""


def read(ctx):
    if not ctx["admitted_ops"]:
        return None
    return ctx["wire_bytes"] / ctx["admitted_ops"]
