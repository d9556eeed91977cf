"""Share of the text patch emissions in the traced window that read only
the round's touched slots: the program's ``backend/diff`` spans whose
``mode`` arg is ``touched``, over all of them (the rest read the whole
document, ``full``), as a fraction. Nothing is read when the span ring
wrapped, the program has no tick child spans or no ``backend/diff``
span."""

from loadbench.spans import window


def read(ctx):
    win = window()
    diffs = [] if win is None else win.spans("backend", "diff")
    if not diffs:
        return None
    touched = sum(1 for r in diffs if (r[5] or {}).get("mode") == "touched")
    return touched / len(diffs)
