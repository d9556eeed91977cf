"""Plain reference for the text deployments: RGA list order over
automerge wire changes, written from the semantics alone.

An ``ins`` op mints element ``actor:elem`` directly after its ``key``
element (``_head`` for the front). Elements inserted after the same
element are ordered by descending ``(elem counter, actor id)``; the
document order is a pre-order walk from ``_head``. A ``set`` op on an
element assigns its value (the mixes set each element exactly once, by
its inserter; a second set is refused rather than guessed at), a ``del``
hides it. This is the order of the reference implementation's op set
(``insertionsAfter`` sorted descending by elemId), restated here so that
nothing of the program under test is imported.

``order(..., sibling_order="arrival")`` is the control: the same walk
with siblings kept in arrival order instead, the shortcut that breaks
convergence (two replicas that receive concurrent inserts in different
orders disagree). Only the control tests and ``--control`` readings use
it.
"""

from __future__ import annotations


def _elem(actor: str, ctr: int) -> str:
    return f"{actor}:{ctr}"


def order(changes, obj_id: str, sibling_order: str = "rga"):
    """(elem ids in document order, values in document order) of the
    list object ``obj_id`` after applying ``changes`` (any causal order;
    each change a wire dict). Elements without a value are skipped, as
    the document shows them."""
    children: dict = {}
    value: dict = {}
    for ch in changes:
        actor = ch["actor"]
        for op in ch["ops"]:
            if op.get("obj") != obj_id:
                continue
            action = op["action"]
            if action == "ins":
                children.setdefault(op["key"], []).append(
                    (op["elem"], actor))
            elif action == "set":
                if op["key"] in value:
                    raise ValueError(f"second set of {op['key']}: "
                                     "concurrent sets are not modeled")
                value[op["key"]] = op["value"]
            elif action == "del":
                value.pop(op["key"], None)
    ids: list = []
    vals: list = []
    stack = [_elem(a, c) for c, a in _siblings(children.get("_head", ()),
                                              sibling_order)]
    while stack:
        eid = stack.pop()
        if eid in value:
            ids.append(eid)
            vals.append(value[eid])
        kids = children.get(eid)
        if kids:
            stack.extend(_elem(a, c) for c, a in _siblings(kids,
                                                          sibling_order))
    return ids, vals


def _siblings(kids, sibling_order: str):
    """Children in stack-push order: the LAST pushed is visited first."""
    if sibling_order == "rga":
        return sorted(kids)                 # ascending -> largest first
    if sibling_order == "arrival":
        return list(reversed(list(kids)))   # first arrived visited first
    raise ValueError(f"unknown sibling order {sibling_order!r}")
