"""Touched-slot text patches against the whole-state path.

After a round that touched at most `TOUCH_K` slots, the device backend
emits a text/list object's diffs from the rows its materialization
gathers at those slots (backend/device.py `_touched_text_diffs`); any
other round reads the position vector and the element tables
(`_full_text_diffs`). Each case replays one seeded history twice into a
receiving document: once as the backend runs it, once with `TOUCH_K`
set to 0, so that every emission takes the whole-state path. Every diff
list the receiver's backend returns must be byte-identical between the
two.

The histories mix remote typing, deletes and assigns from three peers,
concurrent inserts at one parent, concurrent assigns (conflicts),
non-ASCII characters, a list of rich values with a nested map, local
changes with undo and redo, one bulk insert larger than `TOUCH_K` and
one corrupted segment mirror that the engine heals.

The touched-slot vector has one static shape, passed whether or not a
slot is asked for, so the materializations a server compiles ahead of
time are the ones its applies run (`test_warmed_materialize_...`).
"""

import json

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu import Text
from automerge_tpu import frontend as Frontend
from automerge_tpu._common import ROOT_ID
from automerge_tpu.backend import device as dev
from automerge_tpu.engine import text_doc
from automerge_tpu.engine.segments import SegmentMirror

CHARS = list("abcdefgh") + ["é", "ß", "中", "文", "😀"]
SEEDS = [11, 23, 37, 41, 59, 73]
TEXT_OBJ = "00000000-0000-0000-0000-0000000000aa"


def typed_room(n_chars: int):
    """A DocSet with one document whose text object was typed as one
    remote run of `n_chars`, and a function that delivers the next
    one-character remote insert: two concurrent typists, each typing
    after its own last character (the served rooms' change shape)."""
    from automerge_tpu.sync import DocSet
    ops = [{"action": "makeText", "obj": TEXT_OBJ}]
    prev = "_head"
    for c in range(1, n_chars + 1):
        ops += [{"action": "ins", "obj": TEXT_OBJ, "key": prev, "elem": c},
                {"action": "set", "obj": TEXT_OBJ, "key": f"base:{c}",
                 "value": CHARS[c % 8]}]
        prev = f"base:{c}"
    ops.append({"action": "link", "obj": ROOT_ID, "key": "text",
                "value": TEXT_OBJ})
    ds = DocSet()
    ds.deliver("room", [{"actor": "base", "seq": 1, "deps": {}, "ops": ops}])
    last = {"alice": f"base:{n_chars // 2}", "bob": f"base:{n_chars // 3}"}
    seqs = {"alice": 0, "bob": 0}

    def deliver():
        actor = "alice" if sum(seqs.values()) % 2 == 0 else "bob"
        seqs[actor] += 1
        key = f"{actor}:{seqs[actor]}"
        ds.deliver("room", [{"actor": actor, "seq": seqs[actor],
                             "deps": {"base": 1}, "ops": [
            {"action": "ins", "obj": TEXT_OBJ, "key": last[actor],
             "elem": seqs[actor]},
            {"action": "set", "obj": TEXT_OBJ, "key": key, "value": "x"}]}])
        last[actor] = key
    return ds, deliver


def text_engine(ds):
    """The engine document of the room's text object."""
    core = Frontend.get_backend_state(ds.get_doc("room")).read_core()
    return core.objects[TEXT_OBJ].doc


def _peer_edit(doc, rng):
    """One random edit of a peer's text or list."""
    n = len(doc["t"])
    r = rng.random()
    if r < 0.45 or n < 4:
        i = int(rng.integers(0, n + 1))
        chars = [CHARS[int(c)] for c in
                 rng.integers(0, len(CHARS), int(rng.integers(1, 4)))]
        return am.change(doc, lambda d: d["t"].insert_at(i, *chars))
    if r < 0.65:
        i = int(rng.integers(0, n - 2))
        k = int(rng.integers(1, 3))
        return am.change(doc, lambda d: d["t"].delete_at(i, k))
    if r < 0.85:
        i = int(rng.integers(0, n))
        ch = CHARS[int(rng.integers(0, len(CHARS)))]
        return am.change(doc, lambda d: d["t"].set(i, ch))
    m = len(doc["l"])
    if r < 0.93 or m == 0:
        value = [7, "x", 2.5, True, None][int(rng.integers(0, 5))]
        j = int(rng.integers(0, m + 1))
        return am.change(doc, lambda d: d["l"].insert_at(j, value))
    j = int(rng.integers(0, m))
    return am.change(doc, lambda d: d["l"].__setitem__(j, int(j) * 3))


def _history(seed: int) -> list:
    """Steps for the receiver, minted once: ("remote", changes),
    ("local", 0 to insert or 1 to delete, position as a fraction of the
    text), ("undo",), ("redo",), ("corrupt",)."""
    rng = np.random.default_rng(seed)
    base = am.change(am.init("base"), lambda d: (
        d.__setitem__("t", Text("héllo wörld, 中文 text")),
        d.__setitem__("l", [1, "two", {"k": 3}, 4.5])))
    seeded = am.get_changes(am.init("base"), base)
    peers = [am.apply_changes(am.init(f"peer{i}"), seeded)
             for i in range(3)]
    # the receiver's first local change interns its actor on the engine
    # path; the next two ride the write-behind overlay, which the next
    # remote round flushes
    steps = [("remote", seeded)] + [("local", 0, 0.5)] * 3
    pending: list = []
    for step in range(36):
        if step == 12:
            # concurrent inserts at one parent, and concurrent assigns to
            # one element: every peer edits the same synced state
            peers = [am.apply_changes(p, [c for q in peers
                                          for c in am.get_all_changes(q)])
                     for p in peers]
            for i, p in enumerate(peers):
                new = am.change(p, lambda d, i=i: (
                    d["t"].insert_at(3, f"{i}"), d["t"].set(6, CHARS[i])))
                pending.extend(am.get_changes(p, new))
                peers[i] = new
        elif step == 24:
            # one round that inserts more slots than the touched vector
            p = peers[1]
            new = am.change(p, lambda d: d["t"].insert_at(
                2, *("bulk-" * 20)))
            pending.extend(am.get_changes(p, new))
            peers[1] = new
        else:
            i = int(rng.integers(0, 3))
            new = _peer_edit(peers[i], rng)
            pending.extend(am.get_changes(peers[i], new))
            peers[i] = new
            j = int(rng.integers(0, 3))
            if j != i and rng.random() < 0.2:
                peers[j] = am.merge(peers[j], peers[i])
        if step == 30:
            steps.append(("corrupt",))
        if len(pending) >= int(rng.integers(1, 4)):
            steps.append(("remote", pending))
            pending = []
        r = rng.random()
        if r < 0.15:
            steps.append(("local", int(rng.integers(0, 2)),
                          float(rng.random())))
        elif r < 0.22:
            steps.append(("undo",))
        elif r < 0.26:
            steps.append(("redo",))
    if pending:
        steps.append(("remote", pending))
    return steps


def _corrupt_mirror(doc):
    """Make the receiver's text engine disagree with its device chain
    bits: one head's counter is off, so the planned kernel's checksum
    fails and the engine heals."""
    core = Frontend.get_backend_state(doc).read_core()
    eng = next(w.doc for w in core.objects.values()
               if isinstance(w, dev._TextObj) and w.kind == "text")
    m = eng.seg_mirror
    assert m is not None and len(m.hctr) > 2
    hctr = m.hctr.copy()
    hctr[2] += 7
    eng.seg_mirror = SegmentMirror(m.heads.copy(), m.par.copy(), hctr,
                                   m.hactor.copy())


def _replay(steps, monkeypatch) -> tuple:
    """The receiver's diff lists, in order, and its final document."""
    log: list = []
    with monkeypatch.context() as mp:
        for name in ("apply", "do_undo", "do_redo"):
            fn = getattr(dev._DeviceCore, name)

            def logged(self, *a, _fn=fn, **k):
                diffs = _fn(self, *a, **k)
                log.append(json.dumps(diffs, ensure_ascii=False))
                return diffs
            mp.setattr(dev._DeviceCore, name, logged)
        doc = _receive(steps)
    return log, doc


def _receive(steps):
    doc = am.init("receiver")
    for step in steps:
        if step[0] == "remote":
            doc = am.apply_changes(doc, step[1])
        elif step[0] == "corrupt":
            _corrupt_mirror(doc)
        elif step[0] == "local":
            n = len(doc["t"])
            i = int(step[2] * n)
            if step[1] == 0 or n == 0:
                doc = am.change(doc, lambda d: d["t"].insert_at(i, "é", "z"))
            else:
                doc = am.change(doc, lambda d: d["t"].delete_at(min(i, n - 1)))
        elif step[0] == "undo" and Frontend.can_undo(doc):
            doc = am.undo(doc)
        elif step[0] == "redo" and Frontend.can_redo(doc):
            doc = am.redo(doc)
    return doc


def _checked_build(cls, wrapper, _build=dev._TextOverlay.build.__func__):
    """The write-behind overlay, whose visibility comes from the diff
    baseline, checked against the element tables' mirror."""
    ov = _build(cls, wrapper)
    doc = wrapper.doc
    n = doc.n_elems
    if n:
        order_slot = np.empty(n, np.int64)
        order_slot[np.asarray(doc._positions()[1:])] = np.arange(1, n + 1)
        np.testing.assert_array_equal(
            ov.vis, np.array(doc._mirrors()["has_value"], bool)[order_slot])
    return ov


@pytest.mark.parametrize("seed", SEEDS)
def test_touched_patches_match_full_path(seed, monkeypatch):
    steps = _history(seed)
    modes: list = []
    for name in ("_touched_text_diffs", "_full_text_diffs", "rebase"):
        owner = dev._TextObj if name == "rebase" else dev._DeviceCore
        fn = getattr(owner, name)

        def counted(self, *a, _fn=fn, _name=name, **k):
            modes.append(_name)
            return _fn(self, *a, **k)
        monkeypatch.setattr(owner, name, counted)
    monkeypatch.setattr(dev._TextOverlay, "build",
                        classmethod(_checked_build))
    touched_log, touched_doc = _replay(steps, monkeypatch)
    touched_modes, modes[:] = list(modes), []
    monkeypatch.setattr(text_doc, "TOUCH_K", 0)
    full_log, full_doc = _replay(steps, monkeypatch)
    full_modes = list(modes)

    assert touched_log == full_log
    assert am.to_json(touched_doc) == am.to_json(full_doc)
    assert str(touched_doc["t"]) == str(full_doc["t"])
    # both paths ran in the default leg (the bulk round and the heal take
    # the whole state), and only the whole-state path with TOUCH_K = 0
    assert touched_modes.count("_touched_text_diffs") >= 10, touched_modes
    assert "_full_text_diffs" in touched_modes
    assert "rebase" in touched_modes      # a write-behind flush
    assert set(full_modes) == {"_full_text_diffs", "rebase"}


def test_warmed_materialize_covers_touched_applies():
    """Compile every (segments, length) materialization a few more
    one-character inserts can reach, through `_run_materialize(True, S)`
    with `_mat_params` forced and nothing asked for, as the load
    harness's `warm_shapes` does; then those inserts, which each gather
    their touched slots, compile nothing. The seed leaves the document
    two characters below a length bucket, so the inserts cross into a
    bucket that only the forced calls ran."""
    import jax
    import jax.numpy as jnp
    from automerge_tpu.obs import device_truth as dt

    ds, deliver = typed_room(6140)
    deliver()
    deliver()               # both typists' merge rounds are compiled
    eng = text_engine(ds)
    more = 6
    params = type(eng)._mat_params
    n0 = eng.n_elems
    seg_b = sorted({params(eng, seg_bound=s, n_elems=n0)[0]
                    for s in range(1, eng._seg_bound + 2 * more + 2)})
    len_b = sorted({params(eng, seg_bound=1, n_elems=n)[1]
                    for n in range(n0, n0 + more + 1)})
    as_u8 = params(eng)[2]
    staged = eng._n_elems_dev
    try:
        for count in (None, (n0, jnp.asarray(np.int32(n0)))):
            eng._n_elems_dev = count
            for L in len_b:
                for S in seg_b:
                    eng._mat_params = (
                        lambda *a, _p=(S, L, as_u8), **k: _p)
                    jax.block_until_ready(eng._run_materialize(True, S))
    finally:
        eng.__dict__.pop("_mat_params", None)
        eng._n_elems_dev = staged
    length_before = eng._mat_params()[1]
    with dt.steady_state() as ss:
        for _ in range(more):
            deliver()
    ss.assert_zero()
    assert eng._mat_params()[1] != length_before   # a new length bucket
    assert len(len_b) == 2
