"""Checkpoint & compaction tier (automerge_tpu/checkpoint/).

Pins the subsystem's contracts end to end:

- property test over random merge/undo/delete histories: ``load(save(doc))``
  renders byte-for-byte like the oracle backend's document
- checkpoint restore equivalence (document AND re-serialized history),
  delta saves (tail-only payload + tail replay at load)
- integrity: truncated / bit-flipped bundles raise the typed
  ``CheckpointError``; the DocSet bootstrap falls back to full log replay
- async-capture vs sync-capture byte identity; the conflict path degrades
  to a synchronous grab
- engine-level restore equivalence + tail replay (the bench.py seam)
- snapshot-bootstrapped sync, including a corrupt bundle healing through
  the ``noSnapshot`` full-history fallback
"""

import json

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu import frontend as Frontend
from automerge_tpu.backend import facade
from automerge_tpu.checkpoint import (
    AsyncCheckpointer, Checkpoint, CheckpointError, capture_engine,
    capture_state, restore_engine, restore_state,
)
from automerge_tpu.resilience import ProtocolError


def canon(doc) -> str:
    return json.dumps(am.to_json(doc), sort_keys=True, default=str)


def oracle_doc(changes):
    """The same history replayed through the pure-host oracle backend."""
    state, _ = facade.apply_changes(facade.init(), changes)
    patch = facade.get_patch(state)
    patch["state"] = state
    return Frontend.apply_patch(Frontend.init({"backend": facade.Backend}),
                                patch)


def random_history_doc(seed: int):
    """A doc grown through seeded random merge/undo/delete interleavings."""
    rng = np.random.default_rng(seed)
    base = am.change(am.init("base"), lambda d: (
        d.__setitem__("t", am.Text("seed")),
        d.__setitem__("m", {"k": 0})))
    changes = am.get_all_changes(base)
    peers = [am.apply_changes(am.init(f"p{i}"), changes) for i in range(3)]
    for _ in range(int(rng.integers(10, 20))):
        i = int(rng.integers(0, len(peers)))
        act = int(rng.integers(0, 6))
        if act == 0:
            k = f"k{int(rng.integers(0, 4))}"
            v = int(rng.integers(-99, 99))
            peers[i] = am.change(peers[i],
                                 lambda d, k=k, v=v: d.__setitem__(k, v))
        elif act == 1:
            def edit(d):
                t = d["t"]
                if len(t) and rng.integers(0, 3) == 0:
                    t.delete_at(int(rng.integers(0, len(t))))
                else:
                    t.insert_at(int(rng.integers(0, len(t) + 1)),
                                chr(97 + int(rng.integers(0, 26))))
            peers[i] = am.change(peers[i], edit)
        elif act == 2 and am.can_undo(peers[i]):
            peers[i] = am.undo(peers[i])
        elif act == 3 and am.can_redo(peers[i]):
            peers[i] = am.redo(peers[i])
        else:
            j = int(rng.integers(0, len(peers)))
            if j != i:
                peers[i] = am.merge(peers[i], peers[j])
    for _ in range(2):
        for i in range(len(peers)):
            for j in range(len(peers)):
                if i != j:
                    peers[i] = am.merge(peers[i], peers[j])
    return peers[0]


@pytest.mark.parametrize("seed", range(6))
def test_save_load_matches_oracle_property(seed):
    doc = random_history_doc(seed)
    back = am.load(am.save(doc))
    odoc = oracle_doc(am.get_all_changes(doc))
    assert canon(back) == canon(odoc) == canon(doc)


@pytest.mark.parametrize("seed", range(4))
def test_checkpoint_restore_equivalence_property(seed):
    doc = random_history_doc(seed)
    ck = am.checkpoint_doc(doc)
    back = am.restore(ck)
    assert canon(back) == canon(doc)
    # history-complete: the restored doc re-serializes byte-for-byte
    assert am.save(back) == am.save(doc)
    # and keeps syncing: diverge both sides, then re-merge
    back = am.change(back, lambda d: d.__setitem__("after", 1))
    doc = am.change(doc, lambda d: d["t"].insert_at(0, "Q"))
    doc = am.merge(doc, back)
    back = am.merge(back, doc)
    assert canon(back) == canon(doc)


def test_restore_drops_undo_history_like_load():
    doc = am.change(am.init("u"), lambda d: d.__setitem__("x", 1))
    assert am.can_undo(doc)
    assert not am.can_undo(am.restore(am.checkpoint_doc(doc)))
    assert not am.can_undo(am.load(am.save(doc)))


def test_delta_save_tail_replay():
    doc = am.change(am.init("alice"),
                    lambda d: d.__setitem__("t", am.Text("hello")))
    for i in range(5):
        doc = am.change(doc, lambda d, i=i: d["t"].insert_at(0, str(i)))
    ck = am.checkpoint_doc(doc)
    tail_start = doc
    for i in range(3):
        doc = am.change(doc, lambda d, i=i: d["t"].insert_at(0, chr(65 + i)))
    delta = am.save(doc, checkpoint=ck)
    payload = json.loads(delta)
    assert payload["format"] == "automerge-tpu-delta-v1"
    assert payload["checkpointId"] == ck.id
    # compaction: only the tail past the frontier rides in the save
    assert len(payload["changes"]) == 3
    assert len(delta) < len(am.save(doc))
    back = am.load(delta, checkpoint=ck)
    assert canon(back) == canon(doc)
    # the frontier state itself round-trips with an empty tail
    empty_delta = am.save(tail_start, checkpoint=ck)
    assert json.loads(empty_delta)["changes"] == []
    assert canon(am.load(empty_delta, checkpoint=ck)) == canon(tail_start)


def test_delta_load_requires_checkpoint():
    doc = am.change(am.init("a"), lambda d: d.__setitem__("x", 1))
    ck = am.checkpoint_doc(doc)
    doc = am.change(doc, lambda d: d.__setitem__("y", 2))
    delta = am.save(doc, checkpoint=ck)
    with pytest.raises(ValueError, match="delta-compacted"):
        am.load(delta)
    # a different checkpoint is rejected by id before any restore work
    other = am.checkpoint_doc(am.change(am.init("b"),
                                        lambda d: d.__setitem__("z", 9)))
    with pytest.raises(CheckpointError, match="wrong base checkpoint"):
        am.load(delta, checkpoint=other)


def test_delta_save_rejects_non_ancestor():
    doc = am.change(am.init("a"), lambda d: d.__setitem__("x", 1))
    ck = am.checkpoint_doc(am.change(doc,
                                     lambda d: d.__setitem__("y", 2)))
    with pytest.raises(ValueError, match="not an ancestor"):
        am.save(doc, checkpoint=ck)   # doc is BEHIND the checkpoint


# ---------------------------------------------------------------------------
# integrity / fallback
# ---------------------------------------------------------------------------

def _doc_with_history():
    doc = am.change(am.init("alice"),
                    lambda d: d.__setitem__("t", am.Text("integrity")))
    doc = am.change(doc, lambda d: d.__setitem__("m", {"k": [1, 2]}))
    doc = am.change(doc, lambda d: d["t"].delete_at(0))
    return doc


def test_truncated_bundle_raises_checkpoint_error():
    ck = am.checkpoint_doc(_doc_with_history())
    for cut in (10, 50, len(ck.data) // 2, len(ck.data) - 3):
        with pytest.raises(CheckpointError):
            restore_state(ck.data[:cut])


def test_bit_flipped_bundle_raises_checkpoint_error():
    ck = am.checkpoint_doc(_doc_with_history())
    n = len(ck.data)
    # flip bytes across the whole bundle: header, manifest, array blobs
    for pos in (2, n // 4, n // 2, (3 * n) // 4, n - 10):
        data = bytearray(ck.data)
        data[pos] ^= 0x40
        with pytest.raises(CheckpointError):
            restore_state(bytes(data))


def test_manifest_bit_flip_raises_checkpoint_error():
    # the manifest region carries clock/conflicts/value-pool state OUTSIDE
    # the array blobs; a flip that keeps the JSON parseable (e.g. a clock
    # digit) must still fail the header hash, never restore silently
    from automerge_tpu.checkpoint import bundle as _bundle
    ck = am.checkpoint_doc(_doc_with_history())
    hdr = len(_bundle.MAGIC) + 8 + 32
    data = bytearray(ck.data)
    pos = ck.data.index(b'"clock"', hdr) + len(b'"clock"') + 12
    data[pos] ^= 0x01   # single-bit change inside the manifest JSON
    with pytest.raises(CheckpointError, match="manifest"):
        restore_state(bytes(data))
    with pytest.raises(CheckpointError):
        Checkpoint(bytes(data)).clock   # peek is hash-verified too


def test_corrupt_bundle_falls_back_to_full_replay():
    from automerge_tpu import DocSet
    doc = _doc_with_history()
    ck = am.checkpoint_doc(doc)
    corrupt = bytearray(ck.data)
    corrupt[len(corrupt) // 2] ^= 0xFF
    ds = DocSet()
    # without a fallback the corruption surfaces typed
    with pytest.raises(CheckpointError):
        ds.bootstrap_doc("doc", bytes(corrupt))
    # with the full log, restore degrades to replay and still lands
    out = ds.bootstrap_doc("doc", bytes(corrupt),
                           fallback_changes=am.get_all_changes(doc))
    assert canon(out) == canon(doc)


# ---------------------------------------------------------------------------
# engine-level (the bench seam)
# ---------------------------------------------------------------------------

def _engine_text_doc(n=400):
    import bench
    from automerge_tpu.engine import DeviceTextDoc
    doc = DeviceTextDoc("t")
    doc.apply_batch(bench.base_batch("t", n))
    doc.apply_batch(bench.merge_batch("t", 6, 50, n, seed=2))
    return doc, n


def test_engine_restore_equivalence_and_tail_replay():
    import bench
    doc, n = _engine_text_doc()
    data = capture_engine(doc)
    d2 = restore_engine(data)
    assert d2.text() == doc.text()
    assert d2.elem_ids() == doc.elem_ids()
    # tail replay lands identically on original and restored
    tail = bench.merge_batch("t", 4, 30, n, seed=7, actor_prefix="tl")
    doc.apply_batch(tail)
    d2.apply_batch(tail)
    assert d2.text() == doc.text()
    assert d2.elem_ids() == doc.elem_ids()
    assert dict(d2.clock) == dict(doc.clock)


def test_engine_restore_preserves_conflict_registers():
    from automerge_tpu.engine import DeviceTextDoc

    def mk(a, key, parent, val, deps):
        return {"actor": a, "seq": 1, "deps": deps, "ops": [
            {"action": "ins", "obj": "t", "key": parent, "elem": 1},
            {"action": "set", "obj": "t", "key": key, "value": val}]}

    doc = DeviceTextDoc("t")
    doc.apply_changes([mk("base", "base:1", "_head", "x", {})])
    # two concurrent writers on the same element -> a stored conflict
    doc.apply_changes([
        {"actor": "a", "seq": 1, "deps": {"base": 1}, "ops": [
            {"action": "set", "obj": "t", "key": "base:1", "value": "A"}]},
        {"actor": "b", "seq": 1, "deps": {"base": 1}, "ops": [
            {"action": "set", "obj": "t", "key": "base:1", "value": "B"}]},
    ])
    assert doc.conflicts_at(0) is not None
    d2 = restore_engine(capture_engine(doc))
    assert d2.text() == doc.text()
    assert d2.conflicts_at(0) == doc.conflicts_at(0)


def test_engine_capture_rejects_queued_changes():
    from automerge_tpu.engine import DeviceTextDoc
    doc = DeviceTextDoc("t")
    doc.apply_changes([{"actor": "a", "seq": 2, "deps": {}, "ops": [
        {"action": "ins", "obj": "t", "key": "_head", "elem": 1},
        {"action": "set", "obj": "t", "key": "a:1", "value": "x"}]}])
    assert doc.queue   # causally premature: parked in the engine queue
    with pytest.raises(CheckpointError, match="queued"):
        capture_engine(doc)


# ---------------------------------------------------------------------------
# async writer
# ---------------------------------------------------------------------------

def test_async_capture_identity_engine_doc():
    doc, _ = _engine_text_doc(200)
    with AsyncCheckpointer() as w:
        h = w.capture_async(doc)
        sync_bytes = AsyncCheckpointer.capture(doc)
        assert h.result(30) == sync_bytes
        assert w.stats["async_captures"] == 1
        assert w.stats["sync_fallbacks"] == 0
    assert restore_engine(sync_bytes).text() == doc.text()


def test_async_capture_identity_backend_state():
    doc = _doc_with_history()
    state = Frontend.get_backend_state(doc)
    with AsyncCheckpointer() as w:
        h = w.capture_async(state)
        assert h.result(30) == capture_state(state)


def test_async_capture_conflict_degrades_to_sync():
    doc, _ = _engine_text_doc(200)
    doc._busy = 1   # simulate a mutation permanently in flight
    with AsyncCheckpointer(max_grab_retries=2) as w:
        h = w.capture_async(doc)
        h._done.wait(30)
        assert w.stats["sync_fallbacks"] == 1
        assert w.stats["grab_conflicts"] == 2
        doc._busy = 0   # commit boundary: the caller owns quiescence now
        data = h.result(30)
    assert data == AsyncCheckpointer.capture(doc)
    assert restore_engine(data).text() == doc.text()


def test_async_capture_during_pipeline_is_consistent_prefix():
    import bench
    from automerge_tpu.engine import DeviceTextDoc, PipelinedIngestor
    n = 3000
    doc = DeviceTextDoc("p")
    doc.apply_batch(bench.base_batch("p", n))
    halves = [bench.merge_batch("p", 10, 50, n, seed=s, actor_prefix=p_)
              for s, p_ in ((1, "a"), (2, "b"))]
    with AsyncCheckpointer() as w:
        with PipelinedIngestor(doc) as pipe:
            pipe.feed(halves[0])
            h = w.capture_async(doc)
            pipe.feed(halves[1])
            pipe.flush()
        restored = restore_engine(h.result(60))
    # the capture is SOME consistent prefix of the ingestion: replaying
    # the full halves on top converges it to the final doc (idempotent
    # dedup absorbs whatever the snapshot already contained)
    restored.apply_batch(halves[0])
    restored.apply_batch(halves[1])
    assert restored.text() == doc.text()


# ---------------------------------------------------------------------------
# api.load envelope validation (satellite)
# ---------------------------------------------------------------------------

def test_load_rejects_non_dict_payload_typed():
    for bad in ("[1]", '"str"', "3", "null"):
        with pytest.raises(ProtocolError):
            am.load(bad)


def test_load_rejects_missing_changes_typed():
    with pytest.raises(ProtocolError):
        am.load('{"format": "automerge-tpu-v1"}')
    with pytest.raises(ProtocolError):
        am.load('{"format": "automerge-tpu-v1", "changes": 5}')


def test_load_unknown_format_still_value_error():
    with pytest.raises(ValueError):
        am.load('{"format": "something-else", "changes": []}')
    # ProtocolError IS a ValueError, so legacy callers keep working
    assert issubclass(ProtocolError, ValueError)


# ---------------------------------------------------------------------------
# snapshot-bootstrapped sync
# ---------------------------------------------------------------------------

def _wire(sa, sb):
    from automerge_tpu import Connection
    qa, qb = [], []
    ca = Connection(sa, qa.append)
    cb = Connection(sb, qb.append)
    ca.open()
    cb.open()
    return ca, cb, qa, qb


def _pump(ca, cb, qa, qb, mutate=None, log=None):
    for _ in range(12):
        moved = False
        while qa:
            msg = json.loads(json.dumps(qa.pop(0)))   # wire round-trip
            if log is not None:
                log.append(msg)
            if mutate is not None:
                msg = mutate(msg)
            cb.receive_msg(msg)
            moved = True
        while qb:
            ca.receive_msg(json.loads(json.dumps(qb.pop(0))))
            moved = True
        if not moved:
            return


def _long_history_doc_set():
    from automerge_tpu import DocSet
    ds = DocSet()
    doc = am.change(am.init("origin"),
                    lambda d: d.__setitem__("t", am.Text("seed")))
    for i in range(20):
        doc = am.change(
            doc, lambda d, i=i: d["t"].insert_at(len(d["t"]),
                                                 chr(97 + i % 26)))
    ds.set_doc("doc", doc)
    return ds


def test_sync_snapshot_bootstrap(monkeypatch):
    from automerge_tpu import DocSet, SyncHub
    monkeypatch.setattr(SyncHub, "snapshot_min_changes", 8)
    sa, sb = _long_history_doc_set(), DocSet()
    ca, cb, qa, qb = _wire(sa, sb)
    log = []
    _pump(ca, cb, qa, qb, log=log)
    assert any("checkpoint" in m for m in log), \
        "joining peer should have been served a checkpoint bundle"
    assert canon(sa.get_doc("doc")) == canon(sb.get_doc("doc"))
    # bidirectional sync keeps working after the bootstrap
    sb.set_doc("doc", am.change(sb.get_doc("doc"),
                                lambda d: d["t"].insert_at(0, "Z")))
    _pump(ca, cb, qa, qb)
    assert canon(sa.get_doc("doc")) == canon(sb.get_doc("doc"))


def test_sync_snapshot_corrupt_falls_back_to_full_history(monkeypatch):
    from automerge_tpu import DocSet, SyncHub
    monkeypatch.setattr(SyncHub, "snapshot_min_changes", 8)
    sa, sb = _long_history_doc_set(), DocSet()
    ca, cb, qa, qb = _wire(sa, sb)
    n_corrupt = [0]

    def corrupt(msg):
        if "checkpoint" in msg:
            n_corrupt[0] += 1
            raw = bytearray(Checkpoint.from_base64(msg["checkpoint"]).data)
            raw[len(raw) // 2] ^= 0xFF   # hash-mismatched bundle
            msg = dict(msg)
            msg["checkpoint"] = Checkpoint(bytes(raw)).to_base64()
        return msg

    _pump(ca, cb, qa, qb, mutate=corrupt)
    assert n_corrupt[0] >= 1
    # the corrupt bundle was rejected and the peer recovered via the
    # noSnapshot full-history fallback — full log replay, same document
    assert canon(sa.get_doc("doc")) == canon(sb.get_doc("doc"))


def test_sync_snapshot_disabled_by_zero_threshold(monkeypatch):
    from automerge_tpu import DocSet, SyncHub
    monkeypatch.setattr(SyncHub, "snapshot_min_changes", 0)
    sa, sb = _long_history_doc_set(), DocSet()
    ca, cb, qa, qb = _wire(sa, sb)
    log = []
    _pump(ca, cb, qa, qb, log=log)
    assert not any("checkpoint" in m for m in log)
    assert canon(sa.get_doc("doc")) == canon(sb.get_doc("doc"))


def test_soak_checkpoint_profile_session():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import soak
    soak.session_checkpoint(1)


def test_bench_restore_metrics_small_scale():
    import bench
    rec = bench.measure_restore(base_n=4000, tail_actors=4,
                                ops_per_change=40)
    assert rec["restore_full_replay_s"] > 0
    assert rec["restore_snapshot_s"] > 0
    assert rec["restore_bundle_bytes"] > 0
    # no speed assertion at toy scale — the 1M-doc ratio is pinned by the
    # bench record (bench.py measure_restore); this pins shape + equivalence


def test_grab_mid_mutation_serves_commit_boundary_snapshot():
    """ISSUE 12: a grab observing a mutation in flight no longer climbs
    the busy-wait/retry ladder — it reads the doc's cached
    commit-boundary snapshot with zero coordination (CaptureConflict is
    kept only for donated buffers / the cold first-grab race)."""
    doc, _ = _engine_text_doc(200)
    bytes0 = AsyncCheckpointer.capture(doc)   # caches the snapshot
    doc._busy = 1                             # a bulk index merge mid-flight
    try:
        with AsyncCheckpointer(max_grab_retries=2) as w:
            h = w.capture_async(doc)
            data = h.result(30)
            assert w.stats["snapshot_serves"] == 1
            assert w.stats["sync_fallbacks"] == 0
            assert w.stats["grab_conflicts"] == 0
    finally:
        doc._busy = 0
    assert data == bytes0                     # the commit-boundary state


def test_grab_racing_bulk_index_merge_is_consistent_prefix():
    """Async grabs racing a thread of real applies (each holding _busy
    across its bulk index merge): every capture restores to SOME
    consistent prefix — replaying the full stream on top converges it to
    the final document byte-for-byte."""
    import threading
    import time

    import bench
    from automerge_tpu.engine import DeviceTextDoc

    n = 2000
    doc = DeviceTextDoc("r")
    base = bench.base_batch("r", n)
    doc.apply_batch(base)
    batches = [bench.merge_batch("r", 8, 40, n, seed=s, actor_prefix=p)
               for s, p in ((1, "a"), (2, "b"), (3, "c"), (4, "d"))]
    captures = []
    # seed the snapshot cache SYNCHRONOUSLY before the mutator starts:
    # an async seed could lose the race and hit the cold-first-grab
    # CaptureConflict path this test deliberately excludes
    seed = AsyncCheckpointer.capture(doc)
    with AsyncCheckpointer() as w:
        handles = []
        done = threading.Event()

        def mutate():
            for b in batches:
                doc.apply_batch(b)
            done.set()

        t = threading.Thread(target=mutate)
        t.start()
        while not done.is_set() and len(handles) < 12:
            handles.append(w.capture_async(doc))
            time.sleep(0.01)
        t.join(60)
        captures = [seed] + [h.result(60) for h in handles]
        assert w.stats["grab_conflicts"] == 0, w.stats
    final = doc.text()
    for data in captures:
        restored = restore_engine(data)
        for b in batches:
            restored.apply_batch(b)
        assert restored.text() == final


def test_snapshot_not_served_for_donation_enabled_doc():
    """Review regression (ISSUE 12): a cached commit-boundary snapshot
    must NOT be served once the doc enters donated-buffer mode — donated
    commits consume the snapshot's table buffers in place, so the busy
    path falls back to CaptureConflict exactly as pre-snapshot."""
    from automerge_tpu.checkpoint.engine_codec import CaptureConflict, grab

    doc, _ = _engine_text_doc(200)
    AsyncCheckpointer.capture(doc)          # caches the snapshot
    doc.donate_buffers = True
    try:
        with pytest.raises(CaptureConflict):
            grab(doc)                       # deferred grab refuses outright
        doc._busy = 1
        with pytest.raises(CaptureConflict):
            grab(doc, inline=True)          # busy + donated: no stale serve
    finally:
        doc._busy = 0
        doc.donate_buffers = False
    # donation off again and quiescent: live grabs resume
    assert grab(doc)["mode"] == "live"
