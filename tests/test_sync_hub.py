"""SyncHub: N peers served from one DocSet with ONE batched clock
comparison per local change (the vectorized getMissingChanges of SURVEY §5).
Wire compatibility: hub peers interoperate with plain Connections."""

from unittest import mock

import automerge_tpu as am
from automerge_tpu import frontend as Frontend
from automerge_tpu.sync import ClockMatrix, Connection, DocSet, SyncHub


class Pipe:
    """In-process bidirectional message pipe with explicit pumping."""

    def __init__(self):
        self.a_to_b: list = []
        self.b_to_a: list = []

    def pump(self, b_receive, a_receive) -> int:
        n = 0
        while self.a_to_b or self.b_to_a:
            while self.a_to_b:
                b_receive(self.a_to_b.pop(0))
                n += 1
            while self.b_to_a:
                a_receive(self.b_to_a.pop(0))
                n += 1
        return n


def test_clock_matrix_pending_is_batched():
    m = ClockMatrix()
    for d in range(3):
        m.update_ours(f"doc{d}", {"alice": 2, "bob": 1})
    for p in range(4):
        for d in range(3):
            m.set_active(f"peer{p}", f"doc{d}")
            m.update_theirs(f"peer{p}", f"doc{d}", {"alice": 2, "bob": 1})
    assert m.pending() == []
    m.update_ours("doc1", {"alice": 3})
    assert sorted(m.pending()) == [(f"peer{p}", "doc1") for p in range(4)]
    m.update_theirs("peer2", "doc1", {"alice": 3})
    assert ("peer2", "doc1") not in m.pending()


def test_hub_broadcasts_one_change_to_all_peers():
    ds = DocSet()
    hub = SyncHub(ds)
    outboxes = {p: [] for p in ("p1", "p2", "p3")}
    handles = {p: hub.add_peer(p, outboxes[p].append) for p in outboxes}
    hub.open()

    doc = am.change(am.init("alice"), lambda d: d.__setitem__("x", 1))
    ds.set_doc("doc1", doc)
    # unknown peers first get an advertisement, never speculative changes
    for p, box in outboxes.items():
        assert [m for m in box if m.get("changes")] == []
        assert any(m["docId"] == "doc1" for m in box), (p, box)
    # each peer reveals its (empty) clock; the hub then sends the changes
    for p, h in handles.items():
        h.receive_msg({"docId": "doc1", "clock": {}})
    for p, box in outboxes.items():
        with_changes = [m for m in box if m.get("changes")]
        assert len(with_changes) == 1, (p, box)
        assert with_changes[0]["docId"] == "doc1"

    # a subsequent local change now broadcasts changes directly
    ds.set_doc("doc1", am.change(ds.get_doc("doc1"),
                                 lambda d: d.__setitem__("y", 2)))
    for p, box in outboxes.items():
        assert len([m for m in box if m.get("changes")]) == 2, (p, box)


def test_hub_uses_one_batched_comparison_per_change():
    ds = DocSet()
    hub = SyncHub(ds)
    for p in range(5):
        hub.add_peer(f"p{p}", lambda m: None)
    hub.open()
    with mock.patch.object(ClockMatrix, "pending",
                           wraps=hub._matrix.pending) as spy:
        doc = am.change(am.init("alice"), lambda d: d.__setitem__("x", 1))
        ds.set_doc("doc1", doc)
        # one local change -> ONE batched pending() call serves all 5 peers
        assert spy.call_count == 1


def test_n_connections_share_one_hub_and_one_diff():
    """Connections are hub-backed: N Connections on one DocSet share ONE
    ClockMatrix (one batched pending() per local change) and, once the
    peers' believed clocks agree, ONE get_missing_changes extraction
    serves all N (the reference's per-Connection loop would diff N times,
    src/connection.js:58-74)."""
    from automerge_tpu.sync import hub as hub_mod

    ds = DocSet()
    boxes = [[] for _ in range(3)]
    conns = [Connection(ds, boxes[i].append) for i in range(3)]
    for c in conns:
        c.open()
    # all three faces share the doc-set's one hub
    assert len({id(c._hub) for c in conns}) == 1
    hub = conns[0]._hub

    ds.set_doc("doc", am.change(am.init("alice"),
                                lambda d: d.__setitem__("x", 1)))
    for c in conns:   # every peer reveals its (empty) clock
        c.receive_msg({"docId": "doc", "clock": {}})
    for box in boxes:
        assert sum(1 for m in box if m.get("changes")) == 1

    with mock.patch.object(ClockMatrix, "pending",
                           wraps=hub._matrix.pending) as pend, \
         mock.patch.object(hub_mod.Backend, "get_missing_changes",
                           wraps=hub_mod.Backend.get_missing_changes) as gmc:
        ds.set_doc("doc", am.change(ds.get_doc("doc"),
                                    lambda d: d.__setitem__("y", 2)))
        # one local change: ONE batched comparison, ONE shared extraction
        assert pend.call_count == 1
        assert gmc.call_count == 1
    for box in boxes:
        assert sum(1 for m in box if m.get("changes")) == 2


def test_hub_interoperates_with_plain_connection():
    # hub side: two docs
    ds_hub = DocSet()
    hub = SyncHub(ds_hub)
    # peer side: a reference-parity Connection
    ds_peer = DocSet()
    pipe = Pipe()
    peer_handle = hub.add_peer("peer", pipe.a_to_b.append)
    conn = Connection(ds_peer, pipe.b_to_a.append)
    hub.open()
    conn.open()

    d1 = am.change(am.init("alice"), lambda d: d.__setitem__("x", 1))
    ds_hub.set_doc("doc1", d1)
    pipe.pump(conn.receive_msg, peer_handle.receive_msg)
    assert am.to_json(ds_peer.get_doc("doc1")) == {"x": 1}

    # and back: peer edits, hub side converges
    d2 = am.change(ds_peer.get_doc("doc1"),
                   lambda d: d.__setitem__("y", 2))
    ds_peer.set_doc("doc1", d2)
    pipe.pump(conn.receive_msg, peer_handle.receive_msg)
    assert am.to_json(ds_hub.get_doc("doc1")) == {"x": 1, "y": 2}


def test_hub_to_hub_multi_doc_convergence():
    ds_a, ds_b = DocSet(), DocSet()
    hub_a, hub_b = SyncHub(ds_a), SyncHub(ds_b)
    pipe = Pipe()
    pa = hub_a.add_peer("b", pipe.a_to_b.append)
    pb = hub_b.add_peer("a", pipe.b_to_a.append)
    hub_a.open()
    hub_b.open()

    for i in range(3):
        doc = am.change(am.init(f"actor{i}"),
                        lambda d, i=i: d.__setitem__("n", i))
        ds_a.set_doc(f"doc{i}", doc)
    pipe.pump(pb.receive_msg, pa.receive_msg)
    for i in range(3):
        assert am.to_json(ds_b.get_doc(f"doc{i}")) == {"n": i}

    # concurrent edits on both sides, one pump converges everything
    ds_a.set_doc("doc0", am.change(ds_a.get_doc("doc0"),
                                   lambda d: d.__setitem__("a", 1)))
    ds_b.set_doc("doc1", am.change(ds_b.get_doc("doc1"),
                                   lambda d: d.__setitem__("b", 2)))
    pipe.pump(pb.receive_msg, pa.receive_msg)
    assert am.to_json(ds_a.get_doc("doc1")) == am.to_json(ds_b.get_doc("doc1"))
    assert am.to_json(ds_a.get_doc("doc0")) == am.to_json(ds_b.get_doc("doc0"))


def test_no_speculative_changes_for_unrevealed_doc():
    """A peer that revealed a clock for doc A must still only get an
    advertisement for a new doc B (Connection's unknown-peer behavior)."""
    ds = DocSet()
    hub = SyncHub(ds)
    box = []
    h = hub.add_peer("p", box.append)
    hub.open()
    ds.set_doc("A", am.change(am.init("alice"), lambda d: d.__setitem__("a", 1)))
    h.receive_msg({"docId": "A", "clock": {}})
    assert [m["docId"] for m in box if m.get("changes")] == ["A"]
    box.clear()
    ds.set_doc("B", am.change(am.init("bob"), lambda d: d.__setitem__("b", 2)))
    assert [m for m in box if m.get("changes")] == [], box
    assert any(m["docId"] == "B" and "changes" not in m for m in box)


def test_readded_peer_syncs_fresh():
    ds = DocSet()
    hub = SyncHub(ds)
    box = []
    h = hub.add_peer("q", box.append)
    hub.open()
    ds.set_doc("D", am.change(am.init("alice"), lambda d: d.__setitem__("x", 1)))
    h.receive_msg({"docId": "D", "clock": {}})
    assert any(m.get("changes") for m in box)
    hub.remove_peer("q")
    box2 = []
    h2 = hub.add_peer("q", box2.append)
    h2.receive_msg({"docId": "D", "clock": {}})
    assert any(m.get("changes") for m in box2), box2


def test_readded_peer_rerequests_doc_from_prior_session():
    """Churn regression (add -> remove -> re-add mid-sync): the
    don't-re-request-removed-docs guard is scoped to one peer SESSION.
    A doc the hub held during an old peer session (then removed locally)
    must be re-requested when the RE-ADDED peer offers it — the old
    hub-global `_had_doc` suppressed this forever."""
    ds = DocSet()
    hub = SyncHub(ds)
    box = []
    h = hub.add_peer("q", box.append)
    hub.open()
    # session 1: the peer syncs doc D to us mid-sync
    src = am.change(am.init("w"), lambda d: d.__setitem__("x", 1))
    h.receive_msg({"docId": "D", "clock": {"w": 1},
                   "changes": am.get_all_changes(src)})
    assert am.to_json(ds.get_doc("D")) == {"x": 1}
    # we drop the doc locally, and the peer churns
    ds.remove_doc("D")
    hub.remove_peer("q")
    box2 = []
    h2 = hub.add_peer("q", box2.append)
    # session 2: the same-id peer re-offers D -> must be re-requested
    h2.receive_msg({"docId": "D", "clock": {"w": 1}})
    requests = [m for m in box2 if m["docId"] == "D" and m["clock"] == {}]
    assert requests, f"re-add suppressed the re-request: {box2}"
    # and the peer's answer resurrects the doc for the new session
    h2.receive_msg({"docId": "D", "clock": {"w": 1},
                    "changes": am.get_all_changes(src)})
    assert am.to_json(ds.get_doc("D")) == {"x": 1}


def test_same_session_removed_doc_still_not_rerequested():
    """The counterpart: WITHIN one peer session the guard still holds
    (mirrors test_removed_doc_neither_crashes_nor_resurrects, pinned here
    against the session-scoped rewrite)."""
    ds = DocSet()
    hub = SyncHub(ds)
    box = []
    h = hub.add_peer("p", box.append)
    hub.open()
    src = am.change(am.init("w"), lambda d: d.__setitem__("x", 1))
    h.receive_msg({"docId": "D", "clock": {"w": 1},
                   "changes": am.get_all_changes(src)})
    ds.remove_doc("D")
    box.clear()
    h.receive_msg({"docId": "D", "clock": {"w": 1}})
    assert [m for m in box if m["docId"] == "D"] == [], box


def test_late_message_for_removed_peer_absorbed_without_send():
    """A message in flight when remove_peer ran must neither KeyError nor
    write to the dead transport; change-bearing frames are still absorbed
    (the hub-side mirror of the closed-Connection contract)."""
    ds = DocSet()
    hub = SyncHub(ds)
    box = []
    h = hub.add_peer("p", box.append)
    hub.open()
    hub.remove_peer("p")
    box.clear()
    src = am.change(am.init("w"), lambda d: d.__setitem__("x", 1))
    h.receive_msg({"docId": "D", "clock": {"w": 1},
                   "changes": am.get_all_changes(src)})   # absorbed
    h.receive_msg({"docId": "D", "clock": {"w": 1}})      # no re-request
    assert box == []
    assert am.to_json(ds.get_doc("D")) == {"x": 1}


def test_removed_doc_neither_crashes_nor_resurrects():
    ds = DocSet()
    hub = SyncHub(ds)
    box = []
    h = hub.add_peer("p", box.append)
    hub.open()
    ds.set_doc("D", am.change(am.init("alice"), lambda d: d.__setitem__("x", 1)))
    h.receive_msg({"docId": "D", "clock": {}})
    ds.remove_doc("D")
    box.clear()
    # unrelated doc change must not crash on the removed doc
    ds.set_doc("E", am.change(am.init("bob"), lambda d: d.__setitem__("y", 2)))
    assert any(m["docId"] == "E" for m in box)
    # a peer advertising the removed doc must not trigger a re-request
    box.clear()
    h.receive_msg({"docId": "D", "clock": {"alice": 1}})
    assert [m for m in box if m["docId"] == "D"] == [], box


def test_unrevealed_and_removed_pairs_never_enter_pending():
    """pending() must not re-flag pairs flush() can never serve."""
    ds = DocSet()
    hub = SyncHub(ds)
    h = hub.add_peer("p", lambda m: None)
    hub.open()
    ds.set_doc("A", am.change(am.init("alice"), lambda d: d.__setitem__("a", 1)))
    ds.set_doc("B", am.change(am.init("bob"), lambda d: d.__setitem__("b", 2)))
    h.receive_msg({"docId": "A", "clock": {}})
    # B was never revealed by the peer: only caught-up A pairs exist
    assert hub._matrix.pending() == []
    hub.remove_peer("p")
    ds.set_doc("A", am.change(ds.get_doc("A"), lambda d: d.__setitem__("a2", 3)))
    assert hub._matrix.pending() == []


def test_covered_clock_pair_leaves_pending():
    """A pair whose raw clock is behind but transitively covered is
    recorded as caught-up after one flush (no perpetual re-diffing)."""
    ds = DocSet()
    hub = SyncHub(ds)
    h = hub.add_peer("p", lambda m: None)
    hub.open()
    a = am.change(am.init("alice"), lambda d: d.__setitem__("x", 1))
    b = am.merge(am.init("bob"), a)
    b = am.change(b, lambda d: d.__setitem__("y", 2))
    ds.set_doc("D", b)
    # peer reveals only bob's seq: transitively covers alice's change
    h.receive_msg({"docId": "D", "clock": {"bob": 1}})
    assert ("p", "D") not in hub._matrix.pending()


def test_missing_changes_fast_cover_path():
    """A peer whose clock covers the doc gets [] without a closure walk."""
    from automerge_tpu.backend import device as db
    d = am.change(am.init("alice"), lambda doc: doc.__setitem__("x", 1))
    d = am.change(d, lambda doc: doc.__setitem__("y", 2))
    state = Frontend.get_backend_state(d)
    assert db.get_missing_changes(state, dict(state.clock)) == []
    missing = db.get_missing_changes(state, {"alice": 1})
    assert len(missing) == 1 and missing[0]["seq"] == 2
    assert len(db.get_missing_changes(state, {})) == 2


def test_connection_close_unhooks_hub_from_docset():
    """When the last Connection closes, the hub unhooks from the DocSet:
    no handler remains, snapshot set_doc is legal again, and a reopened
    connection starts with fresh peer state."""
    ds = DocSet()
    d1 = am.change(am.init("alice"), lambda d: d.__setitem__("x", 1))
    ds.set_doc("doc", d1)
    c = Connection(ds, lambda m: None)
    c.open()
    assert len(ds._handlers) == 1
    d2 = am.change(d1, lambda d: d.__setitem__("y", 2))
    ds.set_doc("doc", d2)
    c.close()
    assert ds._handlers == []          # hub handler gone
    assert ds._sync_hub is None
    # with no connections, putting an older snapshot back is allowed
    # again (e.g. time-travel UI) — the hub's stale-state guard is gone
    ds.set_doc("doc", d1)
    ds.set_doc("doc", d2)
    c.open()                            # rejoining works, fresh state
    assert len(ds._handlers) == 1
    c.close()


def test_closed_connection_absorbs_late_messages_without_sending():
    """A late in-flight message delivered after close() must neither
    rejoin the hub nor write to the torn-down transport; inbound changes
    are still absorbed."""
    ds_a, ds_b = DocSet(), DocSet()
    out_a, out_b = [], []
    ca, cb = Connection(ds_a, out_a.append), Connection(ds_b, out_b.append)
    ds_a.set_doc("doc", am.change(am.init("alice"),
                                  lambda d: d.__setitem__("x", 1)))
    ca.open(); cb.open()
    while out_a or out_b:               # pump to quiescence
        while out_a:
            cb.receive_msg(out_a.pop(0))
        while out_b:
            ca.receive_msg(out_b.pop(0))
    assert am.to_json(ds_b.get_doc("doc")) == {"x": 1}

    # a sends one more change-bearing message, then b closes BEFORE it
    # arrives
    ds_a.set_doc("doc", am.change(ds_a.get_doc("doc"),
                                  lambda d: d.__setitem__("y", 2)))
    late = [m for m in out_a if m.get("changes")]
    assert late
    cb.close()
    n_sent = len(out_b)
    cb.receive_msg(late[0])             # late delivery after close
    assert len(out_b) == n_sent         # nothing written to dead transport
    assert ds_b._sync_hub is None       # did not rejoin
    assert am.to_json(ds_b.get_doc("doc")) == {"x": 1, "y": 2}  # absorbed


def test_lossy_network_recovers_on_reconnect():
    """Messages dropped at random are recovered by peer reconnection: a
    (re)joining peer is re-advertised everything, so a lossless exchange
    after reconnect converges every node — the protocol's recovery story
    (the reference's, too: re-sends happen on state change or peer (re)
    connect, never spontaneously)."""
    import random

    for seed in (1, 2, 3):
        rng = random.Random(41_000 + seed)
        sets = [DocSet() for _ in range(3)]
        queues = {(i, j): [] for i in range(3) for j in range(3) if i != j}
        conns = {}

        def connect(i, j):
            conns[(i, j)] = Connection(sets[i], queues[(i, j)].append)
            conns[(i, j)].open()

        for i in range(3):
            for j in range(3):
                if i != j:
                    connect(i, j)

        def pump(drop_p, rounds=15):
            for _ in range(rounds):
                moved = False
                for (i, j), q in queues.items():
                    while q:
                        msg = q.pop(0)
                        if rng.random() < drop_p:
                            continue
                        conns[(j, i)].receive_msg(msg)
                        moved = True
                if not moved:
                    break

        sets[0].set_doc("d", am.change(am.init("seed"),
                                       lambda d: d.__setitem__("x", 0)))
        for step in range(6):           # lossy editing period
            i = rng.randrange(3)
            cur = sets[i].get_doc("d")
            if cur is not None:
                sets[i].set_doc("d", am.change(
                    am.set_actor_id(cur, f"n{i}s{step}"),
                    lambda d: d.__setitem__(f"k{step}", i)))
            pump(drop_p=0.3, rounds=2)

        # recovery: reconnect every face, then drain losslessly
        for pair in list(conns):
            conns[pair].close()
            connect(*pair)
        for _ in range(5):
            pump(drop_p=0.0)
        states = [am.to_json(sets[i].get_doc("d")) for i in range(3)
                  if sets[i].get_doc("d") is not None]
        assert len(states) >= 2, f"seed {seed}: doc never spread"
        assert all(s == states[0] for s in states), \
            f"seed {seed}: diverged after reconnect: {states}"


def _revealed_hub(n_peers=2):
    """A hub over one doc whose peers have all revealed its clock;
    returns (doc set, hub, {peer: outbox})."""
    ds = DocSet()
    hub = SyncHub(ds)
    boxes = {f"p{i}": [] for i in range(n_peers)}
    handles = {p: hub.add_peer(p, boxes[p].append) for p in boxes}
    hub.open()
    ds.set_doc("doc1", am.change(am.init("alice"),
                                 lambda d: d.__setitem__("x", 0)))
    for h in handles.values():
        h.receive_msg({"docId": "doc1", "clock": {}})
    for box in boxes.values():
        box.clear()
    return ds, hub, boxes


def test_flush_deferred_is_a_noop_when_nothing_is_pending():
    _ds, hub, boxes = _revealed_hub()
    with mock.patch.object(SyncHub, "flush", wraps=hub.flush) as spy:
        assert hub.flush_deferred() is False
        with hub.batched():
            assert hub.flush_deferred() is False
        assert spy.call_count == 0
    assert all(box == [] for box in boxes.values())


def test_flush_deferred_sends_now_and_a_later_change_flushes_at_exit():
    """Inside one ``batched()`` block: the pending flush runs at once,
    the block keeps deferring, and a change made after it flushes once
    at the block's exit."""
    ds, hub, boxes = _revealed_hub()

    def edit(key):
        ds.set_doc("doc1", am.change(ds.get_doc("doc1"),
                                     lambda d: d.__setitem__(key, 1)))

    def carries(m):
        return bool(m.get("changes")) or m.get("wire") is not None

    def sent():
        return [sum(map(carries, box)) for box in boxes.values()]

    with hub.batched():
        edit("y")
        assert sent() == [0, 0]
        assert hub.flush_deferred() is True
        assert sent() == [1, 1]
        assert hub.flush_deferred() is False
        edit("z")
        assert sent() == [1, 1]         # still deferred
    assert sent() == [2, 2]
    for box in boxes.values():
        last = [m for m in box if carries(m)][-1]
        assert last["clock"] == dict(
            Frontend.get_backend_state(ds.get_doc("doc1")).clock)
