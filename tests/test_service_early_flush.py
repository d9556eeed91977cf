"""A room's hub flush inside the service tick: each room's changes leave
for its peers as soon as the room's own groups of the tick have applied,
not after every room's apply.

A four-room service (room ``r0`` holds two docs) over protocol-faithful
channels, driven on the CPU by a seeded schedule of one-character text
inserts; a recording transport logs every envelope the service hands
it, with the tick, the obs clock and the sending thread.
"""

import json
import random
import threading
from collections import Counter, defaultdict, deque
from unittest import mock

import pytest

import automerge_tpu as am
from automerge_tpu import obs
from automerge_tpu.engine.wire_format import as_frame, split_outgoing
from automerge_tpu.resilience.channel import ResilientChannel
from automerge_tpu.service import ServiceConfig, SyncService

ROOT_OBJ = "00000000-0000-0000-0000-000000000000"
BASE = 4
ROOMS = ("r0", "r1", "r2", "r3")
TWO_DOCS = ("r0", "r0-b")
TICKS = 10


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    yield
    obs.disable()


def _seed(doc_id: str) -> dict:
    """A text of BASE characters, made by actor ``base-<doc>``."""
    obj = f"text-{doc_id}"
    ops = [{"action": "makeText", "obj": obj}]
    prev = "_head"
    for c in range(1, BASE + 1):
        ops.append({"action": "ins", "obj": obj, "key": prev, "elem": c})
        ops.append({"action": "set", "obj": obj, "key": f"base-{doc_id}:{c}",
                    "value": "abcd"[c - 1]})
        prev = f"base-{doc_id}:{c}"
    ops.append({"action": "link", "obj": ROOT_OBJ, "key": "text",
                "value": obj})
    return {"actor": f"base-{doc_id}", "seq": 1, "deps": {}, "ops": ops}


class _Peer:
    """A typing client of one room: a reliable channel to its tenant
    session, over a transport that logs what the service sends."""

    def __init__(self, svc, tid: str, room: str, docs, log: list):
        self.svc, self.tid, self.room, self.docs = svc, tid, room, docs
        self.actor = tid.replace("-", "")
        self.seqs = {d: 0 for d in docs}
        self.log = log
        self.inq: deque = deque()
        self.received: list = []    # payloads the client channel delivered
        self.sess = svc.connect(tid, room, self._arrive)
        self.chan = ResilientChannel(self.sess.on_wire, self.received.append)
        for d in docs:
            self.chan.send({"docId": d, "clock": {f"base-{d}": 1}})

    def _arrive(self, env):
        self.log.append((self.svc._tick_no, obs.now() if obs.ENABLED else 0,
                         threading.get_ident(), self.tid, env))
        self.inq.append(env)

    def type(self, doc_id: str, binary: bool) -> tuple:
        """Send the next one-character insert into ``doc_id``; returns
        its (doc, actor, seq)."""
        self.seqs[doc_id] += 1
        seq = self.seqs[doc_id]
        elem, obj = BASE + seq, f"text-{doc_id}"
        change = {"actor": self.actor, "seq": seq,
                  "deps": {f"base-{doc_id}": 1} if seq == 1 else {},
                  "ops": [{"action": "ins", "obj": obj, "key": "_head",
                           "elem": elem},
                          {"action": "set", "obj": obj,
                           "key": f"{self.actor}:{elem}", "value": "x"}]}
        msg = {"docId": doc_id,
               "clock": {f"base-{doc_id}": 1, self.actor: seq}}
        if binary:
            _prefix, msg["wire"] = split_outgoing([change], min_ops=1)
        else:
            msg["changes"] = [change]
        self.chan.send(msg)
        return (doc_id, self.actor, seq)

    def pump(self):
        while self.inq:
            self.chan.on_wire(self.inq.popleft())
        self.chan.tick()


def _build(svc, rooms, docs_of, per_room: int, log: list) -> list:
    """Seed every room's docs and connect ``per_room`` peers to each,
    interleaved across rooms so that admission interleaves them too."""
    for room in rooms:
        for d in docs_of(room):
            svc.seed_doc(room, am.apply_changes(am.init(f"server-{d}"),
                                                [_seed(d)]), doc_id=d)
    peers = [_Peer(svc, f"{room}-p{j}", room, docs_of(room), log)
             for j in range(per_room) for room in rooms]
    _settle(svc, peers)
    log.clear()
    return peers


def _settle(svc, peers, ticks: int = 6):
    for _ in range(ticks):
        svc.tick()
        for p in peers:
            p.pump()


def _docs_of(room: str) -> tuple:
    return TWO_DOCS if room == "r0" else (room,)


def _drive(seed: int, svc=None, rooms=ROOMS, docs_of=_docs_of,
           per_room: int = 3):
    """Run the seeded schedule: every tick each peer types with
    probability 0.6 into one of its room's docs, on the dict or the
    binary wire; on even ticks ``r0-p0`` and ``r0-p1`` type into the two
    docs of ``r0``. Returns (service, peers, log, typed changes)."""
    svc = svc or SyncService(ServiceConfig())
    log: list = []
    peers = _build(svc, rooms, docs_of, per_room, log)
    rng = random.Random(seed)
    typed = []
    for t in range(TICKS):
        for p in peers:
            forced = t % 2 == 0 and p.tid in ("r0-p0", "r0-p1")
            if forced:
                doc = TWO_DOCS[p.tid == "r0-p1"]
            elif rng.random() < 0.6:
                doc = rng.choice(p.docs)
            else:
                continue
            typed.append((p.room, p.type(doc, binary=rng.random() < 0.5)))
        svc.tick()
        for p in peers:
            p.pump()
    _settle(svc, peers)
    return svc, peers, log, typed


def _keys(payload) -> list:
    """(doc, actor, seq) of every change a sync payload carries."""
    doc = payload["docId"]
    out = [(doc, c["actor"], c["seq"]) for c in payload.get("changes") or ()]
    wire = payload.get("wire")
    if wire is not None:
        b = as_frame(wire).batch()
        out += [(doc, a, s) for a, s in zip(b.actors, b.seqs.tolist())]
    return out


def _carries_changes(env) -> bool:
    payload = env.get("payload")
    return isinstance(payload, dict) and bool(
        payload.get("changes") or payload.get("wire") is not None)


def _assert_each_change_once(peers, typed):
    """Every peer received every change its room's other peers typed,
    exactly once, and nothing else."""
    for p in peers:
        got = Counter(k for payload in p.received for k in _keys(payload))
        want = Counter(k for room, k in typed
                       if room == p.room and k[1] != p.actor)
        assert got == want, p.tid


def _named(records, cat, name):
    return [r for r in records if r[2] == cat and r[3] == name
            and r[1] >= 0]


@pytest.fixture(scope="module")
def traced():
    """The schedule's records, transport log and typed changes with
    tracing on."""
    obs.disable()
    with obs.tracing(capacity=1 << 16) as rec:
        rec.clear()
        svc, peers, log, typed = _drive(seed=11)
        records = obs.snapshot()
    obs.disable()
    svc.close()
    assert rec.n_emitted == rec.n_retained
    return records, log, peers, typed, dict(svc.stats)


def _ticks(records) -> dict:
    """tick number -> its ``svc/tick`` record."""
    return {r[5]["tick"]: r for r in _named(records, "svc", "tick")}


def _in_tick(records, cat, name, tick) -> list:
    t0, t1 = tick[0], tick[0] + tick[1]
    return sorted((r for r in _named(records, cat, name)
                   if t0 <= r[0] and r[0] + r[1] <= t1),
                  key=lambda r: r[0])


def test_envelopes_leave_before_the_next_group_applies(traced):
    """Every envelope carrying a room's changes reaches the transport
    after the room's last ``svc/deliver`` of the tick ends and before
    the next group's ``svc/deliver`` starts."""
    records, log, _peers, _typed, _stats = traced
    ticks = _ticks(records)
    checked = 0
    for tick_no, ts, _tid, peer, env in log:
        if not _carries_changes(env) or tick_no not in ticks:
            continue
        room = peer.rsplit("-", 1)[0]
        delivers = _in_tick(records, "svc", "deliver", ticks[tick_no])
        own = [r for r in delivers if r[5]["room"] == room]
        if not own:
            continue        # a catch-up send outside the delivery phase
        last_end = own[-1][0] + own[-1][1]
        later = [r[0] for r in delivers if r[0] >= last_end]
        assert last_end <= ts, (peer, tick_no)
        if later:
            assert ts < later[0], (peer, tick_no)
        checked += 1
    assert checked > 20


def test_two_doc_room_flushes_once_after_its_last_group(traced):
    """``r0``'s two docs are two groups of a tick; its hub flushes once
    in that tick, after the later of them, even when another room's
    group runs between the two."""
    records, _log, _peers, _typed, _stats = traced
    both = interleaved = 0
    for tick in _ticks(records).values():
        delivers = _in_tick(records, "svc", "deliver", tick)
        own = [i for i, r in enumerate(delivers) if r[5]["room"] == "r0"]
        if len(own) != 2:
            continue
        both += 1
        interleaved += own[1] - own[0] > 1
        flushes = [r for r in _in_tick(records, "hub", "flush", tick)
                   if r[5]["room"] == "r0"]
        assert len(flushes) == 1
        last = delivers[own[1]]
        assert flushes[0][0] >= last[0] + last[1]
    assert both >= TICKS // 2 and interleaved >= 1, (both, interleaved)


def test_one_hub_flush_per_touched_room_per_tick(traced):
    """Each room a tick delivered to flushes exactly once in it, inside
    its ``svc/flush`` span of phase ``deliver``; the service counts
    those flushes."""
    records, _log, _peers, _typed, stats = traced
    early = 0
    for tick in _ticks(records).values():
        rooms = {r[5]["room"]
                 for r in _in_tick(records, "svc", "deliver", tick)}
        flushes = Counter(r[5]["room"]
                          for r in _in_tick(records, "hub", "flush", tick))
        assert all(flushes[room] == 1 for room in rooms), flushes
        spans = _in_tick(records, "svc", "flush", tick)
        phase = Counter(r[5]["phase"] for r in spans)
        assert phase["tick"] == 1 and phase["deliver"] == len(rooms)
        early += len(rooms)
    assert stats["early_flushes"] >= early > 0


def test_every_peer_gets_every_change_once(traced):
    _records, _log, peers, typed, _stats = traced
    _assert_each_change_once(peers, typed)


def _dumps(env) -> str:
    return json.dumps(env, sort_keys=True,
                      default=lambda o: bytes(o.data).hex())


def test_envelopes_match_a_tick_end_flush():
    """The envelopes each peer gets in each tick are byte-identical to
    those of the same schedule with every room's flush left to the
    tick's end: the same deliveries made inside one ``hub.batched()``
    block per room, flushed as the block exits."""
    def per_peer_tick(log):
        out = defaultdict(list)
        for tick_no, _ts, _tid, peer, env in log:
            out[(peer, tick_no)].append(_dumps(env))
        return out

    svc, peers, log, typed = _drive(seed=23)
    early = svc.stats["early_flushes"]
    svc.close()
    with mock.patch.object(SyncService, "_flush_early",
                           lambda self, room: None):
        ref_svc, ref_peers, ref_log, ref_typed = _drive(seed=23)
    ref_early = ref_svc.stats["early_flushes"]
    ref_svc.close()
    assert typed == ref_typed
    assert early > 0 and ref_early == 0
    assert per_peer_tick(log) == per_peer_tick(ref_log)
    _assert_each_change_once(ref_peers, ref_typed)


def test_parallel_leg_sends_on_the_caller_thread(monkeypatch):
    """Two lanes with tick pipelining on: the lane workers apply, the
    caller flushes after the barrier, so the transport only ever runs
    on the thread that called ``tick()``; every change still reaches
    every peer once."""
    monkeypatch.setenv("AMTPU_TICK_PIPELINE", "1")
    svc = SyncService(ServiceConfig(shard_lanes=2))
    try:
        by_lane = defaultdict(list)
        for i in range(64):
            name = f"pl{i}"
            lane = svc._shard_placement.shard_of(name)
            if len(by_lane[lane]) < 2:
                by_lane[lane].append(name)
        rooms = tuple(sorted(by_lane[0] + by_lane[1]))
        assert len(rooms) == 4

        def docs_of(room):
            return (room,)
        svc, peers, log, typed = _drive(seed=5, svc=svc, rooms=rooms,
                                        docs_of=docs_of, per_room=2)
        ex = svc._mesh_executor()
        assert ex is not None and ex.stats["barriers"] > 0
        assert svc.stats["early_flushes"] > 0
        caller = threading.get_ident()
        assert log and {tid for _n, _ts, tid, _p, _e in log} == {caller}
        _assert_each_change_once(peers, typed)
    finally:
        svc.close()
