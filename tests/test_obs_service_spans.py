"""Spans inside the served path (INTERNALS §11.3, §13): the service
tick's children, the per-message inbox wait, the host runtime's garbage
collections and compiles, and the clock link to a ``jax.profiler``
trace.

A two-room service with two typing peers per room, over protocol-
faithful channels, on the CPU.
"""

import gc
import glob
import time
from collections import deque

import jax
import pytest

import automerge_tpu as am
from automerge_tpu import obs
from automerge_tpu.engine.wire_format import split_outgoing
from automerge_tpu.obs.device_truth import KernelHandle
from automerge_tpu.resilience.channel import ResilientChannel
from automerge_tpu.service import ServiceConfig, SyncService, TenantBudget
from loadbench import spans

ROOT_OBJ = "00000000-0000-0000-0000-000000000000"
BASE = 8

# the tick's direct children, in order
CHILDREN = ("admit", "deliver", "sessions", "flush", "lag")


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    yield
    obs.disable()


def _seed(room: str) -> dict:
    obj = f"text-{room}"
    ops = [{"action": "makeText", "obj": obj}]
    prev = "_head"
    for c in range(1, BASE + 1):
        ops.append({"action": "ins", "obj": obj, "key": prev, "elem": c})
        ops.append({"action": "set", "obj": obj, "key": f"base-{room}:{c}",
                    "value": "abcdefgh"[c - 1]})
        prev = f"base-{room}:{c}"
    ops.append({"action": "link", "obj": ROOT_OBJ, "key": "text",
                "value": obj})
    return {"actor": f"base-{room}", "seq": 1, "deps": {}, "ops": ops}


class _Peer:
    """A typing client: a reliable channel to its tenant session."""

    def __init__(self, svc, tid: str, room: str, budget=None):
        self.room, self.actor, self.seq = room, tid.replace("-", ""), 0
        self.inq: deque = deque()
        self.sess = svc.connect(tid, room, self.inq.append, budget=budget)
        self.chan = ResilientChannel(self.sess.on_wire, lambda p: None)
        self.chan.send({"docId": room, "clock": {f"base-{room}": 1}})

    def change(self) -> dict:
        """The next one-character insert at the head of the text."""
        self.seq += 1
        elem = BASE + self.seq
        obj = f"text-{self.room}"
        return {"actor": self.actor, "seq": self.seq,
                "deps": {f"base-{self.room}": 1} if self.seq == 1 else {},
                "ops": [{"action": "ins", "obj": obj, "key": "_head",
                         "elem": elem},
                        {"action": "set", "obj": obj,
                         "key": f"{self.actor}:{elem}", "value": "x"}]}

    def type(self, binary: bool = False):
        change = self.change()
        msg = {"docId": self.room,
               "clock": {f"base-{self.room}": 1, self.actor: self.seq}}
        if binary:
            _prefix, msg["wire"] = split_outgoing([change], min_ops=1)
        else:
            msg["changes"] = [change]
        self.chan.send(msg)

    def pump(self):
        while self.inq:
            self.chan.on_wire(self.inq.popleft())
        self.chan.tick()


def _service(budget=None):
    svc = SyncService(ServiceConfig())
    peers = []
    for room in ("r0", "r1"):
        svc.seed_doc(room, am.apply_changes(am.init(f"server-{room}"),
                                            [_seed(room)]))
        peers += [_Peer(svc, f"{room}-p{j}", room, budget)
                  for j in range(2)]
    _settle(svc, peers)
    return svc, peers


def _settle(svc, peers, ticks: int = 6):
    for _ in range(ticks):
        svc.tick()
        for p in peers:
            p.pump()


def _traced(fn):
    """Records emitted while ``fn`` runs with tracing on."""
    with obs.tracing(capacity=1 << 14) as rec:
        rec.clear()
        fn()
        return obs.snapshot()


def _named(records, cat, name):
    return [r for r in records if r[2] == cat and r[3] == name
            and r[1] >= 0]


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[0] + inner[1] <= outer[0] + outer[1]


@pytest.fixture(scope="module")
def typed_tick():
    """The records of two traced ticks in which every peer typed one
    change, the second on the binary wire; no automatic collection runs
    during them (a pause between two children would not be the tick's
    own work)."""
    obs.disable()
    svc, peers = _service()

    def run():
        for binary in (False, True):
            for p in peers:
                p.type(binary=binary)
            svc.tick()
            for p in peers:
                p.pump()
    was = gc.isenabled()
    gc.disable()
    try:
        return _traced(run)
    finally:
        if was:
            gc.enable()
        obs.disable()
        svc.close()


@pytest.mark.parametrize("cat,name,parent", [
    ("svc", "admit", ("svc", "tick")),
    ("svc", "deliver", ("svc", "tick")),
    ("svc", "sessions", ("svc", "tick")),
    ("svc", "flush", ("svc", "tick")),
    ("svc", "lag", ("svc", "tick")),
    ("backend", "apply", ("svc", "deliver")),
    ("frontend", "patch", ("svc", "deliver")),
    ("hub", "flush", ("svc", "flush")),
])
def test_tick_children_nest_in_their_parent(typed_tick, cat, name, parent):
    """Each named child appears and lies inside a span of its parent."""
    children = _named(typed_tick, cat, name)
    parents = _named(typed_tick, *parent)
    assert children and parents
    for child in children:
        assert any(_inside(child, p) for p in parents), (child, parent)


def test_tick_children_cover_the_tick(typed_tick):
    """Each tick has one admit, sessions and lag span, one tick-end
    flush and one in-delivery flush per touched room, its direct
    children never overlap, and together they cover at least 90% of
    the ticks' time."""
    ticks = _named(typed_tick, "svc", "tick")
    covered = 0
    for tick in ticks:
        kids = sorted((r for n in CHILDREN
                       for r in _named(typed_tick, "svc", n)
                       if _inside(r, tick)), key=lambda r: r[0])
        for n in ("admit", "sessions", "lag"):
            assert sum(1 for r in kids if r[3] == n) == 1
        phases = [r[5]["phase"] for r in kids if r[3] == "flush"]
        assert phases.count("tick") == 1
        early = [r[5]["room"] for r in kids if r[3] == "flush"
                 and r[5]["phase"] == "deliver"]
        assert sorted(early) == ["r0", "r1"]
        for a, b in zip(kids, kids[1:]):
            assert a[0] + a[1] <= b[0]
        covered += sum(r[1] for r in kids)
    total = sum(t[1] for t in ticks)
    assert len(ticks) == 2 and covered >= 0.9 * total, (covered, total)


@pytest.mark.parametrize("fast", [False, True])
def test_deliver_span_args(typed_tick, fast):
    """One ``svc/deliver`` per (room, doc) group and tick, naming the
    room, its changes and ops, and whether the binary wire fast lane
    took it."""
    delivers = [r for r in _named(typed_tick, "svc", "deliver")
                if r[5]["fast"] is fast]
    assert sorted(r[5]["room"] for r in delivers) == ["r0", "r1"]
    for r in delivers:
        assert r[5]["n_changes"] == 2 and r[5]["n_ops"] == 4


def test_hub_flush_span_args(typed_tick):
    flushes = [r for r in _named(typed_tick, "hub", "flush")
               if r[5]["peers"]]
    assert {r[5]["room"] for r in flushes} == {"r0", "r1"}
    for r in flushes:
        assert r[5]["frames"] <= r[5]["peers"]
        assert (r[5]["bytes"] > 0) == (r[5]["frames"] > 0)


@pytest.mark.parametrize("deferred", [False, True])
def test_inbox_wait_per_admitted_change_message(deferred):
    """One ``svc/inbox_wait`` per admitted message that carries changes,
    from the instant it was queued to its admission; a message the
    budget defers keeps its start across ticks. Clock reveals get
    none."""
    budget = TenantBudget(ops_per_tick=2) if deferred else None
    svc, peers = _service(budget)
    typist = peers[0]
    try:
        def run():
            typist.type()
            typist.type()
            typist.chan.send({"docId": typist.room, "clock": {}})
            queued = [t for _m, _b, _n, t in typist.sess.inbox]
            ticks = []
            while typist.sess.inbox:
                ticks.append(svc._tick_no + 1)
                svc.tick()
            run.queued, run.ticks = queued, ticks
        records = _traced(run)
    finally:
        svc.close()
    waits = _named(records, "svc", "inbox_wait")
    assert len(run.queued) == 3 and all(run.queued)
    assert [r[0] for r in waits] == run.queued[:2]
    assert all(r[5]["room"] == "r0" and r[5]["doc"] == "r0" for r in waits)
    assert [r[5]["tick"] for r in waits] == (
        run.ticks[:2] if deferred else run.ticks[:1] * 2)
    ticks = {r[5]["tick"]: r for r in _named(records, "svc", "tick")}
    for r in waits:
        tick = ticks[r[5]["tick"]]
        assert tick[0] <= r[0] + r[1] <= tick[0] + tick[1]
    if deferred:
        assert waits[1][0] < ticks[run.ticks[0]][0]   # waited out tick 1


def test_tracing_off_tick_emits_nothing():
    """With tracing off, queued messages carry no instant and a tick
    with work emits no record."""
    svc, peers = _service()
    try:
        with obs.tracing():
            pass                    # the recorder exists, tracing is off
        obs.clear()
        for p in peers:
            p.type()
        assert all(t == 0 for p in peers for *_x, t in p.sess.inbox)
        svc.tick()
        assert obs.recorder().n_emitted == 0 and obs.snapshot() == []
    finally:
        svc.close()


@pytest.mark.parametrize("tracing", [False, True])
def test_gc_span_only_while_tracing(tracing):
    with obs.tracing(capacity=1 << 12) as rec:
        rec.clear()
    if tracing:
        obs.enable()
    gc.collect()
    found = _named(obs.snapshot(), "host", "gc")
    obs.disable()
    assert (obs._on_gc in gc.callbacks) is False
    if tracing:
        assert any(r[5]["generation"] == 2 for r in found)
    else:
        assert found == []


def test_compile_span_for_a_fresh_shape():
    """A kernel's first call on a new shape is a ``device/compile`` span
    naming the kernel; a repeat call is not."""
    kernel = KernelHandle(jax.jit(lambda x: x * 3 + 1), "obs_fresh_shape")
    x = jax.numpy.arange(7 * 5).reshape(7, 5)

    def run():
        kernel(x)
        kernel(x)
    compiles = _named(_traced(run), "device", "compile")
    assert [r[5]["kernel"] for r in compiles] == ["obs_fresh_shape/plain"]
    assert compiles[0][1] > 0


def test_anchor_maps_ring_span_onto_profiler_trace(tmp_path):
    """Ring spans land within 1 ms of a ``TraceAnnotation`` of the same
    region once the trace's two ``obs.clock`` anchors map the clocks."""
    with obs.tracing(capacity=1 << 12) as rec:
        rec.clear()
        jax.profiler.start_trace(str(tmp_path))
        try:
            obs.anchor_profiler()
            time.sleep(0.02)
            t0 = obs.now()
            with jax.profiler.TraceAnnotation("obs_probe_region"):
                time.sleep(0.05)
            obs.span("test", "region", t0)
            time.sleep(0.02)
            obs.anchor_profiler()
        finally:
            jax.profiler.stop_trace()
        ring = _named(obs.snapshot(), "test", "region")
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    marks = spans.anchors(path)
    assert len(marks) == 2
    (start, end, name), = spans.on_trace(ring, spans.to_trace(marks))
    assert name == "test/region"
    from jax.profiler import ProfileData
    (ann,) = [ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name == "obs_probe_region"]
    assert abs(start - ann.start_ns) < 1e6
    assert abs(end - (ann.start_ns + ann.duration_ns)) < 1e6
