"""Drive the served sync path from the client side.

The system under test is ``SyncService`` (``automerge_tpu/service``) with
one shard lane per chip, so every room's document tables live on the
chip. Clients are protocol-faithful peers: each holds a
``ResilientChannel`` over an in-process transport, opens by advertising
its clock, and sends ``{docId, clock, changes | wire}`` sync messages.
What a client receives is stamped when the server hands it to the
transport and is parsed only after the window.

One loop drives everything, on one thread: hand the due messages to the
transport (``inject``), run one service tick (``svc.tick``), let the
clients take what arrived and acknowledge it (``pump``). The same loop
runs the warm-up, the measured window and the drain.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque

import numpy as np

CLOCK = time.perf_counter


class Peer:
    """One client: a reliable channel to its tenant session."""

    __slots__ = ("online", "sess", "chan", "inq", "log", "_dirty", "_t")

    def __init__(self, svc, tid: str, room: str, online: bool, dirty: set,
                 seed: int):
        from automerge_tpu.resilience.channel import ResilientChannel
        self.online = online
        self.inq: deque = deque()
        self.log: list = []         # (arrival time, payload), online only
        self._dirty = dirty
        self._t = 0.0
        self.sess = svc.connect(tid, room, self._arrive)
        self.chan = ResilientChannel(self._to_server, self._deliver,
                                     seed=seed)

    def _arrive(self, env):
        self.inq.append((CLOCK(), env))
        self._dirty.add(self)

    def _to_server(self, env):
        self.sess.on_wire(env)

    def _deliver(self, payload):
        if self.online:
            self.log.append((self._t, payload))

    def pump(self):
        while self.inq:
            self._t, env = self.inq.popleft()
            self.chan.on_wire(env)


class Harness:
    def __init__(self, config: dict, traffic, trace: bool):
        self.config = config
        self.traffic = traffic
        self.trace = trace
        self.clock = CLOCK
        self.dirty: set = set()
        self.sending: set = set()
        self.svc = None
        self.peers: list = []
        self.ticks: list = []       # (start, seconds) of every svc.tick
        self.shapes_warmed = 0

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- set-up -----------------------------------------------------------

    def build(self):
        """Service, seeded rooms, connected and revealed peers."""
        import automerge_tpu as am
        from automerge_tpu.service import ServiceConfig, SyncService
        self.svc = svc = SyncService(ServiceConfig(**self.config["service"]))
        for spec in self.traffic.rooms:
            room = svc.room(spec["room"])
            ctx = (room.lane.device_ctx() if room.lane is not None
                   else contextlib.nullcontext())
            with ctx:
                doc = am.apply_changes(am.init(f"server-{spec['room']}"),
                                       [spec["seed"]])
            svc.seed_doc(spec["room"], doc)
        for i, spec in enumerate(self.traffic.peers):
            self.peers.append(Peer(svc, spec["tid"], spec["room"],
                                   spec["online"], self.dirty, seed=i))
        for i in range(len(self.peers)):
            self.send(i, self.traffic.reveal(i))
        self.settle(CLOCK() + 120.0)
        for peer in self.peers:
            peer.log.clear()

    def send(self, i: int, msg: dict):
        peer = self.peers[i]
        peer.chan.send(msg)
        if peer.chan.in_flight:
            self.sending.add(peer)

    # -- the loop -----------------------------------------------------------

    def _take_arrivals(self):
        while self.dirty:
            batch = list(self.dirty)
            self.dirty.clear()
            for peer in batch:
                peer.pump()

    def _pump(self):
        self._take_arrivals()
        for peer in list(self.sending):
            if peer.chan.in_flight:
                peer.chan.tick()      # retransmit what was not acked
            else:
                self.sending.discard(peer)
        self._take_arrivals()

    def _step(self):
        with self.annotate("inject"):
            self.traffic.inject(self, CLOCK())
        with self.annotate("svc.tick"):
            t = CLOCK()
            self.svc.tick()
            self.ticks.append((t, CLOCK() - t))
        with self.annotate("pump"):
            self._pump()

    def warm_up(self) -> float:
        """Compile every shape the traffic can reach, then run the
        traffic's warm-up; returns the window's first instant."""
        reach = getattr(self.traffic, "max_room_inserts", None)
        if reach is not None:
            self.shapes_warmed = self.warm_shapes(reach())
        self.traffic.start(CLOCK())
        while True:
            w0 = self.traffic.warm_done(CLOCK())
            if w0 is not None:
                return w0
            self._step()

    def run(self, t_stop: float):
        """Offer, tick and pump until ``t_stop``."""
        while CLOCK() < t_stop:
            self._step()

    def quiet(self) -> bool:
        return (not self.dirty and not self.sending and self.svc.idle())

    def settle(self, deadline: float) -> bool:
        """Tick and pump without offering until nothing is queued or in
        flight anywhere, or the deadline passes."""
        while CLOCK() < deadline:
            with self.annotate("drain"):
                self.svc.tick()
                self._pump()
            if self.quiet():
                return True
        return False

    # -- counters read at the window's edges --------------------------------

    def counters(self) -> dict:
        from automerge_tpu.engine import accounting
        from automerge_tpu.obs import device_truth
        svc = self.svc
        wire = sum(s.channel.stats["bytes_sent"]
                   for s in svc.tenants.values())
        wire += sum(p.chan.stats["bytes_sent"] for p in self.peers)
        return {"t": CLOCK(), "admitted_ops": svc.stats["admitted_ops"],
                "dispatches": accounting.snapshot()["dispatches"],
                "compiles": device_truth.REGISTRY.compile_snapshot(),
                "wire_bytes": wire,
                "protocol_errors": svc.stats["protocol_errors"],
                "evictions": svc.stats["evictions"]}

    # -- what the clients received ------------------------------------------

    def received(self):
        """Per online peer: {(actor, seq): first arrival time}; every
        received change as ((actor, seq), its set values joined); and the
        frames, decoded afresh from their bytes as a socket would deliver
        them. Dict-wire changes come back whole in the frames' place."""
        from automerge_tpu.engine.columnar import KIND_SET
        from automerge_tpu.engine.wire_format import WireFrame
        first = []
        values = []
        dict_changes = []
        frames = []
        for i, peer in enumerate(self.peers):
            seen: dict = {}
            if peer.online:
                for t, payload in peer.log:
                    for ch in payload.get("changes") or ():
                        key = (ch["actor"], ch["seq"])
                        seen.setdefault(key, t)
                        dict_changes.append((i, ch))
                        values.append((key, "".join(
                            op["value"] for op in ch["ops"]
                            if op["action"] == "set")))
                    wire = payload.get("wire")
                    if wire is None:
                        continue
                    data = wire.data if hasattr(wire, "data") else wire
                    frame = WireFrame(bytes(data))
                    b = frame.batch()
                    is_set = b.op_kind == KIND_SET
                    rows = b.op_change[is_set]
                    codes = b.op_value[is_set]
                    cuts = np.searchsorted(rows, np.arange(1, b.n_changes))
                    for actor, seq, part in zip(b.actors, b.seqs.tolist(),
                                                np.split(codes, cuts)):
                        key = (actor, seq)
                        seen.setdefault(key, t)
                        values.append((key, "".join(map(chr,
                                                        part.tolist()))))
                    frames.append((i, frame))
            first.append(seen)
        return first, values, dict_changes, frames

    @contextlib.contextmanager
    def _text_engine(self, room_id: str):
        """The device engine of a room's one text object, inside the
        room's lane context, after replaying any write-behind rounds into
        it; yields (backend state, engine)."""
        from automerge_tpu.frontend import get_backend_state
        room = self.svc.room(room_id)
        state = get_backend_state(room.doc_set.get_doc(room_id))
        ctx = (room.lane.device_ctx() if room.lane is not None
               else contextlib.nullcontext())
        with ctx:
            core = state.read_core()
            core.flush_pending()
            if len(core.objects) != 1:
                raise RuntimeError(f"{room_id}: {len(core.objects)} objects")
            (wrapper,) = core.objects.values()
            yield state, wrapper.doc

    def engine_docs(self, room_ids: list) -> dict:
        """room id -> (clock, elem ids, values) of the room's text object
        as the device engine holds it."""
        out = {}
        for room_id in room_ids:
            with self._text_engine(room_id) as (state, eng):
                out[room_id] = (dict(state.clock), eng.elem_ids(),
                                eng.values())
        return out

    def warm_shapes(self, max_inserts: int) -> int:
        """Compile, in set-up, the text materialization of every shape a
        room can reach with up to ``max_inserts`` more one-character
        inserts; returns the number of shapes.

        The engine sizes that program by two static buckets, one of the
        document's segment count and one of its length (``_mat_params``).
        An insert adds at most two segments, and how many it adds depends
        on where it lands, so the rooms of one run reach different pairs
        of buckets and the warm-up traffic alone leaves some pairs to the
        window. Every pair of a segment count up to ``2 * max_inserts +
        1`` and a length up to ``max_inserts`` more is run here through
        the engine's own materialize call on the first room, with the
        pair forced; the room's document is not changed."""
        import jax
        import jax.numpy as jnp
        room_id = self.traffic.rooms[0]["room"]
        with self._text_engine(room_id) as (_state, eng):
            params = type(eng)._mat_params
            n0 = eng.n_elems
            seg_b = sorted({params(eng, seg_bound=s, n_elems=n0)[0]
                            for s in range(1, 2 * max_inserts + 2)})
            len_b = sorted({params(eng, seg_bound=1, n_elems=n)[1]
                            for n in range(n0, n0 + max_inserts + 1)})
            as_u8 = params(eng)[2]
            staged = eng._n_elems_dev
            try:
                # the element count goes in as a host scalar, or as the
                # device scalar a solo round staged: two call signatures
                for count in (None, (n0, jnp.asarray(np.int32(n0)))):
                    eng._n_elems_dev = count
                    for L in len_b:
                        for S in seg_b:
                            eng._mat_params = (
                                lambda *a, _p=(S, L, as_u8), **k: _p)
                            jax.block_until_ready(
                                eng._run_materialize(True, S))
            finally:
                eng.__dict__.pop("_mat_params", None)
                eng._n_elems_dev = staged
        return len(seg_b) * len(len_b)

    def close(self):
        if self.svc is not None:
            self.svc.close()
        self.svc = None
        self.peers = []
        self.dirty.clear()
        self.sending.clear()
