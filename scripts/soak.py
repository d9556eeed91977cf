"""Seeded randomized soak harness — the round-4 campaign, committed.

Round 4 ran ~800 ad-hoc soak sessions that found a real convergence bug
(net-zero remote histories silently dropped by merge — fixed,
tests/test_integration.py::TestNetZeroMerge); the runner itself was never
committed. This is that harness as a reproducible,
seeded tool, exceeding the reference's fixed-scenario suite
(/root/reference/test/connection_test.js:17-65) by fuzzing at scale.

Profiles (each session is deterministic in its seed):
  general   nested histories with undo/redo and merge interleavings
  conflict  same-key / same-element races with partial pairwise sync
  lossy     Connection-protocol sync over a dropping network with churn
  table     concurrent Table row add/update/remove with partial sync
  chaos     Connection sync over ChaosLink+ResilientChannel (drop/dup/
            reorder/delay plus one partition/heal cycle) — byte-identical
            convergence after heal, no reconnects needed
  checkpoint chaos sync with periodic async snapshots of one peer and a
            mid-run RESTART of that peer from its latest checkpoint
            bundle (automerge_tpu.checkpoint) — byte-identical
            convergence after catch-up
  service   the multi-tenant service tier (automerge_tpu.service,
            INTERNALS §13) at scale: N client sessions over chaotic
            links into one tick-scheduled SyncService (room-sharded
            hubs, budgeted admission, credit backpressure), with
            partitions, slow-peer injection, and kill/rejoin churn.
            Asserts byte-identical convergence of every SURVIVOR with
            its room's server replica, bounded memory (inbox / channel
            reorder window / quarantine peaks never exceed the
            configured caps), no tenant starvation, and full dead-peer
            state reclamation (hub + ClockMatrix + quarantine) after
            eviction.

Usage:
  python scripts/soak.py [--profile all] [--sessions 30] [--seed-base 0]
  python scripts/soak.py --chaos [--sessions 50]     # chaos campaign
  python scripts/soak.py --checkpoint [--sessions 10]
  python scripts/soak.py --service [--clients 1000]  # service-scale soak
  python scripts/soak.py --service --quick           # CI smoke (100)
  python scripts/soak.py --chaos --trace             # + Perfetto trace

Exit 0 iff every session converged; failures print their profile+seed so
`--profile P --sessions 1 --seed-base SEED` reproduces one exactly.

The final line is ONE JSON summary (the machine-readable artifact):
profile, seed_base, per-seed failures, and the aggregated obs event
counters (INTERNALS §11) — chaos injections (drops/dups/reorders/delays/
partition drops), channel retransmits/dedups/window drops, and
quarantine parks/evictions/releases — so a failing soak is diagnosable
from the artifact alone: the seed reproduces it, the event mix says what
the transport actually did. ``--trace`` additionally dumps the retained
flight-recorder records as Chrome trace JSON (``soak_trace.json``;
AMTPU_TRACE_OUT overrides).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _am():
    import automerge_tpu as am
    return am


KEYS = ["alpha", "beta", "gamma", "delta", "eps"]


def _rand_value(rng):
    kind = rng.integers(0, 4)
    if kind == 0:
        return int(rng.integers(-1000, 1000))
    if kind == 1:
        return "".join(chr(97 + int(c)) for c in rng.integers(0, 26, 5))
    if kind == 2:
        return {"n": int(rng.integers(0, 99))}
    return [int(x) for x in rng.integers(0, 9, 3)]


def _text_edit(am, doc, rng):
    def cb(d):
        t = d["t"]
        n = len(t)
        if n and rng.integers(0, 3) == 0:
            t.delete_at(int(rng.integers(0, n)))
        else:
            t.insert_at(int(rng.integers(0, n + 1)),
                        chr(97 + int(rng.integers(0, 26))))
    return am.change(doc, cb)


def _converged(am, docs):
    jsons = [am.to_json(d) for d in docs]
    ref = {k: (str(v) if hasattr(v, "elems") else v)
           for k, v in jsons[0].items()}
    for j in jsons[1:]:
        got = {k: (str(v) if hasattr(v, "elems") else v)
               for k, v in j.items()}
        if got != ref:
            return False, (ref, got)
    return True, None


def session_general(seed: int) -> None:
    """Nested histories + undo/redo + merge interleavings."""
    am = _am()
    from automerge_tpu import Text
    rng = np.random.default_rng(seed)
    base = am.change(am.init("base"), lambda d: (
        d.__setitem__("t", Text("seed")), d.__setitem__("m", {"k": 0})))
    changes = am.get_all_changes(base)
    peers = [am.apply_changes(am.init(f"actor-{i}"), changes)
             for i in range(3)]
    for _ in range(int(rng.integers(15, 30))):
        i = int(rng.integers(0, len(peers)))
        act = int(rng.integers(0, 6))
        if act == 0:
            k = KEYS[int(rng.integers(0, len(KEYS)))]
            v = _rand_value(rng)
            peers[i] = am.change(peers[i],
                                 lambda d, k=k, v=v: d.__setitem__(k, v))
        elif act == 1:
            peers[i] = _text_edit(am, peers[i], rng)
        elif act == 2:
            n = int(rng.integers(0, 50))
            peers[i] = am.change(
                peers[i], lambda d, n=n: d["m"].__setitem__("k", n))
        elif act == 3 and am.can_undo(peers[i]):
            peers[i] = am.undo(peers[i])
        elif act == 4 and am.can_redo(peers[i]):
            peers[i] = am.redo(peers[i])
        else:
            j = int(rng.integers(0, len(peers)))
            if j != i:
                peers[i] = am.merge(peers[i], peers[j])
    # full cross-merge in seed-random order until stable, then converge
    order = rng.permutation(len(peers))
    for _ in range(2):
        for i in order:
            for j in order:
                if i != j:
                    peers[i] = am.merge(peers[i], peers[j])
    ok, diff = _converged(am, peers)
    assert ok, f"general seed {seed} diverged: {diff}"
    # save/load must preserve the converged state
    back = am.load(am.save(peers[0]))
    ok, diff = _converged(am, [peers[0], back])
    assert ok, f"general seed {seed} save/load mismatch: {diff}"


def session_conflict(seed: int) -> None:
    """Same-key and same-element races with partial pairwise sync."""
    am = _am()
    from automerge_tpu import Text
    rng = np.random.default_rng(seed)
    base = am.change(am.init("base"), lambda d: (
        d.__setitem__("t", Text("abcdef")),
        *[d.__setitem__(k, 0) for k in KEYS]))
    changes = am.get_all_changes(base)
    peers = [am.apply_changes(am.init(f"w{i}"), changes) for i in range(4)]
    for step in range(int(rng.integers(10, 20))):
        for i in range(len(peers)):          # every peer races every step
            act = int(rng.integers(0, 3))
            if act == 0:
                k = KEYS[int(rng.integers(0, len(KEYS)))]
                peers[i] = am.change(
                    peers[i], lambda d, k=k, i=i, s=step:
                    d.__setitem__(k, f"w{i}s{s}"))
            elif act == 1 and len(peers[i]["t"]):
                idx = int(rng.integers(0, len(peers[i]["t"])))
                peers[i] = am.change(
                    peers[i], lambda d, idx=idx, i=i:
                    d["t"].set(min(idx, len(d["t"]) - 1), str(i)))
            else:
                peers[i] = _text_edit(am, peers[i], rng)
        if rng.integers(0, 2):               # partial sync: one random pair
            i, j = rng.choice(len(peers), 2, replace=False)
            peers[int(i)] = am.merge(peers[int(i)], peers[int(j)])
    for _ in range(2):
        for i in range(len(peers)):
            for j in range(len(peers)):
                if i != j:
                    peers[i] = am.merge(peers[i], peers[j])
    ok, diff = _converged(am, peers)
    assert ok, f"conflict seed {seed} diverged: {diff}"
    # conflict METADATA must converge too, not just winners
    for k in KEYS:
        refc = am.get_conflicts(peers[0], k)
        for p in peers[1:]:
            assert am.get_conflicts(p, k) == refc, \
                f"conflict seed {seed}: conflicts diverged at {k}"


def session_lossy(seed: int) -> None:
    """Connection sync over a dropping in-memory network with churn."""
    am = _am()
    from automerge_tpu import Connection, DocSet, Text
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 4))
    sets = [DocSet() for _ in range(n)]
    doc0 = am.change(am.init("origin"),
                     lambda d: d.__setitem__("t", Text("start")))
    base_changes = am.get_all_changes(doc0)
    for i, ds in enumerate(sets):
        ds.set_doc("doc", am.apply_changes(am.init(f"peer-{i}"),
                                           base_changes))

    queues: dict = {}
    conns: dict = {}

    def wire(a: int, b: int):
        ca = Connection(sets[a], lambda m, a=a, b=b:
                        queues.setdefault((a, b), []).append(m))
        cb = Connection(sets[b], lambda m, a=a, b=b:
                        queues.setdefault((b, a), []).append(m))
        conns[(a, b)], conns[(b, a)] = ca, cb
        ca.open()
        cb.open()

    def deliver(edge, drop_p: float):
        q = queues.get(edge, [])
        while q:
            msg = q.pop(0)
            if rng.random() < drop_p:
                continue                      # lost on the wire
            conns[(edge[1], edge[0])].receive_msg(msg)

    for a in range(n):
        for b in range(a + 1, n):
            wire(a, b)
    edges = list(conns.keys())

    for step in range(int(rng.integers(10, 25))):
        i = int(rng.integers(0, n))
        doc = sets[i].get_doc("doc")
        sets[i].set_doc("doc", _text_edit(am, doc, rng))
        for edge in edges:
            deliver(edge, drop_p=0.3)
        if rng.integers(0, 5) == 0:           # churn: bounce one pair
            a, b = edges[int(rng.integers(0, len(edges)))]
            if a < b:                         # close both directions once
                conns[(a, b)].close()
                conns[(b, a)].close()
                queues.pop((a, b), None)      # in-flight frames die too
                queues.pop((b, a), None)
                wire(a, b)
    # recovery contract (pinned by tests/test_connection_traces.py):
    # dropped frames are recovered on the next STATE CHANGE or peer
    # RECONNECT — a bare re-delivery of what's still queued is not enough,
    # because the receiver never learns a dropped frame existed. Bounce
    # every connection (reconnect re-advertises clocks, prompting
    # re-sends), then drain losslessly until quiescent.
    for a in range(n):
        for b in range(a + 1, n):
            conns[(a, b)].close()
            conns[(b, a)].close()
            queues.pop((a, b), None)
            queues.pop((b, a), None)
            wire(a, b)
    for _ in range(4):                        # let re-requests settle
        for edge in edges:
            deliver(edge, drop_p=0.0)
    docs = [ds.get_doc("doc") for ds in sets]
    ok, diff = _converged(am, docs)
    assert ok, f"lossy seed {seed} diverged: {diff}"


def session_table(seed: int) -> None:
    """Concurrent Table row add/update/remove with partial sync — the
    row-oriented surface the other profiles never touch."""
    am = _am()
    from automerge_tpu import Table
    rng = np.random.default_rng(seed)
    base = am.change(am.init("base"), lambda d: d.__setitem__("t", Table()))
    changes = am.get_all_changes(base)
    peers = [am.apply_changes(am.init(f"tw{i}"), changes) for i in range(3)]
    known_rows: list = []           # row ids any peer has minted
    for step in range(int(rng.integers(12, 24))):
        i = int(rng.integers(0, len(peers)))
        act = int(rng.integers(0, 4))
        if act == 0 or not known_rows:       # add a row
            holder = {}
            def add(d, i=i, s=step, holder=holder):
                holder["id"] = d["t"].add(
                    {"by": f"tw{i}", "step": s,
                     "v": int(rng.integers(0, 99))})
            peers[i] = am.change(peers[i], add)
            known_rows.append(holder["id"])
        elif act == 1:                       # update a row if visible here
            rid = known_rows[int(rng.integers(0, len(known_rows)))]
            if peers[i]["t"].by_id(rid) is not None:
                peers[i] = am.change(
                    peers[i], lambda d, rid=rid, s=step:
                    d["t"].by_id(rid).__setitem__("v", 1000 + s))
        elif act == 2:                       # remove a row if visible here
            rid = known_rows[int(rng.integers(0, len(known_rows)))]
            if peers[i]["t"].by_id(rid) is not None:
                peers[i] = am.change(
                    peers[i], lambda d, rid=rid: d["t"].remove(rid))
        else:                                # partial sync
            j = int(rng.integers(0, len(peers)))
            if j != i:
                peers[i] = am.merge(peers[i], peers[j])
    for _ in range(2):
        for i in range(len(peers)):
            for j in range(len(peers)):
                if i != j:
                    peers[i] = am.merge(peers[i], peers[j])
    ok, diff = _converged(am, peers)   # to_json renders tables as dicts
    assert ok, f"table seed {seed} diverged: {diff}"


def session_chaos(seed: int) -> None:
    """3-peer Connection sync over a chaotic transport — drop, duplication,
    reordering, delay, and ONE partition/heal cycle — made survivable by
    the resilience layer (ResilientChannel seq/ack/retry over ChaosLink).

    Unlike the `lossy` profile, nothing is ever reconnected and no state
    change is needed for recovery: the channel's retransmit + dedup +
    in-order release restores the lossless transport the wire protocol
    assumes, and causally-premature cross-edge arrivals park in the
    bounded quarantine until their deps land. Convergence is asserted
    byte-identically: same rendered document AND same serialized change
    history on every peer."""
    import json as _json

    am = _am()
    from automerge_tpu import Connection, DocSet, Text
    from automerge_tpu.resilience import ChaosLink, ResilientChannel

    rng = np.random.default_rng(seed)
    n = 3
    sets = [DocSet() for _ in range(n)]
    doc0 = am.change(am.init("origin"),
                     lambda d: d.__setitem__("t", Text("start")))
    base = am.get_all_changes(doc0)
    for i, ds in enumerate(sets):
        ds.set_doc("doc", am.apply_changes(am.init(f"peer-{i}"), base))

    drop = float(rng.uniform(0.05, 0.30))        # ≤ 30% loss
    dup = float(rng.uniform(0.0, 0.20))          # ≤ 20% duplication
    reorder = float(rng.uniform(0.05, 0.30))
    delay = float(rng.uniform(0.0, 0.30))
    edges = [(a, b) for a in range(n) for b in range(n) if a != b]
    links, channels, conns = {}, {}, {}
    for a, b in edges:                            # directed chaos edges
        links[(a, b)] = ChaosLink(
            lambda env, a=a, b=b: channels[(b, a)].on_wire(env),
            rng=rng, drop=drop, dup=dup, reorder=reorder, delay=delay)
    for a, b in edges:                            # reliability endpoints
        channels[(a, b)] = ResilientChannel(
            links[(a, b)].send,
            lambda msg, a=a, b=b: conns[(a, b)].receive_msg(msg),
            seed=seed * 7919 + a * 97 + b)
    for a, b in edges:                            # the UNCHANGED protocol
        conns[(a, b)] = Connection(sets[a], channels[(a, b)].send)
        conns[(a, b)].open()

    def pump(rounds: int = 1):
        for _ in range(rounds):
            for e in edges:
                links[e].pump()
            for e in edges:
                channels[e].tick()

    n_steps = int(rng.integers(12, 22))
    part_at = int(rng.integers(2, n_steps - 6))   # one partition/heal cycle
    part_len = int(rng.integers(2, 6))
    pa, pb = (int(x) for x in rng.choice(n, 2, replace=False))
    for step in range(n_steps):
        if step == part_at:
            links[(pa, pb)].partition()
            links[(pb, pa)].partition()
        if step == part_at + part_len:
            links[(pa, pb)].heal()
            links[(pb, pa)].heal()
        i = int(rng.integers(0, n))
        sets[i].set_doc("doc", _text_edit(am, sets[i].get_doc("doc"), rng))
        pump(1)
    # heal, switch the links lossless, and let retransmission finish the
    # job — no reconnects, no fresh state changes
    for e in edges:
        links[e].heal()
        links[e].drop = links[e].dup = 0.0
        links[e].reorder = links[e].delay = 0.0
    for _ in range(400):
        pump(1)
        if all(ch.idle for ch in channels.values()) \
                and all(ln.idle for ln in links.values()):
            break
    else:
        raise AssertionError(f"chaos seed {seed}: channels never quiesced")

    docs = [ds.get_doc("doc") for ds in sets]
    ok, diff = _converged(am, docs)
    assert ok, f"chaos seed {seed} diverged: {diff}"
    hists = [sorted(_json.dumps(c, sort_keys=True)
                    for c in am.get_all_changes(d)) for d in docs]
    assert hists.count(hists[0]) == len(hists), \
        f"chaos seed {seed}: change histories diverged after heal"
    for ds in sets:                               # nothing left parked
        gate = getattr(ds, "_inbound_gate", None)
        assert not gate or gate.quarantined("doc") == 0, \
            f"chaos seed {seed}: quarantine not drained"


def session_checkpoint(seed: int) -> None:
    """Chaos sync with mid-run checkpointing and a peer RESTART: one peer
    periodically captures its document through the async checkpoint
    writer (automerge_tpu.checkpoint.AsyncCheckpointer), then mid-chaos
    its whole DocSet is torn down and rebuilt from the LAST completed
    checkpoint bundle — in-flight frames die, edits made after the
    capture are forgotten locally — and the sync protocol must pull the
    restarted peer back to byte-identical convergence over the still-
    chaotic links. Exercises capture-under-ingestion, bundle integrity
    verification, snapshot-bootstrapped rejoin, and tail catch-up in one
    scenario."""
    import json as _json

    am = _am()
    from automerge_tpu import Connection, DocSet, Text
    from automerge_tpu.checkpoint import AsyncCheckpointer
    from automerge_tpu.resilience import ChaosLink, ResilientChannel

    rng = np.random.default_rng(seed)
    n = 3
    sets = [DocSet() for _ in range(n)]
    doc0 = am.change(am.init("origin"),
                     lambda d: d.__setitem__("t", Text("start")))
    base = am.get_all_changes(doc0)
    for i, ds in enumerate(sets):
        ds.set_doc("doc", am.apply_changes(am.init(f"peer-{i}"), base))

    drop = float(rng.uniform(0.05, 0.25))
    reorder = float(rng.uniform(0.05, 0.25))
    links, channels, conns = {}, {}, {}

    def wire_edge(a, b):
        links[(a, b)] = ChaosLink(
            lambda env, a=a, b=b: channels[(b, a)].on_wire(env),
            rng=rng, drop=drop, dup=0.05, reorder=reorder, delay=0.1)
        channels[(a, b)] = ResilientChannel(
            links[(a, b)].send,
            lambda msg, a=a, b=b: conns[(a, b)].receive_msg(msg),
            seed=seed * 7919 + a * 97 + b)
        conns[(a, b)] = Connection(sets[a], channels[(a, b)].send)

    edges = [(a, b) for a in range(n) for b in range(n) if a != b]
    for a, b in edges:
        wire_edge(a, b)
    for e in edges:
        conns[e].open()

    def pump(rounds: int = 1):
        for _ in range(rounds):
            for e in edges:
                links[e].pump()
            for e in edges:
                channels[e].tick()

    victim = int(rng.integers(0, n))
    writer = AsyncCheckpointer()
    handles: list = []
    bundle = None
    n_steps = int(rng.integers(14, 22))
    restart_at = int(rng.integers(6, n_steps - 4))
    restarted = False
    try:
        for step in range(n_steps):
            i = int(rng.integers(0, n))
            sets[i].set_doc("doc",
                            _text_edit(am, sets[i].get_doc("doc"), rng))
            if step % 3 == 0:        # periodic async snapshot of the victim
                from automerge_tpu import Frontend
                state = Frontend.get_backend_state(
                    sets[victim].get_doc("doc"))
                handles.append(writer.capture_async(state))
            if step == restart_at:
                for h in handles:    # latest completed capture wins
                    bundle = h.result(30)
                assert bundle is not None, "no checkpoint completed"
                # RESTART: the victim loses everything since its last
                # checkpoint; a fresh DocSet bootstraps from the bundle
                # and fresh links/channels/conns rejoin the mesh
                for a, b in edges:
                    if victim in (a, b):
                        conns[(a, b)].close()
                sets[victim] = DocSet()
                sets[victim].bootstrap_doc("doc", bundle)
                for a, b in edges:
                    if victim in (a, b):
                        wire_edge(a, b)
                        conns[(a, b)].open()
                restarted = True
            pump(1)
    finally:
        writer.close()
    assert restarted
    for e in edges:                  # heal: lossless from here on
        links[e].heal()
        links[e].drop = links[e].dup = 0.0
        links[e].reorder = links[e].delay = 0.0
    for _ in range(400):
        pump(1)
        if all(ch.idle for ch in channels.values()) \
                and all(ln.idle for ln in links.values()):
            break
    else:
        raise AssertionError(f"checkpoint seed {seed}: never quiesced")
    docs = [ds.get_doc("doc") for ds in sets]
    ok, diff = _converged(am, docs)
    assert ok, f"checkpoint seed {seed} diverged after restart: {diff}"
    hists = [sorted(_json.dumps(c, sort_keys=True)
                    for c in am.get_all_changes(d)) for d in docs]
    assert hists.count(hists[0]) == len(hists), \
        f"checkpoint seed {seed}: change histories diverged after restart"


#: Per-profile metrics registry: a profile that wants its numbers in the
#: campaign summary UPDATES ITS ENTRY IN PLACE (never prints its own
#: JSON — the one-line artifact contract lives in emit_summary alone,
#: so a new profile cannot regress it by copy-pasting emission logic).
#: Non-empty entries fold into the summary as "<profile>_metrics".
PROFILE_METRICS: dict = {"service": {}, "sharded": {}, "federation": {},
                         "residency": {}}

#: back-compat alias: the service profile's registry entry
LAST_SERVICE_METRICS = PROFILE_METRICS["service"]

#: --scrape: serve the live Prometheus endpoint during the service soak
#: and validate the exposition + /describe dump from an actual HTTP
#: fetch before the acceptance asserts run
SCRAPE = False


def _validate_scrape(url: str):
    """Fetch the LIVE scrape endpoint: the exposition page must pass the
    format validator (INTERNALS §14.3) and /describe must parse as the
    postmortem schema. Results fold into the summary line."""
    import json as _json
    import urllib.request

    from automerge_tpu.obs.prom import validate_prom

    page = urllib.request.urlopen(url + "/metrics", timeout=10) \
        .read().decode()
    counts = validate_prom(page)
    dump = _json.loads(
        urllib.request.urlopen(url + "/describe", timeout=10).read())
    assert dump.get("schema") == "amtpu-postmortem-v1", dump.get("schema")
    LAST_SERVICE_METRICS.update(scrape_ok=True,
                                scrape_families=counts["families"],
                                scrape_samples=counts["samples"])


class _SvcClient:
    """One tenant-side endpoint: DocSet + Connection + ResilientChannel
    over a pair of directed ChaosLinks into the service."""

    __slots__ = ("tid", "room_id", "ds", "chan", "conn", "c2s", "s2c",
                 "slow", "alive")

    def __init__(self, am, svc, tid, room_id, base_changes, actor,
                 link_seed, chaos, empty=False):
        from automerge_tpu import Connection, DocSet
        from automerge_tpu.resilience import ChaosLink, ResilientChannel
        self.tid = tid
        self.room_id = room_id
        self.slow = 1          # pump every `slow` ticks
        self.alive = True
        self.ds = DocSet()
        # lineage replica-site label: commit hops on this client's gate
        # name the tenant, so the per-replica completeness bar below can
        # ask "did THIS surviving replica see the change" (§18)
        self.ds._lineage_site = tid
        if not empty:
            # a rejoiner starts EMPTY instead: it must bootstrap from the
            # server (snapshot bundle when the history is long enough)
            self.ds.set_doc(room_id,
                            am.apply_changes(am.init(actor), base_changes))
        # frames for an evicted tenant (no live session) die on the
        # floor — exactly what a real listener does for a closed socket
        self.c2s = ChaosLink(
            lambda env: (svc.session(tid) is not None
                         and svc.session(tid).on_wire(env)),
            seed=link_seed, **chaos)
        self.s2c = ChaosLink(lambda env: self.chan.on_wire(env),
                             seed=link_seed + 1, **chaos)
        sess = svc.connect(tid, room_id, self.s2c.send,
                           seed=link_seed + 2)
        assert sess is not None
        self.chan = ResilientChannel(self.c2s.send, None,
                                     seed=link_seed + 3)
        self.conn = Connection(self.ds, self.chan.send)
        self.chan._deliver = self.conn.receive_msg
        self.conn.open()

    def pump(self):
        self.c2s.pump()
        self.s2c.pump()
        self.chan.tick()

    def heal(self):
        for ln in (self.c2s, self.s2c):
            ln.heal()
            ln.drop = ln.dup = ln.reorder = ln.delay = 0.0
        self.slow = 1

    def idle(self):
        return self.chan.idle and self.c2s.idle and self.s2c.idle


def session_service(seed: int, n_clients: int = 24, n_ticks: int = 30,
                    room_size: int = 4, quiesce_ticks: int = 400) -> None:
    """N concurrent tenant sessions against one SyncService under churn,
    partitions, slow peers, and kill/rejoin — the service tier's honest
    load test (ISSUE 8 acceptance run: ``--service --clients 1000``).

    Fault schedule (all seeded): every client link carries drop/dup/
    reorder/delay chaos; ~8% of clients get partitioned for a window;
    ~8% run slow (pump every 4th tick); ~6% are KILLED mid-run (vanish
    without a goodbye — the heartbeat/retransmit-cap ladder must declare
    them dead and reclaim everything), and half the killed REJOIN later
    as fresh sessions bootstrapped by the server (snapshot cache when
    history is long enough, plain changes otherwise).

    Asserted at the end (the acceptance bars):
      1. every room's surviving clients render AND serialize
         byte-identically to the server replica (change histories too);
      2. bounded memory: peak inbox <= inbox_cap + recv_window, peak
         channel reorder buffer <= recv_window, peak quarantine <= the
         aggregate cap — and zero parked changes remain;
      3. no tenant starved: max consecutive backlogged-but-unadmitted
         ticks <= 2x the starvation boost threshold;
      4. every killed-and-not-rejoined tenant was EVICTED and its hub /
         ClockMatrix / quarantine state fully reclaimed;
      5. the telemetry tier agrees: zero replication lag (ClockMatrix
         deficit + un-acked wire frames) for every live tenant at
         quiescence.

    Any failure — never-quiesced, divergence, a violated bound — writes
    the black-box postmortem dump (``SyncService.describe()``) to
    ``AMTPU_POSTMORTEM_OUT`` (default ``service_postmortem.json``)
    before re-raising, so a failed soak leaves flight data, not just a
    seed. With ``--scrape`` the Prometheus endpoint is served live for
    the whole session and validated over real HTTP at the end."""
    am = _am()
    from automerge_tpu.obs import lineage as _lin
    from automerge_tpu.service import ServiceConfig, SyncService, \
        TenantBudget

    # sample-EVERYTHING lineage (the acceptance/debug mode, rate=1)
    # adds measurable per-message work to the admission loop (~40% on
    # tick p50 at 100 clients on this box), which the population-scaled
    # deadline below doesn't know about — scale the budget so the
    # no-starvation bar keeps measuring scheduling fairness, not
    # tracing overhead. Production-rate sampling (1/64) is bounded at
    # <= 5% by the committed cfg14 row and gets no allowance.
    lineage_full = (_lin.ENABLED and _lin.ledger() is not None
                    and _lin.ledger().rate == 1)

    cfg = ServiceConfig(
        heartbeat_ticks=12, suspect_grace_ticks=12, max_retries=24,
        recv_window=256,
        # a real admission deadline so deadline shedding and the
        # starvation accounting are actually EXERCISED at scale — with
        # the default 0.0 the _starve path is unreachable (the first
        # message of a visit always admits) and the no-starvation
        # acceptance bar would be vacuously true. Scaled with the
        # population: the deadline bounds the admission LOOP, whose cost
        # is O(tenants), so a flat sub-ms budget that sheds honestly at
        # 100 clients starves everything at 1000 (measured: 972k sheds,
        # zero drain progress) while a flat generous one never fires
        tick_budget_ms=max(0.5, n_clients / 200.0)
        * (1.5 if lineage_full else 1.0),
        default_budget=TenantBudget(ops_per_tick=64,
                                    bytes_per_tick=32 * 1024,
                                    inbox_cap=32))
    svc = SyncService(cfg)
    # each seeded session is an independent deployment: a fresh ledger,
    # or seed N's acceptance would evaluate seed N-1's chains against
    # rooms that share names across sessions
    if _lin.ENABLED:
        _lin.clear()
    scrape_srv = svc.serve_metrics() if SCRAPE else None
    try:
        _service_scenario(am, svc, cfg, seed, n_clients, n_ticks,
                          room_size, quiesce_ticks)
        if scrape_srv is not None:
            _validate_scrape(scrape_srv.url)
    except Exception:
        # the black-box contract: a failing soak leaves a parseable
        # flight-data dump, not just an assertion message
        path = os.environ.get("AMTPU_POSTMORTEM_OUT",
                              "service_postmortem.json")
        try:
            svc.write_postmortem(path)
            print(f"soak: service postmortem written to {path}",
                  file=sys.stderr, flush=True)
        except Exception as dump_exc:   # noqa: BLE001 — never mask the
            print(f"soak: postmortem dump failed: {dump_exc!r}",  # cause
                  file=sys.stderr, flush=True)
        raise
    finally:
        if scrape_srv is not None:
            scrape_srv.close()


def _service_scenario(am, svc, cfg, seed, n_clients, n_ticks, room_size,
                      quiesce_ticks):
    import json as _json
    import math

    from automerge_tpu import Text

    rng = np.random.default_rng(seed)
    n_rooms = max(1, math.ceil(n_clients / room_size))
    base_changes: dict = {}
    for g in range(n_rooms):
        room_id = f"room-{g}"
        doc0 = am.change(am.init(f"{room_id}-origin"), lambda d: (
            d.__setitem__("t", Text("start")), d.__setitem__("m", {})))
        base_changes[room_id] = am.get_all_changes(doc0)
        svc.seed_doc(room_id,
                     am.apply_changes(am.init(f"server-{g}"),
                                      base_changes[room_id]))
        # small rooms have short histories; a lowered snapshot threshold
        # keeps the rejoin path exercising the cached-bundle bootstrap
        svc.room(room_id).hub.snapshot_min_changes = 8

    chaos = {"drop": float(rng.uniform(0.02, 0.10)),
             "dup": float(rng.uniform(0.0, 0.05)),
             "reorder": float(rng.uniform(0.02, 0.10)),
             "delay": float(rng.uniform(0.0, 0.10))}
    clients: dict = {}
    epoch: dict = {}          # tid -> rejoin epoch (fresh actor ids)

    def wire(tid: str, room_id: str, empty: bool = False):
        e = epoch.get(tid, 0)
        clients[tid] = _SvcClient(
            am, svc, tid, room_id, base_changes[room_id],
            actor=f"c-{tid}-e{e}",
            link_seed=seed * 104729 + int(tid.split("-")[-1]) * 13 + e * 7,
            chaos=chaos, empty=empty)

    for i in range(n_clients):
        wire(f"{seed}-{i}", f"room-{i % n_rooms}")

    ids = list(clients)
    n_slow = max(1, n_clients // 12)
    for tid in rng.choice(ids, n_slow, replace=False):
        clients[str(tid)].slow = 4
    # partitions: a window per victim inside the main loop
    n_part = max(1, n_clients // 12)
    part_victims = [str(t) for t in rng.choice(ids, n_part, replace=False)]
    part_at = {t: int(rng.integers(3, max(4, n_ticks - 10)))
               for t in part_victims}
    part_len = {t: int(rng.integers(3, 9)) for t in part_victims}
    # kills (never the last live member of a room) + later rejoins
    n_kill = max(1, n_clients // 16)
    kill_order = [str(t) for t in rng.choice(ids, n_kill, replace=False)]
    kill_at = {t: int(rng.integers(6, max(7, n_ticks - 4)))
               for t in kill_order}
    rejoiners = set(kill_order[: len(kill_order) // 2])
    rejoin_at = {t: kill_at[t] + int(rng.integers(4, 10))
                 for t in rejoiners}
    killed: set = set()
    n_kills_done = 0
    n_rejoins_done = 0

    def live_room_members(room_id):
        return [c for c in clients.values()
                if c.room_id == room_id and c.alive]

    def pump_all(tick_no: int):
        for c in clients.values():
            if c.alive and tick_no % c.slow == 0:
                c.pump()
        svc.tick()

    for t in range(n_ticks):
        for tid in part_victims:
            c = clients[tid]
            if t == part_at[tid] and c.alive:
                c.c2s.partition()
                c.s2c.partition()
            if t == part_at[tid] + part_len[tid]:
                c.c2s.heal()
                c.s2c.heal()
        for tid, at in kill_at.items():
            c = clients[tid]
            if t == at and c.alive and len(live_room_members(c.room_id)) > 1:
                c.alive = False          # vanishes; no goodbye
                killed.add(tid)
                n_kills_done += 1
        for tid, at in rejoin_at.items():
            if t == at and tid in killed:
                killed.discard(tid)
                epoch[tid] = epoch.get(tid, 0) + 1
                n_rejoins_done += 1
                # fresh everything, EMPTY doc-set: the server must
                # bootstrap the rejoiner (snapshot cache / plain changes)
                wire(tid, clients[tid].room_id, empty=True)
        # edits: a random slice of live clients each tick
        n_edit = max(1, n_clients // 20)
        for tid in rng.choice(ids, n_edit, replace=False):
            c = clients[str(tid)]
            if not c.alive:
                continue
            doc = c.ds.get_doc(c.room_id)
            if doc is None:
                continue    # a rejoiner still waiting on its bootstrap
            if int(rng.integers(0, 3)) == 0:
                doc = _text_edit(am, doc, rng)
            else:
                k = KEYS[int(rng.integers(0, len(KEYS)))]
                v = int(rng.integers(0, 999))
                doc = am.change(doc, lambda d, k=k, v=v:
                                d["m"].__setitem__(k, v))
            c.ds.set_doc(c.room_id, doc)
        pump_all(t)

    # ---- drain: heal everything, then run lossless until quiescent ----
    for c in clients.values():
        c.heal()
    # rooms holding killed-but-unowed tenants get one server-side edit so
    # the hub OWES the dead peer frames — the heartbeat ladder needs an
    # outstanding debt to escalate on (an idle peer is not a dead peer)
    for tid in killed:
        room_id = clients[tid].room_id
        room = svc.room(room_id)
        doc = room.doc_set.get_doc(room_id)
        if doc is not None:
            room.doc_set.set_doc(room_id, am.change(
                doc, lambda d: d["m"].__setitem__("_drain", 1)))
    n_orphan_rejoins = 0
    for q in range(quiesce_ticks):
        # a slow/partitioned-but-live client is server-side
        # indistinguishable from a vanished one, so the health ladder may
        # evict it (a legitimate per-tenant degradation). Its recovery
        # path is the client keepalive noticing the dead session and
        # REJOINING fresh — eviction is degradation, never loss
        for tid, c in list(clients.items()):
            if c.alive and svc.session(tid) is None:
                epoch[tid] = epoch.get(tid, 0) + 1
                n_orphan_rejoins += 1
                wire(tid, c.room_id, empty=True)
        pump_all(q)
        if svc.idle() \
                and all(c.idle() for c in clients.values() if c.alive) \
                and all(svc.session(tid) is None for tid in killed):
            break
    else:
        raise AssertionError(
            f"service seed {seed}: never quiesced "
            f"(unevicted={[t for t in killed if svc.session(t)]}, "
            f"metrics={svc.metrics()})")

    # ---- the acceptance asserts ----
    svc.probe_lag()                 # a fresh lag table for m + assert 5
    m = svc.metrics()
    LAST_SERVICE_METRICS.clear()
    LAST_SERVICE_METRICS.update(m, n_clients=n_clients, n_rooms=n_rooms,
                                killed=n_kills_done,
                                rejoined=n_rejoins_done,
                                orphan_rejoins=n_orphan_rejoins,
                                # the rolling-telemetry view of the tick
                                # tail (log-bucket conservative bound)
                                tick_p99_ms_telemetry=(
                                    svc.tick_p99_ms_telemetry()))
    # 1. byte-identical convergence of every survivor with its room
    for g in range(n_rooms):
        room_id = f"room-{g}"
        server_doc = svc.room(room_id).doc_set.get_doc(room_id)
        members = live_room_members(room_id)
        if server_doc is None:
            assert not members, f"room {room_id} lost its server replica"
            continue
        docs = [server_doc] + [c.ds.get_doc(room_id) for c in members]
        ok, diff = _converged(am, docs)
        assert ok, f"service seed {seed} room {room_id} diverged: {diff}"
        hists = [sorted(_json.dumps(ch, sort_keys=True)
                        for ch in am.get_all_changes(d)) for d in docs]
        assert hists.count(hists[0]) == len(hists), \
            f"service seed {seed} room {room_id}: histories diverged"
    # 2. bounded memory, and nothing left parked
    assert m["peak_inbox"] <= cfg.default_budget.inbox_cap \
        + cfg.recv_window, m
    assert m["peak_recv_buf"] <= cfg.recv_window, m
    assert m["peak_parked"] <= cfg.quarantine_global_capacity, m
    for g in range(n_rooms):
        gate = svc.room(f"room-{g}").gate
        assert gate._n_parked == 0, \
            f"service seed {seed}: room-{g} quarantine not drained"
    for c in clients.values():
        if c.alive:
            assert len(c.chan._recv_buf) <= 1024   # client RECV_WINDOW
    # 3. no tenant starves
    assert m["max_starved_streak"] <= 2 * cfg.starvation_boost_ticks, m
    # 4. dead-peer state fully reclaimed
    for tid in killed:
        assert svc.reclaimed(tid), \
            f"service seed {seed}: tenant {tid} not reclaimed after " \
            f"eviction"
    # every kill ends in exactly one eviction (health-ladder eviction for
    # the vanished, or the rejoin path evicting the stale session first)
    assert m["evictions"] >= n_kills_done, m
    # 5. the telemetry tier agrees convergence is done: zero replication
    #    lag — matrix deficit AND un-acked wire frames — for every live
    #    tenant (a quiesced mesh with nonzero lag would mean the probes
    #    measure something other than what convergence asserts)
    lag = svc.replication_lag()
    laggards = {t: v for t, v in lag.items() if v["ops"]}
    assert not laggards, \
        f"service seed {seed}: replication lag nonzero at quiescence: " \
        f"{dict(list(laggards.items())[:5])}"
    assert m["max_lag_ops"] == 0 and m["max_lag_ticks"] == 0, m
    # 6. lineage acceptance (ISSUE 14, when AMTPU_LINEAGE_RATE enabled
    #    sampling): >= 99% of sampled changes the server committed show
    #    a COMPLETE origin->visibility hop chain on every surviving
    #    replica of their room at quiescence, and the worst quarantine/
    #    defer dwell folds into the summary line
    _lineage_acceptance(svc, clients, seed)


def _lineage_acceptance(svc, clients, seed):
    from automerge_tpu.obs import lineage as lin
    led = lin.ledger()
    if led is None or not lin.ENABLED:
        return
    live_by_room: dict = {}
    for tid, c in clients.items():
        if c.alive and svc.session(tid) is not None:
            live_by_room.setdefault(c.room_id, set()).add(tid)
    total = complete = 0
    incomplete_sample = []
    for ch in led.chains():
        vis = led.visible_sites(ch)
        for room_id in {d for d in ch["docs"]
                        if isinstance(d, str) and d in svc._rooms}:
            server_site = f"svc:{room_id}"
            if server_site not in vis:
                # never committed at the authority over the wire: either
                # pre-seeded history (every replica was born with it) or
                # a dead client's change no survivor holds — out of the
                # per-replica completeness population either way
                continue
            origin = ch["origin_site"] or ""
            # map the origin actor back to its replica: soak client
            # actors are f"c-{tid}-e{epoch}"; everything else (seed
            # docs, server drain edits) originates at the server
            if origin.startswith("c-") and "-e" in origin:
                origin_replica = origin[2:].rsplit("-e", 1)[0]
            else:
                origin_replica = server_site
            expected = {server_site} | live_by_room.get(room_id, set())
            expected.discard(origin_replica)
            total += 1
            if ch["origin_ns"] is not None and expected <= vis:
                complete += 1
            elif len(incomplete_sample) < 5:
                incomplete_sample.append(
                    (ch["actor"], ch["seq"], sorted(expected - vis),
                     [h[0] for h in ch["hops"]]))
    ratio = complete / total if total else 1.0
    LAST_SERVICE_METRICS.update(
        lineage_rate=led.rate,
        lineage_sampled_chains=led.n_chains,
        lineage_commit_population=total,
        lineage_complete_ratio=round(ratio, 4),
        lineage_hops_per_chain=round(
            led.stats["hops_recorded"] / max(1, led.stats[
                "chains_started"]), 2),
        lineage_max_quarantine_dwell_ms=led.max_dwell_ms("quar/park"),
        lineage_max_defer_dwell_ms=led.max_dwell_ms("svc/defer"),
        lineage_visibility_p99_ms=led.visibility_ms(0.99))
    assert total > 0, \
        f"service seed {seed}: lineage sampling enabled but no sampled " \
        f"chain committed at any server replica (rate {led.rate} too " \
        f"selective for this population?)"
    assert ratio >= 0.99, (
        f"service seed {seed}: only {ratio:.2%} of sampled changes have "
        f"a complete origin->visibility chain on every surviving "
        f"replica; first incomplete: {incomplete_sample}")


def _sharded_stream(seed: int, n_docs: int, n_actors: int, n_seqs: int,
                    hot_doc: str, hot_factor: int, n_chunks: int):
    """Deterministic chaotic delivery schedule for one sharded session:
    per-doc causally-chained change lists (every seq depends on every
    actor's previous seq), fully shuffled across docs and seqs (so
    causally-premature arrivals are guaranteed and park in the router
    quarantine), with ~10% duplicated deliveries, chunked into
    `n_chunks` serving rounds. Same seed -> byte-identical schedule,
    whatever the shard count."""
    rng = np.random.default_rng(seed * 7919 + 17)
    docs = [f"sdoc-{seed}-{i}" for i in range(n_docs)]
    flat = []
    for di, doc in enumerate(docs):
        seqs = n_seqs * (hot_factor if doc == hot_doc else 1)
        for s in range(1, seqs + 1):
            for a in range(n_actors):
                actor, run = f"w{a}", 4
                base = (s - 1) * run + 1
                key = "_head" if s == 1 else f"{actor}:{base - 1}"
                ops = []
                for k in range(run):
                    ctr = base + k
                    ops.append({"action": "ins", "obj": doc, "key": key,
                                "elem": ctr})
                    ops.append({"action": "set", "obj": doc,
                                "key": f"{actor}:{ctr}",
                                "value": chr(97 + (ctr + a + di) % 26)})
                    key = f"{actor}:{ctr}"
                deps = {} if s == 1 else \
                    {f"w{b}": s - 1 for b in range(n_actors) if b != a}
                flat.append((doc, {"actor": actor, "seq": s,
                                   "deps": deps, "ops": ops}))
    rng.shuffle(flat)
    for i in rng.choice(len(flat), max(1, len(flat) // 10),
                        replace=False):
        flat.insert(int(rng.integers(0, len(flat))), flat[int(i)])
    per = max(1, -(-len(flat) // n_chunks))
    rounds = []
    for c in range(0, len(flat), per):
        chunk: dict = {}
        for doc, ch in flat[c: c + per]:
            chunk.setdefault(doc, []).append(ch)
        rounds.append(chunk)
    return docs, rounds


def session_sharded(seed: int, n_docs: int = 8, n_actors: int = 2,
                    n_seqs: int = 4, shard_counts=(1, 8)) -> None:
    """Shard-count invariance under chaotic delivery (ISSUE 10): the
    SAME seeded change stream — full cross-doc shuffle (premature
    arrivals park in the router quarantine), duplicated deliveries, and
    a telemetry-triggered hot-doc migration mid-stream on the
    multi-shard mesh — served at every shard count in `shard_counts`
    must converge to byte-identical state: per-doc checkpoint-bundle
    bytes (automerge_tpu.checkpoint.capture_engine — tables, clocks,
    dep closures, conflicts) AND rendered texts equal across meshes,
    with every quarantine drained. On meshes with >= 2 shards the
    rebalance policy must have actually moved the hot doc (the
    acceptance bar's "at least one telemetry-triggered migration
    mid-stream"); single-shard runs prove the same stream without any
    migration, so the comparison also pins migration neutrality."""
    from automerge_tpu.shard import ShardedDocSet
    from automerge_tpu.shard.parallel import parallel_lanes_enabled
    from automerge_tpu.shard.placement import hash_shard

    # hot doc: hammered `hot_factor` harder than the rest, chosen (from
    # ids alone, so every mesh sees the same stream) to share its
    # max-shard-count lane with another doc — migrating it away must
    # actually relieve a co-tenant
    max_shards = max(shard_counts)
    ids = [f"sdoc-{seed}-{i}" for i in range(n_docs)]
    homes = [hash_shard(d, max_shards) for d in ids]
    hot_doc = ids[0]
    for i, d in enumerate(ids):
        if homes.count(homes[i]) >= 2:
            hot_doc = d
            break
    results = {}
    exec_stats = {}
    for n_shards in shard_counts:
        docs, rounds = _sharded_stream(seed, n_docs, n_actors, n_seqs,
                                       hot_doc, hot_factor=4,
                                       n_chunks=6)
        mesh = ShardedDocSet(n_shards=n_shards, capacity=64)
        if n_shards >= 2:
            mesh.attach_rebalancer(ratio=2.0, min_ops=64, cooldown=2)
        # deliver_rounds (not a deliver_round loop): the multi-shard leg
        # runs the INTERNALS §24 parallel tier — per-lane workers + the
        # round-pipelining pre-decode seam — so the byte-identity
        # comparison below also pins parallel-vs-sequential parity (the
        # 1-shard leg stays the sequential comparator by default)
        mesh.deliver_rounds(rounds)
        ex = mesh._executor
        if parallel_lanes_enabled(n_shards):
            assert ex is not None, \
                f"sharded seed {seed} ({n_shards} shards): parallel " \
                "lanes enabled but no executor engaged"
        if ex is not None:
            assert ex.stats["barriers"] > 0 and ex.stats["errors"] == 0 \
                and ex.stats["submitted"] == ex.stats["completed"], \
                f"sharded seed {seed} ({n_shards} shards): lane workers " \
                f"attached but never engaged cleanly ({ex.stats})"
            exec_stats[n_shards] = dict(ex.stats)
        mesh.close()
        for doc in docs:
            assert mesh.quarantined(doc) == 0, \
                f"sharded seed {seed} ({n_shards} shards): quarantine " \
                f"not drained for {doc}"
        if n_shards >= 2:
            assert mesh.stats["migrations"] >= 1, \
                f"sharded seed {seed}: no telemetry-triggered migration " \
                f"on the {n_shards}-shard mesh ({mesh.stats}, loads " \
                f"{mesh.rebalancer.window_loads()})"
        results[n_shards] = (
            {doc: mesh.capture(doc) for doc in docs}, mesh.texts(),
            dict(mesh.stats))
    ref_shards = shard_counts[0]
    bundles0, texts0, _ = results[ref_shards]
    for n_shards, (bundles, texts, _stats) in results.items():
        assert texts == texts0, \
            f"sharded seed {seed}: texts diverged at {n_shards} vs " \
            f"{ref_shards} shards"
        for doc in bundles0:
            assert bundles[doc] == bundles0[doc], \
                f"sharded seed {seed}: checkpoint bytes of {doc} " \
                f"diverged at {n_shards} vs {ref_shards} shards"
    multi = max(shard_counts)
    PROFILE_METRICS["sharded"].clear()
    PROFILE_METRICS["sharded"].update(
        shard_counts=list(shard_counts), n_docs=n_docs,
        hot_doc=hot_doc, **{f"stats_{n}_shards": results[n][2]
                            for n in shard_counts},
        migrations=results[multi][2]["migrations"],
        parked=results[multi][2]["parked"],
        released=results[multi][2]["released"],
        lane_executor={str(n): st for n, st in exec_stats.items()})


def session_residency(seed: int, n_docs: int = 40, n_seqs: int = 4,
                      budget_docs: int = 4) -> None:
    """Bounded-HBM serving (ISSUE 18 acceptance run: ``--residency``):
    a doc population >= 10x the device byte budget served through the
    residency tier. Two legs, same seeded stream — interleaved per-doc
    touches with occasional one-seq-early arrivals (premature parks
    exercise the admission-aware prefetch) and ~10% dup redeliveries:

    1. a REFERENCE mesh with no residency manager (everything stays
       device-resident) establishes the expected captures/texts and the
       measured per-doc footprint the budget derives from;
    2. a budgeted mesh with a disk spill dir serves the identical
       stream; after EVERY round the doc-kind peak footprint gauge must
       be <= the budget (the reservation discipline's absolute bar).

    Convergence is compared doc-at-a-time — the reads themselves demand
    page under the same budget — and the final accounting must name
    every doc in exactly one tier with nothing lost."""
    import tempfile

    from automerge_tpu.obs import device_truth as dtruth
    from automerge_tpu.shard import ShardedDocSet

    rng = np.random.default_rng(seed * 6133 + 11)
    docs = [f"rdoc-{seed}-{i}" for i in range(n_docs)]
    streams = {}
    for di, doc in enumerate(docs):
        actor, run_len = f"r{di}", 3
        chs = []
        for s in range(1, n_seqs + 1):
            base = (s - 1) * run_len + 1
            key = "_head" if s == 1 else f"{actor}:{base - 1}"
            ops = []
            for k in range(run_len):
                ctr = base + k
                ops.append({"action": "ins", "obj": doc, "key": key,
                            "elem": ctr})
                ops.append({"action": "set", "obj": doc,
                            "key": f"{actor}:{ctr}",
                            "value": chr(97 + (ctr + di) % 26)})
                key = f"{actor}:{ctr}"
            chs.append({"actor": actor, "seq": s, "deps": {}, "ops": ops})
        streams[doc] = chs
    # the round schedule: two docs per round (the budget must hold one
    # round's working set — that is the invariant's own precondition),
    # each touch advancing its doc one seq; ~20% of touches send the
    # NEXT seq one touch early (premature -> router park -> prefetch
    # hint), the held-back seq follows on the doc's next touch
    pos = {d: 0 for d in docs}
    skipped: dict = {}
    rounds = []
    while True:
        pool = [d for d in docs if pos[d] < n_seqs or d in skipped]
        if not pool:
            break
        chunk = {}
        for i in rng.choice(len(pool), size=min(2, len(pool)),
                            replace=False):
            d = pool[int(i)]
            if d in skipped:
                out = [streams[d][skipped.pop(d)]]
            elif pos[d] + 1 < n_seqs and rng.random() < 0.2:
                skipped[d] = pos[d]
                out = [streams[d][pos[d] + 1]]
                pos[d] += 2
            else:
                out = [streams[d][pos[d]]]
                pos[d] += 1
            if rng.random() < 0.1:
                out = out + [out[0]]            # dup redelivery
            chunk[d] = out
        rounds.append(chunk)

    # leg 1: the unbounded reference (no residency manager attached)
    ref = ShardedDocSet(n_shards=2, capacity=64)
    for chunk in rounds:
        ref.deliver_round(chunk)
    ref_caps = {d: ref.capture(d) for d in docs}
    ref_texts = ref.texts()
    per_doc = max(doc.device_footprint()["device_bytes"]
                  for lane in ref.lanes for doc in lane.docs.values())
    budget = budget_docs * per_doc
    assert n_docs * per_doc >= 10 * budget, \
        f"residency seed {seed}: population only " \
        f"{n_docs * per_doc / budget:.1f}x the budget"

    # leg 2: the budgeted mesh — fresh gauge session, disk spill tier
    dtruth.REGISTRY.clear_session()
    with tempfile.TemporaryDirectory() as spill:
        mesh = ShardedDocSet(n_shards=2, capacity=64)
        res = mesh.attach_residency(budget_bytes=budget, spill_dir=spill,
                                    cold_after=4)
        for n, chunk in enumerate(rounds):
            mesh.deliver_round(chunk)
            peak = dtruth.REGISTRY.footprint()["peak_device_bytes"]
            assert peak <= budget, \
                f"residency seed {seed}: round {n} peak {peak} > " \
                f"budget {budget}"
        for d in docs:
            assert mesh.quarantined(d) == 0, \
                f"residency seed {seed}: quarantine not drained for {d}"
        acct = res.accounting()
        population = sorted(acct["hot"] + acct["warm"] + acct["cold"])
        assert population == sorted(docs), \
            f"residency seed {seed}: tier accounting lost docs"
        m = res.metrics()
        assert m["budget_overruns"] == 0, \
            f"residency seed {seed}: {m['budget_overruns']} budget " \
            f"overruns (working set exceeded the budget)"
        assert m["page_outs"] > 0 and m["page_ins"] > 0
        assert m["prefetches"] > 0, \
            f"residency seed {seed}: premature arrivals never " \
            f"prefetched a demoted doc ({m})"
        assert m["cold_ages"] > 0, \
            f"residency seed {seed}: the disk tier never engaged ({m})"
        # doc-at-a-time convergence: the reads page under the budget
        texts = {}
        for d in docs:
            assert mesh.capture(d) == ref_caps[d], \
                f"residency seed {seed}: capture of {d} diverged " \
                f"after paging churn"
            res.ensure_resident(d)
            lane = mesh.lane_of(d)
            with lane.device_ctx():
                texts[d] = lane.docs[d].text()
        assert texts == ref_texts, \
            f"residency seed {seed}: texts diverged after paging churn"
        peak = dtruth.REGISTRY.footprint()["peak_device_bytes"]
        assert peak <= budget, \
            f"residency seed {seed}: paged reads breached the budget " \
            f"({peak} > {budget})"
        final = res.metrics()
    PROFILE_METRICS["residency"].clear()
    PROFILE_METRICS["residency"].update(
        n_docs=n_docs, budget_bytes=budget, per_doc_bytes=per_doc,
        population_over_budget=round(n_docs * per_doc / budget, 1),
        peak_resident_bytes=final["peak_resident_bytes"],
        gauge_peak_bytes=peak, hit_rate=final["hit_rate"],
        page_in_p99_ms=final["page_in_p99_ms"],
        page_ins=final["page_ins"], page_outs=final["page_outs"],
        prefetches=final["prefetches"], cold_ages=final["cold_ages"],
        cold_loads=final["cold_loads"],
        budget_overruns=final["budget_overruns"])


def session_federation(seed: int, n_rooms: int = 6,
                       n_sessions: int = 1000, n_ticks: int = 80,
                       quiesce_rounds: int = 6000) -> None:
    """Three federated regions over WAN chaos (ISSUE 16 acceptance run:
    ``--federation``): `n_sessions` write sessions land across the
    fabric while region pairs partition and heal and one whole region
    is KILLED mid-run and REJOINS empty (snapshot-bootstrapped by the
    survivors through the probe/hello reconnect handshake).

    Asserted at the end:
      1. every room converges byte-identically on all three regions —
         canonical saves (history replayed in deterministic order under
         one probe actor) AND sorted change histories;
      2. zero residual cross-region lag (pending group-token envelopes
         + partition-buffered payloads) on every link, every link back
         on the ``ok`` rung;
      3. full reclamation: no parked quarantine changes, no partition
         buffers, no channel reorder state anywhere in the fabric.

    Any failure writes a federation postmortem (every region's
    ``describe()``, federation block included) before re-raising."""
    am = _am()
    import json as _json

    from automerge_tpu import Text
    from automerge_tpu.federation import (
        FederatedRegion, RegionPlacement, connect_regions,
    )
    from automerge_tpu.service import ServiceConfig, SyncService

    rng = np.random.default_rng(seed)
    names = ["us", "eu", "ap"]
    placement = RegionPlacement(names)

    def mk_region(name):
        return FederatedRegion(
            SyncService(ServiceConfig(region=name)), name,
            placement=placement, probe_every=2, max_buffer=256,
            max_retries=4)

    regions = {n: mk_region(n) for n in names}
    chaos = {}
    s = seed * 7919 + 1
    for i in range(len(names)):
        for j in range(i + 1, len(names)):
            a, b = names[i], names[j]
            _, _, fwd, rev = connect_regions(
                regions[a], regions[b], profile="cross_region", seed=s)
            chaos[(a, b)] = (fwd, rev)      # fwd: a -> b, rev: b -> a
            s += 10

    room_ids = [f"room-{g}" for g in range(n_rooms)]
    for room_id in room_ids:
        doc0 = am.change(am.init(f"{room_id}-origin"), lambda d: (
            d.__setitem__("t", Text("start")), d.__setitem__("m", {})))
        base = am.get_all_changes(doc0)
        for r in regions.values():
            r.svc.seed_doc(room_id, am.apply_changes(
                am.init(f"srv-{r.name}-{room_id}"), base))
            # short histories at soak scale: a lowered threshold keeps
            # the rejoined region exercising the snapshot bootstrap
            r.svc.room(room_id).hub.snapshot_min_changes = 8

    def pump_all(rounds=1):
        for _ in range(rounds):
            for r in regions.values():
                r.pump()
                r.svc.tick()

    def edit(region_name, room_id):
        ds = regions[region_name].svc.room(room_id).doc_set
        doc = ds.get_doc(room_id)
        if doc is None:
            return False     # a rejoined region still bootstrapping
        if int(rng.integers(0, 3)) == 0:
            doc = _text_edit(am, doc, rng)
        else:
            k = KEYS[int(rng.integers(0, len(KEYS)))]
            doc = am.change(doc, lambda d, k=k,
                            v=int(rng.integers(0, 999)):
                            d["m"].__setitem__(k, v))
        ds.set_doc(room_id, doc)
        return True

    # fault schedule: two pair-partition windows + one region kill
    cut_a = ("us", "eu")
    cut_a_at, cut_a_len = n_ticks // 5, max(4, n_ticks // 6)
    cut_b = ("eu", "ap")
    cut_b_at, cut_b_len = (2 * n_ticks) // 3, max(4, n_ticks // 8)
    kill_name = "ap"
    kill_at = n_ticks // 2
    rejoin_at = kill_at + max(4, n_ticks // 8)
    killed = False
    n_writes = 0
    n_skipped = 0
    per_tick = max(1, n_sessions // n_ticks)

    def kill_edges(name):
        """A vanished region: its WAN edges go dark in BOTH directions
        (frames die in flight; survivors' channels hit the retransmit
        cap and walk the ladder to `partitioned`)."""
        for (a, b), (f, r) in chaos.items():
            if name in (a, b):
                f.partition()
                r.partition()

    def rejoin_region(name):
        """A fresh, EMPTY region under the old name: new service, new
        links, the same chaos edges rewired and healed — the survivors'
        probe loop finds it and the hello handshake bootstraps it."""
        fresh = mk_region(name)
        ls = seed * 104729 + 17
        for (a, b), (f, r) in chaos.items():
            if b == name:     # fwd a->b delivers to name's link
                ln = fresh.link_to(a, seed=ls)
                f._deliver = ln.on_raw
                ln.attach_transport(r)
            elif a == name:   # rev b->a delivers to name's link
                ln = fresh.link_to(b, seed=ls)
                r._deliver = ln.on_raw
                ln.attach_transport(f)
            else:
                continue
            ls += 3
            f.heal()
            r.heal()
        regions[name] = fresh

    try:
        for t in range(n_ticks):
            if t == cut_a_at:
                f, r = chaos[cut_a]
                f.partition()
                r.partition()
            if t == cut_a_at + cut_a_len and not killed:
                f, r = chaos[cut_a]
                f.heal()
                r.heal()
            if t == cut_b_at:
                f, r = chaos[cut_b]
                f.partition()
                r.partition()
            if t == cut_b_at + cut_b_len:
                f, r = chaos[cut_b]
                f.heal()
                r.heal()
            if t == kill_at:
                killed = True
                regions.pop(kill_name)
                kill_edges(kill_name)
            if t == rejoin_at:
                killed = False
                rejoin_region(kill_name)
            for _ in range(per_tick):
                room_id = room_ids[int(rng.integers(0, n_rooms))]
                # placement decides the normal write home; any region
                # accepts writes (rung one: local-writes-always-accepted)
                if int(rng.integers(0, 5)) == 0:
                    target = list(regions)[int(rng.integers(0,
                                                            len(regions)))]
                else:
                    target = placement.home(room_id)
                    if target not in regions:   # its home is the corpse
                        target = next(iter(regions))
                if edit(target, room_id):
                    n_writes += 1
                else:
                    n_skipped += 1
            pump_all()

        # ---- heal everything, then drain until the fabric is idle ----
        if killed:
            rejoin_region(kill_name)
        for f, r in chaos.values():
            f.heal()
            r.heal()
        for q in range(quiesce_rounds):
            pump_all()
            if q > 5 and all(r.idle() for r in regions.values()):
                break
        else:
            raise AssertionError(
                f"federation seed {seed}: never quiesced: "
                f"{ {n: r.lag_table() for n, r in regions.items()} }")

        # 1. byte-identical convergence: canonical saves AND histories
        for room_id in room_ids:
            docs = {n: r.svc.room(room_id).doc_set.get_doc(room_id)
                    for n, r in regions.items()}
            assert all(d is not None for d in docs.values()), \
                f"federation seed {seed} {room_id}: missing replica in " \
                f"{ {n: d is None for n, d in docs.items()} }"
            saves = {}
            hists = {}
            for n, d in docs.items():
                chs = sorted(am.get_all_changes(d),
                             key=lambda c: (c["actor"], c["seq"]))
                saves[n] = am.save(am.apply_changes(
                    am.init("canon-probe"), chs))
                hists[n] = sorted(_json.dumps(c, sort_keys=True)
                                  for c in chs)
            assert len(set(saves.values())) == 1, \
                f"federation seed {seed} {room_id}: saves diverged " \
                f"{ {n: len(sv) for n, sv in saves.items()} }"
            ref = next(iter(hists.values()))
            assert all(h == ref for h in hists.values()), \
                f"federation seed {seed} {room_id}: histories diverged"
        # 2. zero residual cross-region lag, every link healthy
        residual = {(n, peer): entry
                    for n, r in regions.items()
                    for peer, entry in r.lag_table().items()
                    if entry["lag_tokens"] or entry["state"] != "ok"}
        assert not residual, \
            f"federation seed {seed}: residual lag at quiescence: " \
            f"{residual}"
        # 3. full reclamation: no parked changes, no partition buffers,
        #    no channel reorder state anywhere
        for n, r in regions.items():
            for room_id in room_ids:
                gate = r.svc.room(room_id).gate
                assert gate._n_parked == 0, \
                    f"federation seed {seed}: {n}/{room_id} quarantine " \
                    f"not drained"
            for peer, link in r.links.items():
                assert not link._buf_adverts and not link._buf_data, \
                    f"federation seed {seed}: {n}->{peer} partition " \
                    f"buffer not drained"
                assert not link.chan._recv_buf, \
                    f"federation seed {seed}: {n}->{peer} reorder " \
                    f"buffer not drained"
    except Exception:
        path = os.environ.get("AMTPU_POSTMORTEM_OUT",
                              "federation_postmortem.json")
        try:
            with open(path, "w") as fh:
                _json.dump({n: r.svc.describe()
                            for n, r in regions.items()}, fh, indent=1)
            print(f"soak: federation postmortem written to {path}",
                  file=sys.stderr, flush=True)
        except Exception as dump_exc:   # noqa: BLE001 — never mask
            print(f"soak: postmortem dump failed: {dump_exc!r}",
                  file=sys.stderr, flush=True)
        raise

    links = [(n, peer, link) for n, r in regions.items()
             for peer, link in r.links.items()]
    PROFILE_METRICS["federation"].clear()
    PROFILE_METRICS["federation"].update(
        regions=len(regions), rooms=n_rooms, writes=n_writes,
        writes_skipped_bootstrapping=n_skipped,
        region_kills=1, residual_lag_tokens=0,
        reconnects=sum(ln.stats["reconnects"] for _, _, ln in links),
        channel_revives=sum(ln.chan.stats["revives"]
                            for _, _, ln in links),
        buffer_dropped=sum(ln.stats["buffer_dropped"]
                           for _, _, ln in links),
        shipped=sum(ln.stats["shipped"] for _, _, ln in links),
        delivered=sum(ln.stats["delivered"] for _, _, ln in links),
        group_tokens_minted=sum(r.clock.stats["minted"]
                                for r in regions.values()),
        group_tokens_observed=sum(r.clock.stats["observed"]
                                  for r in regions.values()),
        ladder_transitions={
            k: sum(ln.transitions.get(k, 0) for _, _, ln in links)
            for k in sorted({t for _, _, ln in links
                             for t in ln.transitions})})


PROFILES = {"general": session_general, "conflict": session_conflict,
            "lossy": session_lossy, "table": session_table,
            "chaos": session_chaos, "checkpoint": session_checkpoint,
            "service": session_service, "sharded": session_sharded,
            "residency": session_residency,
            "federation": session_federation}


def run(profile: str, sessions: int, seed_base: int,
        trace: bool = False, clients: int = None,
        scrape: bool = False, quick: bool = False) -> int:
    import json

    from automerge_tpu import obs

    global SCRAPE
    SCRAPE = scrape
    failures = []
    t0 = time.perf_counter()
    names = list(PROFILES) if profile == "all" else [profile]
    profiles = dict(PROFILES)
    if clients is not None:
        # the service profile at an explicit scale (--service --clients N):
        # tick count grows mildly with scale so churn/partition windows
        # stay proportionate
        profiles["service"] = lambda seed: session_service(
            seed, n_clients=clients, n_ticks=40 if clients >= 500 else 30)
    if quick:
        # the CI smoke scale: same scenario shape (partitions + region
        # kill/rejoin), an order of magnitude fewer write sessions
        profiles["federation"] = lambda seed: session_federation(
            seed, n_rooms=3, n_sessions=150, n_ticks=40)
        # same tier ladder + 10x-over-budget ratio, half the population
        profiles["residency"] = lambda seed: session_residency(
            seed, n_docs=20, n_seqs=3, budget_docs=2)
    # the soak ALWAYS records (counters are exact across ring
    # wraparound, so the summary is right even for long campaigns); the
    # --trace flag only controls whether the ring is also exported
    with obs.tracing():
        # the summary reports THIS campaign's event delta: the recorder
        # may outlive run() (a second campaign in-process, earlier traced
        # tests), and counters are lifetime totals by design
        ev0 = obs.metrics_snapshot()["counters"]
        n0 = obs.metrics_snapshot()["emitted"]
        for name in names:
            fn = profiles[name]
            for s in range(sessions):
                seed = seed_base + s
                try:
                    fn(seed)
                except Exception as exc:  # noqa: BLE001 — record + continue
                    failures.append((name, seed, repr(exc)))
                    print(f"FAIL {name} seed {seed}: {exc!r}", flush=True)
        dt = time.perf_counter() - t0
        total = len(names) * sessions
        print(f"soak: {total - len(failures)}/{total} sessions converged "
              f"({dt:.1f}s)", flush=True)
        for name, seed, exc in failures:
            print(f"  reproduce: python scripts/soak.py --profile {name} "
                  f"--sessions 1 --seed-base {seed}")
        snap = obs.metrics_snapshot()
        events = {k: v - ev0.get(k, 0) for k, v in snap["counters"].items()
                  if v - ev0.get(k, 0) > 0}
        if trace:
            path = os.environ.get("AMTPU_TRACE_OUT", "soak_trace.json")
            obs.write_trace(path)
            print(f"soak: trace written to {path} "
                  "(load at https://ui.perfetto.dev)", file=sys.stderr)
    emit_summary(
        names, sessions, seed_base, total, failures, dt, events,
        obs_records={"emitted": snap["emitted"] - n0,
                     "retained": snap["retained"]},
        trace_path=path if trace else None)
    return 1 if failures else 0


def emit_summary(names, sessions, seed_base, total, failures, dt,
                 events, obs_records, trace_path=None):
    """THE one summary emitter: every campaign — whatever mix of
    profiles ran — ends with exactly ONE machine-readable JSON line
    (profile + SEEDS + event mix: the diagnosable-soak contract, ISSUE
    6; last line of stdout, pinned by tests/test_soak_smoke.py).
    Profiles contribute numbers by updating their PROFILE_METRICS entry
    in place — never by printing JSON themselves, so a new profile
    cannot regress the one-line artifact by copy-pasting emission
    logic."""
    import json

    summary = {
        "soak_profiles": names,
        "sessions_per_profile": sessions,
        "seed_base": seed_base,
        "converged": total - len(failures),
        "total": total,
        "elapsed_s": round(dt, 1),
        "failures": [{"profile": n, "seed": sd, "error": e}
                     for n, sd, e in failures],
        "events": events,
        "obs_records": obs_records,
        **{f"{name}_metrics": dict(PROFILE_METRICS[name])
           for name in names
           if PROFILE_METRICS.get(name)},
        **({"trace_path": trace_path} if trace_path else {}),
    }
    print(json.dumps(summary, sort_keys=True), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="all",
                    choices=["all"] + list(PROFILES))
    ap.add_argument("--chaos", action="store_true",
                    help="shorthand for --profile chaos")
    ap.add_argument("--checkpoint", action="store_true",
                    help="shorthand for --profile checkpoint (snapshot "
                         "mid-chaos + restart one peer from its bundle)")
    ap.add_argument("--service", action="store_true",
                    help="shorthand for --profile service at scale "
                         "(--clients concurrent sessions, default 1000; "
                         "--sessions defaults to 1 seed)")
    ap.add_argument("--federation", action="store_true",
                    help="shorthand for --profile federation (3 regions "
                         "over WAN chaos with pair partitions and a "
                         "killed-and-rejoined region; byte-identical "
                         "survivor convergence + zero residual "
                         "cross-region lag; --sessions defaults to 1 "
                         "seed, --quick runs the CI smoke scale)")
    ap.add_argument("--sharded", action="store_true",
                    help="shorthand for --profile sharded (shard-count "
                         "invariance: the same seeded chaotic stream on "
                         "1 vs 8 shards must converge byte-identically, "
                         "with a telemetry-triggered hot-doc migration "
                         "mid-stream on the mesh; --sessions defaults "
                         "to 8 seeds)")
    ap.add_argument("--residency", action="store_true",
                    help="shorthand for --profile residency (bounded-HBM "
                         "serving: a doc population >= 10x the device "
                         "budget pages through the residency tier; the "
                         "peak footprint gauge must never exceed the "
                         "budget and every doc must converge "
                         "byte-identically with a no-residency "
                         "reference mesh; --sessions defaults to 4 "
                         "seeds, --quick halves the population)")
    ap.add_argument("--clients", type=int, default=None,
                    help="service profile: concurrent client sessions "
                         "(default 1000 with --service)")
    ap.add_argument("--quick", action="store_true",
                    help="service/federation profiles: the CI smoke "
                         "scale (100 clients / 150 write sessions)")
    ap.add_argument("--sessions", type=int, default=None)
    ap.add_argument("--seed-base", type=int, default=0)
    ap.add_argument("--trace", action="store_true",
                    help="dump the obs flight recorder as Chrome trace "
                         "JSON (Perfetto-loadable) after the campaign")
    ap.add_argument("--scrape", action="store_true",
                    help="service profile: serve the live Prometheus "
                         "scrape endpoint during the soak and validate "
                         "the exposition + /describe over real HTTP")
    args = ap.parse_args()
    profile = ("chaos" if args.chaos
               else "checkpoint" if args.checkpoint
               else "service" if args.service
               else "federation" if args.federation
               else "sharded" if args.sharded
               else "residency" if args.residency else args.profile)
    clients = args.clients
    if args.service and clients is None:
        clients = 100 if args.quick else 1000
    sessions = args.sessions
    if sessions is None:
        # one seed at service scale (a 1000-session scenario IS the
        # campaign); 8 for the sharded profile (each seed runs the full
        # stream at EVERY shard count); 30 everywhere else
        sessions = (1 if profile in ("service", "federation")
                    else 8 if profile == "sharded"
                    else 4 if profile == "residency" else 30)
    return run(profile, sessions, args.seed_base, trace=args.trace,
               clients=clients, scrape=args.scrape, quick=args.quick)


if __name__ == "__main__":
    from automerge_tpu._env import setup_compile_cache

    setup_compile_cache()
    sys.exit(main())
