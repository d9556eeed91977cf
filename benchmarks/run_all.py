"""BASELINE.md benchmark configs 1-5 + conflict-heavy (6),
interactive-latency (7), and frontend-splice (8) configs.

Usage: python -m benchmarks.run_all [--quick] [--record ROUND]

One JSON line per config on stdout; `--record 3` additionally writes them
to BENCH_CONFIGS_r03.json (the per-round record). Config 5 (the
headline 1M-char / 10k-actor merge) is bench.py at the repo root — the
driver runs it separately; --record re-runs it here, in this process, so
the record file covers the whole surface. Every leg that needs the chip
runs in this process; only the cfg12/cfg20 dryruns fork, onto the virtual
CPU mesh. A sweep that finds no TPU fails unless JAX_PLATFORMS=cpu asks
for the CPU. --quick shrinks configs 3 and 4 for
fast iteration.

Each config asserts it exercised the path it claims (e.g. cfg4 asserts the
nested Trellis document stayed on the DEVICE tier with zero graduations;
cfg6 asserts the residual/slow register path actually ran).
"""

import json
import sys
import time

import numpy as np

from benchmarks.common import (RESULTS, TRACKING_ONLY, emit, timed,
                               write_record)


def _bench_row(measure, quick: bool, record_session: bool) -> dict:
    """Run one bench.py measurement in THIS process and return its row.
    One process holds the chip: a child started after this process
    touched JAX could not reach it, so the chip legs never fork. The row
    joins the session log when asked, and always from a chip run."""
    import bench as B
    rec = measure(quick=quick)
    if record_session or rec["platform"] == "tpu":
        B.append_session_log(rec)
    return rec


def config1_text_two_actor(n_chars: int = 1000):
    """Single Text doc, 2 actors, concurrent 1k-char insert (facade path)."""
    import automerge_tpu as am

    def run():
        a = am.change(am.init("actor-a"),
                      lambda d: d.__setitem__("t", am.Text("x" * 10)))
        b = am.merge(am.init("actor-b"), a)
        half = n_chars // 2
        a2 = am.change(a, lambda d: d["t"].insert_at(5, *("a" * half)))
        b2 = am.change(b, lambda d: d["t"].insert_at(5, *("b" * half)))
        m1 = am.merge(a2, b2)
        m2 = am.merge(b2, a2)
        assert str(m1["t"]) == str(m2["t"])
        assert len(str(m1["t"])) == 10 + n_chars

    dt = timed(run, warmups=1, reps=2)
    emit("cfg1_text_2actor_concurrent_insert", n_chars / dt, "chars/s",
         threshold=TRACKING_ONLY)


def config2_map_counter(n_actors: int = 100, n_keys: int = 100):
    """Map doc: n_actors concurrent actors each setting n_keys keys plus a
    shared counter, merged through the device map engine."""
    from automerge_tpu.engine import DeviceMapDoc, MapChangeBatch

    base = {"actor": "base", "seq": 1, "deps": {}, "ops":
            [{"action": "set", "obj": "m", "key": "count", "value": 0,
              "datatype": "counter"}]}
    changes = []
    for a in range(n_actors):
        ops = [{"action": "set", "obj": "m", "key": f"k{a}-{i}", "value": i}
               for i in range(n_keys)]
        ops.append({"action": "inc", "obj": "m", "key": "count", "value": 1})
        changes.append({"actor": f"actor-{a:04d}", "seq": 1,
                        "deps": {"base": 1}, "ops": ops})
    batch = MapChangeBatch.from_changes(changes, "m")
    n_ops = batch.n_ops

    def run():
        doc = DeviceMapDoc("m")
        doc.apply_changes([base])
        doc.apply_batch(batch)
        assert doc.get("count") == n_actors
        assert len(doc) == n_actors * n_keys + 1

    dt = timed(run, warmups=1, reps=2)
    emit("cfg2_map_counter_100x100", n_ops / dt, "ops/s",
         threshold=TRACKING_ONLY)


def config3_docset(n_docs: int = 1000, n_actors: int = 10,
                   chars_per_actor: int = 50):
    """DocSet of n_docs text docs, n_actors concurrent writers per doc,
    merged in ONE vmapped device program over the doc axis (the reference
    loops one doc at a time, src/doc_set.js:29-37)."""
    from automerge_tpu.engine import DeviceTextDocSet, TextChangeBatch
    from automerge_tpu.engine.columnar import HEAD_PARENT, KIND_INS, KIND_SET

    def doc_batch(obj_id: str, seed: int) -> TextChangeBatch:
        """n_actors concurrent typing runs from the head of an empty doc."""
        run = chars_per_actor
        n_ops = n_actors * run * 2
        actors = [f"actor-{i:03d}" for i in range(n_actors)]
        op_change = np.repeat(np.arange(n_actors, dtype=np.int32), run * 2)
        kind = np.tile(np.array([KIND_INS, KIND_SET], np.int8),
                       n_actors * run)
        ta = np.repeat(np.arange(n_actors, dtype=np.int32), run * 2)
        tc = np.zeros(n_ops, np.int32)
        pa = np.zeros(n_ops, np.int32)
        pc = np.zeros(n_ops, np.int32)
        val = np.zeros(n_ops, np.int64)
        ctrs = np.arange(1, run + 1, dtype=np.int32)
        for a in range(n_actors):
            s = a * run * 2
            tc[s: s + 2 * run: 2] = ctrs
            tc[s + 1: s + 2 * run: 2] = ctrs
            pa[s] = HEAD_PARENT
            pa[s + 2: s + 2 * run: 2] = a
            pc[s + 2: s + 2 * run: 2] = ctrs[:-1]
            val[s + 1: s + 2 * run: 2] = 97 + ((a + seed) % 26)
        return TextChangeBatch(
            obj_id=obj_id, actors=actors,
            seqs=np.ones(n_actors, np.int32), deps=[{}] * n_actors,
            messages=[None] * n_actors, op_change=op_change, op_kind=kind,
            op_target_actor=ta, op_target_ctr=tc, op_parent_actor=pa,
            op_parent_ctr=pc, op_value=val, actor_table=actors,
            value_pool=[])

    batches = [doc_batch(f"d{d}", d) for d in range(n_docs)]
    n_ops = sum(b.n_ops for b in batches)

    def run():
        ds = DeviceTextDocSet([f"d{d}" for d in range(n_docs)],
                              capacity=n_actors * chars_per_actor + 64)
        ds.apply_batches({f"d{d}": b for d, b in enumerate(batches)})
        total = sum(len(t) for t in ds.texts().values())
        assert total == n_docs * n_actors * chars_per_actor

    dt = timed(run, warmups=1, reps=1)
    emit("cfg3_docset_1k_docs", n_ops / dt, "ops/s",
         threshold=TRACKING_ONLY)
    emit("cfg3_docset_docs_per_sec", n_docs / dt, "docs/s",
         threshold=TRACKING_ONLY)


def trellis_changes(n_actors: int, n_cards: int = 10):
    """The cfg4 workload: a shared nested board + n_actors concurrent
    mixed edits (task appends, title retitles, task deletes), minted on
    the oracle tier (the emitted change JSON is backend-independent, and
    building n_actors peers on the device tier would pay thousands of
    device round trips in untimed setup). Returns (base doc, flattened
    changes, n_ops). Shared with benchmarks/cfg4_smoke.py so the CI
    smoke and the recorded config can never measure different shapes."""
    import automerge_tpu as am
    from automerge_tpu.backend import facade as oracle_backend

    base = am.change(am.init("base"), lambda d: d.update(
        {"cards": [{"title": f"card{i}", "tasks": [f"t{j}" for j in range(3)]}
                   for i in range(n_cards)]}))
    base_changes = am.get_all_changes(base)
    all_changes = []
    for a in range(n_actors):
        peer = am.apply_changes(
            am.init({"actorId": f"actor-{a:05d}",
                     "backend": oracle_backend.Backend}), base_changes)
        k = a % n_cards
        if a % 3 == 0:
            peer2 = am.change(peer, lambda d, k=k: d["cards"][k]["tasks"]
                              .append(f"new-{a}"))
        elif a % 3 == 1:
            peer2 = am.change(peer, lambda d, k=k: d["cards"][k]
                              .__setitem__("title", f"retitled-{a}"))
        else:
            peer2 = am.change(peer, lambda d, k=k: d["cards"][k]["tasks"]
                              .__delitem__(0))
        all_changes.extend(am.get_changes(base, peer2))
    n_ops = sum(len(c["ops"]) for c in all_changes)
    return base, all_changes, n_ops


def config4_trellis(n_actors: int = 1000, quick: bool = False):
    """Trellis-style nested cards[]/tasks[]: n_actors concurrent actors do
    mixed insert/update/delete on a shared board, merged on the DEVICE
    nested-document tier (asserted: no graduation). Since the stacked
    multi-object tier (engine/stacked.py, INTERNALS §12) the row also
    records the merge's device-dispatch terms — dispatch_per_op and the
    per-round stacked stats — so the old ~270-device_put per-object
    ceiling and its removal are both machine-visible, and the stacked
    path's object-count-independent budget is ASSERTED in the run."""
    import automerge_tpu as am
    from automerge_tpu import frontend as Frontend
    from automerge_tpu.backend import device as device_backend
    from automerge_tpu.engine import accounting, stacked

    if quick:
        n_actors = 100
    base, all_changes, n_ops = trellis_changes(n_actors)

    device_backend.GRADUATION_STATS.clear()
    acct: dict = {}

    def run():
        from automerge_tpu.engine.accounting import labeled_snapshot
        stacked.LAST_STATS.clear()
        before = labeled_snapshot()["dispatch"]
        with accounting.track() as tr:
            merged = am.apply_changes(base, all_changes)
        after = labeled_snapshot()["dispatch"]
        acct["merge_dispatches"] = tr.thread_stats["dispatches"]
        acct["merge_syncs"] = tr.thread_stats["syncs"]
        acct["labels"] = {
            lbl: agg["n"] - before.get(lbl, {}).get("n", 0)
            for lbl, agg in after.items()
            if agg["n"] - before.get(lbl, {}).get("n", 0) > 0}
        acct["stacked"] = dict(stacked.LAST_STATS)
        assert len(am.to_json(merged)["cards"]) == 10
        # path assertion: the nested board was served by the device tier
        assert isinstance(Frontend.get_backend_state(merged),
                          device_backend.DeviceBackendState)
        assert device_backend.GRADUATION_STATS == {}

    dt = timed(run, warmups=0, reps=1)
    st = acct["stacked"]
    extra = {}
    if st:
        # the tentpole's acceptance criterion, enforced in the recorded
        # run itself: dispatches <= 8 + 16/round, object-count-independent
        stacked.assert_round_budget(st)
        extra["stacked"] = st
        extra["dispatch_per_round"] = round(
            st["dispatches"] / max(1, st["rounds"]), 2)
        extra["dispatch_budget"] = (
            "asserted in code: stacked merge <= "
            f"{stacked.APPLY_DISPATCH_BASE} + "
            f"{stacked.PASS_DISPATCH_BUDGET} device programs per "
            "round-pass (>= 1 pass per causal round), independent of "
            "object count (engine/stacked.py)")
    else:
        extra["dispatch_budget"] = ("per-object comparator "
                                    "(AMTPU_STACKED_ROUNDS=0): unbudgeted")
    emit(f"cfg4_trellis_nested_{n_actors}_actors", n_ops / dt, "ops/s",
         tier="device",
         merge_dispatch_total=acct["merge_dispatches"],
         dispatch_per_op=round(acct["merge_dispatches"] / n_ops, 4),
         merge_sync_total=acct["merge_syncs"],
         dispatch_labels=acct["labels"],
         **extra,
         threshold=TRACKING_ONLY)


def config6_conflict_heavy(n_actors: int = 200, n_targets: int = 500):
    """Residual/slow-path config: n_actors concurrently overwrite the SAME
    n_targets elements (multi-writer registers -> conflicts), plus deletes
    and counter increments — everything the dense run path skips. Times
    apply_residual + the host slow register path (asserted: conflicts
    minted, i.e. the slow path actually ran)."""
    from automerge_tpu.engine import DeviceTextDoc, TextChangeBatch

    base_ops = []
    for i in range(1, n_targets + 1):
        key = "_head" if i == 1 else f"base:{i - 1}"
        base_ops.append({"action": "ins", "obj": "t", "key": key, "elem": i})
        base_ops.append({"action": "set", "obj": "t", "key": f"base:{i}",
                         "value": chr(97 + i % 26)})
    base = {"actor": "base", "seq": 1, "deps": {}, "ops": base_ops}

    changes = []
    for a in range(n_actors):
        ops = []
        for i in range(1, n_targets + 1):
            if (a + i) % 5 == 0:
                ops.append({"action": "del", "obj": "t",
                            "key": f"base:{i}"})
            else:
                ops.append({"action": "set", "obj": "t", "key": f"base:{i}",
                            "value": chr(65 + (a + i) % 26)})
        changes.append({"actor": f"actor-{a:04d}", "seq": 1,
                        "deps": {"base": 1}, "ops": ops})
    batch = TextChangeBatch.from_changes(changes, "t")
    n_ops = batch.n_ops
    state = {}

    def run():
        doc = DeviceTextDoc("t")
        doc.apply_changes([base])
        doc.apply_batch(batch)
        doc.text()
        state["doc"] = doc

    dt = timed(run, warmups=1, reps=2)
    doc = state["doc"]
    # path assertions: genuine multi-writer registers resolved on the host
    # slow path and survive as conflicts
    assert doc.conflicts, "conflict-heavy config minted no conflicts"
    emit(f"cfg6_conflict_heavy_{n_actors}x{n_targets}", n_ops / dt, "ops/s",
         n_conflicts=len(doc.conflicts), threshold=TRACKING_ONLY)


def config11_service(n_sessions: int = 200, room_size: int = 5,
                     n_rounds: int = 10, quick: bool = False,
                     record_session: bool = False):
    """Multi-tenant sync service throughput (automerge_tpu/service,
    INTERNALS §13) — the ISSUE 8 service bench row (specified there as
    "cfg6"; cfg6 was already the conflict-heavy config, so the service
    row is cfg11). N tenant sessions over lossless queue transports into
    one tick-scheduled SyncService, every client editing each round;
    measured from first edit to full quiescence (admission + grouped
    gate deliveries + hub fan-out + client applies all inside dt).
    Records the acceptance terms: sessions, aggregate_ops_per_sec,
    shed_total, evictions, p99_tick_ms (+ deferrals and the bound
    peaks). Chaos/churn live in scripts/soak.py --service; this row is
    the clean-path capacity number."""
    import time as _time
    from collections import deque

    import automerge_tpu as am
    from automerge_tpu import Connection, DocSet, Text
    from automerge_tpu.resilience import ResilientChannel
    from automerge_tpu.service import ServiceConfig, SyncService, \
        TenantBudget

    if quick:
        n_sessions, n_rounds = 50, 6

    class Client:
        def __init__(self, svc, tid, room_id, base):
            self.svc, self.tid, self.room_id = svc, tid, room_id
            self.to_server, self.to_client = deque(), deque()
            self.ds = DocSet()
            self.ds.set_doc(room_id,
                            am.apply_changes(am.init(f"c-{tid}"), base))
            svc.connect(tid, room_id, self.to_client.append)
            self.chan = ResilientChannel(self.to_server.append, None)
            self.conn = Connection(self.ds, self.chan.send)
            self.chan._deliver = self.conn.receive_msg
            self.conn.open()

        def pump(self):
            while self.to_server:
                env = self.to_server.popleft()
                sess = self.svc.session(self.tid)
                if sess is not None:
                    sess.on_wire(env)
            while self.to_client:
                self.chan.on_wire(self.to_client.popleft())
            self.chan.tick()

    svc = SyncService(ServiceConfig(
        default_budget=TenantBudget(ops_per_tick=256, inbox_cap=64)))
    n_rooms = max(1, n_sessions // room_size)
    bases = {}
    for g in range(n_rooms):
        rid = f"room-{g}"
        doc0 = am.change(am.init(f"{rid}-origin"), lambda d: (
            d.__setitem__("t", Text("svc")), d.__setitem__("m", {})))
        bases[rid] = am.get_all_changes(doc0)
        svc.seed_doc(rid, am.apply_changes(am.init(f"server-{g}"),
                                           bases[rid]))
    clients = [Client(svc, f"t{i}", f"room-{i % n_rooms}",
                      bases[f"room-{i % n_rooms}"])
               for i in range(n_sessions)]

    def settle(max_ticks=800):
        for _ in range(max_ticks):
            for c in clients:
                c.pump()
            svc.tick()
            if svc.idle() and all(c.chan.idle and not c.to_server
                                  and not c.to_client for c in clients):
                return
        raise AssertionError(f"service bench never quiesced: "
                             f"{svc.metrics()}")

    settle()                                 # join handshake off the clock
    ops_before = svc.stats["admitted_ops"]
    t0 = _time.perf_counter()
    for r in range(n_rounds):
        for i, c in enumerate(clients):
            c.ds.set_doc(c.room_id, am.change(
                c.ds.get_doc(c.room_id),
                lambda d, r=r, i=i: d["m"].__setitem__(f"k{i}", r)))
            c.pump()
        svc.tick()
    settle()
    dt = _time.perf_counter() - t0
    admitted = svc.stats["admitted_ops"] - ops_before
    assert admitted >= n_sessions * n_rounds, (admitted, svc.metrics())
    # convergence sanity: one spot-check room, server vs every member
    rid = "room-0"
    canon = lambda d: json.dumps(am.to_json(d), sort_keys=True)  # noqa: E731
    want = canon(svc.room(rid).doc_set.get_doc(rid))
    for c in clients:
        if c.room_id == rid:
            assert canon(c.ds.get_doc(rid)) == want, "room-0 diverged"
    svc.probe_lag()                  # fresh lag table for the record
    m = svc.metrics()
    emit(f"cfg11_service_{n_sessions}_sessions", admitted / dt, "ops/s",
         sessions=n_sessions, aggregate_ops_per_sec=round(admitted / dt, 1),
         shed_total=m["shed_total"], evictions=m["evictions"],
         p99_tick_ms=m["p99_tick_ms"], p50_tick_ms=m["p50_tick_ms"],
         deferrals=m["deferrals"], rooms=m["rooms"],
         peak_inbox=m["peak_inbox"], peak_parked=m["peak_parked"],
         admitted_ops=admitted,
         # telemetry-tier SLO terms (benchmarks/slo_gate.py checks
         # these against the committed rows): residual lag at
         # quiescence must be zero; peaks + shed rate are tracked
         max_lag_ops=m["max_lag_ops"], max_lag_ticks=m["max_lag_ticks"],
         peak_lag_ops=m["peak_lag_ops"],
         peak_lag_ticks=m["peak_lag_ticks"],
         shed_rate=round(m["shed_total"] / max(1, admitted), 6),
         tick_p99_ms_telemetry=svc.tick_p99_ms_telemetry(),
         threshold=TRACKING_ONLY)
    if record_session:
        import datetime

        import bench as B
        from benchmarks.common import RESULTS
        row = dict(RESULTS[-1])
        row["recorded_at_utc"] = datetime.datetime.now(
            datetime.timezone.utc).isoformat()
        row["git_sha"] = B._git_sha()
        try:
            import subprocess as _sp
            if _sp.run(["git", "status", "--porcelain"],
                       capture_output=True, text=True,
                       timeout=10).stdout.strip():
                row["git_dirty"] = True
        except Exception:
            pass
        row["timed_region"] = (
            f"{n_sessions} tenant sessions x {n_rounds} edit rounds "
            "through SyncService.tick (budgeted admission -> grouped "
            "per-doc gate delivery -> one hub flush per room -> client "
            "applies over lossless queue transports); dt = first edit "
            "-> full quiescence; value = admitted ops/s aggregate.")
        B.append_session_log(row)
        print(f"# appended to {B.SESSION_LOG_PATH}", file=sys.stderr)


def config12_sharded(quick: bool = False, record_session: bool = False):
    """Sharded serving tier (automerge_tpu/shard, INTERNALS §15): the
    ISSUE-10 cfg12 row — aggregate mesh ops/s across the full shard
    population vs the same workload on one shard. Runs in a SUBPROCESS
    on the 8-virtual-cpu-device platform (JAX_PLATFORMS=cpu: the child
    never reaches for the chip this process holds, and XLA_FLAGS must
    predate its jax init); `bench.py --sharded` asserts the
    budgets / zero-collective audit / >=4x bar inside the measurement
    and, with ``--session``, appends its own honest cpu row to
    BENCH_SESSIONS.jsonl. The emitted sweep row carries
    ``measured_platform`` so a chip sweep cannot launder the cpu dryrun
    as a chip measurement."""
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from automerge_tpu._env import virtual_cpu_env
    env = virtual_cpu_env(8)
    cmd = [sys.executable, os.path.join(root, "bench.py"), "--sharded"]
    if quick:
        cmd.append("--quick")
    if record_session:
        cmd.append("--session")
    out = subprocess.run(cmd, capture_output=True, text=True, cwd=root,
                         env=env, timeout=3000)
    if out.returncode != 0:
        raise RuntimeError(
            f"cfg12 sharded bench failed rc={out.returncode}: "
            f"{out.stderr[-800:]}")
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    emit("cfg12_sharded_aggregate_ops_per_sec", rec["value"], "ops/s",
         vs_baseline=rec["vs_baseline"],
         n_shards=rec["n_shards"], n_docs=rec["n_docs"],
         single_shard_ops_per_sec=rec["single_shard_ops_per_sec"],
         scaleup_vs_single_shard=rec["scaleup_vs_single_shard"],
         value_spread_pct=rec["value_spread_pct"],
         zero_collectives=rec["zero_collectives"],
         collective_audit=rec["collective_audit"],
         sharded_applies=rec["sharded_applies"],
         single_shard_applies=rec["single_shard_applies"],
         measured_platform=rec["platform"],
         threshold=rec["threshold"])
    if record_session:
        print(f"# cfg12 session row appended by bench.py --sharded "
              f"--session (platform {rec['platform']})", file=sys.stderr)


def config12t_text_prepare(quick: bool = False,
                           record_session: bool = False):
    """Cross-doc cold text planning (ISSUE 12, INTERNALS §16): the
    cfg12t microbench — span-derived detect_runs / index_merge /
    rank_resolve terms A/B'd against the per-doc planner + sorted-insert
    index, with the bulk-merge budget asserted inside the measurement.
    Runs in this process (`_bench_row`); ``--session`` appends the row
    to BENCH_SESSIONS.jsonl."""
    import bench as B
    rec = _bench_row(B.measure_text_prepare, quick, record_session)
    emit("cfg12t_text_cold_prepare_ops_per_sec", rec["value"], "ops/s",
         n_docs=rec["n_docs"],
         per_doc_ops_per_sec=rec["per_doc_ops_per_sec"],
         speedup_vs_per_doc=rec["speedup_vs_per_doc"],
         value_spread_pct=rec["value_spread_pct"],
         plan_terms_s=rec["plan_terms_s"],
         per_doc_plan_terms_s=rec["per_doc_plan_terms_s"],
         index_merges_per_doc_round=rec["index_merges_per_doc_round"],
         cross_doc=rec["cross_doc"],
         measured_platform=rec["platform"],
         threshold=rec["threshold"])


def config19_learned_index(quick: bool = False,
                           record_session: bool = False):
    """Learned-index host planning A/B (ISSUE 19, INTERNALS §23): the
    cfg19 row — the cfg12t population stream with the production
    planner config on BOTH legs, A/B'd across AMTPU_LEARNED_INDEX
    alone. Byte-identical final text, learned-site engagement, the
    rank_resolve bar (cfg12t-shape scaled <= 0.36 s, >= 2x under the
    same-run exact leg), zero model-wrong-answers on the untimed
    audit pass and zero demotions all asserted inside the measurement.
    Runs in this process (`_bench_row`); ``--session`` appends the row
    to BENCH_SESSIONS.jsonl."""
    import bench as B
    rec = _bench_row(B.measure_learned_index, quick, record_session)
    emit("cfg19_learned_index_ops_per_sec", rec["value"], "ops/s",
         n_docs=rec["n_docs"],
         exact_ops_per_sec=rec["exact_ops_per_sec"],
         speedup_vs_exact=rec["speedup_vs_exact"],
         value_spread_pct=rec["value_spread_pct"],
         rank_resolve_s=rec["rank_resolve_s"],
         exact_rank_resolve_s=rec["exact_rank_resolve_s"],
         rank_resolve_speedup=rec["rank_resolve_speedup"],
         model_wrong_answers=rec["model_wrong_answers"],
         model_misses=rec["model_misses"],
         model_refits=rec["model_refits"],
         demotions=rec["demotions"],
         audit_lookups_checked=rec["audit_lookups_checked"],
         site_stats=rec["site_stats"],
         measured_platform=rec["platform"],
         threshold=rec["threshold"])


def config20_parallel(quick: bool = False, record_session: bool = False):
    """Parallel mesh execution A/B (ISSUE 20, INTERNALS §24): the cfg20
    row — the SAME mesh size + map-population stream with the per-lane
    worker threads ON vs OFF (AMTPU_PARALLEL_LANES), byte-identical
    sample captures + per-lane counters asserted across the legs on
    every paired attempt, the overlap seam asserted engaged, the
    zero-collective audit and zero steady-state recompiles asserted
    in-run, and the 1.5x speedup bar asserted on >= 4-core hosts
    (n_cores is recorded; 1-core boxes record the honest ratio).
    Subprocess on the 8-virtual-cpu-device platform for the same reason
    as cfg12; ``--session`` appends the honest row to
    BENCH_SESSIONS.jsonl."""
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from automerge_tpu._env import virtual_cpu_env
    env = virtual_cpu_env(8)
    env.pop("AMTPU_PARALLEL_LANES", None)   # the bench drives the flag
    cmd = [sys.executable, os.path.join(root, "bench.py"), "--parallel"]
    if quick:
        cmd.append("--quick")
    if record_session:
        cmd.append("--session")
    out = subprocess.run(cmd, capture_output=True, text=True, cwd=root,
                         env=env, timeout=3000)
    if out.returncode != 0:
        raise RuntimeError(
            f"cfg20 parallel-mesh bench failed rc={out.returncode}: "
            f"{out.stderr[-800:]}")
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    emit("cfg20_parallel_mesh_aggregate_ops_per_sec", rec["value"],
         "ops/s",
         n_shards=rec["n_shards"], n_docs=rec["n_docs"],
         n_cores=rec["n_cores"],
         sequential_ops_per_sec=rec["sequential_ops_per_sec"],
         parallel_speedup_vs_sequential=rec[
             "parallel_speedup_vs_sequential"],
         speedup_bar_applicable=rec["speedup_bar_applicable"],
         value_spread_pct=rec["value_spread_pct"],
         executor=rec["executor"],
         zero_collectives=rec["zero_collectives"],
         recompiles=rec["recompiles"],
         measured_platform=rec["platform"],
         threshold=rec["threshold"])
    if record_session:
        print(f"# cfg20 session row appended by bench.py --parallel "
              f"--session (platform {rec['platform']})", file=sys.stderr)


def config13_wire(quick: bool = False, record_session: bool = False):
    """Binary columnar wire A/B at service scale (ISSUE 13, INTERNALS
    §17): the cfg13 row — dict vs AMTPUWIRE1 frames on the SAME seeded
    service session, byte-identical committed state asserted in-run,
    span-derived service-ingest decode term >= 5x smaller, binary
    decode under 5% of the tick budget, wire bytes/op recorded for both
    legs. Runs in this process (`_bench_row`); ``--session`` appends the
    row to BENCH_SESSIONS.jsonl."""
    import bench as B
    rec = _bench_row(B.measure_wire, quick, record_session)
    emit("cfg13_wire_service_ops_per_sec", rec["value"], "ops/s",
         sessions=rec["sessions"],
         dict_ops_per_sec=rec["dict_ops_per_sec"],
         decode_s=rec["decode_s"],
         dict_decode_s=rec["dict_decode_s"],
         decode_speedup_vs_dict=rec["decode_speedup_vs_dict"],
         decode_share_of_tick=rec["decode_share_of_tick"],
         wire_bytes_per_op=rec["wire_bytes_per_op"],
         dict_wire_bytes_per_op=rec["dict_wire_bytes_per_op"],
         measured_platform=rec["platform"],
         threshold=rec["threshold"])


def config14_lineage(quick: bool = False, record_session: bool = False):
    """Change-lineage overhead A/B at service scale (ISSUE 14,
    INTERNALS §18): the cfg14 row — the cfg11-shaped seeded service
    session with lineage off vs deterministic 1/64 sampling,
    byte-identical committed state and 100% clean-path chain
    completeness asserted in-run, sampled overhead <= 5%, visibility
    quantiles + per-stage dwell maxima recorded. Runs in this process
    (`_bench_row`); ``--session`` appends the row to
    BENCH_SESSIONS.jsonl."""
    import os
    os.environ.pop("AMTPU_LINEAGE_RATE", None)   # the bench drives the flag
    import bench as B
    rec = _bench_row(B.measure_lineage, quick, record_session)
    emit("cfg14_lineage_service_ops_per_sec", rec["value"], "ops/s",
         sessions=rec["sessions"],
         lineage_rate=rec["lineage_rate"],
         lineage_off_ops_per_sec=rec["lineage_off_ops_per_sec"],
         off_ratio_vs_baseline=rec["off_ratio_vs_baseline"],
         overhead_pct=rec["overhead_pct"],
         sampled_chains=rec["sampled_chains"],
         hops_per_sampled_change=rec["hops_per_sampled_change"],
         visibility_p50_ms=rec["visibility_p50_ms"],
         visibility_p99_ms=rec["visibility_p99_ms"],
         max_quarantine_dwell_ms=rec["max_quarantine_dwell_ms"],
         measured_platform=rec["platform"],
         threshold=rec["threshold"])


def config15_device_truth(quick: bool = False,
                          record_session: bool = False):
    """Device-truth observability row (ISSUE 15, INTERNALS §19): the
    cfg15 steady-state stream — zero compile events asserted inside the
    timed reps, exact h2d/d2h staged bytes per op, dtype x shape peak
    device footprint, cost-model flops/bytes per op, and the
    persistent-compile-cache state. Runs in this process (`_bench_row`);
    ``--session`` appends the row to BENCH_SESSIONS.jsonl."""
    import bench as B
    rec = _bench_row(B.measure_device_truth, quick, record_session)
    emit("cfg15_device_truth_ops_per_sec", rec["value"], "ops/s",
         compile_count=rec["compile_count"],
         recompiles_at_steady_state=rec["recompiles_at_steady_state"],
         bytes_staged_per_op=rec["bytes_staged_per_op"],
         d2h_bytes_per_op=rec["d2h_bytes_per_op"],
         peak_device_bytes=rec["peak_device_bytes"],
         cost_model_flops_per_op=rec["cost_model_flops_per_op"],
         cost_model_bytes_per_op=rec["cost_model_bytes_per_op"],
         compile_cache_entries=rec["compile_cache"]["entries"],
         measured_platform=rec["platform"],
         threshold=rec["threshold"])


def config16_federation(n_rounds: int = 12, n_rooms: int = 4,
                        quick: bool = False,
                        record_session: bool = False):
    """Geo-federation replication throughput (ISSUE 16, INTERNALS §20):
    the cfg16 row — three FederatedRegions full-meshed over the seeded
    ``cross_region`` WAN chaos profile, every region writing every room
    every round (concurrent cross-region merge), measured from first
    write to full fabric quiescence.  value = replica-commits/s: each
    write must become visible on ALL three regions, so the fabric does
    3x the write volume in committed replica state.  Lineage runs at
    rate=1 inside the timed region, so the row records the REAL
    cross-region visibility quantiles (origin -> remote commit across
    the WAN), plus the SLO terms the gate checks: residual lag tokens
    (absolute zero bar) and group-token economy.  Clean-path capacity:
    no partitions here — chaos partitions + region kill/rejoin live in
    scripts/soak.py --federation."""
    import time as _time

    import automerge_tpu as am
    from automerge_tpu.federation import FederatedRegion, connect_regions
    from automerge_tpu.obs import lineage
    from automerge_tpu.service import ServiceConfig, SyncService

    if quick:
        n_rounds, n_rooms = 6, 2

    was_enabled = lineage.ENABLED
    lineage.enable(rate=1)
    lineage.clear()
    try:
        names = ["us", "eu", "ap"]
        regions = {n: FederatedRegion(
            SyncService(ServiceConfig(region=n)), n) for n in names}
        s = 16
        for i in range(len(names)):
            for j in range(i + 1, len(names)):
                connect_regions(regions[names[i]], regions[names[j]],
                                profile="cross_region", seed=s)
                s += 10
        room_ids = [f"room-{g}" for g in range(n_rooms)]
        for rid in room_ids:
            doc0 = am.change(am.init(f"{rid}-origin"),
                             lambda d: d.__setitem__("m", {}))
            base = am.get_all_changes(doc0)
            for r in regions.values():
                r.svc.seed_doc(rid, am.apply_changes(
                    am.init(f"srv-{r.name}-{rid}"), base))

        def pump_all():
            for r in regions.values():
                r.pump()
                r.svc.tick()

        def settle(max_rounds=4000):
            for q in range(max_rounds):
                pump_all()
                if q > 5 and all(r.idle() for r in regions.values()):
                    return
            raise AssertionError(
                f"federation bench never quiesced: "
                f"{ {n: r.lag_table() for n, r in regions.items()} }")

        settle()                    # join adverts off the clock
        lineage.clear()             # visibility stats: timed region only
        n_writes = 0
        t0 = _time.perf_counter()
        for rnd in range(n_rounds):
            for name, r in regions.items():
                for rid in room_ids:
                    ds = r.svc.room(rid).doc_set
                    ds.set_doc(rid, am.change(
                        ds.get_doc(rid),
                        lambda d, n=name, rnd=rnd:
                        d["m"].__setitem__(f"k-{n}", rnd)))
                    n_writes += 1
            pump_all()
        settle()
        dt = _time.perf_counter() - t0

        # convergence: canonical saves byte-identical on all 3 regions
        for rid in room_ids:
            saves = set()
            for r in regions.values():
                doc = r.svc.room(rid).doc_set.get_doc(rid)
                chs = sorted(am.get_all_changes(doc),
                             key=lambda c: (c["actor"], c["seq"]))
                saves.add(am.save(am.apply_changes(
                    am.init("canon-probe"), chs)))
            assert len(saves) == 1, f"cfg16 {rid}: replicas diverged"
        residual = sum(e["lag_tokens"] for r in regions.values()
                       for e in r.lag_table().values())
        led = lineage.ledger()
        links = [ln for r in regions.values()
                 for ln in r.links.values()]
        replica_commits = n_writes * len(regions)
        emit("cfg16_federation", replica_commits / dt, "ops/s",
             regions=len(regions), rooms=n_rooms, writes=n_writes,
             replica_commits=replica_commits,
             aggregate_replica_commits_per_sec=round(
                 replica_commits / dt, 1),
             cross_region_visibility_p50_ms=led.visibility_ms(0.50),
             cross_region_visibility_p99_ms=led.visibility_ms(0.99),
             residual_lag_tokens=residual,
             group_tokens_minted=sum(r.clock.stats["minted"]
                                     for r in regions.values()),
             group_tokens_observed=sum(r.clock.stats["observed"]
                                       for r in regions.values()),
             envelopes_shipped=sum(ln.stats["shipped"] for ln in links),
             envelopes_delivered=sum(ln.stats["delivered"]
                                     for ln in links),
             wan_profile="cross_region",
             threshold=TRACKING_ONLY)
    finally:
        if not was_enabled:
            lineage.disable()
        lineage.clear()
    if record_session:
        import datetime

        import bench as B
        from benchmarks.common import RESULTS
        row = dict(RESULTS[-1])
        row["recorded_at_utc"] = datetime.datetime.now(
            datetime.timezone.utc).isoformat()
        row["git_sha"] = B._git_sha()
        try:
            import subprocess as _sp
            if _sp.run(["git", "status", "--porcelain"],
                       capture_output=True, text=True,
                       timeout=10).stdout.strip():
                row["git_dirty"] = True
        except Exception:
            pass
        row["timed_region"] = (
            f"3 federated regions x {n_rooms} rooms x {n_rounds} write "
            "rounds over the seeded cross_region WAN chaos profile "
            "(group-token manifests -> RegionLink channels -> remote "
            "gate commits); dt = first write -> full fabric quiescence; "
            "value = replica-commits/s (every write visible on all 3 "
            "regions); lineage rate=1 inside the timed region supplies "
            "the cross-region visibility quantiles.")
        B.append_session_log(row)
        print(f"# appended to {B.SESSION_LOG_PATH}", file=sys.stderr)


def config17_fused(quick: bool = False, record_session: bool = False):
    """Fused-round megakernel A/B row (ISSUE 17, INTERNALS §21): the
    cfg17 bench pairs every rewritten kernel (solo mixed round, the
    both-lanes stacked megakernel, the combined scatter) with its XLA
    comparator on the SAME pre-generated stream — fused vs XLA seconds
    by cost-model attribution, roofline ratio both legs, dispatch count
    per round — with identical committed state, byte-identical frontend
    saves across AMTPU_FUSED_ROUNDS, the tightened round budget, and
    zero steady-state recompiles all asserted in-run. Runs in this
    process (`_bench_row`); ``--session`` appends the row to
    BENCH_SESSIONS.jsonl."""
    import bench as B
    rec = _bench_row(B.measure_fused, quick, record_session)
    emit("cfg17_fused_rounds_ops_per_sec", rec["value"], "ops/s",
         xla_ops_per_sec=rec["xla_ops_per_sec"],
         speedup_vs_xla=rec["speedup_vs_xla"],
         dispatch_per_round=rec["dispatch_per_round"],
         xla_dispatch_per_round=rec["xla_dispatch_per_round"],
         dispatch_reduction=rec["dispatch_reduction"],
         recompiles_at_steady_state=rec["recompiles_at_steady_state"],
         roofline_ratio_fused=rec["roofline_ratio_fused"],
         roofline_ratio_xla=rec["roofline_ratio_xla"],
         roofline_ratio_vs_xla=rec["roofline_ratio_vs_xla"],
         kernel_ab=rec["kernel_ab"],
         saves_byte_identical=rec["saves_byte_identical"],
         measured_platform=rec["platform"],
         threshold=rec["threshold"])


def config18_residency(quick: bool = False, record_session: bool = False):
    """Bounded-HBM residency row (ISSUE 18, INTERNALS §22): a doc
    population 10x+ the device byte budget served through the paging
    mesh — demand page-ins through the disk tier every round, rotating
    hot set for the steady-state hit rate, peak footprint gauge <= the
    budget, zero overruns, and byte-identical captures vs an unbounded
    reference all asserted in-run before the record is emitted.
    Runs in this process (`_bench_row`); ``--session`` appends the row
    to BENCH_SESSIONS.jsonl."""
    import bench as B
    rec = _bench_row(B.measure_residency, quick, record_session)
    emit("cfg18_residency_ops_per_sec", rec["value"], "ops/s",
         budget_bytes=rec["budget_bytes"],
         peak_footprint_bytes=rec["peak_footprint_bytes"],
         population_over_budget=rec["population_over_budget"],
         touched_docs=rec["touched_docs"],
         hit_rate=rec["hit_rate"],
         page_in_p99_ms=rec["page_in_p99_ms"],
         page_ins=rec["page_ins"],
         page_outs=rec["page_outs"],
         cold_ages=rec["cold_ages"],
         cold_loads=rec["cold_loads"],
         budget_overruns=rec["budget_overruns"],
         restore_h2d_bytes=rec["restore_h2d_bytes"],
         tier_counts=rec["tier_counts"],
         captures_byte_identical=rec["captures_byte_identical"],
         measured_platform=rec["platform"],
         threshold=rec["threshold"])


def config5b_residual_heavy(n_actors: int = 10_000, quick: bool = False):
    """Adversarial headline shape: 20% of ops are RESIDUALS (bare deletes
    of distinct base elements + bare inserts without values) that cannot
    ride the dense run path — they go through apply_residual_packed. The
    clean headline (cfg5/bench.py) has ZERO residuals in the timed region;
    this row bounds the cost of realistic mixed loads. Regression
    threshold: >= 25% of the clean headline's ops/s on the same platform.
    Path under test: ops/ingest.py apply_residual_packed."""
    import bench as B
    from automerge_tpu.engine import DeviceTextDoc, TextChangeBatch
    from automerge_tpu.engine.columnar import KIND_DEL, KIND_INS, KIND_SET

    if quick:
        n_actors = 500
    base_n = 100 * n_actors          # every actor gets a distinct del range
    run_pairs, n_del, n_bare = 400, 100, 100   # 800+100+100 = 1000 ops
    n_per = 2 * run_pairs + n_del + n_bare
    n_ops = n_actors * n_per
    actors = [f"actor-{i:06d}" for i in range(n_actors)]
    op_change = np.repeat(np.arange(n_actors, dtype=np.int32), n_per)
    kind = np.empty(n_ops, np.int8)
    ta = np.zeros(n_ops, np.int32)
    tc = np.zeros(n_ops, np.int32)
    pa = np.zeros(n_ops, np.int32)
    pc = np.zeros(n_ops, np.int32)
    val = np.zeros(n_ops, np.int64)
    pair_kind = np.tile(np.array([KIND_INS, KIND_SET], np.int8), run_pairs)
    ctrs = np.arange(1, run_pairs + 1, dtype=np.int32) + base_n + 1
    for a in range(n_actors):
        s = a * n_per
        e_run = s + 2 * run_pairs
        kind[s:e_run] = pair_kind
        ta[s:e_run] = a
        tc[s: e_run: 2] = ctrs
        tc[s + 1: e_run: 2] = ctrs
        pa[s] = n_actors                      # 'base' rank
        pc[s] = a * 100 + 1
        pa[s + 2: e_run: 2] = a
        pc[s + 2: e_run: 2] = ctrs[:-1]
        val[s + 1: e_run: 2] = 97 + (a % 26)
        # 100 bare deletes of this actor's distinct base range
        d0 = e_run
        kind[d0: d0 + n_del] = KIND_DEL
        ta[d0: d0 + n_del] = n_actors
        tc[d0: d0 + n_del] = a * 100 + 1 + np.arange(n_del)
        # 100 bare inserts (no value: invisible elements)
        b0 = d0 + n_del
        kind[b0: b0 + n_bare] = KIND_INS
        ta[b0: b0 + n_bare] = a
        tc[b0: b0 + n_bare] = ctrs[-1] + 1 + np.arange(n_bare)
        pa[b0: b0 + n_bare] = n_actors
        pc[b0: b0 + n_bare] = a * 100 + 50
    batch = TextChangeBatch(
        obj_id="t", actors=actors, seqs=np.ones(n_actors, np.int32),
        deps=[{"base": 1}] * n_actors, messages=[None] * n_actors,
        op_change=op_change, op_kind=kind, op_target_actor=ta,
        op_target_ctr=tc, op_parent_actor=pa, op_parent_ctr=pc,
        op_value=val, actor_table=actors + ["base"], value_pool=[])

    def merge_once(merge_batch, expect_vis):
        """bench.py's exact timing discipline (bench.py run_once): base
        doc built untimed, prepare (host plan + h2d staging) untimed,
        timed region = commit_prepared + codes-only materialize + the one
        scalar-fetch sync. Returns best-of-2 commit seconds after a
        warm-up pays the jit compiles."""
        def once():
            doc = DeviceTextDoc("t")
            doc.eager_materialize = True
            doc.apply_batch(B.base_batch("t", base_n))
            doc.text()
            prepared = doc.prepare_batch(merge_batch)
            t0 = time.perf_counter()
            doc.commit_prepared(prepared)
            doc._materialize(with_pos=False)
            scal = doc._scalars()
            dt = time.perf_counter() - t0
            assert int(scal[0]) == expect_vis, (int(scal[0]), expect_vis)
            return dt
        once()                      # warm-up: compiles at these shapes
        return min(once() for _ in range(2))

    # the CLEAN same-scale merge, timed with the identical discipline in
    # the same process — the only way the 4x bound is actually comparable
    # (round 4's version timed base-doc rebuild + double materialize for
    # the residual row but commit-only for clean: unfalsifiable).
    # Same 3-attempt contention discipline as cfg7/cfg8: the residual
    # region is scatter-bound on XLA:CPU and a probe-loop burst inside
    # either side's ~0.1-3 s pass skews the RATIO, not just the rate.
    clean = B.merge_batch("t", n_actors, n_per, base_n)
    import time as _time
    for attempt in range(3):
        clean_dt = merge_once(clean, base_n + n_actors * (n_per // 2))
        resid_dt = merge_once(
            batch, base_n - n_actors * n_del + n_actors * run_pairs)
        clean_rate = clean.n_ops / clean_dt
        resid_rate = n_ops / resid_dt
        slowdown = clean_rate / resid_rate
        if slowdown < 4.0:
            break
        if attempt < 2:
            _time.sleep(4)
    # the stated bound, ASSERTED so the suite fails when the residual
    # path regresses instead of recording an unfalsifiable string. The
    # 4x bound is a claim about the RECORD scale (10k actors, where
    # per-round fixed costs — the S-sized planned-materialize stage, the
    # one packed d2h fetch, dispatch overhead — amortize over 10M ops);
    # --quick shrinks the shape 20x for iteration speed and sits at the
    # bound's edge by construction, so quick rows record tracking-only
    # with the measured ratio instead of gating on a miscalibrated bar
    enforce = not quick
    bound = ("<4x slower than clean same-scale merge, identical timed "
             "region (commit+materialize+sync)")
    if enforce:
        assert slowdown < 4.0, (
            f"residual-heavy merge {slowdown:.1f}x slower than the clean "
            f"same-scale merge (bound: <4x): clean {clean_rate:,.0f} ops/s "
            f"vs residual {resid_rate:,.0f} ops/s")
    emit(f"cfg5b_residual_heavy_{n_actors}_actors", resid_rate, "ops/s",
         vs_baseline=resid_rate / 100e6,
         residual_fraction=0.2,
         clean_same_scale_ops_per_sec=round(clean_rate),
         slowdown_vs_clean=round(slowdown, 2),
         threshold=(f"asserted in code: {bound}" if enforce
                    else ("tracking-only at --quick scale (bound "
                          "enforced at the 10k-actor record scale): "
                          + bound)))


def config5d_overlap(n_actors: int = 10_000, quick: bool = False):
    """The PreparedBatch pipelining seam, exercised end-to-end: two
    causally independent half-batches merge back-to-back;
    the overlapped schedule runs `prepare_batch` of half 2 (host planning
    + h2d staging) WHILE the device still executes half 1's commit — jax
    dispatch is asynchronous, and the engine's only forced syncs are the
    prepare-side `block_until_ready(staged)` (waits on the new round's
    transfers, not the running kernels) and the final scalar fetch. The
    serial comparator hard-barriers on half 1's output tables before
    planning half 2. e2e_overlapped ~ max(prepare, commit) per round where
    host and device are separate processors (the chip); on this box's ONE
    CPU core, host planning and 'device' compute share the core, so rough
    parity here + a gain on the chip row is the expected shape.

    Path under test: engine/base.py prepare_batch/commit_prepared (the
    seam's contract: plan binds to a generation; commit is bookkeeping +
    dispatch only)."""
    import bench as B
    from automerge_tpu.engine import DeviceTextDoc

    if quick:
        n_actors = 500
    base_n = 100 * n_actors
    half = n_actors // 2
    b1 = B.merge_batch("t", half, 1000, base_n, seed=1, actor_prefix="alpha")
    b2 = B.merge_batch("t", half, 1000, base_n, seed=2, actor_prefix="beta")
    n_ops = b1.n_ops + b2.n_ops
    expect = base_n + 2 * half * 500

    def run(overlap):
        # the ONE shared schedule harness (bench.run_overlapped);
        # barrier=True is the serial comparator — a pure completion
        # barrier between commits, so the A/B isolates scheduling alone
        return B.run_overlapped([b1, b2], expect, obj_id="t",
                                base_n=base_n, barrier=not overlap)

    run(True)                                  # warm-up: jit compiles
    serial = min(run(False) for _ in range(2))
    overlapped = min(run(True) for _ in range(2))
    gain = serial / overlapped
    # overlap must never LOSE meaningfully: it removes a barrier and adds
    # no work (generous margin absorbs one-core scheduling noise)
    assert overlapped <= serial * 1.15, (
        f"overlapped schedule slower than serial: {overlapped:.4f}s vs "
        f"{serial:.4f}s")
    emit(f"cfg5d_e2e_overlapped_{n_actors}_actors", n_ops / overlapped,
         "ops/s", vs_baseline=(n_ops / overlapped) / 100e6,
         e2e_serial_s=round(serial, 4),
         e2e_overlapped_s=round(overlapped, 4),
         overlap_gain=round(gain, 3),
         threshold=("asserted in code: overlapped <= 1.15x serial "
                    "(tracking: gain ~1 on one shared CPU core; the win "
                    "shows where host and device are separate processors)"))


def config5e_incremental_pull(n_base: int = 1_000_000, n_actors: int = 20,
                              ops_per_change: int = 100,
                              quick: bool = False):
    """Incremental text pull: a SMALL merge into a large warm document,
    then `text()`. The host string cache + dirty-span reconciliation
    (engine/text_doc._text_incremental) must ship O(edits) bytes d2h —
    asserted on the ENGINE-REPORTED span bytes, not wall clock, so the
    row gates identically on every platform. Reports the
    bytes a full pull would have moved for scale."""
    import bench as B
    from automerge_tpu.engine import DeviceTextDoc

    if quick:
        n_base = 100_000
    doc = DeviceTextDoc("t")
    doc.eager_materialize = True
    doc.apply_batch(B.base_batch("t", n_base))
    doc.text()                         # warm pull seeds the host cache
    assert doc._text_cache is not None, "text cache failed to seed"
    batch = B.merge_batch("t", n_actors, ops_per_change, n_base, seed=11,
                          actor_prefix="inc")
    doc.apply_batch(batch)
    t0 = time.time()
    text = doc.text()
    pull_s = time.time() - t0
    edit_chars = n_actors * (ops_per_change // 2)
    assert len(text) == n_base + edit_chars
    stats = doc.pull_stats
    assert stats["mode"] == "incremental", stats
    # O(edits): the merge inserted edit_chars visible chars; allow slack
    # for the S-sized seg-info row but nothing close to the doc itself
    budget = 4 * edit_chars + stats.get("info_bytes", 0) + 4096
    assert stats["span_bytes"] <= budget, (stats, budget)
    emit(f"cfg5e_incremental_pull_{n_base // 1000}k_doc",
         stats["span_bytes"], "bytes_pulled",
         pull_s=round(pull_s, 4),
         n_spans=stats["n_spans"],
         info_bytes=stats.get("info_bytes", 0),
         full_pull_bytes=n_base + edit_chars,
         edit_chars=edit_chars,
         threshold="asserted in code: span_bytes <= 4x edit chars + "
                   "seg-info row (O(edits), not O(doc)); byte-count "
                   "gate, platform-independent")


def config5f_pipeline(quick: bool = False):
    """The sustained streaming tier (ISSUE 4 tentpole): B causally-
    independent batches through the K-deep PipelinedIngestor ring with
    buffer donation. Delegates to the ONE shared harness
    (bench.measure_pipeline) so this row and `bench.py --pipeline`
    can never measure different schedules; the harness itself asserts
    the machine checks (median-of->=5, per-batch dispatch/sync budget,
    ring actually chained) — a regression crashes the row rather than
    recording an unfalsifiable string."""
    import bench as B

    rec = B.measure_pipeline(quick=quick)
    emit("cfg5f_" + rec["metric"], rec["value"], rec["unit"],
         vs_baseline=rec["vs_baseline"],
         n_reps=rec["n_reps"],
         reps_ops_per_sec=rec["reps_ops_per_sec"],
         value_spread_pct=rec["value_spread_pct"],
         ring=rec["ring"],
         dispatches_per_batch_max=rec["dispatches_per_batch_max"],
         syncs_per_batch_max=rec["syncs_per_batch_max"],
         pipeline_gain_vs_serial=rec["pipeline_gain_vs_serial"],
         serial_profile=rec["serial_profile"],
         floor_met=rec["floor_met"],
         **({"shortfall": rec["shortfall"]} if "shortfall" in rec else {}),
         **({"threshold_met": rec["threshold_met"]}
            if "threshold_met" in rec else {}),
         threshold=rec["threshold"])


def config5c_two_causal_rounds(n_actors: int = 10_000, quick: bool = False):
    """Adversarial headline shape: every actor delivers TWO causally
    chained changes (seq 2 depends on seq 1), so the merge cannot be one
    round — admission schedules two rounds and the engine pays two
    prepare/commit cycles. Bounds the per-round overhead the single-round
    headline never shows. Path under test: engine/base.py _schedule +
    multi-round prepare."""
    import bench as B
    from automerge_tpu.engine import DeviceTextDoc, TextChangeBatch
    from automerge_tpu.engine.columnar import KIND_INS, KIND_SET

    if quick:
        n_actors = 500
    base_n = 50_000 if quick else 1_000_000
    pairs_per_change = 250           # 500 ops x 2 changes = 1k ops/actor
    n_changes = 2 * n_actors
    n_per = 2 * pairs_per_change
    n_ops = n_changes * n_per
    actors = [f"actor-{i:06d}" for i in range(n_actors)]
    # change rows: actor a seq 1 = row 2a, seq 2 = row 2a+1
    op_change = np.repeat(np.arange(n_changes, dtype=np.int32), n_per)
    kind = np.tile(np.array([KIND_INS, KIND_SET], np.int8),
                   n_changes * pairs_per_change)
    ta = np.repeat(np.arange(n_actors, dtype=np.int32), 2 * n_per)
    tc = np.zeros(n_ops, np.int32)
    pa = np.zeros(n_ops, np.int32)
    pc = np.zeros(n_ops, np.int32)
    val = np.zeros(n_ops, np.int64)
    rng = np.random.default_rng(7)
    targets = rng.integers(1, base_n, n_actors)
    c1 = np.arange(1, pairs_per_change + 1, dtype=np.int32) + base_n + 1
    c2 = c1 + pairs_per_change
    for a in range(n_actors):
        for half, ctrs in ((0, c1), (1, c2)):
            s = (2 * a + half) * n_per
            tc[s: s + n_per: 2] = ctrs
            tc[s + 1: s + n_per: 2] = ctrs
            if half == 0:
                pa[s] = n_actors
                pc[s] = int(targets[a])
            else:
                pa[s] = a                 # continue own seq-1 run
                pc[s] = c1[-1]
            pa[s + 2: s + n_per: 2] = a
            pc[s + 2: s + n_per: 2] = ctrs[:-1]
            val[s + 1: s + n_per: 2] = 97 + (a % 26)
    seqs = np.empty(n_changes, np.int32)
    seqs[0::2] = 1
    seqs[1::2] = 2
    shared = {"base": 1}
    batch = TextChangeBatch(
        obj_id="t", actors=[a for a in actors for _ in range(2)],
        seqs=seqs, deps=[shared] * n_changes,
        messages=[None] * n_changes, op_change=op_change, op_kind=kind,
        op_target_actor=ta, op_target_ctr=tc, op_parent_actor=pa,
        op_parent_ctr=pc, op_value=val, actor_table=actors + ["base"],
        value_pool=[])

    def run():
        doc = DeviceTextDoc("t")
        doc.eager_materialize = True
        doc.apply_batch(B.base_batch("t", base_n))
        doc.text()
        prepared = doc.prepare_batch(batch)
        assert len(prepared.rounds) == 2      # genuinely two causal rounds
        doc.commit_prepared(prepared)
        assert len(doc.text()) == base_n + n_ops // 2

    dt = timed(run, warmups=1, reps=1)
    emit(f"cfg5c_two_causal_rounds_{n_actors}_actors", n_ops / dt, "ops/s",
         vs_baseline=(n_ops / dt) / 100e6, n_rounds=2,
         threshold="tracking-only: measured against the 100M north star "
                   "(vs_baseline) but carries no asserted bound; "
                   "regressions caught by diffing same-platform rows "
                   "across round records")


def config7_interactive_latency(n_base: int = 100_000, n_changes: int = 60):
    """Interactive latency: ONE 10-op change applied to an n_base-element
    Text document through the full public API (the reference's core
    editing loop, frontend/index.js change -> backend applyLocalChange ->
    patch). Reports full-API and backend-only p50/p99 per-change wall
    time. Target: < 1 ms backend p50 — met by the write-behind host fast
    path (INTERNALS §4.8; measured 0.83 ms on the virtual CPU platform);
    the full-API number adds the frontend's immutable-snapshot cost."""
    import time as _time

    import automerge_tpu as am
    from automerge_tpu import Text

    from automerge_tpu import frontend as _F
    from automerge_tpu.backend import default as _B

    orig_alc = _B.Backend.apply_local_change
    be_box: list = []

    def timed_alc(state, request):
        t0 = _time.perf_counter()
        out = orig_alc(state, request)
        be_box.append(_time.perf_counter() - t0)
        return out

    skip = n_changes // 6                           # drop compile warmup

    def pcts(series):
        w = np.asarray(series[skip:]) * 1e3
        return (float(np.percentile(w, 50)), float(np.percentile(w, 99)))

    from automerge_tpu.engine import accounting
    acct_box: list = []            # (dispatches, syncs) per change

    def measure():
        """One full measurement: fresh doc, n_changes timed edits."""
        doc = am.change(am.init("user"),
                        lambda d: d.__setitem__("t", Text("x" * n_base)))
        lat = []
        be_box.clear()
        acct_box.clear()
        # the frontend resolves the backend through the injected class
        # (options.backend seam), so patch the class attribute
        _B.Backend.apply_local_change = staticmethod(timed_alc)
        try:
            for i in range(n_changes):
                t0 = _time.perf_counter()
                with accounting.track() as tr:
                    doc = am.change(
                        doc, lambda d, i=i: d["t"].insert_at(5000 + 11 * i,
                                                             *"helloworld"))
                lat.append(_time.perf_counter() - t0)
                acct_box.append((tr.stats["dispatches"], tr.stats["syncs"]))
        finally:
            _B.Backend.apply_local_change = staticmethod(orig_alc)
        assert len(doc["t"]) == n_base + 10 * n_changes
        assert _F.get_backend_state(doc) is not None
        return pcts(lat), pcts(be_box)

    # Up to 3 attempts, asserting only a PERSISTENT miss. A single
    # attempt on a shared box is routinely poisoned by unrelated load,
    # which says nothing about the engine. A genuine regression fails
    # every attempt; transient contention passes a later one (the sleep
    # escapes the burst window).
    P50_TARGET_MS, P99_TARGET_MS, ATTEMPTS = 1.5, 10.0, 3
    for attempt in range(ATTEMPTS):
        (p50, p99), (be_p50, be_p99) = measure()
        if p50 <= P50_TARGET_MS and p99 <= P99_TARGET_MS:
            break
        if attempt < ATTEMPTS - 1:
            _time.sleep(4)               # escape the contention burst
    # stated-and-asserted interactive targets: the ChunkedElems COW store
    # removed the per-keystroke O(n) snapshot copy (cpu: p50 3.12 -> 1.01
    # ms, p99 40.8 -> 2.4 ms at this size)
    assert p50 <= P50_TARGET_MS, \
        f"interactive full-API p50 {p50:.2f} ms > {P50_TARGET_MS} ms"
    assert p99 <= P99_TARGET_MS, \
        f"interactive full-API p99 {p99:.2f} ms > {P99_TARGET_MS} ms"
    # device-interaction budget of the write-behind path (ISSUE 4,
    # INTERNALS §9): an interactive change must stay HOST work — device
    # dispatches and blocking syncs per am.change are measured
    # (engine/accounting.py) and asserted <= a small constant on EVERY
    # platform (counting is link-independent, unlike the latency bounds).
    # Steady state measures 0/0; the budget of 2 absorbs a deferred
    # flush landing inside a change without ever letting a per-keystroke
    # device round trip back in (tests/test_dispatch_budget.py pins the
    # same bar in CI).
    DISPATCH_BUDGET = SYNC_BUDGET = 2
    disp_max = max(d for d, _ in acct_box)
    sync_max = max(s for _, s in acct_box)
    assert disp_max <= DISPATCH_BUDGET, (
        f"write-behind change dispatched {disp_max} device programs "
        f"(budget {DISPATCH_BUDGET})")
    assert sync_max <= SYNC_BUDGET, (
        f"write-behind change blocked on {sync_max} device syncs "
        f"(budget {SYNC_BUDGET})")
    emit("cfg7_interactive_10op_change_100k_doc", p50, "ms_p50",
         p99_ms=round(p99, 2),
         backend_p50_ms=round(be_p50, 3),
         backend_p99_ms=round(be_p99, 3),
         dispatches_per_change_max=disp_max,
         syncs_per_change_max=sync_max,
         dispatch_budget=(f"asserted in code: <= {DISPATCH_BUDGET} "
                          "dispatches and <= 2 blocking syncs per "
                          "am.change, every platform (count, not time)"),
         n_changes=n_changes,
         threshold=(f"asserted in code: p50 <= {P50_TARGET_MS} ms, "
                    f"p99 <= {P99_TARGET_MS} ms (persistent across up to "
                    f"{ATTEMPTS} attempts; transient one-core contention "
                    "is not a regression)"),
         note="one 10-char insert per change through am.change; backend_* "
              "isolates apply_local_change (the device-tier write-behind "
              "fast path, INTERNALS 4.8); the remainder is frontend "
              "snapshot cost (ChunkedElems COW, types.py)")


def config7b_nested_under_large_root(n_root: int = 100_000,
                                     n_changes: int = 20):
    """Interactive latency for the REALISTIC nested-document shape: one
    small nested map edited under a large root. Round 5 found the parent
    relink pass scanning every root entry per nested change (~70 ms at
    this size); the keyed relink (InboundIndex.key_of,
    frontend/apply_patch.py) makes the cost the root's own clone, not a
    scan. Same 3-attempt contention discipline as cfg7."""
    import time as _time

    import automerge_tpu as am

    doc = am.init("user")
    for c in range(4):
        doc = am.change(doc, lambda d, c=c: [
            d.__setitem__(f"k{c}-{i}", i) for i in range(n_root // 4)])
    doc = am.change(doc, lambda d: d.__setitem__(
        "board", {"meta": {"title": "t"}}))

    P50_TARGET_MS, ATTEMPTS = 10.0, 3
    skip = n_changes // 5

    def measure(doc):
        lat = []
        for i in range(n_changes):
            t0 = _time.perf_counter()
            doc = am.change(doc, lambda d, i=i: d["board"]["meta"]
                            .__setitem__("title", f"v{i}"))
            lat.append(_time.perf_counter() - t0)
        assert am.to_json(doc)["board"]["meta"]["title"] == \
            f"v{n_changes - 1}"
        return float(np.percentile(np.asarray(lat[skip:]) * 1e3, 50)), doc

    for attempt in range(ATTEMPTS):
        p50, doc = measure(doc)
        if p50 <= P50_TARGET_MS:
            break
        if attempt < ATTEMPTS - 1:
            _time.sleep(4)
    assert p50 <= P50_TARGET_MS, \
        f"nested-change p50 {p50:.2f} ms > {P50_TARGET_MS} ms"
    emit(f"cfg7b_nested_change_under_{n_root // 1000}k_root", p50,
         "ms_p50", n_changes=n_changes,
         threshold=(f"asserted in code: p50 <= {P50_TARGET_MS} ms "
                    f"(persistent across up to {ATTEMPTS} attempts); "
                    "was ~70 ms on cpu pre keyed-relink"),
         note="one nested map key set per am.change under a "
              f"{n_root}-key root; cost = root clone, not a root scan "
              "(frontend/apply_patch.py InboundIndex.key_of)")


def config8_frontend_splice(n_big: int = 1_000_000, n_base_ab: int = 200_000,
                            n_ins_ab: int = 20_000):
    """Frontend patch application: a bulk text-insert patch landing in the
    MIDDLE of a large existing document (a remote peer's typing run merged
    into a big doc — the reference's splice-batching case,
    apply_patch.js:332-384). Element-wise application shifts the whole tail
    per insert (O(n_ins * n_base)); the splice-batched path is one slice
    assignment (O(n_base + n_ins)). Tail-append patches are linear either
    way, so the A/B uses a mid-document run. Host-only (no device).
    Regression threshold: batched >= 4x element-wise at the A/B size
    (was 10x against the flat-list elems store; the chunked COW store
    made element-wise insertion O(CHUNK) per insert, see the assert)."""
    import time as _time

    from automerge_tpu.frontend.apply_patch import apply_diffs
    from automerge_tpu.frontend.types import instantiate_text

    def base_doc(n):
        elems = [{"elemId": f"b:{i + 1}", "value": "x", "conflicts": None}
                 for i in range(n)]
        return instantiate_text("T", elems, n)

    def insert_diffs(n, at):
        return [{"type": "text", "obj": "T", "action": "insert",
                 "index": at + i, "elemId": f"a:{i + 1}", "value": "y"}
                for i in range(n)]

    def apply_once(n_base, n_ins, splice):
        cache = {"T": base_doc(n_base)}
        updated = {}
        diffs = insert_diffs(n_ins, at=1000)
        t0 = _time.perf_counter()
        apply_diffs(diffs, cache, updated, {}, splice_batch=splice)
        dt = _time.perf_counter() - t0
        assert len(updated["T"].elems) == n_base + n_ins
        return dt, updated["T"]

    # Pre-ChunkedElems, element-wise insertion shifted the flat list's
    # whole tail per insert (O(n_ins * n_base)) and batching won 40-50x.
    # The chunked COW elems store made element-wise O(n_ins * CHUNK), so
    # the remaining batched win is amortized per-insert bookkeeping
    # (~7-9x observed at 20k-into-200k); the threshold tracks that
    # regime. Same 3-attempt contention guard as cfg7: the batched pass
    # is ~0.07 s, and one probe-loop jax-import burst inside it would
    # inflate sp_s severalfold — a transient, not a regression.
    for attempt in range(3):
        el_s, el_doc = apply_once(n_base_ab, n_ins_ab, splice=False)
        sp_s, sp_doc = apply_once(n_base_ab, n_ins_ab, splice=True)
        assert [e["elemId"] for e in el_doc.elems] == \
            [e["elemId"] for e in sp_doc.elems]      # A/B parity
        speedup = el_s / sp_s
        if speedup >= 4:
            break
        if attempt < 2:
            _time.sleep(4)                 # escape the contention burst
    assert speedup >= 4, f"splice batching only {speedup:.1f}x"
    big_s, _ = apply_once(n_big, n_big, splice=True)
    emit(f"cfg8_frontend_apply_{n_big // 1000}k_insert_patch",
         n_big / big_s, "chars/s",
         elementwise_s_at_20k_into_200k=round(el_s, 4),
         batched_s_at_20k_into_200k=round(sp_s, 4),
         speedup=round(speedup, 1),
         threshold="asserted in code: batched >= 4x element-wise at the "
                   "20k-into-200k A/B size")


def config9_sync_fanout(n_peers: int = 20, n_changes: int = 50):
    """Multi-peer sync throughput: one author DocSet fanning every local
    change out to n_peers over the Connection protocol. The reference
    instantiates one Connection per peer, each re-diffing every doc per
    local change (src/connection.js:58-88); here all author-side
    Connections share one SyncHub (sync/hub.py) — one vectorized
    ClockMatrix comparison per change regardless of peer count. Measured:
    end-to-end deliveries (change applied at a peer) per second, full
    protocol included (clock bookkeeping, extraction, message pump,
    remote apply + frontend patch)."""
    import time as _time

    import automerge_tpu as am
    from automerge_tpu import Connection, DocSet, Text

    author_set = DocSet()
    author_set.set_doc("doc", am.change(
        am.init("author"), lambda d: d.__setitem__("t", Text("base"))))
    peer_sets = [DocSet() for _ in range(n_peers)]
    out_q = [[] for _ in range(n_peers)]
    in_q = [[] for _ in range(n_peers)]
    author_conns = [Connection(author_set, out_q[i].append)
                    for i in range(n_peers)]
    peer_conns = [Connection(peer_sets[i], in_q[i].append)
                  for i in range(n_peers)]
    for c in author_conns + peer_conns:
        c.open()

    def pump():
        moved = True
        while moved:
            moved = False
            for i in range(n_peers):
                while out_q[i]:
                    peer_conns[i].receive_msg(out_q[i].pop(0))
                    moved = True
                while in_q[i]:
                    author_conns[i].receive_msg(in_q[i].pop(0))
                    moved = True

    pump()                                   # initial advertisements
    t0 = _time.perf_counter()
    for k in range(n_changes):
        doc = author_set.get_doc("doc")
        author_set.set_doc("doc", am.change(
            doc, lambda d, k=k: d["t"].insert_at(0, *"0123456789")))
        pump()
    dt = _time.perf_counter() - t0
    # each change splices its run at position 0, so the LAST change's run
    # is frontmost and every run reads in order — full content equality
    # catches RGA mis-ordering that a length check would miss
    expect = "0123456789" * n_changes + "base"
    for ps in peer_sets:
        got = str(am.to_json(ps.get_doc("doc"))["t"])
        assert got == expect, (got[:40], len(got))
    deliveries = n_changes * n_peers
    emit(f"cfg9_sync_fanout_{n_peers}peers", deliveries / dt,
         "deliveries/s",
         changes_per_sec=round(n_changes / dt, 1),
         n_peers=n_peers, n_changes=n_changes,
         threshold=TRACKING_ONLY)


def config10_save_load(n_changes: int = 40, run_chars: int = 250):
    """Persistence round-trip (reference: src/automerge.js save/load —
    serialize the change history, rebuild by replay). Load used to grow
    each device doc through every capacity bucket, paying a fresh XLA
    compile per bucket shape (~12 s for this doc, round 5); creation
    sizing from the delivery's op totals (backend/device.py _distribute)
    pins the shapes, leaving one-time per-shape compiles (warm process:
    ~0.2 s). Reported warm: best of 2 loads after a throwaway first."""
    import time as _time

    import automerge_tpu as am
    from automerge_tpu import Text

    doc = am.change(am.init("u"), lambda d: d.__setitem__("t", Text("x")))
    for _ in range(n_changes):
        doc = am.change(doc, lambda d: d["t"]
                        .insert_at(0, *("ab" * (run_chars // 2))))
    n_chars = 1 + n_changes * run_chars
    t0 = _time.perf_counter()
    blob = am.save(doc)
    save_s = _time.perf_counter() - t0
    holder = {}

    def one_load():
        holder["back"] = am.load(blob)

    load_s = timed(one_load, warmups=1, reps=2)   # shared discipline
    assert str(am.to_json(holder["back"])["t"]) == str(am.to_json(doc)["t"])
    emit(f"cfg10_save_load_{n_chars // 1000}k_chars_{n_changes}_changes",
         n_chars / load_s, "chars_loaded/s",
         save_ms=round(save_s * 1e3, 1), load_ms=round(load_s * 1e3, 1),
         blob_kb=len(blob) // 1024,
         threshold=TRACKING_ONLY)


def main():
    from automerge_tpu._env import setup_compile_cache
    from benchmarks.common import bench_platform
    setup_compile_cache()
    bench_platform()
    quick = "--quick" in sys.argv
    if "--service-session" in sys.argv:
        # ONLY the service row, full JSON appended to BENCH_SESSIONS.jsonl
        config11_service(quick=quick, record_session=True)
        return
    if "--sharded-session" in sys.argv:
        # ONLY the sharded row: the subprocess's honest cpu-dryrun JSON
        # appended to BENCH_SESSIONS.jsonl (the acceptance bar is
        # defined there)
        config12_sharded(quick=quick, record_session=True)
        return
    if "--text-prepare-session" in sys.argv:
        # ONLY the cold-planning row
        config12t_text_prepare(quick=quick, record_session=True)
        return
    if "--wire-session" in sys.argv:
        # ONLY the binary-wire A/B row
        config13_wire(quick=quick, record_session=True)
        return
    if "--lineage-session" in sys.argv:
        # ONLY the lineage A/B row
        config14_lineage(quick=quick, record_session=True)
        return
    if "--device-truth-session" in sys.argv:
        # ONLY the device-truth row
        config15_device_truth(quick=quick, record_session=True)
        return
    if "--federation-session" in sys.argv:
        # ONLY the federation row
        config16_federation(quick=quick, record_session=True)
        return
    if "--fused-session" in sys.argv:
        # ONLY the fused-round A/B row
        config17_fused(quick=quick, record_session=True)
        return
    if "--residency-session" in sys.argv:
        # ONLY the bounded-HBM row
        config18_residency(quick=quick, record_session=True)
        return
    if "--learned-session" in sys.argv:
        # ONLY the learned-index A/B row
        config19_learned_index(quick=quick, record_session=True)
        return
    if "--parallel-session" in sys.argv:
        # ONLY the parallel-mesh A/B row
        config20_parallel(quick=quick, record_session=True)
        return
    record_round = None
    record_path = None
    if "--record" in sys.argv:
        import os
        record_round = int(sys.argv[sys.argv.index("--record") + 1])
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        record_path = os.path.join(
            root, f"BENCH_CONFIGS_r{record_round:02d}.json")

    def fold_headline():
        # cfg5 = the headline bench, in this process (one process holds
        # the chip), folded into the record FIRST; a failure fails the
        # sweep
        import bench as B
        rec = B._measure()
        if rec["platform"] == "tpu":
            B.append_session_log(rec)
        RESULTS.append({**rec, "metric": "cfg5_" + rec["metric"]})
        print(json.dumps(RESULTS[-1]), flush=True)

    steps = [
        config1_text_two_actor,
        config2_map_counter,
        lambda: config3_docset(n_docs=100 if quick else 1000),
        lambda: config4_trellis(quick=quick),
        lambda: config5b_residual_heavy(quick=quick),
        lambda: config5c_two_causal_rounds(quick=quick),
        lambda: config5d_overlap(quick=quick),
        lambda: config5e_incremental_pull(quick=quick),
        lambda: config5f_pipeline(quick=quick),
        config6_conflict_heavy,
        lambda: config7_interactive_latency(n_changes=20 if quick else 60),
        lambda: config7b_nested_under_large_root(
            n_root=20_000 if quick else 100_000),
        lambda: config8_frontend_splice(n_big=200_000 if quick else 1_000_000),
        lambda: config9_sync_fanout(n_peers=8 if quick else 20,
                                    n_changes=20 if quick else 50),
        lambda: config10_save_load(n_changes=15 if quick else 40),
        lambda: config11_service(quick=quick),
        lambda: config12_sharded(quick=quick),
        lambda: config12t_text_prepare(quick=quick),
        lambda: config13_wire(quick=quick),
        lambda: config14_lineage(quick=quick),
        lambda: config15_device_truth(quick=quick),
        lambda: config17_fused(quick=quick),
        lambda: config18_residency(quick=quick),
        lambda: config19_learned_index(quick=quick),
        lambda: config20_parallel(quick=quick),
    ]
    if record_path is not None:
        steps.insert(0, fold_headline)
    for step in steps:
        step()
        if record_path is not None:
            # incremental: every completed config survives a later
            # config's failure
            write_record(record_path)
    if record_path is None and not quick:
        print("# cfg5 (headline): python bench.py", file=sys.stderr)


if __name__ == "__main__":
    main()
