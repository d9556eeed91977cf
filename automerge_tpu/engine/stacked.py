"""Stacked multi-object rounds: one device dispatch per causal round.

The nested-document production shape — a Trellis-style board, a form of
many small sections — routes ONE causal round across many small
per-object engine docs. The per-object path (backend/device.py
`_distribute` -> `doc.apply_changes` per object) pays 1-2 jitted
programs plus their h2d staging per (object, round): ~270 tiny
device_puts for a 400-op board merge, the recorded cfg4 ceiling.
This module executes the SAME rounds as a
constant number of stacked device programs per round, independent of
object count — PAM's batch-parallel-over-many-keys shape (PAPERS.md)
applied to the object axis:

- per-object admission and planning stay on the host and REUSE the
  per-object machinery verbatim (`_decode_wire` -> `_schedule` ->
  `_group_round` -> `_round_bookkeeping` -> `_plan_round` /
  `_plan_map_round`), so the two paths cannot drift semantically: the
  stacked tier changes WHERE device work happens, never what is
  computed;
- per-object tables pad to a common capacity and stack along a doc
  axis (one gather program per kind, pending actor-rank remaps folded
  in so a reordering intern costs zero extra dispatches);
- each causal round executes as vmapped round kernels over the stacked
  tables: one `stacked_map_round` for every map/table object, one
  `stacked_mixed_round` per distinct static-flag shape for text/list
  objects — each fed by ONE packed (D, ...) upload (the round's shared
  descriptor template / value blob / residual matrix) instead of
  per-object staging;
- the host slow-register residue of ALL objects reads back as one
  packed slow_info fetch and writes back as one stacked scatter; one
  unstack program plus one packed mirror fetch re-seed every doc's row
  tables and host mirrors at the end of the apply.

Padded stacking + vmap was chosen over a doc-id column in shared flat
tables: the run-expansion kernels write one contiguous slot window per
document (`expand_runs_dense`'s base_slot contract), which a doc-id
column cannot express without per-doc windows — vmap keeps each doc's
slot space intact and the kernels unchanged (INTERNALS §12 records the
tradeoff). Padding waste is bounded by the eligibility gate
(`AMTPU_STACKED_MAX_CELLS`); skewed populations fall back to the
per-object path.

The per-object path is kept verbatim as the parity comparator behind
``AMTPU_STACKED_ROUNDS=0``; tests/test_stacked_rounds.py pins
byte-identical committed state across both paths (and both planners)
on randomized out-of-order/duplicate nested-doc deliveries.

Failure atomicity: `apply_stacked` is entered from
`_DeviceCore._distribute`, whose caller restores the whole core by
deterministic replay on ANY exception (backend/device.py
`_device_apply._restore` contract) — a mid-apply failure here leaves
per-doc state partially advanced exactly like a failed per-object
apply that already touched earlier docs, and the same restore covers
both.
"""

from __future__ import annotations

import os

import numpy as np

from .. import obs
from ..obs import lineage
from . import accounting, cross_doc
from .map_doc import DeviceMapDoc
from .text_doc import DeviceTextDoc

#: Stats of the most recent stacked apply (bench / budget-test
#: introspection): docs, rounds, passes, device dispatches, blocking
#: syncs, packed h2d uploads.
LAST_STATS: dict = {}

#: Asserted dispatch budget (tests/test_stacked_rounds.py, the cfg4
#: smoke): a stacked apply may launch at most BASE + PER_PASS * passes
#: device programs — CONSTANT in the number of objects. A PASS is one
#: (round, source-batch-group) step: every causal round takes >= 1
#: pass, and a round splits into one pass per source batch when queued
#: batches release together — so the pass count scales with delivery
#: fragmentation, never with object count (the quantity this budget
#: bounds). BASE covers the per-apply fixed programs (two gathers, two
#: unstacks, two mirror fetches); PER_PASS covers one pass's round
#: kernels (one map round + up to a handful of text shape groups, each
#: with its slow-path scatter).
APPLY_DISPATCH_BASE = 8
PASS_DISPATCH_BUDGET = 16
#: The TIGHTENED per-pass budget when the ISSUE-17 fused path ran
#: (stats["fused"]): one megakernel (both lanes) + at most one combined
#: slow-path scatter per pass — the 4 leaves headroom for nothing; it is
#: double the structural count so a single added program trips the
#: assert before it doubles the round cost.
FUSED_PASS_DISPATCH_BUDGET = 4

_MAP_MIRROR_KEYS = ("value", "has_value", "win_counter")
_TEXT_MIRROR_KEYS = ("parent", "ctr", "actor", "value", "has_value")
_BOOL_KEYS = frozenset(("has_value", "win_counter", "chain"))


def stacked_rounds_enabled() -> bool:
    """Stacked multi-object rounds are the default nested-object path;
    ``AMTPU_STACKED_ROUNDS=0`` selects the per-object parity comparator
    (read per call so tests can pin either path)."""
    return os.environ.get("AMTPU_STACKED_ROUNDS", "1") != "0"


def _min_ops() -> int:
    return int(os.environ.get("AMTPU_STACKED_MIN_OPS", "16"))


def _max_cells() -> int:
    return int(os.environ.get("AMTPU_STACKED_MAX_CELLS", str(1 << 23)))


def worth_trying(n_wire_ops: int, n_op_docs: int) -> bool:
    """Cheap pre-gate callers apply BEFORE building per-object change
    windows (backend/device.py `_distribute_routed`): the stacked path
    only ever engages for >= 2 op-bearing objects carrying >=
    AMTPU_STACKED_MIN_OPS wire ops — the same gates `apply_stacked`
    re-checks, hoisted so a declined attempt costs no window/decoding
    work on the interactive hot path."""
    return n_op_docs >= 2 and n_wire_ops >= _min_ops()


def _identity_stage(arr):
    return arr


def assert_round_budget(stats: dict = None):
    """Assert the object-count-independent dispatch budget against the
    most recent stacked apply (accounting is exact: every stacked
    program launch passes through `_count`)."""
    s = LAST_STATS if stats is None else stats
    assert s, "no stacked apply recorded"
    per_pass = (FUSED_PASS_DISPATCH_BUDGET if s.get("fused")
                else PASS_DISPATCH_BUDGET)
    limit = APPLY_DISPATCH_BASE + per_pass * max(1, s["passes"])
    assert s["dispatches"] <= limit, (
        f"stacked apply launched {s['dispatches']} device programs for "
        f"{s['passes']} round-pass(es) over {s['docs']} objects "
        f"(budget {limit}; per-pass dispatch must not scale with "
        f"object count)")
    # the tightened emission budget (ROADMAP 1a): every finalized text
    # doc's RGA positions were seeded from the ONE stacked linearize +
    # packed fetch, so the diff emission right after the apply pays ZERO
    # per-object positions dispatches (it used to pay one rga_linearize
    # or materialize+scalars round trip per text object)
    assert s.get("pos_seeded", 0) == s.get("text_finalized", 0), (
        f"stacked apply finalized {s.get('text_finalized', 0)} text docs "
        f"but seeded positions for {s.get('pos_seeded', 0)} — diff "
        "emission would fall back to per-object linearize dispatches")
    # the index bulk-update budget (ISSUE 12): a round's minted ranges
    # land as ONE bulk merge per doc — never one sorted insert per range
    assert s.get("index_merges", 0) <= s.get("text_plans", 0), (
        f"stacked apply performed {s.get('index_merges', 0)} index merges "
        f"for {s.get('text_plans', 0)} planned text rounds (budget: one "
        "bulk merge per doc per round)")


def _count(stats: dict, label: str):
    accounting.record_dispatch(1, None, label=label)
    stats["dispatches"] += 1


def _count_sync(stats: dict, label: str, t0_ns: int, d2h_bytes: int = 0):
    accounting.record_sync(1, None, label=label,
                           dur_ns=(obs.now() - t0_ns) if t0_ns else 0,
                           d2h_bytes=d2h_bytes)
    stats["syncs"] += 1


def _note_h2d(stats: dict, n_uploads: int, nbytes: int):
    """One stacked upload seam: transfer COUNT into the per-apply stats
    (the budget surface), exact BYTES into the process meter
    (engine/accounting.py h2d_bytes; ISSUE 15)."""
    stats["h2d"] += n_uploads
    accounting.record_h2d(nbytes)


class _LaneSet:
    """Stacked device tables for one kind's participating docs.

    Gathered lazily at the first pass that needs them (pending
    actor-rank remaps folded into the gather program); `cols` then hold
    the live stacked (D, cap) tables until the final unstack."""

    def __init__(self, docs, keys, kind: str):
        self.docs = list(docs)
        self.keys = keys
        self.kind = kind                       # "map" | "text"
        self.idx = {id(d): i for i, d in enumerate(self.docs)}
        self.cols = None
        self.cap = 0
        self.remaps: dict = {}                 # id(doc) -> composite remap

    def note_remap(self, doc, remap: np.ndarray):
        acc = self.remaps.get(id(doc))
        self.remaps[id(doc)] = (remap if acc is None
                                else remap[acc].astype(np.int32))

    def ensure(self, out_cap: int, stats: dict):
        """Gather per-doc tables into the stacked columns (one program)."""
        if self.cols is not None:
            return
        import jax.numpy as jnp
        from ..ops import ingest as K
        tables = tuple(tuple(doc._ensure_dev()[k] for k in self.keys)
                       for doc in self.docs)
        L = max([len(doc.actor_table) for doc in self.docs] + [1])
        rem = np.tile(np.arange(L, dtype=np.int32), (len(self.docs), 1))
        for i, doc in enumerate(self.docs):
            r = self.remaps.get(id(doc))
            if r is not None:
                rem[i, : len(r)] = r
        self.remaps.clear()
        out_cap = max(out_cap,
                      max(doc._cap for doc in self.docs))
        if self.kind == "map":
            _count(stats, "stacked_gather")
            self.cols = K.stack_register_tables(
                tables, jnp.asarray(rem), out_cap=out_cap)
        else:
            n_elems = np.asarray([doc.n_elems for doc in self.docs],
                                 np.int32)
            _count(stats, "stacked_gather")
            self.cols = K.stack_element_tables(
                tables, jnp.asarray(rem), jnp.asarray(n_elems),
                out_cap=out_cap)
        self.cap = out_cap
        _note_h2d(stats, 1, rem.nbytes)


def _host_remap(doc, remap: np.ndarray):
    """The host half of `_apply_remap` (conflicts + index/mirror
    re-rank); the device half — the actor columns — folds into the
    stacked gather instead of paying one remap program per doc."""
    for ops in doc.conflicts.values():
        for op in ops:
            op["actor_rank"] = int(remap[op["actor_rank"]])
    if isinstance(doc, DeviceTextDoc):
        doc.index = doc.index.remap_actors(remap.astype(np.int64))
        if doc.seg_mirror is not None:
            doc.seg_mirror.remap_actors(remap.astype(np.int64))
    doc._invalidate()


def _item_ops(subs) -> int:
    """Wire-op count of one item's change window: a list of wire dicts or
    an already-decoded columnar batch (the shard lanes / DocSet tier feed
    decoded batches; the backend feeds wire windows)."""
    if hasattr(subs, "n_ops"):
        return int(subs.n_ops)
    return sum(len(c.get("ops", ())) for c in subs)


def apply_stacked(items):
    """Apply one routed delivery as stacked multi-object rounds.

    `items`: ``[(doc, sub_changes), ...]`` — one entry per participating
    engine doc (map or text), each with its per-object change window
    exactly as `_DeviceCore._distribute` routes them (wire dicts), or an
    already-decoded columnar batch (the shard-lane / DocSet callers).
    Returns False when the population is ineligible (the caller then
    runs the per-object path, with nothing mutated); the apply's stats
    dict (truthy — also mirrored in LAST_STATS) when the delivery was
    applied, so concurrent shard lanes can assert their own per-apply
    budgets without racing on the module global."""
    if not stacked_rounds_enabled() or len(items) < 2:
        return False
    n_wire_ops = sum(_item_ops(subs) for _, subs in items)
    if n_wire_ops < _min_ops():
        return False
    docs = [d for d, _ in items]
    for doc in docs:
        if doc._device_lost or doc.donate_buffers:
            return False
        if not isinstance(doc, (DeviceMapDoc, DeviceTextDoc)):
            return False

    # cheap PRE-decode gates, from wire-op counts / doc kinds / current
    # caps only: a population that is ineligible every apply (one hot
    # object, or a skewed-capacity mix) must not pay a discarded
    # decode+schedule on top of the per-object fallback's own
    op_docs = [d for d, subs in items if _item_ops(subs)]
    n_map = sum(isinstance(d, DeviceMapDoc) for d in op_docs)
    n_text = len(op_docs) - n_map
    if n_map + n_text < 2:
        return False
    # padded-stacking memory gate: a skewed population (one huge doc
    # among many small ones) would inflate every row to the max cap
    if max(d._cap for d in op_docs) * (5 * n_map + 9 * n_text) \
            > _max_cells():
        return False

    # ---- decode + admission (pure: nothing committed until the GO) ----
    _t0 = obs.now() if obs.ENABLED else 0
    decoded = [(doc, changes if hasattr(changes, "n_changes")
                else doc._decode_wire(changes))
               for doc, changes in items]
    # cross-doc columnar planning (INTERNALS §16): ONE planning pass for
    # the whole touched population — batches with identical planning
    # columns share admission templates, run detection, and (after the
    # interning hoist below) rank caches, instead of re-running
    # _schedule_columnar + the detection walk per doc. None when
    # disabled (AMTPU_CROSS_DOC_PLAN=0 keeps the per-doc path verbatim)
    # or when no two docs share a shape.
    cross = cross_doc.preplan(decoded)
    sched = []           # (doc, [groups per round], queue_after, n_ops)
    for doc, batch in decoded:
        out = cross.schedule(doc, batch) if cross is not None else None
        if out is None:
            out = doc._schedule(batch)
        rounds, queue_after, _prior = out
        groups = [doc._group_round(r) for r in rounds]
        n_ops = sum(b.n_ops for gs in groups for b, _r, _m in gs)
        sched.append((doc, groups, queue_after, n_ops))

    # device lanes: docs whose ROUNDS carry ops (released queue batches
    # included, all-duplicate batches excluded); the rest only need
    # clock/deps bookkeeping and never touch the device this apply
    map_docs = [d for d, g, _q, n in sched
                if n and isinstance(d, DeviceMapDoc)]
    text_docs = [d for d, g, _q, n in sched
                 if n and isinstance(d, DeviceTextDoc)]
    if map_docs or text_docs:
        # released queue batches can pull in docs the pre-gate never
        # saw: re-check the memory gate against the real lane sets (a
        # rare late fallback beats stacking an unbounded row width)
        cap_hint = max(d._cap for d in map_docs + text_docs)
        if cap_hint * (5 * len(map_docs) + 9 * len(text_docs)) \
                > _max_cells():
            return False

    # ---- GO: commit queues, hoist interning, run the passes ----------
    from ..ops import fused_round as _F
    fused = _F.fused_rounds_enabled() and all(
        getattr(d, "fused_rounds", True) for d in docs)
    stats = {"docs": len(docs), "map_docs": len(map_docs),
             "text_docs": len(text_docs), "rounds": 0, "passes": 0,
             "dispatches": 0, "syncs": 0, "h2d": 0,
             "text_finalized": 0, "pos_seeded": 0,
             "text_plans": 0, "index_merges": 0, "fused": fused}
    map_set = (_LaneSet(map_docs,
                        ("value", "has_value", "win_actor", "win_seq",
                         "win_counter"), "map") if map_docs else None)
    text_set = (_LaneSet(text_docs, DeviceTextDoc._TABLE_KEYS, "text")
                if text_docs else None)
    lane_of = {}
    for s in (map_set, text_set):
        if s is not None:
            for d in s.docs:
                lane_of[id(d)] = s

    for doc in docs:
        doc._busy += 1
    try:
        for doc, groups, queue_after, _n in sched:
            doc.queue = queue_after
        # actor interning, hoisted across every round (content-free: it
        # renames ranks consistently and adds no document content —
        # the same reordering-safety argument as prepare_batch's
        # pre-planning intern). Device-lane remaps fold into the
        # gather; bookkeeping-only docs remap through the normal path.
        for doc, groups, _q, _n in sched:
            lane = lane_of.get(id(doc))
            for gs in groups:
                for b, _rows, _mask in gs:
                    remap = doc._intern_batch_actors(b)
                    if remap is None:
                        continue
                    if lane is None:
                        doc._apply_remap(remap)
                    else:
                        _host_remap(doc, remap)
                        lane.note_remap(doc, remap)
        if cross is not None:
            # the vectorized per-doc rank join runs AFTER the interning
            # hoist (ranks are only defined once every batch actor is
            # interned); the seeded caches feed every _plan_round below
            cross.seed_ranks()
            stats["cross_doc"] = dict(cross.stats)
        if obs.ENABLED:
            obs.span("plan", "stack", _t0, args={
                "docs": len(docs), "map_docs": len(map_docs),
                "text_docs": len(text_docs), "n_ops": n_wire_ops})
        if lineage.ENABLED:
            # the stacked-plan hop: the change's round is part of THIS
            # multi-object device program population (recorded at the
            # GO, after every ineligibility gate passed)
            for _doc, batch in decoded:
                lineage.hop_delivery(batch, "plan/stacked",
                                     doc=batch.obj_id)

        max_rounds = max((len(g) for _, g, _q, _n in sched), default=0)
        stats["rounds"] = max_rounds
        for k in range(max_rounds):
            in_round = [(doc, groups[k]) for doc, groups, _q, _n in sched
                        if len(groups) > k]
            max_groups = max((len(gs) for _, gs in in_round), default=0)
            for j in range(max_groups):
                _tp = obs.now() if obs.ENABLED else 0
                d0 = stats["dispatches"]
                map_plans, text_plans = [], []
                for doc, gs in in_round:
                    if len(gs) <= j:
                        continue
                    b, rows_arr, mask = gs[j]
                    doc._round_bookkeeping(b, rows_arr)
                    if not b.n_ops:
                        continue
                    if isinstance(doc, DeviceMapDoc):
                        p = doc._plan_map_round(b, mask)
                        if p is not None:
                            map_plans.append((doc, b, p))
                    else:
                        doc._stager = _identity_stage
                        try:
                            plan, _sh = doc._plan_round(
                                b, mask, doc._plan_shadow())
                        finally:
                            del doc._stager
                        if plan is not None:
                            text_plans.append((doc, b, plan))
                if text_plans:
                    stats["text_plans"] += len(text_plans)
                    stats["index_merges"] += sum(
                        p.n_index_merges for _, _, p in text_plans)
                if fused and (map_plans or text_plans):
                    # ISSUE-17 fused pass: both lanes' rounds in ONE
                    # megakernel dispatch + at most one combined scatter
                    _exec_fused_pass(map_set, map_plans,
                                     text_set, text_plans, stats)
                else:
                    if map_plans:
                        _exec_map_pass(map_set, map_plans, stats)
                    if text_plans:
                        _exec_text_pass(text_set, text_plans, stats)
                stats["passes"] += 1
                if obs.ENABLED:
                    obs.span("commit", "stacked_round", _tp, args={
                        "round": k, "pass": j,
                        "map_objs": len(map_plans),
                        "text_objs": len(text_plans),
                        "dispatches": stats["dispatches"] - d0})

        _finalize(map_set, stats)
        _finalize(text_set, stats)
    except BaseException:
        # partial device work happened: per-doc plans/caches can no
        # longer be trusted. The backend caller restores the WHOLE core
        # by replay (fresh doc objects); these bumps only keep direct
        # engine-level users loud rather than subtly stale.
        for doc in docs:
            doc._gen += 1
            doc._plan_failed()
        raise
    finally:
        for doc in docs:
            doc._busy -= 1

    LAST_STATS.clear()
    LAST_STATS.update(stats)
    return stats


def _conflict_matrix(docs, out_cap: int):
    """(D, K) conflict-slot matrix shared by the map and text lanes:
    every doc's host-held conflict slots, padded with the OOB sentinel."""
    from ..ops.ingest import bucket

    Kc = bucket(max([len(d.conflicts) for d in docs] + [1]), 64)
    conflict = np.full((len(docs), Kc), out_cap, np.int32)
    for d, doc in enumerate(docs):
        if doc.conflicts:
            cl = list(doc.conflicts)
            conflict[d, : len(cl)] = cl
    return conflict


def _exec_map_pass(lane_set: _LaneSet, plans, stats: dict):
    """One causal round across every participating map/table object:
    one packed (D, 5, M) op upload + one vmapped `apply_map_round`, one
    packed slow_info fetch, one stacked slow-path scatter."""
    import jax.numpy as jnp
    from ..ops import ingest as K
    from ..ops.ingest import bucket

    docs = lane_set.docs
    D = len(docs)
    out_cap = max(max(p["out_cap"] for _, _, p in plans), lane_set.cap)
    lane_set.ensure(out_cap, stats)
    out_cap = max(out_cap, lane_set.cap)
    M = bucket(max(p["n_ops"] for _, _, p in plans), 128)
    ops = np.zeros((D, 5, M), np.int32)
    ops[:, K.MOP_KIND, :] = -1
    ops[:, K.MOP_SLOT, :] = out_cap
    conflict = _conflict_matrix(docs, out_cap)
    active = {}
    for doc, b, p in plans:
        d = lane_set.idx[id(doc)]
        active[d] = (doc, b, p)
        n = p["n_ops"]
        ops[d, K.MOP_KIND, :n] = p["kind"]
        ops[d, K.MOP_SLOT, :n] = p["slot"]
        ops[d, K.MOP_VALUE, :n] = p["value"]
        ops[d, K.MOP_WIN_ACTOR, :n] = p["win_actor"]
        ops[d, K.MOP_WIN_SEQ, :n] = p["win_seq"]
    _count(stats, "stacked_map_round")
    _note_h2d(stats, 2, ops.nbytes + conflict.nbytes)
    out = K.stacked_map_round(*lane_set.cols, jnp.asarray(ops),
                              jnp.asarray(conflict), out_cap=out_cap)
    lane_set.cols = out[:5]
    lane_set.cap = out_cap
    # ONE packed d2h fetch serves every object's slow residue
    _ts = obs.now() if obs.ENABLED else 0
    info = np.asarray(out[5])
    _count_sync(stats, "stacked_slow_info", _ts, d2h_bytes=info.nbytes)
    wbs = {}
    for d, (doc, b, p) in active.items():
        row = info[d][:, : p["n_ops"]]
        if row[0].any():
            idxs = np.nonzero(row[0])[0]
            wbs[d] = doc._resolve_slow_host(
                b, row[1][idxs], p["kind"][idxs], p["val64"][idxs],
                p["win_actor"][idxs], p["win_seq"][idxs],
                slot_cap=out_cap,
                reg_state=tuple(row[r][idxs] for r in range(2, 7)))
    if wbs:
        _stacked_slow_scatter(lane_set, wbs, out_cap, stats,
                              reg_offset=0)
    for _d, (doc, _b, _p) in active.items():
        doc._cap = out_cap
        doc._invalidate()


def _stacked_slow_scatter(lane_set: _LaneSet, wbs: dict, out_cap: int,
                          stats: dict, reg_offset: int):
    """Every doc's host-resolved (6, S_d) writeback, stacked to one
    (D, 6, S) upload + one vmapped scatter over the 5 register columns
    (`reg_offset` locates them inside the lane set's table tuple: 0 for
    map sets, 3 for the element tables)."""
    import jax.numpy as jnp
    from ..ops import ingest as K
    from ..ops.ingest import bucket

    D = len(lane_set.docs)
    S = bucket(max(wb.shape[1] for wb in wbs.values()), 64)
    stacked_wb = np.zeros((D, 6, S), np.int32)
    stacked_wb[:, 0, :] = out_cap            # padding rows: OOB drop
    for d, wb in wbs.items():
        stacked_wb[d, :, : wb.shape[1]] = wb
    regs = lane_set.cols[reg_offset: reg_offset + 5]
    _count(stats, "stacked_scatter")
    _note_h2d(stats, 1, stacked_wb.nbytes)
    out = K.stacked_scatter_registers(*regs, jnp.asarray(stacked_wb))
    lane_set.cols = (lane_set.cols[:reg_offset] + tuple(out)
                     + lane_set.cols[reg_offset + 5:])


def _wb_matrix(n_docs: int, wbs: dict, out_cap: int):
    """Stack per-doc (6, S_d) host-resolved writebacks into one
    (D, 6, S) upload (padding rows: OOB slot, dropped by the scatter)."""
    from ..ops.ingest import bucket

    S = bucket(max(wb.shape[1] for wb in wbs.values()), 64)
    m = np.zeros((n_docs, 6, S), np.int32)
    m[:, 0, :] = out_cap
    for d, wb in wbs.items():
        m[d, :, : wb.shape[1]] = wb
    return m


def _exec_fused_pass(map_set, map_plans, text_set, text_plans,
                     stats: dict):
    """ISSUE-17 megakernel pass: one causal round across EVERY
    participating object — both lanes — as ONE `fused_stacked_round`
    dispatch, then (when any object's round left slow residue) ONE
    combined `fused_scatter_registers` dispatch. Replaces
    `_exec_map_pass` + the per-shape-group `_exec_text_pass` sequence:
    the text lane runs the flag-free fused core, so shape groups (and
    the dense path's padded-window capacity inflation) disappear — every
    plan shares one uniform scatter-expansion program."""
    import jax.numpy as jnp
    from ..ops import fused_round as F
    from ..ops import ingest as K
    from ..ops.ingest import (DESC_ELEM_BASE, RES_NEW_SLOT, RES_SLOT,
                              bucket)

    mode = F.fused_mode()
    absent = F._absent()
    uploads = []

    # ---- map lane staging (the _exec_map_pass recipe, dispatch
    # deferred into the megakernel) ----
    with_map = bool(map_plans)
    m_ops = m_conflict = None
    m_active = {}
    map_cap = 1
    if with_map:
        m_docs = map_set.docs
        map_cap = max(max(p["out_cap"] for _, _, p in map_plans),
                      map_set.cap)
        map_set.ensure(map_cap, stats)
        map_cap = max(map_cap, map_set.cap)
        M = bucket(max(p["n_ops"] for _, _, p in map_plans), 128)
        m_ops = np.zeros((len(m_docs), 5, M), np.int32)
        m_ops[:, K.MOP_KIND, :] = -1
        m_ops[:, K.MOP_SLOT, :] = map_cap
        m_conflict = _conflict_matrix(m_docs, map_cap)
        for doc, b, p in map_plans:
            d = map_set.idx[id(doc)]
            m_active[d] = (doc, b, p)
            n = p["n_ops"]
            m_ops[d, K.MOP_KIND, :n] = p["kind"]
            m_ops[d, K.MOP_SLOT, :n] = p["slot"]
            m_ops[d, K.MOP_VALUE, :n] = p["value"]
            m_ops[d, K.MOP_WIN_ACTOR, :n] = p["win_actor"]
            m_ops[d, K.MOP_WIN_SEQ, :n] = p["win_seq"]
        uploads += [m_ops, m_conflict]

    # ---- text lane staging: ONE uniform group (no static shape flags,
    # no dense-window capacity inflation — the fused expand drops
    # padding through the scatter's OOB sentinel) ----
    with_text = bool(text_plans)
    desc_g = blob_g = res_g = conflict_g = touch_g = None
    t_active = {}
    text_cap = 1
    text_res = False
    if with_text:
        t_docs = text_set.docs
        Dt = len(t_docs)
        text_cap = max(max(p.out_cap for _, _, p in text_plans),
                       text_set.cap)
        text_set.ensure(text_cap, stats)
        text_cap = max(text_cap, text_set.cap)
        R = bucket(max([p.desc.shape[1] for _, _, p in text_plans
                        if p.desc is not None] + [1]), 64)
        N = bucket(max([p.blob.shape[0] for _, _, p in text_plans
                        if p.blob is not None] + [1]), 256)
        desc_g = np.zeros((Dt, 9, R), np.int32)
        desc_g[:, DESC_ELEM_BASE, :] = N
        blob_g = np.zeros((Dt, N), np.int32)
        Mt = bucket(max([p.res.shape[1] for _, _, p in text_plans
                         if p.res is not None] + [1]), 128)
        res_g = np.zeros((Dt, 8, Mt), np.int32)
        res_g[:, 0, :] = -1                      # RES_KIND padding
        res_g[:, RES_SLOT, :] = text_cap
        res_g[:, RES_NEW_SLOT, :] = text_cap
        conflict_g = _conflict_matrix(t_docs, text_cap)
        T = bucket(max([p.touch.shape[1] for _, _, p in text_plans
                        if p.touch is not None] + [1]), 64)
        touch_g = np.zeros((Dt, 3, T), np.int32)
        touch_g[:, 1:, :] = -1
        for doc, b, p in text_plans:
            d = text_set.idx[id(doc)]
            t_active[d] = (doc, b, p)
            if p.desc is not None:
                w = p.desc.shape[1]
                desc_g[d, :, :w] = p.desc
                pn = p.blob.shape[0]
                eb = desc_g[d, DESC_ELEM_BASE]
                eb[eb == pn] = N                 # re-pad the sentinel
                blob_g[d, :pn] = p.blob
            if p.res is not None:
                text_res = True
                w = p.res.shape[1]
                res_g[d, :, :w] = p.res
                for r in (RES_SLOT, RES_NEW_SLOT):
                    row = res_g[d, r]
                    row[row == p.out_cap] = text_cap
            if p.touch is not None:
                w = p.touch.shape[1]
                touch_g[d, :, :w] = p.touch
            doc._begin_round_host(p)
        uploads += [desc_g, blob_g, res_g, conflict_g, touch_g]

    # ---- THE dispatch of the pass ----
    _count(stats, "fused_stacked_round")
    _note_h2d(stats, len(uploads), sum(x.nbytes for x in uploads))
    args_map = ((tuple(map_set.cols) + (jnp.asarray(m_ops),
                                        jnp.asarray(m_conflict)))
                if with_map else (absent,) * 7)
    args_text = ((tuple(text_set.cols)
                  + (jnp.asarray(desc_g), jnp.asarray(blob_g),
                     jnp.asarray(res_g), jnp.asarray(conflict_g),
                     jnp.asarray(touch_g)))
                 if with_text else (absent,) * 14)
    out = F.fused_stacked_round(
        *args_map, *args_text, map_cap=map_cap, text_cap=text_cap,
        with_map=with_map, with_text=with_text, mode=mode)
    i = 0
    m_info_dev = t_info_dev = None
    if with_map:
        map_set.cols = out[:5]
        map_set.cap = map_cap
        m_info_dev = out[5]
        i = 6
    if with_text:
        text_set.cols = out[i: i + 9]
        text_set.cap = text_cap
        t_info_dev = out[i + 9]
        for _d, (doc, _b, p) in t_active.items():
            doc._cap = text_cap
            doc._finish_round_host(p)

    # ---- slow residue: one packed d2h fetch per lane, host resolution,
    # one COMBINED scatter dispatch ----
    map_wbs = {}
    if with_map:
        _ts = obs.now() if obs.ENABLED else 0
        info = np.asarray(m_info_dev)
        _count_sync(stats, "stacked_slow_info", _ts,
                    d2h_bytes=info.nbytes)
        for d, (doc, b, p) in m_active.items():
            row = info[d][:, : p["n_ops"]]
            if row[0].any():
                idxs = np.nonzero(row[0])[0]
                map_wbs[d] = doc._resolve_slow_host(
                    b, row[1][idxs], p["kind"][idxs], p["val64"][idxs],
                    p["win_actor"][idxs], p["win_seq"][idxs],
                    slot_cap=map_cap,
                    reg_state=tuple(row[r][idxs] for r in range(2, 7)))
    text_wbs = {}
    if text_res:
        _ts = obs.now() if obs.ENABLED else 0
        info = np.asarray(t_info_dev)
        _count_sync(stats, "stacked_slow_info", _ts,
                    d2h_bytes=info.nbytes)
        for d, (doc, b, p) in t_active.items():
            row = info[d][:, : p.n_res]
            if not p.n_res or not row[0].any():
                continue
            res_kind, res_vals, res_rank, res_seq = p.res_host
            idxs = np.nonzero(row[0])[0]
            text_wbs[d] = doc._resolve_slow_host(
                b, row[1][idxs], res_kind[idxs], res_vals[idxs],
                res_rank[idxs], res_seq[idxs], slot_cap=text_cap,
                reg_state=tuple(row[r][idxs] for r in range(2, 7)))
    if map_wbs or text_wbs:
        m_wb = (_wb_matrix(len(map_set.docs), map_wbs, map_cap)
                if map_wbs else None)
        t_wb = (_wb_matrix(len(text_set.docs), text_wbs, text_cap)
                if text_wbs else None)
        _count(stats, "fused_scatter")
        _note_h2d(stats, sum(1 for x in (m_wb, t_wb) if x is not None),
                  sum(x.nbytes for x in (m_wb, t_wb) if x is not None))
        out = F.fused_scatter_registers(
            *(tuple(map_set.cols) + (jnp.asarray(m_wb),)
              if map_wbs else (absent,) * 6),
            *(tuple(text_set.cols[3:8]) + (jnp.asarray(t_wb),)
              if text_wbs else (absent,) * 6),
            with_map=bool(map_wbs), with_text=bool(text_wbs))
        i = 0
        if map_wbs:
            map_set.cols = out[:5]
            i = 5
        if text_wbs:
            text_set.cols = (tuple(text_set.cols[:3]) + tuple(out[i: i + 5])
                             + tuple(text_set.cols[8:]))
    for _d, (doc, _b, _p) in m_active.items():
        doc._cap = map_cap
        doc._invalidate()
    for d in text_wbs:
        t_active[d][0]._invalidate()


def _text_shape(plan):
    expand = (("dense" if plan.dense else "sparse") if plan.n_runs
              else "none")
    return (expand, bool(plan.n_res), plan.touch is not None)


def _exec_text_pass(lane_set: _LaneSet, plans, stats: dict):
    """One causal round across every participating text/list object:
    per distinct static-flag shape, ONE shared (D, 9, R) descriptor
    template + (D, N) value blob + (D, 8, M) residual matrix upload and
    ONE vmapped `apply_mixed_round`; the whole round's slow residue is
    one packed fetch + one stacked scatter."""
    import jax.numpy as jnp
    from ..ops import ingest as K
    from ..ops.ingest import (DESC_ELEM_BASE, DESC_META, META_BASE_SLOT,
                              RES_NEW_SLOT, RES_SLOT, bucket)

    docs = lane_set.docs
    D = len(docs)
    for key in sorted(set(_text_shape(p) for _, _, p in plans)):
        expand_kind, with_res, with_touch = key
        group = [(doc, b, p) for doc, b, p in plans
                 if _text_shape(p) == key]
        out_cap = max(max(p.out_cap for _, _, p in group), lane_set.cap)
        lane_set.ensure(out_cap, stats)
        out_cap = max(out_cap, lane_set.cap)

        dummy = np.zeros((D, 1, 1), np.int32)
        desc_g = blob_g = res_g = touch_g = None
        conflict_g = None
        if expand_kind != "none":
            R = bucket(max(p.desc.shape[1] for _, _, p in group), 64)
            N = bucket(max(p.blob.shape[0] for _, _, p in group), 256)
            if expand_kind == "dense":
                # every lane (inactive included) writes its padded
                # window [n_elems+1, n_elems+1+N) — the DocSet
                # convention; capacity must cover all of them
                need = max(doc.n_elems for doc in docs) + 1 + N
                out_cap = max(out_cap, bucket(need))
            desc_g = np.zeros((D, 9, R), np.int32)
            desc_g[:, DESC_ELEM_BASE, :] = N
            for d, doc in enumerate(docs):
                desc_g[d, DESC_META, META_BASE_SLOT] = doc.n_elems + 1
            blob_g = np.zeros((D, N), np.int32)
        if with_res:
            M = bucket(max(p.res.shape[1] for _, _, p in group), 128)
            res_g = np.zeros((D, 8, M), np.int32)
            res_g[:, 0, :] = -1                      # RES_KIND padding
            res_g[:, RES_SLOT, :] = out_cap
            res_g[:, RES_NEW_SLOT, :] = out_cap
            conflict_g = _conflict_matrix(docs, out_cap)
        if with_touch:
            T = bucket(max(p.touch.shape[1] for _, _, p in group), 64)
            touch_g = np.zeros((D, 3, T), np.int32)
            touch_g[:, 1:, :] = -1

        active = {}
        for doc, b, p in group:
            d = lane_set.idx[id(doc)]
            active[d] = (doc, b, p)
            if p.desc is not None:
                w = p.desc.shape[1]
                desc_g[d, :, :w] = p.desc
                pn = p.blob.shape[0]
                eb = desc_g[d, DESC_ELEM_BASE]
                eb[eb == pn] = N                 # re-pad the sentinel
                blob_g[d, :pn] = p.blob
            if p.res is not None:
                w = p.res.shape[1]
                res_g[d, :, :w] = p.res
                for r in (RES_SLOT, RES_NEW_SLOT):
                    row = res_g[d, r]
                    row[row == p.out_cap] = out_cap
            if p.touch is not None:
                w = p.touch.shape[1]
                touch_g[d, :, :w] = p.touch
            doc._begin_round_host(p)

        _count(stats, "stacked_mixed_round")
        uploads = [x for x in (desc_g, blob_g, res_g, touch_g, conflict_g)
                   if x is not None]
        _note_h2d(stats, len(uploads), sum(x.nbytes for x in uploads))
        out = K.stacked_mixed_round(
            *lane_set.cols,
            jnp.asarray(desc_g) if desc_g is not None else dummy,
            jnp.asarray(blob_g) if blob_g is not None else dummy[:, 0],
            jnp.asarray(res_g) if res_g is not None else dummy,
            jnp.asarray(conflict_g) if conflict_g is not None
            else dummy[:, 0],
            jnp.asarray(touch_g) if touch_g is not None else dummy,
            out_cap=out_cap, expand_kind=expand_kind,
            with_res=with_res, with_touch=with_touch)
        lane_set.cols = out[:9]
        lane_set.cap = out_cap
        for _d, (doc, _b, p) in active.items():
            doc._cap = out_cap
            doc._finish_round_host(p)

        if with_res:
            _ts = obs.now() if obs.ENABLED else 0
            info = np.asarray(out[9])
            _count_sync(stats, "stacked_slow_info", _ts,
                        d2h_bytes=info.nbytes)
            wbs = {}
            for d, (doc, b, p) in active.items():
                row = info[d][:, : p.n_res]
                if not row[0].any():
                    continue
                res_kind, res_vals, res_rank, res_seq = p.res_host
                idxs = np.nonzero(row[0])[0]
                wbs[d] = doc._resolve_slow_host(
                    b, row[1][idxs], res_kind[idxs], res_vals[idxs],
                    res_rank[idxs], res_seq[idxs], slot_cap=out_cap,
                    reg_state=tuple(row[r][idxs] for r in range(2, 7)))
            if wbs:
                _stacked_slow_scatter(lane_set, wbs, out_cap, stats,
                                      reg_offset=3)
                for d in wbs:
                    active[d][0]._invalidate()


def _finalize(lane_set: _LaneSet, stats: dict):
    """Unstack the final stacked tables back onto each doc (one program)
    and seed every doc's host mirror from ONE packed d2h fetch, so the
    backend's diff emission right after the apply reads pure host
    state. For the text lane the fetch also carries every doc's RGA
    positions (one vmapped `stacked_linearize` program, riding the same
    packed transfer): emission's `_positions()` reads the seeded cache
    instead of paying one linearize dispatch + sync per object — the
    stacked path's residual per-object d2h, removed (ROADMAP 1a;
    asserted by `assert_round_budget`).

    The fetch (and the linearize's sort) is sliced to the LIVE slot
    prefix, not the table capacity: a serving population preallocates
    capacity headroom (INTERNALS §15), and shipping (D, K, cap) when
    max live slots is a fraction of cap made the packed fetch the
    stacked path's dominant per-apply cost. Host mirrors are rebuilt at
    full width with ZERO padding — strictly safer than the device
    tables' padding bytes, which dense-expansion rounds scribble on for
    inactive lanes; no consumer may read a slot past its live count
    either way (capture/save serialize live prefixes only, so bundle
    bytes are unchanged)."""
    if lane_set is None:
        return
    from ..ops import ingest as K
    from ..ops.ingest import bucket
    if lane_set.cols is None:
        # no round ran on this kind, but a pending remap must still
        # reach the device columns: gather + unstack applies it
        if not lane_set.remaps:
            return
        lane_set.ensure(lane_set.cap or 1, stats)
    _count(stats, "stacked_unstack")
    rows = K.unstack_rows(lane_set.cols)
    mirror_keys = (_MAP_MIRROR_KEYS if lane_set.kind == "map"
                   else _TEXT_MIRROR_KEYS)
    m_idx = [lane_set.keys.index(k) for k in mirror_keys]
    cap = lane_set.cap
    if lane_set.kind == "text":
        live = [doc.n_elems + 1 for doc in lane_set.docs]
    else:
        live = [len(doc.key_table) for doc in lane_set.docs]
    w = min(cap, bucket(max(live + [1]), 64))
    fetch_cols = [lane_set.cols[i][:, :w] for i in m_idx]
    if lane_set.kind == "text":
        import jax.numpy as jnp
        from ..ops.linearize import stacked_linearize
        n_el = np.asarray([doc.n_elems for doc in lane_set.docs],
                          np.int32)
        _count(stats, "stacked_linearize")
        _note_h2d(stats, 1, n_el.nbytes)
        fetch_cols.append(stacked_linearize(
            lane_set.cols[lane_set.keys.index("parent")][:, :w],
            lane_set.cols[lane_set.keys.index("ctr")][:, :w],
            lane_set.cols[lane_set.keys.index("actor")][:, :w],
            jnp.asarray(n_el)))
        stats["text_finalized"] += len(lane_set.docs)
    _count(stats, "stacked_mirror_fetch")
    _ts = obs.now() if obs.ENABLED else 0
    packed = np.asarray(K.stacked_pack_rows(*fetch_cols))
    _count_sync(stats, "stacked_mirror_fetch", _ts,
                d2h_bytes=packed.nbytes)
    for d, doc in enumerate(lane_set.docs):
        doc._dev = dict(zip(lane_set.keys, rows[d]))
        doc._cap = cap
        host = {}
        for i, k in enumerate(mirror_keys):
            if k in _BOOL_KEYS:
                full = np.zeros(cap, bool)
                full[:w] = packed[d, i].astype(bool)
            else:
                full = np.zeros(cap, np.int32)
                full[:w] = packed[d, i]
            host[k] = full
        doc._host = host
        if lane_set.kind == "text":
            doc._pos_cache = packed[d, len(mirror_keys)][: doc.n_elems + 1]
            stats["pos_seeded"] += 1
