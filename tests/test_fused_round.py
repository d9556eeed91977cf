"""ISSUE-17 fused-round kernels vs the XLA parity path (INTERNALS §21).

The fused tier (ops/fused_round.py, the AMTPU_FUSED_ROUNDS default) must
commit EXACTLY the XLA program path's state on every delivery — across
the full flag matrix (fused x AMTPU_STACKED_ROUNDS x AMTPU_COLUMNAR_PLAN),
randomized out-of-order/duplicated streams, and both the solo and stacked
executors — plus the TIGHTENED accounting contract: a fused stacked pass
is one megakernel dispatch + at most one combined scatter
(FUSED_PASS_DISPATCH_BUDGET), and the fused entry points recompile zero
times at steady state. The multi-channel Pallas scan that powers the
fused expansion is unit-tested here against numpy on both the interpret
and lax rungs.
"""

import random

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu.engine import stacked

from test_stacked_rounds import (canon, engine_state, make_board,
                                 rand_peer_changes)


@pytest.fixture(autouse=True)
def _small_gate(monkeypatch):
    """Engage the stacked path at test scale."""
    monkeypatch.setenv("AMTPU_STACKED_MIN_OPS", "1")


# ---------------------------------------------------------------------------
# multi_scan: the (K, N) multi-channel prefix sum (ops/scan_pallas.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(1, 5), (6, 513), (3, 1024), (6, 4096)])
def test_multi_scan_interpret_matches_numpy(shape):
    from automerge_tpu.ops.scan_pallas import multi_scan
    rng = np.random.default_rng(shape[0] * 10007 + shape[1])
    x = rng.integers(-5, 6, size=shape).astype(np.int32)
    got = np.asarray(multi_scan(x, interpret=True))
    assert np.array_equal(got, np.cumsum(x, axis=1))


def test_multi_scan_vmaps_under_interpret():
    """The megakernel runs multi_scan under jax.vmap over the doc axis;
    the batching rule must hold on the interpret rung cpu tier-1 uses."""
    import jax
    from automerge_tpu.ops.scan_pallas import multi_scan

    rng = np.random.default_rng(7)
    x = rng.integers(-3, 4, size=(4, 6, 700)).astype(np.int32)
    got = np.asarray(
        jax.jit(jax.vmap(lambda a: multi_scan(a, interpret=True)))(x))
    assert np.array_equal(got, np.cumsum(x, axis=2))


def test_cumsum_rows_lax_rung():
    from automerge_tpu.ops.fused_round import _cumsum_rows
    x = np.arange(12, dtype=np.int32).reshape(2, 6)
    assert np.array_equal(np.asarray(_cumsum_rows(x, "lax")),
                          np.cumsum(x, axis=1))


def test_fused_mode_ladder(monkeypatch):
    from automerge_tpu.ops import fused_round as F
    for rung in ("pallas", "interpret", "lax"):
        monkeypatch.setenv("AMTPU_FUSED_MODE", rung)
        assert F.fused_mode() == rung
    monkeypatch.delenv("AMTPU_FUSED_MODE")
    assert F.fused_mode() in ("pallas", "lax")   # backend-selected rung
    monkeypatch.setenv("AMTPU_FUSED_ROUNDS", "0")
    assert not F.fused_rounds_enabled()
    monkeypatch.delenv("AMTPU_FUSED_ROUNDS")
    assert F.fused_rounds_enabled()              # default ON


def test_fused_mode_raises_when_backend_probe_fails(monkeypatch):
    """The rung is never guessed: a backend that cannot be probed
    raises instead of silently running the lax rung."""
    from automerge_tpu.ops import fused_round as F

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.delenv("AMTPU_FUSED_MODE", raising=False)
    monkeypatch.setattr(F.jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="cannot probe"):
        F.fused_mode()


# ---------------------------------------------------------------------------
# direct kernel parity: the fused core vs the XLA comparator
# ---------------------------------------------------------------------------


def _synthetic_round(cap=64):
    """One mixed round's packed operands: a 3-element run, one residual
    insert + one contended set, the matching touch rows."""
    from automerge_tpu._common import KIND_INS, KIND_SET
    from automerge_tpu.ops import ingest as K

    R, N, M, T = 8, 8, 4, 4
    desc = np.zeros((9, R), np.int32)
    desc[K.DESC_ELEM_BASE] = N
    desc[K.DESC_HEAD_SLOT, 0] = 6
    desc[K.DESC_PARENT_SLOT, 0] = 2
    desc[K.DESC_CTR0, 0] = 10
    desc[K.DESC_ACTOR, 0] = 3
    desc[K.DESC_WIN_ACTOR, 0] = 1
    desc[K.DESC_WIN_SEQ, 0] = 4
    desc[K.DESC_ELEM_BASE, 0] = 0
    desc[K.DESC_HAS_VALUE, 0] = 1
    desc[K.DESC_META, K.META_N_ELEMS] = 3
    desc[K.DESC_META, K.META_BASE_SLOT] = 6
    desc[K.DESC_META, K.META_N_RUNS] = 1
    blob = np.zeros(N, np.int32)
    blob[:3] = [97, 98, 99]
    res = np.zeros((8, M), np.int32)
    res[K.RES_KIND] = -1
    res[K.RES_SLOT] = cap
    res[K.RES_NEW_SLOT] = cap
    res[K.RES_KIND, 0] = KIND_INS
    res[K.RES_SLOT, 0] = 3
    res[K.RES_NEW_SLOT, 0] = 9
    res[K.RES_CTR, 0] = 11
    res[K.RES_ACTOR, 0] = 4
    res[K.RES_KIND, 1] = KIND_SET
    res[K.RES_SLOT, 1] = 1
    res[K.RES_VALUE, 1] = 120
    res[K.RES_WIN_ACTOR, 1] = 2
    res[K.RES_WIN_SEQ, 1] = 7
    touch = np.zeros((3, T), np.int32)
    touch[1:] = -1
    touch[:, 0] = [2, 10, 3]
    touch[:, 1] = [3, 11, 4]
    conflict = np.full(4, cap, np.int32)
    return desc, blob, res, conflict, touch


def _fresh_tables(cap=64, n_elems=5):
    import jax.numpy as jnp

    parent = np.zeros(cap, np.int32)
    ctr = np.zeros(cap, np.int32)
    actor = np.zeros(cap, np.int32)
    value = np.zeros(cap, np.int32)
    has = np.zeros(cap, bool)
    wa = np.full(cap, -1, np.int32)
    ws = np.zeros(cap, np.int32)
    wc = np.zeros(cap, bool)
    chain = np.zeros(cap, bool)
    for s in range(1, n_elems + 1):
        parent[s] = s - 1
        ctr[s] = s
        actor[s] = 1
        value[s] = 64 + s
        has[s] = True
        wa[s] = 1
        ws[s] = s
        chain[s] = s > 1
    return tuple(jnp.asarray(a)
                 for a in (parent, ctr, actor, value, has, wa, ws, wc,
                           chain))


@pytest.mark.parametrize("mode", ["lax", "interpret"])
def test_fused_mixed_round_matches_apply_mixed_round(mode):
    from automerge_tpu.ops import fused_round as F
    from automerge_tpu.ops import ingest as K

    cap = 64
    desc, blob, res, conflict, touch = _synthetic_round(cap)
    xla = K.apply_mixed_round(
        *_fresh_tables(cap), desc, blob, res, conflict, touch,
        out_cap=cap, expand_kind="sparse", with_res=True, with_touch=True)
    fused = F.fused_mixed_round(
        *_fresh_tables(cap), desc, blob, res, conflict, touch,
        out_cap=cap, mode=mode)
    for a, b in zip(xla, fused):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fused_dense_round_matches_live_prefix():
    """Dense rounds run the uniform scatter expansion in the fused core;
    the XLA dense path writes padded run-tail garbage past the live
    region, so parity is over the live prefix (the only slots any
    reader — save, to_json, later rounds — ever consumes)."""
    from automerge_tpu.ops import fused_round as F
    from automerge_tpu.ops import ingest as K

    cap = 64
    desc, blob, _res, _conflict, _touch = _synthetic_round(cap)
    dd, db, dr, dc, dt = F.round_dummies(cap)
    xla = K.apply_mixed_round(
        *_fresh_tables(cap), desc, blob, K._dummy_i32(), K._dummy_i32(),
        K._dummy_i32(), out_cap=cap, expand_kind="dense", with_res=False,
        with_touch=False)
    fused = F.fused_mixed_round(
        *_fresh_tables(cap), desc, blob, dr, dc, dt, out_cap=cap,
        mode="lax")
    live = 5 + 3 + 1                       # base elems + run + head slot
    for a, b in zip(xla[:9], fused[:9]):
        assert np.array_equal(np.asarray(a)[:live], np.asarray(b)[:live])


def test_megakernel_lanes_match_stacked_comparators():
    """Both lanes of one `fused_stacked_round` dispatch equal the
    per-lane XLA programs (`stacked_map_round` + the fused solo core)."""
    import jax.numpy as jnp
    from automerge_tpu._common import KIND_SET
    from automerge_tpu.ops import fused_round as F
    from automerge_tpu.ops import ingest as K

    cap, mcap, D, M = 64, 32, 3, 4
    desc, blob, res, conflict, touch = _synthetic_round(cap)
    per_doc = [_fresh_tables(cap) for _ in range(D)]
    stk = tuple(jnp.stack([per_doc[i][k] for i in range(D)])
                for k in range(9))
    bcast = lambda a: np.broadcast_to(a, (D,) + a.shape).copy()
    mv = jnp.zeros((D, mcap), jnp.int32)
    mh = jnp.zeros((D, mcap), bool)
    mwa = jnp.full((D, mcap), -1, jnp.int32)
    mws = jnp.zeros((D, mcap), jnp.int32)
    mwc = jnp.zeros((D, mcap), bool)
    ops = np.zeros((D, 5, M), np.int32)
    ops[:, K.MOP_KIND] = -1
    ops[:, K.MOP_SLOT] = mcap
    ops[0, K.MOP_KIND, 0] = KIND_SET
    ops[0, K.MOP_SLOT, 0] = 2
    ops[0, K.MOP_VALUE, 0] = 42
    ops[0, K.MOP_WIN_ACTOR, 0] = 1
    ops[0, K.MOP_WIN_SEQ, 0] = 1
    mconf = np.full((D, 4), mcap, np.int32)

    out = F.fused_stacked_round(
        mv, mh, mwa, mws, mwc, ops, mconf, *stk, bcast(desc), bcast(blob),
        bcast(res), bcast(conflict), bcast(touch), map_cap=mcap,
        text_cap=cap, with_map=True, with_text=True, mode="lax")
    assert len(out) == 16                  # 5+1 map, 9+1 text

    xla_map = K.stacked_map_round(mv, mh, mwa, mws, mwc, ops, mconf,
                                  out_cap=mcap)
    for a, b in zip(xla_map, out[:6]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    solo = F.fused_mixed_round(*per_doc[0], desc, blob, res, conflict,
                               touch, out_cap=cap, mode="lax")
    for s, o in zip(solo, out[6:]):
        got = np.asarray(o)
        for d in range(D):
            assert np.array_equal(got[d], np.asarray(s))


# ---------------------------------------------------------------------------
# engine parity matrix: fused x AMTPU_STACKED_ROUNDS x AMTPU_COLUMNAR_PLAN
# ---------------------------------------------------------------------------


def _apply_flags(fused, stacked_flag, columnar, base, deliveries,
                 monkeypatch):
    monkeypatch.setenv("AMTPU_FUSED_ROUNDS", fused)
    monkeypatch.setenv("AMTPU_STACKED_ROUNDS", stacked_flag)
    monkeypatch.setenv("AMTPU_COLUMNAR_PLAN", columnar)
    doc = base
    for chunk in deliveries:
        doc = am.apply_changes(doc, chunk)
    return doc


@pytest.mark.parametrize("stacked_flag", ["1", "0"])
@pytest.mark.parametrize("columnar", ["1", "0"])
@pytest.mark.parametrize("seed", [0, 1])
def test_fused_parity_matrix(seed, columnar, stacked_flag, monkeypatch):
    """Randomized out-of-order/duplicated chunked streams: the fused and
    XLA paths commit byte-identical saves + to_json + full engine state
    in every (stacked, columnar) flag cell."""
    rng = random.Random(seed)
    base = make_board()
    per_peer = rand_peer_changes(rng, base, n_actors=10, chained=True)
    changes = [c for cs in per_peer for c in cs]
    rng.shuffle(changes)                        # out-of-order delivery
    for _ in range(2):                          # duplicated deliveries
        changes.insert(rng.randrange(len(changes) + 1),
                       dict(rng.choice(changes)))
    chunks = []
    i = 0
    while i < len(changes):
        n = rng.randrange(1, 8)
        chunks.append(changes[i: i + n])
        i += n
    d1 = _apply_flags("1", stacked_flag, columnar, base, chunks,
                      monkeypatch)
    d0 = _apply_flags("0", stacked_flag, columnar, base, chunks,
                      monkeypatch)
    assert canon(d1) == canon(d0)
    assert am.save(d1) == am.save(d0)
    assert engine_state(d1) == engine_state(d0)


def test_fused_interpret_rung_engine_parity(monkeypatch):
    """The interpret rung (the real Pallas kernel under the interpreter)
    commits the same state as the lax rung end-to-end."""
    rng = random.Random(9)
    base = make_board()
    changes = [c for cs in rand_peer_changes(rng, base, n_actors=6)
               for c in cs]
    monkeypatch.setenv("AMTPU_FUSED_MODE", "interpret")
    d_i = _apply_flags("1", "1", "1", base, [changes], monkeypatch)
    monkeypatch.setenv("AMTPU_FUSED_MODE", "lax")
    d_l = _apply_flags("1", "1", "1", base, [changes], monkeypatch)
    assert canon(d_i) == canon(d_l)
    assert am.save(d_i) == am.save(d_l)
    assert engine_state(d_i) == engine_state(d_l)


# ---------------------------------------------------------------------------
# the tightened accounting contract
# ---------------------------------------------------------------------------


def _merge_stats(monkeypatch, fused, n_actors=12):
    monkeypatch.setenv("AMTPU_FUSED_ROUNDS", fused)
    monkeypatch.setenv("AMTPU_STACKED_ROUNDS", "1")
    rng = random.Random(5)
    base = make_board()
    changes = [c for cs in rand_peer_changes(rng, base,
                                             n_actors=n_actors)
               for c in cs]
    stacked.LAST_STATS.clear()
    am.apply_changes(base, changes)
    assert stacked.LAST_STATS, "stacked path did not engage"
    return dict(stacked.LAST_STATS)


def test_fused_budget_tightened(monkeypatch):
    """A fused stacked apply fits APPLY_DISPATCH_BASE +
    FUSED_PASS_DISPATCH_BUDGET per pass — 4, not the XLA path's 16 —
    and `assert_round_budget` enforces the tightened bound."""
    s = _merge_stats(monkeypatch, fused="1")
    assert s["fused"] is True
    stacked.assert_round_budget(s)
    assert s["dispatches"] <= (stacked.APPLY_DISPATCH_BASE
                               + stacked.FUSED_PASS_DISPATCH_BUDGET
                               * max(1, s["passes"]))
    assert (stacked.FUSED_PASS_DISPATCH_BUDGET
            < stacked.PASS_DISPATCH_BUDGET)


def test_fused_budget_asserts_not_bypassed(monkeypatch):
    """The tightened bound actually bites: a fused stats dict inflated
    past the fused ceiling fails the assert even though it would fit
    the legacy 16/pass budget."""
    s = _merge_stats(monkeypatch, fused="1")
    bad = dict(s)
    bad["dispatches"] = (stacked.APPLY_DISPATCH_BASE
                         + stacked.FUSED_PASS_DISPATCH_BUDGET
                         * max(1, s["passes"]) + 1)
    with pytest.raises(AssertionError):
        stacked.assert_round_budget(bad)
    stacked.assert_round_budget({**bad, "fused": False})  # legacy bound


def test_unfused_path_unchanged(monkeypatch):
    """AMTPU_FUSED_ROUNDS=0 runs the verbatim XLA program path: no fused
    label in the apply, legacy budget."""
    s = _merge_stats(monkeypatch, fused="0")
    assert s["fused"] is False
    stacked.assert_round_budget(s)


def test_fused_dispatch_count_object_independent(monkeypatch):
    """The megakernel collapse is object-count independent AND strictly
    cheaper per pass than the XLA path on the same workload."""
    s_small = _merge_stats(monkeypatch, fused="1", n_actors=6)
    s_big = _merge_stats(monkeypatch, fused="1", n_actors=18)
    per_pass_small = s_small["dispatches"] / max(1, s_small["passes"])
    per_pass_big = s_big["dispatches"] / max(1, s_big["passes"])
    assert per_pass_big <= per_pass_small + 1e-9
    s_xla = _merge_stats(monkeypatch, fused="0", n_actors=18)
    assert s_big["dispatches"] < s_xla["dispatches"]


def test_fused_steady_state_zero_recompiles(monkeypatch):
    """The fused entry points compile once per shape: re-applying an
    identically-shaped delivery recompiles NOTHING (the cfg17 in-run
    assert, pinned here at test scale)."""
    from automerge_tpu.obs import device_truth as dt

    monkeypatch.setenv("AMTPU_FUSED_ROUNDS", "1")
    monkeypatch.setenv("AMTPU_STACKED_ROUNDS", "1")

    def run():
        rng = random.Random(11)
        base = make_board()
        changes = [c for cs in rand_peer_changes(rng, base, n_actors=8)
                   for c in cs]
        am.apply_changes(base, changes)

    run()                                   # warmup compiles
    with dt.steady_state() as ss:
        run()                               # identical shapes
    fused_recompiles = {k: v for k, v in ss.recompiles.items()
                        if k[0].startswith("fused_")}
    assert fused_recompiles == {}


def test_doc_opt_out_pins_xla_path(monkeypatch):
    """A doc class with fused_rounds=False keeps the whole apply on the
    XLA comparator path even with the env gate on."""
    from automerge_tpu import frontend as Frontend

    monkeypatch.setenv("AMTPU_FUSED_ROUNDS", "1")
    monkeypatch.setenv("AMTPU_STACKED_ROUNDS", "1")
    rng = random.Random(3)
    base = make_board()
    changes = [c for cs in rand_peer_changes(rng, base, n_actors=6)
               for c in cs]
    core = Frontend.get_backend_state(base)._core
    docs = [core.root] + list(core.objects.values())
    try:
        for w in docs:
            w.doc.fused_rounds = False
        stacked.LAST_STATS.clear()
        am.apply_changes(base, changes)
        assert stacked.LAST_STATS.get("fused") is False
    finally:
        for w in docs:
            del w.doc.fused_rounds
