"""Fused round kernels: one device program per causal round (ISSUE 17).

PR-15's roofline attribution split `device_wait_s` into per-kernel shares
and put `apply_mixed_round`, the stacked mixed/map round programs, and the
scatter paths at the top of the queue. This module collapses that queue:
the per-round program *sequence* (expand -> residual -> chain breaks, then
a separate map-lane program, then per-lane scatters) becomes

  - `fused_mixed_round`   — the solo-doc text round, ONE program with no
    static shape flags: the expand/residual/touch phases of
    `_apply_mixed_round` run unconditionally over padding-convention
    no-ops, so every round of every shape shares one trace per capacity
    bucket instead of one per (expand_kind, with_res, with_touch) cell.
  - `fused_stacked_round` — the megakernel: BOTH stacked lanes (every
    map/table object's round AND every text/list object's round) in one
    dispatch, replacing `stacked_map_round` + one `stacked_mixed_round`
    per shape group.
  - `fused_scatter_registers` — both lanes' host-resolved slow writebacks
    as one dispatch, replacing two `stacked_scatter_registers` launches.

The expansion's (6, N) boundary-delta cumsum — the only multi-pass XLA
reduction left on the commit path — lowers through the mode ladder
(`fused_mode()`): "pallas" runs `ops/scan_pallas.multi_scan` (one VMEM
tile pass, SMEM carries) on TPU, "interpret" runs the same kernel under
the Pallas interpreter so cpu tier-1 exercises the real kernel, "lax"
composes `jnp.cumsum` for backends with no Mosaic at all. Everything else
in the fused bodies is ordinary lax that XLA fuses around the scan.

Parity contract (the PR-5/7 discipline): the XLA program path —
`apply_mixed_round`, `stacked_map_round`, `stacked_mixed_round`,
`stacked_scatter_registers` — stays verbatim behind `AMTPU_FUSED_ROUNDS=0`
as the byte-identical comparator. The fused core reorders NOTHING
observable: run-head chain breaks move from the dense expand into the
uniform expand (sparse plans' touch matrices already cover the same
(parent, ctr, actor) triples, and breaks are sticky Lamport maxima, so
applying them from the descriptor too is idempotent), and padding
conventions (kind=-1 residual rows, slot=out_cap sentinels, p_slot=0
touches) make absent phases exact no-ops.
"""

from __future__ import annotations

import os
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from .ingest import (  # noqa: F401
    DESC_ELEM_BASE, DESC_META, META_BASE_SLOT, META_N_ELEMS, META_N_RUNS,
    MOP_KIND, MOP_SLOT, MOP_VALUE, MOP_WIN_ACTOR, MOP_WIN_SEQ,
    RES_KIND, RES_NEW_SLOT, RES_SLOT,
    _TABLE_ARGNUMS, _apply_map_round, _apply_residual_packed,
    _break_chains_core, _break_chains_packed, _jit_pair,
    _materialize_core, _materialize_core_planned, _scatter_rows_9,
    _scatter_registers_packed, _slice_live, _unpack_desc,
)

_MODES = ("pallas", "interpret", "lax")


def fused_rounds_enabled() -> bool:
    """AMTPU_FUSED_ROUNDS gate, default ON (read per call so tests and
    the A/B harness can flip it per leg)."""
    return os.environ.get("AMTPU_FUSED_ROUNDS", "1") != "0"


def fused_mode() -> str:
    """The scan-lowering rung: AMTPU_FUSED_MODE when explicitly set
    ("pallas" | "interpret" | "lax"), else "pallas" on TPU and "lax"
    elsewhere. "lax" is the default off-chip rung because the Pallas
    interpreter pays a per-tile Python dispatch tax that would slow the
    cpu tier-1 suite; the interpret rung is exercised by the targeted
    parity tests instead. A backend that cannot be probed raises: the
    rung is never guessed."""
    m = os.environ.get("AMTPU_FUSED_MODE", "")
    if m in _MODES:
        return m
    try:
        backend = jax.default_backend()
    except RuntimeError as exc:
        raise RuntimeError(
            "fused_mode: cannot probe the JAX backend to pick the scan "
            f"rung ({exc}); set AMTPU_FUSED_MODE to choose one") from exc
    return "pallas" if backend == "tpu" else "lax"


def _cumsum_rows(x, mode: str):
    """Row-wise inclusive prefix sum of (K, N) int32 via the mode ladder."""
    if mode == "lax":
        return jnp.cumsum(x, axis=1)
    from .scan_pallas import multi_scan
    return multi_scan(x, interpret=(mode != "pallas"))


def _fused_expand(tables, desc, blob, *, out_cap: int, mode: str):
    """`expand_runs` with the (6, N) column cumsum lowered through the
    mode ladder, plus the dense path's fused run-head chain breaks
    applied uniformly from the descriptor (idempotent for sparse plans —
    their touch matrices carry the same run-head triples)."""
    (run_head_slot, run_parent_slot, run_ctr0, run_actor, run_win_actor,
     run_win_seq, run_elem_base, run_has_value) = _unpack_desc(desc)
    n_run_elems = desc[DESC_META, META_N_ELEMS]
    R = run_head_slot.shape[0]
    N = blob.shape[0]

    run_len_prev = run_elem_base - jnp.concatenate(
        [jnp.zeros(1, run_elem_base.dtype), run_elem_base[:-1]])
    prev = lambda a: jnp.concatenate([jnp.zeros(1, a.dtype), a[:-1]])
    first = jnp.arange(R, dtype=jnp.int32) == 0
    d_ctr = jnp.where(first, run_ctr0,
                      run_ctr0 - (prev(run_ctr0) + run_len_prev - 1))
    d_slot = jnp.where(first, run_head_slot,
                       run_head_slot
                       - (prev(run_head_slot) + run_len_prev - 1))
    wa_v = jnp.where(run_has_value, run_win_actor, -1)
    ws_v = jnp.where(run_has_value, run_win_seq, 0)
    has_v = run_has_value.astype(jnp.int32)
    d_actor = jnp.where(first, run_actor, run_actor - prev(run_actor))
    d_wa = jnp.where(first, wa_v, wa_v - prev(wa_v))
    d_ws = jnp.where(first, ws_v, ws_v - prev(ws_v))
    d_has = jnp.where(first, has_v, has_v - prev(has_v))

    deltas = jnp.ones((6, N), jnp.int32)
    deltas = deltas.at[2:].set(0)
    deltas = deltas.at[:, run_elem_base].set(
        jnp.stack([d_ctr, d_slot, d_actor, d_wa, d_ws, d_has]),
        mode="drop")                      # padding runs: elem_base == N
    cols = _cumsum_rows(deltas, mode)
    ctr_col, slot_col = cols[0], cols[1]

    j = jnp.arange(N, dtype=jnp.int32)
    live = j < n_run_elems
    is_start = jnp.zeros(N, bool).at[run_elem_base].set(True, mode="drop")
    tgt = jnp.where(live, slot_col, out_cap)    # OOB sentinel drops padding
    parent_col = (slot_col - 1).at[run_elem_base].set(
        run_parent_slot, mode="drop")
    has_col = (cols[5] > 0) & live

    tables = _scatter_rows_9(
        tables, tgt,
        (parent_col, ctr_col, cols[2], blob.astype(jnp.int32), has_col,
         jnp.where(has_col, cols[3], -1), jnp.where(has_col, cols[4], 0),
         jnp.zeros(N, jnp.int32), live & ~is_start),
        out_cap)

    n_runs = desc[DESC_META, META_N_RUNS]
    live_r = jnp.arange(R, dtype=jnp.int32) < n_runs
    chain_n = _break_chains_core(
        tables[8], tables[0], tables[1], tables[2],
        jnp.where(live_r, run_parent_slot, 0),
        jnp.where(live_r, run_ctr0, -1),
        jnp.where(live_r, run_actor, -1))
    return tables[:8] + (chain_n,)


def _fused_mixed_core(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, desc, blob, res, conflict_slots, touch,
    *, out_cap: int, mode: str,
):
    """The flag-free mixed round: every phase of `_apply_mixed_round`
    runs unconditionally — absent phases ride padding conventions (a
    runless descriptor expands nothing, kind=-1 residual rows are
    no-ops, p_slot=0 touches break nothing) — so one trace per capacity
    bucket covers every round shape. Returns the 9 tables + slow_info
    (always: callers skip the d2h fetch when the round staged no
    residuals)."""
    tables = (parent, ctr, actor, value, has_value, win_actor, win_seq,
              win_counter, chain)
    tables = _fused_expand(tables, desc, blob, out_cap=out_cap, mode=mode)
    out = _apply_residual_packed(*tables, res, conflict_slots,
                                 out_cap=out_cap)
    tables, slow_info = out[:9], out[9]
    tables = tables[:8] + (_break_chains_packed(
        tables[8], tables[0], tables[1], tables[2], touch),)
    return tables + (slow_info,)


fused_mixed_round, fused_mixed_round_donated = _jit_pair(
    _fused_mixed_core, _TABLE_ARGNUMS, ("out_cap", "mode"))


def _fused_commit_core(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, desc, blob, *, out_cap: int, S: int, as_u8: bool, L: int,
    mode: str,
):
    """The ring-commit megakernel (the PR-17 follow-on): the pipelined
    ingestor's steady-state commit — the common-case dense merge round
    END TO END, expansion (scan lowered through the mode ladder) plus
    the codes-only materialization — as ONE fused-tier program. The XLA
    pair (`merge_and_materialize_dense*`, ops/ingest.py) stays verbatim
    behind AMTPU_FUSED_ROUNDS=0 as the byte-identical comparator."""
    tables = _fused_expand(
        (parent, ctr, actor, value, has_value, win_actor, win_seq,
         win_counter, chain), desc, blob, out_cap=out_cap, mode=mode)
    n_elems = (desc[DESC_META, META_BASE_SLOT]
               + desc[DESC_META, META_N_ELEMS] - 1)
    cols = _slice_live((tables[0], tables[1], tables[2], tables[3],
                        tables[4], tables[8]), L)
    codes, scalars = _materialize_core(*cols, n_elems, S, with_pos=False,
                                       as_u8=as_u8)
    return tables + (codes, scalars)


fused_commit_round, fused_commit_round_donated = _jit_pair(
    _fused_commit_core, _TABLE_ARGNUMS, ("out_cap", "S", "as_u8", "L",
                                         "mode"))


def _fused_commit_planned_core(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, desc, blob, segplan, *, out_cap: int, S: int, as_u8: bool,
    L: int, mode: str,
):
    """`_fused_commit_core` with the materialization's segment structure
    staged from the host plan — no device sort, no pointer doubling;
    the fused-tier twin of `merge_and_materialize_dense_planned`."""
    tables = _fused_expand(
        (parent, ctr, actor, value, has_value, win_actor, win_seq,
         win_counter, chain), desc, blob, out_cap=out_cap, mode=mode)
    n_elems = (desc[DESC_META, META_BASE_SLOT]
               + desc[DESC_META, META_N_ELEMS] - 1)
    cols = _slice_live((tables[0], tables[1], tables[2], tables[3],
                        tables[4], tables[8]), L)
    codes, scalars = _materialize_core_planned(
        *cols, n_elems, segplan, S, with_pos=False, as_u8=as_u8)
    return tables + (codes, scalars)


fused_commit_round_planned, fused_commit_round_planned_donated = _jit_pair(
    _fused_commit_planned_core, _TABLE_ARGNUMS,
    ("out_cap", "S", "as_u8", "L", "mode"))


def _fused_stacked_round(
    # map lane: 5 stacked register tables + (D, 5, M) ops + (D, K) conflicts
    m_value, m_has, m_wa, m_ws, m_wc, m_ops, m_conflict,
    # text lane: 9 stacked element tables + stacked round operands
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, desc, blob, res, t_conflict, touch,
    *, map_cap: int, text_cap: int, with_map: bool, with_text: bool,
    mode: str,
):
    """The megakernel: one causal round of EVERY participating object —
    both lanes — as ONE device program. Absent lanes ride `_absent()`
    placeholders (static flags dead-code them). Returns the map lane's
    5 tables + (D, 7, M) slow_info when `with_map`, then the text lane's
    9 tables + (D, 7, M) slow_info when `with_text`."""
    out = ()
    if with_map:
        def one_map(v, h, wa, ws, wc, o, cs):
            return _apply_map_round(
                v, h, wa, ws, wc, o[MOP_KIND].astype(jnp.int8), o[MOP_SLOT],
                o[MOP_VALUE], o[MOP_WIN_ACTOR], o[MOP_WIN_SEQ], cs,
                out_cap=map_cap)
        out += jax.vmap(one_map)(m_value, m_has, m_wa, m_ws, m_wc, m_ops,
                                 m_conflict)
    if with_text:
        fn = partial(_fused_mixed_core, out_cap=text_cap, mode=mode)
        out += jax.vmap(fn)(parent, ctr, actor, value, has_value, win_actor,
                            win_seq, win_counter, chain, desc, blob, res,
                            t_conflict, touch)
    return out


fused_stacked_round = jax.jit(
    _fused_stacked_round,
    static_argnames=("map_cap", "text_cap", "with_map", "with_text",
                     "mode"))


def _fused_scatter_registers(
    m_value, m_has, m_wa, m_ws, m_wc, m_wb,
    t_value, t_has, t_wa, t_ws, t_wc, t_wb,
    *, with_map: bool, with_text: bool,
):
    """Both lanes' host-resolved slow-register writebacks as ONE program
    (two (D, 6, S) uploads, one dispatch) — replaces the per-lane
    `stacked_scatter_registers` launches."""
    out = ()
    if with_map:
        out += jax.vmap(_scatter_registers_packed)(
            m_value, m_has, m_wa, m_ws, m_wc, m_wb)
    if with_text:
        out += jax.vmap(_scatter_registers_packed)(
            t_value, t_has, t_wa, t_ws, t_wc, t_wb)
    return out


fused_scatter_registers = jax.jit(
    _fused_scatter_registers, static_argnames=("with_map", "with_text"))


# --- padding operands -------------------------------------------------------

_ABSENT = None
_DUMMIES: dict = {}


def _absent():
    """Shared placeholder for a dead lane's traced operands of
    `fused_stacked_round` (static flags cut the branches; a fresh upload
    per call would still pay a transfer)."""
    global _ABSENT
    if _ABSENT is None:
        _ABSENT = jnp.zeros((1, 1), jnp.int32)
    return _ABSENT


def round_dummies(out_cap: int):
    """Cached no-op operands for the phases a solo round did not stage:
    (desc, blob, res, conflict_slots, touch). Each follows the padding
    convention its phase treats as absent — a runless descriptor with
    the elem_base sentinel, kind=-1/slot=out_cap residual rows, an
    all-out_cap conflict vector, p_slot=0 touch rows."""
    d = _DUMMIES.get(out_cap)
    if d is None:
        desc = np.zeros((9, 1), np.int32)
        desc[DESC_ELEM_BASE, 0] = 1       # == blob length: padding sentinel
        res = np.zeros((8, 1), np.int32)
        res[RES_KIND] = -1
        res[RES_SLOT] = out_cap
        res[RES_NEW_SLOT] = out_cap
        d = (jnp.asarray(desc), jnp.zeros(1, jnp.int32), jnp.asarray(res),
             jnp.full(1, out_cap, jnp.int32), jnp.zeros((3, 1), jnp.int32))
        _DUMMIES[out_cap] = d
    return d


# --- device-truth registry (obs/device_truth.py; INTERNALS §19/§21) --------
#
# Same discipline as ops/ingest.py: the kernels the engine DISPATCHES are
# re-bound to instrumented handles; the building blocks that only run
# inside them (_fused_expand, _fused_mixed_core, multi_scan) are not.

from ..obs import device_truth as _device_truth  # noqa: E402

fused_mixed_round, fused_mixed_round_donated = \
    _device_truth.instrument_pair(
        (fused_mixed_round, fused_mixed_round_donated), "fused_mixed_round")
fused_commit_round, fused_commit_round_donated = \
    _device_truth.instrument_pair(
        (fused_commit_round, fused_commit_round_donated),
        "fused_commit_round")
fused_commit_round_planned, fused_commit_round_planned_donated = \
    _device_truth.instrument_pair(
        (fused_commit_round_planned, fused_commit_round_planned_donated),
        "fused_commit_round_planned")
fused_stacked_round = _device_truth.instrument(fused_stacked_round,
                                               "fused_stacked_round")
fused_scatter_registers = _device_truth.instrument(
    fused_scatter_registers, "fused_scatter_registers")
