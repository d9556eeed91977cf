"""Device-side batch ingestion for the columnar text/list engine.

The reference applies ops one at a time (`applyOps`/`applyInsert`/
`applyAssign`, /root/reference/backend/op_set.js:63-283), with an
order-statistic skip list for elemId<->index queries. Here one causally-ready
*round* of changes — often millions of ops — updates the device tables in at
most two jitted XLA programs, all int32/int8/bool (the TPU emulates int64;
int64 sorts/searches run emulated, severalfold slower - design
assumption, `profile_bench.py --int64` measures it):

- **expand_runs**: the bulk path. Typing runs (ins+set chains with
  consecutive counters) arrive as ~20-byte descriptors plus a value blob;
  the kernel expands them into element-table rows with one cummax (run-of-
  element) and a handful of scatters — O(elements) at HBM bandwidth, no
  sort, no searchsorted. Host<->device traffic is bytes-per-run, not
  bytes-per-op.
- **apply_residual**: everything irregular (bare inserts, dels, incs,
  assigns to old elements, pooled values). References are pre-resolved to
  slot numbers on the host (engine/host_index.py), so the kernel is pure
  scatters: place inserts, run the LWW register fast path, and flag the
  genuinely contended registers into a `slow` mask the host resolves
  against its conflict/value-pool state — exactly the reference's
  applyAssign semantics, partitioned so the device does the common case.

`materialize_text` turns the tables into list positions + visible values via
the chain-condensed RGA linearization (see ops/linearize.py).

All shapes are static; callers bucket sizes with `bucket()` so XLA retraces
rarely.

**Buffer donation (the streaming tier, INTERNALS §9).** The commit-path
kernels that *replace* the document tables (`expand_runs*_packed`,
`apply_residual_packed`, `merge_and_materialize_dense*`,
`break_chains_packed`, `scatter_registers_packed`) each have a
`*_donated` twin jitted with ``donate_argnums`` over the table operands:
XLA may then write outputs in place of the inputs, so a K-deep pipeline
ring's steady-state device allocation is flat (one table set + staged
inputs) instead of accumulating K generations of dead tables until the
allocator catches up. Donation is a caller CONTRACT, not a hint the
engine can ignore: a donated input buffer is dead after the call, so the
engine only selects the donated twins when the document has opted in
(``CausalDeviceDoc.donate_buffers`` — the checkpoint writer's zero-copy
grab holds raw table references and is incompatible; see
checkpoint/engine_codec.grab).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .._common import KIND_DEL, KIND_INC, KIND_INS, KIND_SET  # noqa: F401


def bucket(n: int, minimum: int = 256) -> int:
    """Half-octave size buckets (2^k and 3·2^(k-1)): <=25% padding waste."""
    cap = minimum
    while cap < n:
        cap = cap * 3 // 2 if (cap & (cap - 1)) == 0 else (cap // 3) * 4
    return cap


# the 9 element-table operands every commit-path kernel leads with
_TABLE_ARGNUMS = tuple(range(9))
_REG_ARGNUMS = tuple(range(5))      # the 5 register tables

_DONATION = None
_DONATION_FILTERED = False


def donation_enabled() -> bool:
    """Whether the *_donated kernel twins are usable on this backend.

    Donation is an aliasing optimization; results are identical either
    way, but backends that cannot alias emit a per-compile warning which
    this gate suppresses once. ``AMTPU_DONATE=0/1`` forces the answer
    (tests force 1 on cpu to exercise the donated code path); the
    default is on for every non-cpu backend — exactly the platforms
    where steady-state HBM headroom matters."""
    global _DONATION, _DONATION_FILTERED
    if _DONATION is None:
        v = os.environ.get("AMTPU_DONATE", "")
        if v in ("0", "1"):
            _DONATION = v == "1"
        else:
            _DONATION = jax.default_backend() != "cpu"
    if _DONATION and not _DONATION_FILTERED:
        # registered ONCE: this sits on the per-committed-round hot path,
        # and filterwarnings() invalidates the process-wide warning cache
        # on every call
        _DONATION_FILTERED = True
        import warnings
        # backends that cannot alias a particular donated operand
        # (shape-growing rounds; cpu) warn per compile — donation is
        # best-effort there by design
        warnings.filterwarnings("ignore", message=".*onated buffer.*")
    return _DONATION


def buffers_consumed(arrays) -> bool:
    """True iff any of `arrays` was consumed by a donated call — the
    poison-or-recover decision after a raising donated commit (a
    trace/compile failure consumes nothing and must stay retryable)."""
    return any(getattr(a, "is_deleted", lambda: False)() for a in arrays)


def _jit_pair(fn, donate_argnums, static_argnames=()):
    """(plain, donated) jit twins of one kernel implementation."""
    kw = {"static_argnames": static_argnames} if static_argnames else {}
    return (jax.jit(fn, **kw),
            jax.jit(fn, donate_argnums=donate_argnums, **kw))


def _ext(a, fill, out_cap):
    C = a.shape[0]
    if C >= out_cap:
        return a
    return jnp.concatenate([a, jnp.full(out_cap - C, fill, a.dtype)])


@partial(jax.jit, static_argnames=("out_cap",))
def expand_runs(
    # document tables, capacity C
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain,
    # run descriptors, capacity R (padding: len=0, elem_base=N sentinel)
    run_head_slot, run_parent_slot, run_ctr0, run_actor, run_win_actor,
    run_win_seq, run_elem_base, run_has_value,
    # value blob in run-element order, capacity N
    blob,
    n_run_elems,                  # scalar i32: live prefix of the blob
    *, out_cap: int,
):
    """Expand run descriptors into element-table rows (see module docstring).

    Element j of run r lands at slot run_head_slot[r]+j with parent
    slot-1 (or run_parent_slot for j=0), counter run_ctr0[r]+j, and — when
    run_has_value[r] — an LWW register won by the run's change. Interior
    elements start with their chain bit set (they are their predecessor's
    only — hence Lamport-max — child at insert time; `break_chains` clears
    bits as concurrent children arrive)."""
    R = run_head_slot.shape[0]
    N = blob.shape[0]

    # GATHER-FREE, like `expand_runs_dense`: every per-element column —
    # including the target SLOT itself — is piecewise affine over runs
    # (constant or +1 per element, resetting at run starts), so instead
    # of `table[run_of]` gathers the columns come from one (6, N)
    # boundary-delta cumsum; the only O(N)-indexed operation left is the
    # final single stacked (C, 9) scatter (shared index vector across
    # all nine columns — scatter cost is per-INDEX, so one pass instead
    # of nine is a ~3.4x measured win at residual-round shapes on cpu).
    run_len_prev = run_elem_base - jnp.concatenate(
        [jnp.zeros(1, run_elem_base.dtype), run_elem_base[:-1]])
    prev = lambda a: jnp.concatenate([jnp.zeros(1, a.dtype), a[:-1]])
    first = jnp.arange(R, dtype=jnp.int32) == 0
    # +1-per-element columns: reset to (ctr0, head_slot) at run starts
    d_ctr = jnp.where(first, run_ctr0,
                      run_ctr0 - (prev(run_ctr0) + run_len_prev - 1))
    d_slot = jnp.where(first, run_head_slot,
                       run_head_slot
                       - (prev(run_head_slot) + run_len_prev - 1))
    # piecewise-constant columns: value deltas at run starts
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
    cols = jnp.cumsum(deltas, axis=1)
    ctr_col, slot_col = cols[0], cols[1]

    j = jnp.arange(N, dtype=jnp.int32)
    live = j < n_run_elems
    is_start = jnp.zeros(N, bool).at[run_elem_base].set(True, mode="drop")
    tgt = jnp.where(live, slot_col, out_cap)    # OOB sentinel drops padding
    # parent: slot-1 everywhere except run heads (R-sized scatter)
    parent_col = (slot_col - 1).at[run_elem_base].set(
        run_parent_slot, mode="drop")
    has_col = (cols[5] > 0) & live

    return _scatter_rows_9(
        (parent, ctr, actor, value, has_value, win_actor, win_seq,
         win_counter, chain),
        tgt,
        (parent_col, ctr_col, cols[2], blob.astype(jnp.int32), has_col,
         jnp.where(has_col, cols[3], -1), jnp.where(has_col, cols[4], 0),
         jnp.zeros(N, jnp.int32), live & ~is_start),
        out_cap)


def _scatter_rows_9(tables, idx, updates, out_cap: int):
    """Write 9 aligned element-table rows at `idx` as ONE (C, 9) scatter
    (shared index vector; OOB `idx` drops). `tables` / `updates` follow
    the canonical column order (parent, ctr, actor, value, has_value,
    win_actor, win_seq, win_counter, chain); bool columns are carried as
    int32 and cast back on the way out."""
    parent, ctr, actor, value, has_value, win_actor, win_seq, \
        win_counter, chain = tables
    tbl = jnp.stack([
        _ext(parent, 0, out_cap), _ext(ctr, 0, out_cap),
        _ext(actor, 0, out_cap), _ext(value, 0, out_cap),
        _ext(has_value, False, out_cap).astype(jnp.int32),
        _ext(win_actor, -1, out_cap), _ext(win_seq, 0, out_cap),
        _ext(win_counter, False, out_cap).astype(jnp.int32),
        _ext(chain, False, out_cap).astype(jnp.int32)], axis=1)
    upd = jnp.stack([u.astype(jnp.int32) for u in updates], axis=1)
    out = tbl.at[idx].set(upd, mode="drop")
    return (out[:, 0], out[:, 1], out[:, 2], out[:, 3],
            out[:, 4].astype(bool), out[:, 5], out[:, 6],
            out[:, 7].astype(bool), out[:, 8].astype(bool))


@partial(jax.jit, static_argnames=("out_cap",))
def expand_runs_dense(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain,
    run_head_slot, run_parent_slot, run_ctr0, run_actor, run_win_actor,
    run_win_seq, run_elem_base, run_has_value,
    blob, n_run_elems, base_slot,
    *, out_cap: int,
):
    """`expand_runs` for the common case where the round mints no residual
    inserts, so the new elements occupy one contiguous slot window
    [base_slot, base_slot + n_run_elems). The element columns are computed
    densely in run-element space and written with dynamic_update_slice —
    contiguous stores instead of 9 scatters. Caller guarantees
    base_slot + N <= out_cap (N = padded blob length).

    GATHER-FREE: every column is piecewise affine over runs (constant, or
    +1 per element), so instead of `table[run_of]` gathers — ~140M elem/s
    on v5e, they dominated the merge at bench scale — each column is a
    run-boundary delta scatter (R elements) + a shared prefix sum: one
    (5, N) cumsum and a handful of R-sized ops, all at vector throughput.
    Slots past n_run_elems inside the padded window receive run-tail
    garbage exactly as before (they are beyond n_elems until a later round
    dus-overwrites them)."""
    R = run_head_slot.shape[0]
    N = blob.shape[0]

    # per-run deltas against the previous run's final element value
    run_len_prev = run_elem_base - jnp.concatenate(
        [jnp.zeros(1, run_elem_base.dtype), run_elem_base[:-1]])
    prev = lambda a: jnp.concatenate([jnp.zeros(1, a.dtype), a[:-1]])
    first = jnp.arange(R, dtype=jnp.int32) == 0
    # ctr column: +1 per element, resets to run_ctr0 at run starts
    # (cum[eb_r] = cum[eb_r - 1] + d_ctr[r] must equal run_ctr0[r], with
    # cum[eb_r - 1] = ctr0[r-1] + len_{r-1} - 1)
    d_ctr = jnp.where(first, run_ctr0,
                      run_ctr0 - (prev(run_ctr0) + run_len_prev - 1))
    # piecewise-constant columns: value deltas at run starts
    wa_v = jnp.where(run_has_value, run_win_actor, -1)
    ws_v = jnp.where(run_has_value, run_win_seq, 0)
    has_v = run_has_value.astype(jnp.int32)
    d_actor = jnp.where(first, run_actor, run_actor - prev(run_actor))
    d_wa = jnp.where(first, wa_v, wa_v - prev(wa_v))
    d_ws = jnp.where(first, ws_v, ws_v - prev(ws_v))
    d_has = jnp.where(first, has_v, has_v - prev(has_v))

    # one boundary scatter per column family + one shared (5, N) prefix sum
    # (padding runs have elem_base == N: OOB, dropped)
    deltas = jnp.ones((5, N), jnp.int32)
    deltas = deltas.at[1:].set(0)
    deltas = deltas.at[:, run_elem_base].set(
        jnp.stack([d_ctr, d_actor, d_wa, d_ws, d_has]), mode="drop")
    cols = jnp.cumsum(deltas, axis=1)

    j = jnp.arange(N, dtype=jnp.int32)
    live = j < n_run_elems
    is_start = jnp.zeros(N, bool).at[run_elem_base].set(True, mode="drop")
    # parent: slot-1 everywhere except run heads (R-sized scatter)
    parent_col = (base_slot - 1) + j
    parent_col = parent_col.at[run_elem_base].set(
        run_parent_slot, mode="drop")
    has_col = (cols[4] > 0) & live

    def dus(table, col, fill):
        return jax.lax.dynamic_update_slice(
            _ext(table, fill, out_cap), col.astype(table.dtype), (base_slot,))

    return (dus(parent, parent_col, 0),
            dus(ctr, cols[0], 0),
            dus(actor, cols[1], 0),
            dus(value, blob, 0),
            dus(has_value, has_col, False),
            dus(win_actor, jnp.where(has_col, cols[2], -1), -1),
            dus(win_seq, jnp.where(has_col, cols[3], 0), 0),
            dus(win_counter, jnp.zeros(N, bool), False),
            dus(chain, live & ~is_start, False))


# Packed-descriptor row layout for expand_runs*_packed: one (9, R) int32
# host->device transfer replaces eight separate array transfers (each costs
# a PCIe round trip of latency, which dominates a small payload). The META row
# carries the round's scalars ([n_run_elems, base_slot, n_runs], rest 0) so
# commit-time dispatch uploads NOTHING host->device.
DESC_HEAD_SLOT, DESC_PARENT_SLOT, DESC_CTR0, DESC_ACTOR, DESC_WIN_ACTOR, \
    DESC_WIN_SEQ, DESC_ELEM_BASE, DESC_HAS_VALUE, DESC_META = range(9)
META_N_ELEMS, META_BASE_SLOT, META_N_RUNS = range(3)

# Residual-op packed layout for apply_residual_packed: one (8, M) int32.
RES_KIND, RES_SLOT, RES_NEW_SLOT, RES_CTR, RES_ACTOR, RES_VALUE, \
    RES_WIN_ACTOR, RES_WIN_SEQ = range(8)


def _unpack_desc(desc):
    return (desc[DESC_HEAD_SLOT], desc[DESC_PARENT_SLOT], desc[DESC_CTR0],
            desc[DESC_ACTOR], desc[DESC_WIN_ACTOR], desc[DESC_WIN_SEQ],
            desc[DESC_ELEM_BASE], desc[DESC_HAS_VALUE].astype(bool))


def _expand_runs_packed(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, desc, blob, *, out_cap: int,
):
    """`expand_runs` taking the run descriptors as one packed (9, R) int32
    matrix (row layout: DESC_*, scalars in the META row). Single h2d
    transfer + single dispatch, no commit-time scalar uploads."""
    return expand_runs(
        parent, ctr, actor, value, has_value, win_actor, win_seq,
        win_counter, chain, *_unpack_desc(desc), blob,
        desc[DESC_META, META_N_ELEMS], out_cap=out_cap)


expand_runs_packed, expand_runs_packed_donated = _jit_pair(
    _expand_runs_packed, _TABLE_ARGNUMS, ("out_cap",))


def _expand_runs_dense_packed(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, desc, blob, *, out_cap: int,
):
    """`expand_runs_dense` + fused `break_chains`, packed descriptors.

    The dense path's chain breaks touch exactly the run heads' parents,
    whose (slot, ctr, actor) already sit in the descriptor matrix — so the
    whole common-case merge round is ONE descriptor transfer, ONE value-blob
    transfer, and ONE device program."""
    (head_slot, parent_slot, ctr0, ractor, rwa, rws, elem_base,
     has) = _unpack_desc(desc)
    n_run_elems = desc[DESC_META, META_N_ELEMS]
    base_slot = desc[DESC_META, META_BASE_SLOT]
    n_runs = desc[DESC_META, META_N_RUNS]
    tables = expand_runs_dense(
        parent, ctr, actor, value, has_value, win_actor, win_seq,
        win_counter, chain, head_slot, parent_slot, ctr0, ractor, rwa, rws,
        elem_base, has, blob, n_run_elems, base_slot, out_cap=out_cap)
    R = desc.shape[1]
    live = jnp.arange(R, dtype=jnp.int32) < n_runs
    chain_n = _break_chains_core(
        tables[8], tables[0], tables[1], tables[2],
        jnp.where(live, parent_slot, 0), jnp.where(live, ctr0, -1),
        jnp.where(live, ractor, -1))
    return tables[:8] + (chain_n,)


expand_runs_dense_packed, expand_runs_dense_packed_donated = _jit_pair(
    _expand_runs_dense_packed, _TABLE_ARGNUMS, ("out_cap",))


def _break_chains_core(chain, parent, ctr, actor, p_slots, h_ctr, h_actor):
    """Clear the chain bit of slot p+1 for every touched parent p whose new
    child Lamport-exceeds (ctr, actor) of p+1.

    This is the incremental form of the reference's `insertionsAfter`
    ordering (/root/reference/backend/op_set.js:440-454): slot p+1 heads its
    own segment once it is no longer p's Lamport-maximal child. Breaks are
    sticky — Lamport maxima only grow — so bits never need re-setting.
    R-sized work per round instead of a full O(C) census per materialize."""
    C = chain.shape[0]
    q = jnp.clip(p_slots + 1, 0, C - 1)
    cq = ctr[q]
    aq = actor[q]
    brk = (p_slots >= 1) & ((h_ctr > cq) | ((h_ctr == cq) & (h_actor > aq)))
    return chain.at[jnp.where(brk, q, C)].set(False, mode="drop")


break_chains = jax.jit(_break_chains_core)


def _break_chains_packed(chain, parent, ctr, actor, touch):
    """`break_chains` with the (p_slot, ctr, actor) touch rows packed as one
    (3, T) int32 transfer."""
    return _break_chains_core(chain, parent, ctr, actor,
                              touch[0], touch[1], touch[2])


break_chains_packed, break_chains_packed_donated = _jit_pair(
    _break_chains_packed, (0,))     # only `chain` is replaced


def _apply_residual_packed(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, res, conflict_slots, *, out_cap: int,
):
    """`apply_residual` taking the residual op columns as one packed
    (8, M) int32 matrix (row layout: RES_*)."""
    return apply_residual(
        parent, ctr, actor, value, has_value, win_actor, win_seq,
        win_counter, chain,
        res[RES_KIND].astype(jnp.int8), res[RES_SLOT], res[RES_NEW_SLOT],
        res[RES_CTR], res[RES_ACTOR], res[RES_VALUE], res[RES_WIN_ACTOR],
        res[RES_WIN_SEQ], conflict_slots, out_cap=out_cap)


apply_residual_packed, apply_residual_packed_donated = _jit_pair(
    _apply_residual_packed, _TABLE_ARGNUMS, ("out_cap",))


def _apply_mixed_round(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, desc, blob, res, conflict_slots, touch,
    *, out_cap: int, expand_kind: str, with_res: bool, with_touch: bool,
):
    """One device program for a whole MIXED round: run expansion
    (dense or sparse, per `expand_kind`), residual placement + register
    fast path, and chain breaks, composed by static flags. The commit of
    any round — dense, sparse, residual-bearing or not — is therefore
    ONE dispatch, and XLA fuses the phases' elementwise work (the
    per-phase (C, 9) stack/unstack round trips of the split programs
    disappear). Unused operands ride as tiny dummies (static flags cut
    the dead branches at trace time). Returns the 9 tables, plus
    `slow_info` when `with_res`."""
    tables = (parent, ctr, actor, value, has_value, win_actor, win_seq,
              win_counter, chain)
    if expand_kind == "dense":
        tables = _expand_runs_dense_packed(*tables, desc, blob,
                                           out_cap=out_cap)
    elif expand_kind == "sparse":
        tables = _expand_runs_packed(*tables, desc, blob, out_cap=out_cap)
    slow_info = None
    if with_res:
        out = _apply_residual_packed(*tables, res, conflict_slots,
                                     out_cap=out_cap)
        tables, slow_info = out[:9], out[9]
    if with_touch:
        tables = tables[:8] + (_break_chains_packed(
            tables[8], tables[0], tables[1], tables[2], touch),)
    return tables + ((slow_info,) if with_res else ())


apply_mixed_round, apply_mixed_round_donated = _jit_pair(
    _apply_mixed_round, _TABLE_ARGNUMS,
    ("out_cap", "expand_kind", "with_res", "with_touch"))

_DUMMY_I32 = None


def _dummy_i32():
    """Shared tiny placeholder for unused traced operands of
    apply_mixed_round (static flags dead-code them; a fresh upload per
    call would still pay a transfer)."""
    global _DUMMY_I32
    if _DUMMY_I32 is None:
        _DUMMY_I32 = jnp.zeros((1, 1), jnp.int32)
    return _DUMMY_I32


@partial(jax.jit, static_argnames=("out_cap",))
def apply_residual(
    # document tables (post expand_runs), capacity C == out_cap
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain,
    # residual op columns, capacity M (padding: kind=-1, slots=out_cap)
    op_kind,        # int8
    op_slot,        # ins: resolved parent slot (0 = head); assigns: target slot
    op_new_slot,    # ins: assigned element slot; else out_cap
    op_ctr, op_actor,             # ins: minted elemId (global actor rank)
    op_value,                     # int32 (negatives = host value-pool refs)
    op_win_actor, op_win_seq,     # the op's change (global rank, seq)
    conflict_slots,               # [K] slots with host-held conflicts (pad C)
    *, out_cap: int,
):
    """Place irregular inserts and run the LWW register fast path.

    Returns the updated tables + the packed (7, M) `slow_info` matrix (see
    `_register_fast_path`): ops needing host resolution — multi-writer
    rounds, occupied registers, dels, incs, pooled values — plus their
    register state, in op order, as one device->host transfer."""
    M = op_kind.shape[0]
    kind = op_kind.astype(jnp.int32)
    is_ins = kind == KIND_INS
    is_assign = (kind == KIND_SET) | (kind == KIND_DEL) | (kind == KIND_INC)

    ins_idx = jnp.where(is_ins, op_new_slot, out_cap)
    zeros = jnp.zeros(M, jnp.int32)
    # one stacked scatter for the insert placement (see _scatter_rows_9)
    (parent_n, ctr_n, actor_n, value_n, has_n, wa_n, ws_n, wc_n,
     chain_n) = _scatter_rows_9(
        (parent, ctr, actor, value, has_value, win_actor, win_seq,
         win_counter, chain),
        ins_idx,
        (op_slot, op_ctr, op_actor, zeros, zeros,
         jnp.full(M, -1, jnp.int32), zeros, zeros, zeros),
        out_cap)

    (value_n, has_n, wa_n, ws_n, wc_n, slow_info) = _register_fast_path(
        value_n, has_n, wa_n, ws_n, wc_n, kind, is_assign, op_slot,
        op_value, op_win_actor, op_win_seq, conflict_slots, out_cap)
    return (parent_n, ctr_n, actor_n, value_n, has_n, wa_n, ws_n, wc_n,
            chain_n, slow_info)


def _register_fast_path(value_n, has_n, wa_n, ws_n, wc_n, kind, is_assign,
                        op_slot, op_value, op_win_actor, op_win_seq,
                        conflict_slots, out_cap):
    """Shared LWW register resolution (text elements and map keys).

    Fast = a single plain inline set in this round targeting either an
    empty register or the op's own actor's earlier write (always causally
    covered). Everything else -> `slow` for host resolution.

    Returns the updated tables plus `slow_info`, a single packed (7, M)
    int32 array [slow, tslot, reg_value, reg_has, reg_win_actor,
    reg_win_seq, reg_win_counter]: everything the host slow path needs in
    ONE device->host transfer (device round trips dominate small
    rounds)."""
    tslot = jnp.where(is_assign, op_slot, out_cap)
    tclip = jnp.clip(tslot, 0, out_cap - 1)
    counts = jnp.zeros(out_cap + 1, jnp.int32).at[
        jnp.clip(tslot, 0, out_cap)].add(is_assign.astype(jnp.int32))
    cmask = jnp.zeros(out_cap + 1, bool).at[
        jnp.clip(conflict_slots, 0, out_cap)].set(True)
    empty = ~has_n[tclip] & (wa_n[tclip] < 0)
    self_over = (~wc_n[tclip] & (wa_n[tclip] == op_win_actor)
                 & (ws_n[tclip] < op_win_seq))
    fast = (is_assign & (kind == KIND_SET)
            & (counts[tclip] == 1) & (empty | self_over)
            & ~cmask[tclip] & (op_value >= 0))
    f_idx = jnp.where(fast, tslot, out_cap)
    # one stacked (C, 5) scatter over the register columns (shared index
    # vector — same per-index-overhead argument as _scatter_rows_9)
    M = f_idx.shape[0]
    regs = jnp.stack([value_n, has_n.astype(jnp.int32), wa_n, ws_n,
                      wc_n.astype(jnp.int32)], axis=1)
    upd = jnp.stack([op_value, jnp.ones(M, jnp.int32), op_win_actor,
                     op_win_seq, jnp.zeros(M, jnp.int32)], axis=1)
    regs = regs.at[f_idx].set(upd, mode="drop")
    value_n, has_n, wa_n, ws_n, wc_n = (
        regs[:, 0], regs[:, 1].astype(bool), regs[:, 2], regs[:, 3],
        regs[:, 4].astype(bool))

    slow = is_assign & ~fast
    # register state at each slow op's slot, post fast-path/insert writes
    # (a slot is never both fast- and slow-targeted: counts==1 gates fast)
    slow_info = jnp.stack([
        slow.astype(jnp.int32), tslot,
        value_n[tclip], has_n[tclip].astype(jnp.int32),
        wa_n[tclip], ws_n[tclip], wc_n[tclip].astype(jnp.int32)])
    return value_n, has_n, wa_n, ws_n, wc_n, slow_info


def _apply_map_round(
    # register tables, capacity K
    value, has_value, win_actor, win_seq, win_counter,
    # op columns, capacity M (padding: kind=-1, slot=out_cap)
    op_kind, op_slot, op_value, op_win_actor, op_win_seq,
    conflict_slots,
    *, out_cap: int,
):
    """One causally-ready round of map ops (set/del/inc on interned keys).

    The map analogue of `apply_residual` without inserts: key registers are
    dense slots, the LWW fast path handles single uncontended inline-int
    sets, and everything else (dels, incs, pooled values, multi-writer
    rounds, occupied registers) lands in the `slow` mask for host
    resolution — the reference's `applyAssign` partitioned the same way
    (/root/reference/backend/op_set.js:196-258, map branch)."""
    kind = op_kind.astype(jnp.int32)
    is_assign = (kind == KIND_SET) | (kind == KIND_DEL) | (kind == KIND_INC)

    value_n = _ext(value, 0, out_cap)
    has_n = _ext(has_value, False, out_cap)
    wa_n = _ext(win_actor, -1, out_cap)
    ws_n = _ext(win_seq, 0, out_cap)
    wc_n = _ext(win_counter, False, out_cap)
    return _register_fast_path(
        value_n, has_n, wa_n, ws_n, wc_n, kind, is_assign, op_slot,
        op_value, op_win_actor, op_win_seq, conflict_slots, out_cap)


apply_map_round = jax.jit(_apply_map_round, static_argnames=("out_cap",))


def _merge_and_materialize_dense(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, desc, blob, *, out_cap: int, S: int, as_u8: bool, L: int,
):
    """The common-case merge round END TO END in one device program:
    `expand_runs_dense_packed` (with fused chain breaks) followed by the
    codes-only materialization. One launch instead of two — launch/flush
    overhead is a measurable slice of the commit path, and XLA can overlap the phases' elementwise work.

    Returns the 9 updated tables + (codes, scalars). n_elems for the
    materialization comes from the descriptor META row (base_slot +
    n_run_elems - 1), so the call uploads nothing."""
    tables = expand_runs_dense_packed(
        parent, ctr, actor, value, has_value, win_actor, win_seq,
        win_counter, chain, desc, blob, out_cap=out_cap)
    n_elems = (desc[DESC_META, META_BASE_SLOT]
               + desc[DESC_META, META_N_ELEMS] - 1)
    cols = _slice_live((tables[0], tables[1], tables[2], tables[3],
                        tables[4], tables[8]), L)
    codes, scalars = _materialize_core(*cols, n_elems, S, with_pos=False,
                                       as_u8=as_u8)
    return tables + (codes, scalars)


merge_and_materialize_dense, merge_and_materialize_dense_donated = _jit_pair(
    _merge_and_materialize_dense, _TABLE_ARGNUMS,
    ("out_cap", "S", "as_u8", "L"))


@jax.jit
def remap_ranks(win_actor, remap):
    """Re-rank the winner-actor column after an interning order change."""
    hi = remap.shape[0] - 1
    return jnp.where(win_actor >= 0, remap[jnp.clip(win_actor, 0, hi)],
                     win_actor)


def _linearize_segments(parent, attach_off, ctr, actor, weight, valid):
    """Condensed-tree linearization (see ops/linearize.py for the
    derivation): per-parent children sort descending by (attach, ctr, actor),
    successor chain by pointer doubling, weighted list ranking."""
    import math
    n = parent.shape[0]
    steps = max(1, math.ceil(math.log2(max(2, n))))
    idx = jnp.arange(n, dtype=jnp.int32)
    is_seg = valid & (idx != 0)
    big = jnp.int32(n + 1)

    sort_parent = jnp.where(is_seg, parent, big)
    neg_off = jnp.where(is_seg, -attach_off, big)
    neg_ctr = jnp.where(is_seg, -ctr, big)
    neg_actor = jnp.where(is_seg, -actor, big)
    p_s, _, _, _, idx_s = jax.lax.sort(
        (sort_parent, neg_off, neg_ctr, neg_actor, idx), num_keys=4)

    in_group = p_s < big
    same_next = jnp.concatenate(
        [(p_s[1:] == p_s[:-1]) & in_group[1:], jnp.zeros(1, bool)])
    next_in_sorted = jnp.concatenate([idx_s[1:], jnp.full(1, -1, idx_s.dtype)])
    next_sib = jnp.full((n,), -1, jnp.int32)
    next_sib = next_sib.at[idx_s].set(jnp.where(same_next, next_in_sorted, -1))

    group_start = jnp.concatenate(
        [jnp.ones(1, bool), p_s[1:] != p_s[:-1]]) & in_group
    first_child = jnp.full((n,), -1, jnp.int32)
    first_child = first_child.at[jnp.where(group_start, p_s, big - 1)].set(
        jnp.where(group_start, idx_s, -1), mode="drop")

    has_next = next_sib >= 0
    safe_parent = jnp.where(is_seg, parent, 0)
    anc = jnp.where(has_next | (idx == 0), idx, safe_parent)
    anc = jax.lax.fori_loop(0, steps, lambda _, a: a[a], anc)

    succ = jnp.where(first_child >= 0, first_child, next_sib[anc])

    end = jnp.int32(n)
    nxt = jnp.where(succ >= 0, succ, end)
    nxt = jnp.where(is_seg | (idx == 0), nxt, idx)
    nxt = jnp.concatenate([nxt, jnp.full(1, end, jnp.int32)])
    dist = jnp.where(is_seg, weight, 0).astype(jnp.int32)
    dist = jnp.concatenate([dist, jnp.zeros(1, jnp.int32)])

    def rank_step(_, carry):
        d, nx = carry
        return d + d[nx], nx[nx]

    dist, nxt = jax.lax.fori_loop(0, steps + 1, rank_step, (dist, nxt))
    start = dist[0] - dist[:n]
    return jnp.where(is_seg, start, jnp.where(idx == 0, 0, big))


def _materialize_core(parent, ctr, actor, value, has_value, chain, n_elems,
                      S, with_pos, as_u8, touched=None):
    """RGA positions + visible compaction from the maintained chain bits.

    Segments (maximal chain runs, contiguous in slot space) compact into S
    nodes (S is a static bucket >= n_segs+1, estimated by the host), the
    condensed tree linearizes in O(S log S), and element position = segment
    start + offset. Visible ranks come from one visibility prefix-sum in
    slot order plus a per-segment base computed in segment space — the
    device-native replacement for the reference skip list's index queries
    (/root/reference/backend/skip_list.js:260-305).
    """
    C = parent.shape[0]
    idx = jnp.arange(C, dtype=jnp.int32)
    is_elem = (idx >= 1) & (idx <= n_elems)
    seg_start = is_elem & ~chain
    vis = has_value & is_elem
    # one fused (2, C) prefix sum: segment ranks + inclusive visible counts
    two = jnp.cumsum(jnp.stack([seg_start.astype(jnp.int32),
                                vis.astype(jnp.int32)]), axis=1)
    rank_incl, cumvis = two[0], two[1]                   # node id per slot
    n_segs = rank_incl[-1]

    # head slot of segment k: rank_incl is non-decreasing and jumps to k at
    # the k-th segment start, so a binary search replaces the C-sized
    # scatter (scatter cost is per-INDEX: ~190M/s over all C slots on v5e;
    # this is S*log C gathers)
    sidx = jnp.arange(S, dtype=jnp.int32)
    heads = jnp.searchsorted(rank_incl, sidx, side="left").astype(jnp.int32)
    heads = jnp.clip(heads, 0, C - 1)

    # segment ranks are assigned in slot order, so heads is sorted by slot
    # and each segment's size is the gap to the next head
    valid = sidx <= n_segs
    live_seg = valid & (sidx >= 1)
    next_head = jnp.where((sidx + 1 <= n_segs) & (sidx + 1 < S),
                          heads[jnp.clip(sidx + 1, 0, S - 1)], n_elems + 1)

    p_slot = parent[heads]
    node_parent = rank_incl[p_slot]
    # attach offset of a parent slot inside its own segment, S-sized:
    # seg_head[p] == heads[rank_incl[p]]
    attach = p_slot - heads[jnp.clip(node_parent, 0, S - 1)]
    nctr = ctr[heads]
    nactor = actor[heads]
    weight = jnp.where(live_seg, next_head - heads, 0)
    starts = _linearize_segments(node_parent, attach, nctr, nactor, weight, valid)

    # visible ranking, segment-space: rank = (visible in segments placed
    # earlier) + (visible before me inside my segment)
    n_vis = cumvis[C - 1]
    head_pre = cumvis[heads] - vis[heads].astype(jnp.int32)
    last = jnp.clip(next_head - 1, 0, C - 1)
    seg_vis = jnp.where(live_seg, cumvis[last] - head_pre, 0)

    big = jnp.int32(C + 2)
    order_key = jnp.where(live_seg, starts, big)
    _, perm = jax.lax.sort((order_key, sidx), num_keys=1)
    sv_perm = seg_vis[perm]
    base_perm = jnp.cumsum(sv_perm) - sv_perm            # exclusive, by pos
    rank_base = jnp.zeros(S, jnp.int32).at[perm].set(base_perm)
    seg_base = rank_base - head_pre                      # one combined table

    # expand S-space tables to slot space GATHER-FREE: `rank_incl` is
    # non-decreasing, so table[rank_incl] is piecewise constant with jumps
    # at segment heads — scatter per-segment deltas at head slots (S-sized)
    # and prefix-sum, instead of a C-sized gather (~140M elem/s on v5e vs
    # vector-rate cumsum). Segment k covers slots [heads[k], heads[k+1]);
    # slots before heads[1] (the head slot 0) read 0, and are never visible.
    def expand_S(table):
        prev = jnp.concatenate([jnp.zeros(1, table.dtype), table[:-1]])
        d = jnp.where(sidx == 1, table, table - prev)
        tgt = jnp.where(live_seg, heads, C)
        return jnp.zeros(C, table.dtype).at[tgt].set(d, mode="drop")

    if with_pos:
        d3 = jnp.stack([expand_S(seg_base), expand_S(starts),
                        expand_S(heads)])
        exp = jnp.cumsum(d3, axis=1)
        sb_exp, starts_exp, seg_head_exp = exp[0], exp[1], exp[2]
    else:
        sb_exp = jnp.cumsum(expand_S(seg_base))
        starts_exp = seg_head_exp = None
    vis_rank = sb_exp + cumvis - vis.astype(jnp.int32)

    if as_u8:
        # known-7-bit documents scatter 1-byte codes: 4x less HBM traffic
        # on the scatter AND 4x less device->host transfer
        codes = jnp.zeros(C, jnp.uint8).at[
            jnp.where(vis, vis_rank, C)].set(
            value.astype(jnp.uint8), mode="drop")
    else:
        codes = jnp.full(C, -1, value.dtype).at[
            jnp.where(vis, vis_rank, C)].set(value, mode="drop")
    scalars = jnp.stack([n_vis, n_segs])   # one packed scalar fetch

    if with_pos:
        pos = jnp.where(is_elem, starts_exp + (idx - seg_head_exp),
                        jnp.where(idx == 0, -1, C + 1))
        return pos, codes, _with_touched_rows(
            scalars, touched, pos, vis_rank, vis, value, actor, ctr)
    return codes, scalars


TOUCHED_ROWS = 6   # pos, visible prefix, visible, value, actor, ctr


def _with_touched_rows(scalars, touched, pos, vis_rank, vis, value, actor,
                       ctr):
    """The scalars, followed by TOUCHED_ROWS rows of K int32 gathered at
    the `touched` slots (int32[K], padded with slot 0): each slot's RGA
    position, the count of visible elements before it in list order, its
    visibility, value, actor rank and counter. The host's diff emission
    reads a round's changed slots from this one small transfer instead of
    pulling the position vector and the element tables
    (backend/device.py `_text_diffs`)."""
    t = jnp.clip(touched, 0, pos.shape[0] - 1)
    rows = jnp.stack([pos[t], vis_rank[t], vis[t].astype(jnp.int32),
                      value[t], actor[t], ctr[t]])
    return jnp.concatenate([scalars, rows.reshape(-1)])


# Odd 32-bit mixing constants (Knuth golden-ratio / murmur3) for the
# plan-consistency hashes. The per-element mix must be NONLINEAR before the
# sum reduce: a purely multiplicative hash is linear, so any divergence that
# preserves the plain sum (e.g. heads {3,5} vs {2,6}) also preserves
# sum(K*h). The xorshift stages break that cancellation.
# engine/segments.SegmentMirror.{head_checksum,aux_checksum} are the numpy
# twins of `_mix32` — both run the identical uint32-wrapping pipeline.
HASH_K1 = np.uint32(2654435761)   # 0x9E3779B1
HASH_K2 = np.uint32(2246822519)   # 0x85EBCA77
HASH_K3 = np.uint32(3266489917)   # 0xC2B2AE3D
HASH_K4 = np.uint32(668265263)    # 0x27D4EB2F


def _mix32(x):
    """murmur3-fmix-style nonlinear 32-bit mix (device); uint32 wrapping."""
    x = x.astype(jnp.uint32) * HASH_K1
    x = x ^ (x >> 15)
    x = x * HASH_K2
    x = x ^ (x >> 13)
    return x


def mix32_np(x: np.ndarray) -> np.ndarray:
    """Host twin of `_mix32` — identical uint32 pipeline in numpy."""
    x = x.astype(np.uint32) * HASH_K1
    x = x ^ (x >> np.uint32(15))
    x = x * HASH_K2
    x = x ^ (x >> np.uint32(13))
    return x


def _materialize_core_planned(parent, ctr, actor, value, has_value, chain,
                              n_elems, segplan, S, with_pos, as_u8,
                              touched=None):
    """Materialization with HOST-PLANNED segment structure.

    `segplan` is the (4, S) int32 matrix from
    engine/segments.SegmentMirror.plan(): [head slots, position->segment
    permutation, segment starts, meta(n_segs)]. The host already knows the
    chain/segment structure it staged (every head is a planned run head,
    residual insert, or chain break), so the structural S-stage of
    `_materialize_core` — the 4-key sort, the pointer-doubling
    linearization, and the head searchsorted — disappears from the device
    program. What remains is inherently data-dependent: the visibility
    prefix sum, the S->slot expansion sum, and the codes scatter.

    Trust but verify: the kernel re-derives, from the REAL chain bits, the
    segment count plus TWO int32-wrapping mixing hashes — one over the head
    slots themselves, one over the heads' (parent slot, ctr, actor) columns,
    which fully determine the linearization order — and returns them in the
    scalars. The engine compares them against the mirror at its scalar sync
    and self-heals through the self-contained kernel on mismatch
    (engine/text_doc.DeviceTextDoc._scalars). Multiplicative mixing (Knuth/
    murmur odd constants) makes a divergence that preserves count AND both
    hashes implausible — a plain count+sum check would pass head-set swaps
    like {3,5} vs {2,6}."""
    C = value.shape[0]
    idx = jnp.arange(C, dtype=jnp.int32)
    is_elem = (idx >= 1) & (idx <= n_elems)
    vis = has_value & is_elem
    cumvis = jnp.cumsum(vis.astype(jnp.int32))
    n_vis = cumvis[C - 1]

    heads_raw = segplan[0]
    heads = jnp.clip(heads_raw, 0, C - 1)
    perm = segplan[1]
    n_segs = segplan[3, 0]
    sidx = jnp.arange(S, dtype=jnp.int32)
    live_seg = (sidx >= 1) & (sidx <= n_segs)

    next_head = jnp.where((sidx + 1 <= n_segs) & (sidx + 1 < S),
                          heads_raw[jnp.clip(sidx + 1, 0, S - 1)],
                          n_elems + 1)
    head_pre = cumvis[heads] - vis[heads].astype(jnp.int32)
    last = jnp.clip(next_head - 1, 0, C - 1)
    seg_vis = jnp.where(live_seg, cumvis[last] - head_pre, 0)

    sv_perm = seg_vis[perm]
    base_perm = jnp.cumsum(sv_perm) - sv_perm          # exclusive, by pos
    rank_base = jnp.zeros(S, jnp.int32).at[perm].set(base_perm)
    seg_base = rank_base - head_pre

    def expand_S(table):
        prev = jnp.concatenate([jnp.zeros(1, table.dtype), table[:-1]])
        d = jnp.where(sidx == 1, table, table - prev)
        tgt = jnp.where(live_seg, heads, C)
        return jnp.zeros(C, table.dtype).at[tgt].set(d, mode="drop")

    if with_pos:
        starts = segplan[2]
        d3 = jnp.stack([expand_S(seg_base), expand_S(starts),
                        expand_S(heads)])
        exp = jnp.cumsum(d3, axis=1)
        sb_exp, starts_exp, seg_head_exp = exp[0], exp[1], exp[2]
    else:
        sb_exp = jnp.cumsum(expand_S(seg_base))
        starts_exp = seg_head_exp = None
    vis_rank = sb_exp + cumvis - vis.astype(jnp.int32)

    if as_u8:
        codes = jnp.zeros(C, jnp.uint8).at[
            jnp.where(vis, vis_rank, C)].set(
            value.astype(jnp.uint8), mode="drop")
    else:
        codes = jnp.full(C, -1, value.dtype).at[
            jnp.where(vis, vis_rank, C)].set(value, mode="drop")

    # plan-consistency scalars from the real chain bits: cheap reduces with
    # a NONLINEAR per-element mix (uint32, wraps deterministically), so
    # divergences cannot cancel in the sum
    seg_start = is_elem & ~chain
    n_segs_dev = jnp.sum(seg_start.astype(jnp.int32))
    head_hash_dev = jax.lax.bitcast_convert_type(
        jnp.sum(jnp.where(seg_start, _mix32(idx), jnp.uint32(0))),
        jnp.int32)
    aux_key = (parent.astype(jnp.uint32) * HASH_K2
               + ctr.astype(jnp.uint32) * HASH_K3
               + actor.astype(jnp.uint32) * HASH_K4)
    aux_hash_dev = jax.lax.bitcast_convert_type(
        jnp.sum(jnp.where(seg_start, _mix32(aux_key + idx.astype(jnp.uint32)),
                          jnp.uint32(0))),
        jnp.int32)
    scalars = jnp.stack([n_vis, n_segs, n_segs_dev, head_hash_dev,
                         aux_hash_dev])

    if with_pos:
        pos = jnp.where(is_elem, starts_exp + (idx - seg_head_exp),
                        jnp.where(idx == 0, -1, C + 1))
        return pos, codes, _with_touched_rows(
            scalars, touched, pos, vis_rank, vis, value, actor, ctr)
    return codes, scalars


@partial(jax.jit, static_argnames=("S", "as_u8", "L"))
def materialize_text_planned(parent, ctr, actor, value, has_value, chain,
                             n_elems, segplan, touched,
                             *, S: int, as_u8: bool = False, L: int = None):
    """`materialize_text` with host-planned segment structure (see
    `_materialize_core_planned`). parent/ctr/actor feed the
    plan-consistency hash reduces and the touched-slot rows, not the
    linearization."""
    cols = _slice_live((parent, ctr, actor, value, has_value, chain), L)
    return _materialize_core_planned(*cols, n_elems, segplan, S,
                                     with_pos=True, as_u8=as_u8,
                                     touched=touched)


@partial(jax.jit, static_argnames=("S", "as_u8", "L"))
def materialize_codes_planned(parent, ctr, actor, value, has_value, chain,
                              n_elems, segplan,
                              *, S: int, as_u8: bool = False, L: int = None):
    """`materialize_codes` with host-planned segment structure."""
    cols = _slice_live((parent, ctr, actor, value, has_value, chain), L)
    return _materialize_core_planned(*cols, n_elems, segplan, S,
                                     with_pos=False, as_u8=as_u8)


def _merge_and_materialize_dense_planned(
    parent, ctr, actor, value, has_value, win_actor, win_seq, win_counter,
    chain, desc, blob, segplan, *, out_cap: int, S: int, as_u8: bool, L: int,
):
    """`merge_and_materialize_dense` with the materialization's segment
    structure staged from the host plan: the whole common-case merge round
    is ONE device program containing no sort and no pointer doubling."""
    tables = expand_runs_dense_packed(
        parent, ctr, actor, value, has_value, win_actor, win_seq,
        win_counter, chain, desc, blob, out_cap=out_cap)
    n_elems = (desc[DESC_META, META_BASE_SLOT]
               + desc[DESC_META, META_N_ELEMS] - 1)
    cols = _slice_live((tables[0], tables[1], tables[2], tables[3],
                        tables[4], tables[8]), L)
    codes, scalars = _materialize_core_planned(
        *cols, n_elems, segplan, S, with_pos=False, as_u8=as_u8)
    return tables + (codes, scalars)


(merge_and_materialize_dense_planned,
 merge_and_materialize_dense_planned_donated) = _jit_pair(
    _merge_and_materialize_dense_planned, _TABLE_ARGNUMS,
    ("out_cap", "S", "as_u8", "L"))


def _slice_live(cols, L):
    """Restrict the element columns to the live-window bucket `L` (static):
    table capacity can exceed the live prefix by up to 50%, and every pass
    in the materialize kernel scales with operand length."""
    if L is None or L >= cols[0].shape[0]:
        return cols
    return tuple(c[:L] for c in cols)


@partial(jax.jit, static_argnames=("S", "L"))
def segment_visible_counts(has_value, n_elems, segplan,
                           *, S: int, L: int = None):
    """Per-segment VISIBLE character counts — the dirty-span descriptor
    feed for the incremental text pull (engine/text_doc.DeviceTextDoc
    `_text_incremental`).

    The host mirror knows the segment structure exactly (heads, order,
    positions: engine/segments.SegmentMirror) but visibility is data the
    device owns, so an incremental pull fetches this one S-sized row —
    tens of KB — instead of the whole O(doc) codes buffer, and the host
    derives every changed span's [visible start, length) from it. Same
    seg_vis formulation as `_materialize_core_planned`; `segplan` is the
    mirror's packed plan (row 0 = head slots, row 3 meta[0] = n_segs)."""
    hv = _slice_live((has_value,), L)[0]
    C = hv.shape[0]
    idx = jnp.arange(C, dtype=jnp.int32)
    vis = hv & (idx >= 1) & (idx <= n_elems)
    cumvis = jnp.cumsum(vis.astype(jnp.int32))
    heads_raw = segplan[0]
    n_segs = segplan[3, 0]
    sidx = jnp.arange(S, dtype=jnp.int32)
    live_seg = (sidx >= 1) & (sidx <= n_segs)
    heads = jnp.clip(heads_raw, 0, C - 1)
    next_head = jnp.where((sidx + 1 <= n_segs) & (sidx + 1 < S),
                          heads_raw[jnp.clip(sidx + 1, 0, S - 1)],
                          n_elems + 1)
    head_pre = cumvis[heads] - vis[heads].astype(jnp.int32)
    last = jnp.clip(next_head - 1, 0, C - 1)
    return jnp.where(live_seg, cumvis[last] - head_pre, 0)


@partial(jax.jit, static_argnames=("S", "as_u8", "L"))
def materialize_text(parent, ctr, actor, value, has_value, chain, n_elems,
                     touched, *, S: int, as_u8: bool = False, L: int = None):
    """Full materialization: (pos, codes, [n_vis, n_segs] + touched-slot
    rows). `pos` includes tombstones (head = -1, padding > n); `codes` is
    visible values scattered into list order (uint8 when `as_u8` — the
    host tracks 7-bit-ness); the rows are gathered at the int32[K]
    `touched` slots (`_with_touched_rows`). The host retries with a
    bigger S when n_segs+1 > S."""
    cols = _slice_live((parent, ctr, actor, value, has_value, chain), L)
    return _materialize_core(*cols, n_elems, S, with_pos=True, as_u8=as_u8,
                             touched=touched)


@partial(jax.jit, static_argnames=("S", "as_u8", "L"))
def materialize_codes(parent, ctr, actor, value, has_value, chain, n_elems,
                      *, S: int, as_u8: bool = False, L: int = None):
    """Codes-only materialization for `text()`: skips the per-element
    position gather."""
    cols = _slice_live((parent, ctr, actor, value, has_value, chain), L)
    return _materialize_core(*cols, n_elems, S, with_pos=False, as_u8=as_u8)


@jax.jit
def remap_actors(actor, win_actor, remap, n_elems):
    """Re-rank actor ids after interning breaks lexicographic rank order.

    Rare: only when a new actor id sorts before an existing one. The host
    remaps its range index separately (host_index.ElemRangeIndex.remap)."""
    C = actor.shape[0]
    idx = jnp.arange(C, dtype=jnp.int32)
    live = (idx >= 1) & (idx <= n_elems)
    hi = remap.shape[0] - 1
    actor_n = jnp.where(live, remap[jnp.clip(actor, 0, hi)], actor)
    wa_n = jnp.where(win_actor >= 0, remap[jnp.clip(win_actor, 0, hi)],
                     win_actor)
    return actor_n, wa_n


@jax.jit
def pack_rows(*arrays):
    """Stack same-length device arrays into one int32 matrix: the host
    mirror fetch becomes a single device->host transfer."""
    return jnp.stack([a.astype(jnp.int32) for a in arrays])


@jax.jit
def scatter_registers(value, has_value, win_actor, win_seq, win_counter,
                      slots, v, h, wa, ws, wc):
    """Write back host-resolved registers (OOB sentinel slots drop).

    LEGACY per-column upload shape: six separate host arrays, each a
    distinct h2d transfer paying per-transfer link latency. Kept as the
    parity comparator for `scatter_registers_packed`
    (tests/test_dispatch_budget.py) and selectable via
    ``CausalDeviceDoc.packed_residual_writeback = False``."""
    return (value.at[slots].set(v, mode="drop"),
            has_value.at[slots].set(h, mode="drop"),
            win_actor.at[slots].set(wa, mode="drop"),
            win_seq.at[slots].set(ws, mode="drop"),
            win_counter.at[slots].set(wc, mode="drop"))


# Packed-writeback row layout for scatter_registers_packed: one (6, S)
# int32 host->device transfer replaces the six separate arrays above —
# with the packed (7, M) slow_info fetch, the whole host slow-register
# residue costs exactly ONE d2h round trip + ONE h2d upload per round.
WB_SLOT, WB_VALUE, WB_HAS, WB_WIN_ACTOR, WB_WIN_SEQ, WB_WIN_COUNTER = \
    range(6)


def _scatter_registers_packed(value, has_value, win_actor, win_seq,
                              win_counter, wb):
    """`scatter_registers` with the resolved rows packed as one (6, S)
    int32 matrix (row layout: WB_*; padding rows carry an OOB slot)."""
    slots = wb[WB_SLOT]
    return (value.at[slots].set(wb[WB_VALUE], mode="drop"),
            has_value.at[slots].set(wb[WB_HAS].astype(bool), mode="drop"),
            win_actor.at[slots].set(wb[WB_WIN_ACTOR], mode="drop"),
            win_seq.at[slots].set(wb[WB_WIN_SEQ], mode="drop"),
            win_counter.at[slots].set(wb[WB_WIN_COUNTER].astype(bool),
                                      mode="drop"))


scatter_registers_packed, scatter_registers_packed_donated = _jit_pair(
    _scatter_registers_packed, _REG_ARGNUMS)


# ---------------------------------------------------------------------------
# Stacked multi-object rounds (engine/stacked.py; INTERNALS §12)
#
# The nested-document production shape is MANY SMALL objects: a Trellis
# board fans one causal round across ~21 per-object engine docs, and the
# per-(object, round) programs plus their h2d staging dominate the merge
# (the cfg4 cpu profile). These kernels execute one causal
# round across EVERY participating object as a constant number of
# programs: per-object tables pad to a common capacity and stack along a
# leading doc axis, and the existing round kernels run under `jax.vmap` —
# the padded-stack shape the DocSet tier already uses for homogeneous
# text docs (engine/doc_set.py), generalized to the mixed map/text
# workload. Padded stacking was chosen over a doc-id column in shared
# flat tables because the run-expansion kernels write one contiguous
# slot window per document (`expand_runs_dense`'s base_slot contract),
# which a doc-id column cannot express without per-doc windows; vmap
# keeps every doc's slot space intact and the kernels unchanged.
# ---------------------------------------------------------------------------

# fill values per table column when padding to the common stacked width
_REG_FILLS = (0, False, -1, 0, False)
_ELEM_FILLS = (0, 0, 0, 0, False, -1, 0, False, False)

# row layout of the packed (D, 5, M) stacked map-op upload
MOP_KIND, MOP_SLOT, MOP_VALUE, MOP_WIN_ACTOR, MOP_WIN_SEQ = range(5)


def _stack_padded(tables, fills, out_cap):
    return tuple(
        jnp.stack([_ext(doc[k], fills[k], out_cap) for doc in tables])
        for k in range(len(fills)))


def _stack_register_tables(tables, remaps, *, out_cap: int):
    """Per-doc register tables -> stacked (D, out_cap) columns.

    `tables` is a tuple of per-doc 5-tuples (value, has_value, win_actor,
    win_seq, win_counter); `remaps` a (D, L) int32 matrix of pending
    actor-rank remaps (identity rows for unaffected docs), folded into
    the gather so a reordering intern costs zero extra programs instead
    of one `remap_ranks` dispatch per document."""
    value, has_value, win_actor, win_seq, win_counter = _stack_padded(
        tables, _REG_FILLS, out_cap)
    hi = remaps.shape[1] - 1
    win_actor = jnp.where(
        win_actor >= 0,
        jnp.take_along_axis(remaps, jnp.clip(win_actor, 0, hi), axis=1),
        win_actor)
    return value, has_value, win_actor, win_seq, win_counter


stack_register_tables = jax.jit(_stack_register_tables,
                                static_argnames=("out_cap",))


def _stack_element_tables(tables, remaps, n_elems, *, out_cap: int):
    """Per-doc element tables -> stacked (D, out_cap) columns with each
    doc's pending actor-rank remap folded in (`remap_actors` semantics
    per row: live slots 1..n_elems re-rank `actor`, any slot re-ranks a
    non-negative `win_actor`)."""
    (parent, ctr, actor, value, has_value, win_actor, win_seq,
     win_counter, chain) = _stack_padded(tables, _ELEM_FILLS, out_cap)
    hi = remaps.shape[1] - 1
    idx = jnp.arange(out_cap, dtype=jnp.int32)[None, :]
    live = (idx >= 1) & (idx <= n_elems[:, None])
    actor = jnp.where(live, jnp.take_along_axis(
        remaps, jnp.clip(actor, 0, hi), axis=1), actor)
    win_actor = jnp.where(win_actor >= 0, jnp.take_along_axis(
        remaps, jnp.clip(win_actor, 0, hi), axis=1), win_actor)
    return (parent, ctr, actor, value, has_value, win_actor, win_seq,
            win_counter, chain)


stack_element_tables = jax.jit(_stack_element_tables,
                               static_argnames=("out_cap",))


@partial(jax.jit, static_argnames=("out_cap",))
def stacked_map_round(value, has_value, win_actor, win_seq, win_counter,
                      ops, conflict_slots, *, out_cap: int):
    """`apply_map_round` vmapped over the doc axis: one program merges
    one causal round of EVERY participating map/table object. `ops`
    carries the whole round's op columns as one (D, 5, M) int32 upload
    (MOP_* rows; padding kind=-1, slot=out_cap), `conflict_slots` one
    (D, K) matrix. Returns the 5 stacked tables + (D, 7, M) slow_info."""
    def one(v, h, wa, ws, wc, o, cs):
        return _apply_map_round(
            v, h, wa, ws, wc, o[MOP_KIND].astype(jnp.int8), o[MOP_SLOT],
            o[MOP_VALUE], o[MOP_WIN_ACTOR], o[MOP_WIN_SEQ], cs,
            out_cap=out_cap)
    return jax.vmap(one)(value, has_value, win_actor, win_seq,
                         win_counter, ops, conflict_slots)


@partial(jax.jit,
         static_argnames=("out_cap", "expand_kind", "with_res",
                          "with_touch"))
def stacked_mixed_round(parent, ctr, actor, value, has_value, win_actor,
                        win_seq, win_counter, chain, desc, blob, res,
                        conflict_slots, touch, *, out_cap: int,
                        expand_kind: str, with_res: bool,
                        with_touch: bool):
    """`apply_mixed_round` vmapped over the doc axis: one program for one
    causal round of every text/list object sharing the group's static
    shape flags. Stacked operands: desc (D, 9, R), blob (D, N), res
    (D, 8, M), conflict_slots (D, K), touch (D, 3, T). Inactive docs
    ride with padding rows — their dense write window lands past their
    live region, exactly the DocSet convention (engine/doc_set.py)."""
    fn = partial(_apply_mixed_round, out_cap=out_cap,
                 expand_kind=expand_kind, with_res=with_res,
                 with_touch=with_touch)
    return jax.vmap(fn)(parent, ctr, actor, value, has_value, win_actor,
                        win_seq, win_counter, chain, desc, blob, res,
                        conflict_slots, touch)


@jax.jit
def stacked_scatter_registers(value, has_value, win_actor, win_seq,
                              win_counter, wb):
    """`scatter_registers_packed` vmapped over the doc axis: every doc's
    host-resolved slow-register writeback lands as ONE (D, 6, S) upload
    + one program (padding rows carry an OOB slot and drop)."""
    return jax.vmap(_scatter_registers_packed)(
        value, has_value, win_actor, win_seq, win_counter, wb)


@jax.jit
def stacked_pack_rows(*tables):
    """vmapped `pack_rows`: stacked (D, cap) columns -> one (D, K, cap)
    int32 matrix, so ONE d2h fetch re-seeds every participating doc's
    host mirror after a stacked apply."""
    return jnp.stack([t.astype(jnp.int32) for t in tables], axis=1)


@jax.jit
def unstack_rows(cols):
    """Split stacked (D, cap) columns back into per-doc row tuples — one
    program with D x K outputs, so re-binding every doc's tables after a
    stacked apply costs one dispatch, not one slice per (doc, table)."""
    D = cols[0].shape[0]
    return tuple(tuple(c[d] for c in cols) for d in range(D))


# ---------------------------------------------------------------------------
# Device-truth registry (obs/device_truth.py; INTERNALS §19)
#
# Every kernel the engine DISPATCHES (the module attributes the labeled
# `_count_dispatch` sites launch) is re-bound to an instrumented handle:
# one ~60 ns cache-size probe per launch detects compile events (wall
# time + shape signature + default device), and the registry lazily
# captures XLA cost/memory analysis once per compiled executable. The
# building-block kernels that only ever run INSIDE fused programs
# (expand_runs*, break_chains*, apply_residual*) are deliberately NOT
# wrapped — they never launch on their own from the engine, and wrapping
# them would record phantom compile events during the fused kernels'
# traces. Call sites are unchanged: the handles ARE the module
# attributes everyone already imports.
# ---------------------------------------------------------------------------

from ..obs import device_truth as _device_truth  # noqa: E402

apply_mixed_round, apply_mixed_round_donated = \
    _device_truth.instrument_pair(
        (apply_mixed_round, apply_mixed_round_donated), "apply_mixed_round")
apply_map_round = _device_truth.instrument(apply_map_round,
                                           "apply_map_round")
merge_and_materialize_dense, merge_and_materialize_dense_donated = \
    _device_truth.instrument_pair(
        (merge_and_materialize_dense, merge_and_materialize_dense_donated),
        "merge_and_materialize_dense")
(merge_and_materialize_dense_planned,
 merge_and_materialize_dense_planned_donated) = \
    _device_truth.instrument_pair(
        (merge_and_materialize_dense_planned,
         merge_and_materialize_dense_planned_donated),
        "merge_and_materialize_dense_planned")
scatter_registers = _device_truth.instrument(scatter_registers,
                                             "scatter_registers")
scatter_registers_packed, scatter_registers_packed_donated = \
    _device_truth.instrument_pair(
        (scatter_registers_packed, scatter_registers_packed_donated),
        "scatter_registers_packed")
pack_rows = _device_truth.instrument(pack_rows, "pack_rows")
remap_ranks = _device_truth.instrument(remap_ranks, "remap_ranks")
remap_actors = _device_truth.instrument(remap_actors, "remap_actors")
materialize_text = _device_truth.instrument(materialize_text,
                                            "materialize_text")
materialize_codes = _device_truth.instrument(materialize_codes,
                                             "materialize_codes")
materialize_text_planned = _device_truth.instrument(
    materialize_text_planned, "materialize_text_planned")
materialize_codes_planned = _device_truth.instrument(
    materialize_codes_planned, "materialize_codes_planned")
segment_visible_counts = _device_truth.instrument(
    segment_visible_counts, "segment_visible_counts")
stack_register_tables = _device_truth.instrument(
    stack_register_tables, "stack_register_tables")
stack_element_tables = _device_truth.instrument(
    stack_element_tables, "stack_element_tables")
stacked_map_round = _device_truth.instrument(stacked_map_round,
                                             "stacked_map_round")
stacked_mixed_round = _device_truth.instrument(stacked_mixed_round,
                                               "stacked_mixed_round")
stacked_scatter_registers = _device_truth.instrument(
    stacked_scatter_registers, "stacked_scatter_registers")
stacked_pack_rows = _device_truth.instrument(stacked_pack_rows,
                                             "stacked_pack_rows")
unstack_rows = _device_truth.instrument(unstack_rows, "unstack_rows")
