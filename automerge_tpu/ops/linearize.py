"""Batched RGA linearization: element tree -> dense list positions.

This replaces the reference's per-element tree walk (`getNext`/`getPrevious`/
`insertionsAfter`, /root/reference/backend/op_set.js:432-489) with a
fixed-iteration, data-parallel formulation that XLA tiles onto TPU:

1. **Sibling ordering** — one `lax.sort` over (parent, -ctr, -actor) puts each
   parent's children in descending Lamport order (the reference's
   `insertionsAfter` order: op_set.js:440-454), giving `first_child` and
   `next_sib` pointers via segment boundaries.
2. **Up-chain resolution** — `getNext`'s ancestor walk becomes pointer
   doubling on `f(i) = i if next_sib[i] else parent[i]`: log-depth instead of
   data-dependent loops.
3. **List ranking** — the successor chain (head -> first element -> ...) is
   ranked by pointer doubling (`dist += dist[nxt]; nxt = nxt[nxt]`), yielding
   each element's dense position in O(log n) gather rounds.

Everything is static-shape and jittable; total work O(n log n), depth O(log n).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HEAD = 0  # index 0 is the virtual head of the list


@partial(jax.jit, static_argnames=("P",))
def gather_spans(codes, spans, *, P: int):
    """Gather arbitrary [start, start+len) spans of `codes` into ONE dense
    buffer of bucketed static length `P` — the device half of the
    incremental text pull: D changed spans ship d2h as a single transfer
    of O(edits) bytes instead of the whole O(doc) codes buffer (or D
    separate round-trip-bound fetches).

    `spans` is a packed (2, D) int32 matrix [starts, lens] (padding rows:
    len 0). Output element j belongs to the span whose cumulative-length
    interval contains j (a searchsorted over the running ends — zero-
    length padding collapses to duplicate ends, which side='right' skips);
    positions past the live total return 0."""
    starts, lens = spans[0], spans[1]
    D = starts.shape[0]
    ends = jnp.cumsum(lens)
    total = ends[D - 1]
    begins = ends - lens
    j = jnp.arange(P, dtype=jnp.int32)
    span_of = jnp.clip(jnp.searchsorted(ends, j, side="right"), 0, D - 1)
    pos = starts[span_of] + (j - begins[span_of])
    C = codes.shape[0]
    pos = jnp.clip(jnp.where(j < total, pos, 0), 0, C - 1)
    out = codes[pos]
    return jnp.where(j < total, out, jnp.zeros((), codes.dtype))


def _doubling_steps(n: int) -> int:
    return max(1, math.ceil(math.log2(max(2, n))))


def pad_capacity(n: int, minimum: int = 16) -> int:
    """Bucket a live size to the next power of two, so retraces are rare."""
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


def _rga_linearize(parent: jax.Array, ctr: jax.Array, actor: jax.Array,
                   valid: jax.Array) -> jax.Array:
    """Compute RGA list positions for a padded element table.

    Index 0 is the virtual head; real elements live at indexes 1..n-1 (padded
    entries have valid=False). `parent[i]` is the element index whose position
    this element was inserted after (HEAD for list start). `ctr`/`actor` are
    the Lamport timestamp components (actor as an order-preserving dense rank:
    actor ids are assigned ranks in lexicographic string order, so integer
    comparison equals the reference's string comparison).

    Returns pos[i]: 0-based position of element i in the linearized list
    (tombstones included), with pos[HEAD] == -1 and pos of invalid entries
    >= number of live elements (they sort to the end).
    """
    n = parent.shape[0]
    steps = _doubling_steps(n)
    idx = jnp.arange(n, dtype=jnp.int32)

    is_elem = valid & (idx != HEAD)
    big = jnp.int32(n + 1)

    # --- 1. sibling sort: (parent, -ctr, -actor) ascending == per-parent
    # descending Lamport order; head/padding sort to the end ---
    sort_parent = jnp.where(is_elem, parent, big)
    neg_ctr = jnp.where(is_elem, -ctr, big)
    neg_actor = jnp.where(is_elem, -actor, big)
    p_s, _, _, idx_s = jax.lax.sort((sort_parent, neg_ctr, neg_actor, idx), num_keys=3)

    in_group = p_s < big
    same_next = jnp.concatenate([(p_s[1:] == p_s[:-1]) & in_group[1:], jnp.array([False])])
    next_in_sorted = jnp.concatenate([idx_s[1:], jnp.array([-1], dtype=idx_s.dtype)])

    next_sib = jnp.full((n,), -1, dtype=jnp.int32)
    next_sib = next_sib.at[idx_s].set(jnp.where(same_next, next_in_sorted, -1))

    group_start = jnp.concatenate([jnp.array([True]), p_s[1:] != p_s[:-1]]) & in_group
    first_child = jnp.full((n,), -1, dtype=jnp.int32)
    first_child = first_child.at[jnp.where(group_start, p_s, big - 1)].set(
        jnp.where(group_start, idx_s, -1), mode="drop")

    # --- 2. nearest ancestor-or-self with a next sibling (pointer doubling) ---
    has_next = next_sib >= 0
    safe_parent = jnp.where(is_elem, parent, HEAD)
    anc0 = jnp.where(has_next | (idx == HEAD), idx, safe_parent)
    anc = jax.lax.fori_loop(0, steps, lambda _, a: a[a], anc0)

    # --- 3. successor pointers: first child, else next sibling up the chain ---
    succ = jnp.where(first_child >= 0, first_child, next_sib[anc])

    # --- 4. list ranking by pointer doubling ---
    end = jnp.int32(n)  # virtual end-of-list sentinel
    nxt = jnp.where(succ >= 0, succ, end)
    nxt = jnp.where(is_elem | (idx == HEAD), nxt, idx)  # padding: self-loop
    nxt = jnp.concatenate([nxt, jnp.array([end], dtype=jnp.int32)])
    dist = jnp.where(is_elem | (idx == HEAD), 1, 0).astype(jnp.int32)
    dist = jnp.concatenate([dist, jnp.array([0], dtype=jnp.int32)])

    def rank_step(_, carry):
        dist, nxt = carry
        return dist + dist[nxt], nxt[nxt]

    dist, nxt = jax.lax.fori_loop(0, steps + 1, rank_step, (dist, nxt))

    # dist[i] = #chain nodes from i (inclusive) to end; head is position -1.
    pos = dist[HEAD] - dist[:n] - 1
    # push padding (and anything unreachable) after all live elements
    pos = jnp.where(is_elem, pos, jnp.where(idx == HEAD, -1, big))
    return pos


# jitted form the engine dispatches; the stacked kernel vmaps the CORE
# so its trace never re-enters the instrumented jit boundary below
rga_linearize = jax.jit(_rga_linearize)


@jax.jit
def stacked_linearize(parent: jax.Array, ctr: jax.Array, actor: jax.Array,
                      n_elems: jax.Array) -> jax.Array:
    """`rga_linearize` vmapped over a doc axis: one program computes every
    stacked document's RGA positions from its (D, cap) element tables.
    `n_elems` is the per-doc live count (slots 1..n_elems valid, slot 0
    the head); padding slots sort past the live elements exactly as in
    the single-doc kernel. The stacked multi-object executor
    (engine/stacked.py `_finalize`) runs this once per apply and ships
    the (D, cap) result inside the packed mirror fetch, so diff emission
    after a stacked round reads positions from host state instead of
    paying one linearize dispatch + sync per text object."""
    idx = jnp.arange(parent.shape[1], dtype=jnp.int32)[None, :]
    valid = idx <= n_elems[:, None]
    return jax.vmap(_rga_linearize)(parent, ctr, actor, valid)


@jax.jit
def rga_linearize_segments(parent: jax.Array, attach_off: jax.Array,
                           ctr: jax.Array, actor: jax.Array,
                           weight: jax.Array, valid: jax.Array) -> jax.Array:
    """Linearize a *condensed* RGA tree of chain segments.

    Real histories are dominated by typing runs: chains where each element's
    parent is the previous element and is its maximal child. Contracting those
    chains (host-side, vectorized) leaves a condensed tree with one node per
    segment — typically #concurrent-insertion-points nodes, orders of
    magnitude smaller than #elements. Segments are atomic in RGA order
    (children sorted descending means a chain continuation precedes any
    concurrent sibling's subtree), so element position = segment start +
    offset within segment.

    `parent[i]` is the segment whose element this segment's head was inserted
    after, `attach_off` the offset of that element within the parent segment,
    `ctr`/`actor` the head's Lamport key, `weight` the segment length.
    Children of a segment order by (-attach_off, -ctr, -actor): higher
    attachment points first (DFS backtracking order), then descending Lamport.

    Returns start[i]: 0-based position of segment i's first element.
    """
    n = parent.shape[0]
    steps = _doubling_steps(n)
    idx = jnp.arange(n, dtype=jnp.int32)

    is_seg = valid & (idx != HEAD)
    big = jnp.int32(n + 1)

    sort_parent = jnp.where(is_seg, parent, big)
    neg_off = jnp.where(is_seg, -attach_off, big)
    neg_ctr = jnp.where(is_seg, -ctr, big)
    neg_actor = jnp.where(is_seg, -actor, big)
    p_s, _, _, _, idx_s = jax.lax.sort(
        (sort_parent, neg_off, neg_ctr, neg_actor, idx), num_keys=4)

    in_group = p_s < big
    same_next = jnp.concatenate([(p_s[1:] == p_s[:-1]) & in_group[1:], jnp.array([False])])
    next_in_sorted = jnp.concatenate([idx_s[1:], jnp.array([-1], dtype=idx_s.dtype)])
    next_sib = jnp.full((n,), -1, dtype=jnp.int32)
    next_sib = next_sib.at[idx_s].set(jnp.where(same_next, next_in_sorted, -1))

    group_start = jnp.concatenate([jnp.array([True]), p_s[1:] != p_s[:-1]]) & in_group
    first_child = jnp.full((n,), -1, dtype=jnp.int32)
    first_child = first_child.at[jnp.where(group_start, p_s, big - 1)].set(
        jnp.where(group_start, idx_s, -1), mode="drop")

    has_next = next_sib >= 0
    safe_parent = jnp.where(is_seg, parent, HEAD)
    anc = jnp.where(has_next | (idx == HEAD), idx, safe_parent)
    for _ in range(steps):
        anc = anc[anc]

    succ = jnp.where(first_child >= 0, first_child, next_sib[anc])

    end = jnp.int32(n)
    nxt = jnp.where(succ >= 0, succ, end)
    nxt = jnp.where(is_seg | (idx == HEAD), nxt, idx)
    nxt = jnp.concatenate([nxt, jnp.array([end], dtype=jnp.int32)])
    dist = jnp.where(is_seg, weight, 0).astype(jnp.int32)
    dist = jnp.concatenate([dist, jnp.array([0], dtype=jnp.int32)])
    for _ in range(steps + 1):
        dist = dist + dist[nxt]
        nxt = nxt[nxt]

    # dist[i] = total weight from segment i (inclusive) to the end
    start = dist[HEAD] - dist[:n]
    return jnp.where(is_seg, start, jnp.where(idx == HEAD, 0, big))


# --- device-truth registry (obs/device_truth.py; INTERNALS §19) ------------
# the three linearize-side kernels the engine dispatches under labels
# ("rga_linearize", "gather_spans", "stacked_linearize") get the same
# compile/cost instrumentation as the ingest kernels; rga_linearize_segments
# is host-experimented only and stays unwrapped until a label dispatches it
from ..obs import device_truth as _device_truth  # noqa: E402

rga_linearize = _device_truth.instrument(rga_linearize, "rga_linearize")
gather_spans = _device_truth.instrument(gather_spans, "gather_spans")
stacked_linearize = _device_truth.instrument(stacked_linearize,
                                             "stacked_linearize")
