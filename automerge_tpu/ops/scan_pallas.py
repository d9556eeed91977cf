"""Pallas TPU kernels: fused multi-scan for text materialization.

`_materialize_core` (ops/ingest.py) needs three prefix scans over the element
tables — segment ranks (cumsum of segment starts), segment heads (cummax),
and the visibility prefix-sum that replaces the reference's order-statistic
skip list (/root/reference/backend/skip_list.js:260-305). XLA emits each as
its own HBM round trip plus the elementwise producers; this kernel computes
all three in ONE pass: each grid step loads a (ROWS, LANES) tile into VMEM,
derives `seg_start`/`vis` on the VPU, scans within the tile, and carries the
running (rank, head, vis) totals across the sequential TPU grid in SMEM
scratch — the standard single-pass carry pattern (grid steps execute in
order on a TPU core).

The kernel is shape-generic: inputs pad internally to a ROWS*LANES tile
multiple and outputs slice back to the caller's capacity. `interpret=True`
runs it on CPU for the parity tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS, LANES = 8, 128
TILE = ROWS * LANES


def _scan_add(x, axis):
    """Inclusive prefix-sum along `axis` via log-shift adds (Mosaic has no
    cumsum primitive; pltpu.roll + mask is the standard in-kernel scan)."""
    n = x.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    k = 1
    while k < n:
        x = x + jnp.where(pos >= k, pltpu.roll(x, k, axis), 0)
        k *= 2
    return x


def _scan_max(x, axis):
    """Inclusive prefix-max along `axis`, same shift pattern."""
    n = x.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    k = 1
    while k < n:
        x = jnp.maximum(x, jnp.where(pos >= k, pltpu.roll(x, k, axis),
                                     jnp.iinfo(jnp.int32).min))
        k *= 2
    return x


def _tile_scans(seg_start, vis, base):
    """Within-tile inclusive scans in row-major flat order.

    Returns (rank_incl, cumvis, flat_idx)."""
    # scan along lanes, then add exclusive row-total prefixes
    cs = _scan_add(seg_start, 1)
    row_tot = cs[:, -1:]
    row_pre = _scan_add(row_tot, 0) - row_tot
    rank = cs + row_pre

    cv = _scan_add(vis, 1)
    vrow_tot = cv[:, -1:]
    vrow_pre = _scan_add(vrow_tot, 0) - vrow_tot
    cumvis = cv + vrow_pre

    flat = (base + LANES * jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)
            + jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1))
    return rank, cumvis, flat


def _fused_kernel(n_ref, chain_ref, has_ref, rank_ref, head_ref, cv_ref,
                  carry):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        carry[0] = 0   # segment-rank running total
        carry[1] = 0   # running segment head (cummax)
        carry[2] = 0   # visibility running total

    n_elems = n_ref[0]
    base = n_ref[1] + i * TILE   # n_ref[1]: the caller's global slot offset
    chain = chain_ref[:]
    has = has_ref[:]

    flat0 = (base + LANES * jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)
             + jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1))
    is_elem = (flat0 >= 1) & (flat0 <= n_elems)
    seg_start = (is_elem & ~chain).astype(jnp.int32)
    vis = (is_elem & has).astype(jnp.int32)

    rank, cumvis, flat = _tile_scans(seg_start, vis, base)
    rank_ref[:] = rank + carry[0]
    cv_ref[:] = cumvis + carry[2]

    # segment head: prefix-max of (seg_start ? flat_idx : 0) in flat order,
    # same two-level trick with max instead of add
    cand = jnp.where(seg_start > 0, flat, 0)
    cm = _scan_max(cand, 1)
    row_max = cm[:, -1:]
    rp_incl = _scan_max(row_max, 0)
    pos0 = jax.lax.broadcasted_iota(jnp.int32, rp_incl.shape, 0)
    row_pre = jnp.where(pos0 >= 1, pltpu.roll(rp_incl, 1, 0), 0)
    head = jnp.maximum(cm, jnp.maximum(row_pre, carry[1]))
    head_ref[:] = head

    carry[0] = carry[0] + jnp.sum(seg_start)
    carry[1] = jnp.maximum(carry[1], jnp.max(cand))
    carry[2] = carry[2] + jnp.sum(vis)


@partial(jax.jit, static_argnames=("interpret",))
def fused_segment_scans(chain, has_value, n_elems, base=0, *,
                        interpret: bool = False):
    """-> (rank_incl, seg_head, cumvis), all int32[C], inclusive scans.

    rank_incl[i] = number of segment starts at slots <= i (the condensed-tree
    node id of i's segment); seg_head[i] = slot of the latest segment head
    <= i; cumvis[i] = number of visible elements at slots <= i (the
    skip-list-index replacement). Any capacity works; inputs pad internally
    to a tile multiple (engine buckets are 2^k or 3*2^(k-1), not all tile
    multiples) and the outputs are sliced back.

    `base` is the caller's global slot offset: a shard of a larger table
    passes its start so head/is_elem masking use GLOBAL slot numbers (the
    sharded form exchanges carries across shards — `sharded_fused_scans`).
    """
    C0 = chain.shape[0]
    C = ((C0 + TILE - 1) // TILE) * TILE
    if C != C0:
        pad = ((0, C - C0),)
        chain = jnp.pad(chain, pad)
        has_value = jnp.pad(has_value, pad)
    grid = C // TILE
    shape2d = (grid * ROWS, LANES)

    out = pl.pallas_call(
        _fused_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct(shape2d, jnp.int32)] * 3,
        scratch_shapes=[pltpu.SMEM((3,), jnp.int32)],
        interpret=interpret,
    )(jnp.stack([jnp.asarray(n_elems, jnp.int32),
                 jnp.asarray(base, jnp.int32)]),
      chain.reshape(shape2d), has_value.reshape(shape2d))
    rank, head, cumvis = (o.reshape(C)[:C0] for o in out)
    return rank, head, cumvis


def _multi_scan_kernel(x_ref, o_ref, carry):
    """K independent row-wise prefix sums, one (K, ROWS, LANES) tile per
    grid step, per-channel running totals carried in SMEM."""
    i = pl.program_id(0)
    n_chan = x_ref.shape[0]

    @pl.when(i == 0)
    def _():
        for k in range(n_chan):
            carry[k] = 0

    for k in range(n_chan):
        x = x_ref[k]
        cs = _scan_add(x, 1)
        row_tot = cs[:, -1:]
        row_pre = _scan_add(row_tot, 0) - row_tot
        o_ref[k] = cs + row_pre + carry[k]
        carry[k] = carry[k] + jnp.sum(x)


@partial(jax.jit, static_argnames=("interpret",))
def multi_scan(x, *, interpret: bool = False):
    """Row-wise inclusive prefix sum of an int32 (K, N) matrix in ONE
    kernel: the fused-round expansion (ops/fused_round.py) scans its six
    boundary-delta channels here instead of six XLA cumsum programs. Same
    tile/carry structure as `fused_segment_scans`; any N works (internal
    pad to a TILE multiple, outputs sliced back)."""
    K, N0 = x.shape
    N = ((N0 + TILE - 1) // TILE) * TILE
    if N != N0:
        x = jnp.pad(x, ((0, 0), (0, N - N0)))
    grid = N // TILE
    shape3d = (K, grid * ROWS, LANES)

    out = pl.pallas_call(
        _multi_scan_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((K, ROWS, LANES), lambda i: (0, i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((K, ROWS, LANES), lambda i: (0, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(shape3d, jnp.int32),
        scratch_shapes=[pltpu.SMEM((K,), jnp.int32)],
        interpret=interpret,
    )(x.astype(jnp.int32).reshape(shape3d))
    return out.reshape(K, N)[:, :N0]


def sharded_fused_scans(mesh, chain, has_value, n_elems, *, axis: str = "elem",
                        interpret: bool = False):
    """`fused_segment_scans` over an element-sharded table: each device
    scans its shard locally (SMEM carries within the shard), then the three
    per-shard totals exchange over ICI — one tiny all_gather — and offset
    the local results. This is the sharded long-sequence form promised in
    ops/scan.py: the per-block carry becomes an explicit collective instead
    of XLA gathering the whole table for an unpartitionable scan.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    C = chain.shape[0]
    n_shards = mesh.shape[axis]
    if C % n_shards:
        raise ValueError(f"capacity {C} must divide over {n_shards} shards")

    def local(chain_s, has_s, n_elems_s):
        idx = jax.lax.axis_index(axis)
        base = idx * (C // n_shards)
        rank, head, cumvis = fused_segment_scans(
            chain_s, has_s, n_elems_s[0], base, interpret=interpret)
        totals = jnp.stack([rank[-1], head[-1], cumvis[-1]])
        # the carry exchange: every shard learns every prior shard's totals
        all_tot = jax.lax.all_gather(totals, axis)        # (n_shards, 3)
        pre = jnp.where(jnp.arange(n_shards)[:, None] < idx, all_tot, 0)
        rank_pre = jnp.sum(pre[:, 0])
        vis_pre = jnp.sum(pre[:, 2])
        head_pre = jnp.max(jnp.where(
            jnp.arange(n_shards) < idx, all_tot[:, 1], 0))
        return (rank + rank_pre, jnp.maximum(head, head_pre),
                cumvis + vis_pre)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axis), P(axis), P()),
                   out_specs=(P(axis), P(axis), P(axis)),
                   # pallas_call outputs carry no vma/replication info
                   check_vma=False)
    return fn(chain, has_value, jnp.asarray([n_elems], jnp.int32))
