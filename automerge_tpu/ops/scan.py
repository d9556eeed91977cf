"""Prefix-scan utilities: the device replacement for the skip list.

The reference maps elemId <-> visible index through an order-statistic skip
list (/root/reference/backend/skip_list.js). On device, the same queries are a
prefix sum over visibility flags in linearized order: `visible_index[i]` is
the rank of element i among visible elements — O(n) work, log depth, and it
batches over whole documents.

`visible_index` runs on the XLA path (cumsum fuses well). `scan_pallas.py`
holds the fused Pallas variant: one kernel computes the segment-rank,
segment-head, and visibility scans in a single HBM pass with SMEM carries
(designed for bandwidth parity with XLA's fused scans; the on-chip A/B
lives in profile_bench.py --pallas - and kept
as the building block for the sharded long-sequence case,
where the per-block carries become explicit ICI exchanges).
"""

from __future__ import annotations

import jax.numpy as jnp


def visible_index(pos: jnp.ndarray, visible: jnp.ndarray, capacity: int | None = None):
    """Rank among visible elements, by linearized position.

    pos: element positions from rga_linearize (head=-1, padding large).
    visible: bool per element (has at least one surviving value op).
    Returns (vis_rank, n_visible): vis_rank[i] = index of element i in the
    user-facing list (only meaningful where visible[i]), n_visible = total.
    """
    n = pos.shape[0]
    capacity = capacity or n
    # scatter visibility into position order, prefix-sum, gather back
    by_pos = jnp.zeros((capacity + 1,), dtype=jnp.int32)
    slot = jnp.clip(pos, 0, capacity)
    by_pos = by_pos.at[slot].add(visible.astype(jnp.int32))
    cum = jnp.cumsum(by_pos)
    # exclusive rank of the element at position p (clipped padding slots can
    # collide, but their ranks are never read)
    vis_rank = cum[slot] - by_pos[slot]
    n_visible = cum[capacity]
    return vis_rank, n_visible


def segment_starts(sorted_keys: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask of group starts in a sorted key array."""
    return jnp.concatenate([jnp.array([True]), sorted_keys[1:] != sorted_keys[:-1]])
