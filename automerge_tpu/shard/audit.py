"""Compiled-HLO collective audit of the sharded commit path.

The serving tier's scaling claim rests on one invariant: the commit
path moves ZERO bytes between devices. The lanes make that structural
(every lane program is single-device), and this module proves the
stronger SPMD formulation the mesh design rests on:
the PR-7 stacked round kernels, lowered with every operand sharded over
a doc-only mesh, compile to modules containing **no all-reduce /
all-gather / all-to-all / collective-permute / reduce-scatter** — XLA's
partitioner agrees the doc axis is embarrassingly parallel for the real
round kernels, not just for the simplified `merge_step` the earlier
evidence audited. `bench.py --sharded` runs this audit and records the
counts in the cfg12 session row; tests assert the zero.
"""

from __future__ import annotations

import re

import numpy as np

COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


def count_collectives(lowerable, args) -> dict:
    """Compile and count collective ops in the HLO text (zero-count
    keys dropped — an empty dict IS the pass)."""
    hlo = lowerable.lower(*args).compile().as_text()
    counts = {c: len(re.findall(rf"\b{c}\b", hlo)) for c in COLLECTIVES}
    return {c: n for c, n in counts.items() if n}


def doc_mesh(n_devices: int = None):
    """A doc-axis-only mesh over the available devices."""
    import jax
    from jax.sharding import Mesh
    devices = jax.devices()
    if n_devices:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("doc",))


def commit_path_collectives(mesh=None, docs_per_device: int = 2,
                            cap: int = 256) -> dict:
    """Audit the three stacked commit-path kernels over a doc-sharded
    mesh: {kernel name: {collective: count}} (empty inner dicts = the
    zero-collective invariant holds). Shapes are small — the audit is
    about partitioning structure, not scale."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..ops import ingest as K

    if mesh is None:
        mesh = doc_mesh()
    shard = NamedSharding(mesh, P("doc"))
    D = mesh.shape["doc"] * docs_per_device
    M, R, N, Kc, T, S = 64, 64, 256, 64, 64, 64

    def put(arr):
        # lowering needs shapes and shardings only — no device buffers,
        # so the audit also compiles for a described (unattached) mesh
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=shard)

    i32 = np.int32
    elem_tables = (put(np.zeros((D, cap), i32)),          # parent
                   put(np.zeros((D, cap), i32)),          # ctr
                   put(np.zeros((D, cap), i32)),          # actor
                   put(np.zeros((D, cap), i32)),          # value
                   put(np.zeros((D, cap), bool)),         # has_value
                   put(np.full((D, cap), -1, i32)),       # win_actor
                   put(np.zeros((D, cap), i32)),          # win_seq
                   put(np.zeros((D, cap), bool)),         # win_counter
                   put(np.zeros((D, cap), bool)))         # chain
    reg_tables = (put(np.zeros((D, cap), i32)),           # value
                  put(np.zeros((D, cap), bool)),          # has_value
                  put(np.full((D, cap), -1, i32)),        # win_actor
                  put(np.zeros((D, cap), i32)),           # win_seq
                  put(np.zeros((D, cap), bool)))          # win_counter

    out = {}
    # one causal round of every map/table object on the mesh
    ops = np.zeros((D, 5, M), i32)
    ops[:, K.MOP_KIND, :] = -1
    ops[:, K.MOP_SLOT, :] = cap
    conflict = np.full((D, Kc), cap, i32)
    map_fn = jax.jit(
        lambda *a: K.stacked_map_round(*a, out_cap=cap),
        in_shardings=(shard,) * 7, out_shardings=shard)
    out["stacked_map_round"] = count_collectives(
        map_fn, reg_tables + (put(ops), put(conflict)))

    # one causal round of every text/list object, the full static shape
    # (dense expansion + residuals + touches — the worst case)
    desc = np.zeros((D, 9, R), i32)
    desc[:, K.DESC_ELEM_BASE, :] = N
    blob = np.zeros((D, N), i32)
    res = np.zeros((D, 8, M), i32)
    res[:, 0, :] = -1
    res[:, K.RES_SLOT, :] = cap
    res[:, K.RES_NEW_SLOT, :] = cap
    touch = np.zeros((D, 3, T), i32)
    touch[:, 1:, :] = -1
    mixed_fn = jax.jit(
        lambda *a: K.stacked_mixed_round(
            *a, out_cap=cap, expand_kind="dense", with_res=True,
            with_touch=True),
        in_shardings=(shard,) * 14, out_shardings=shard)
    out["stacked_mixed_round"] = count_collectives(
        mixed_fn, elem_tables + (put(desc), put(blob), put(res),
                                 put(conflict), put(touch)))

    # every object's host-resolved slow residue, one stacked scatter
    wb = np.zeros((D, 6, S), i32)
    wb[:, 0, :] = cap
    scatter_fn = jax.jit(
        lambda *a: K.stacked_scatter_registers(*a),
        in_shardings=(shard,) * 6, out_shardings=shard)
    out["stacked_scatter_registers"] = count_collectives(
        scatter_fn, reg_tables + (put(wb),))

    # ISSUE 17: the fused megakernel (both lanes in one program) and the
    # combined scatter must stay embarrassingly parallel over the doc
    # axis too. Audited on the "lax" scan rung — the audit is about the
    # SPMD partitioner's view of the doc axis, and the Pallas rung lowers
    # the same per-shard program bodies.
    from ..ops import fused_round as F
    fused_fn = jax.jit(
        lambda *a: F.fused_stacked_round(
            *a, map_cap=cap, text_cap=cap, with_map=True, with_text=True,
            mode="lax"),
        in_shardings=(shard,) * 21, out_shardings=shard)
    out["fused_stacked_round"] = count_collectives(
        fused_fn,
        reg_tables + (put(ops), put(conflict)) + elem_tables
        + (put(desc), put(blob), put(res), put(conflict), put(touch)))
    fscatter_fn = jax.jit(
        lambda *a: F.fused_scatter_registers(
            *a, with_map=True, with_text=True),
        in_shardings=(shard,) * 12, out_shardings=shard)
    out["fused_scatter_registers"] = count_collectives(
        fscatter_fn,
        reg_tables + (put(wb),) + elem_tables[3:8] + (put(wb),))

    # ISSUE 18 (the PR-17 leftover): the ring-commit megakernels — the
    # whole common-case merge round (dense expansion + materialization)
    # in ONE program, the pipelined ring's steady-state commit — must
    # also stay embarrassingly parallel over the doc axis. The raw
    # per-doc kernels vmap over the leading doc dimension.
    segplan = np.zeros((D, 4, S), i32)
    planned_fn = jax.jit(
        jax.vmap(lambda *a: K._merge_and_materialize_dense_planned(
            *a, out_cap=cap, S=S, as_u8=True, L=cap)),
        in_shardings=(shard,) * 12, out_shardings=shard)
    out["merge_and_materialize_dense_planned"] = count_collectives(
        planned_fn, elem_tables + (put(desc), put(blob), put(segplan)))
    dense_fn = jax.jit(
        jax.vmap(lambda *a: K._merge_and_materialize_dense(
            *a, out_cap=cap, S=S, as_u8=True, L=cap)),
        in_shardings=(shard,) * 11, out_shardings=shard)
    out["merge_and_materialize_dense"] = count_collectives(
        dense_fn, elem_tables + (put(desc), put(blob)))

    # ISSUE 19: the fused-tier ring-commit megakernels (the production
    # route of the pipelined commit path under AMTPU_FUSED_ROUNDS) must
    # hold the same invariant as their XLA comparators above. Audited on
    # the "lax" scan rung, same rationale as the fused stacked round.
    fused_planned_fn = jax.jit(
        jax.vmap(lambda *a: F._fused_commit_planned_core(
            *a, out_cap=cap, S=S, as_u8=True, L=cap, mode="lax")),
        in_shardings=(shard,) * 12, out_shardings=shard)
    out["fused_commit_round_planned"] = count_collectives(
        fused_planned_fn, elem_tables + (put(desc), put(blob),
                                         put(segplan)))
    fused_commit_fn = jax.jit(
        jax.vmap(lambda *a: F._fused_commit_core(
            *a, out_cap=cap, S=S, as_u8=True, L=cap, mode="lax")),
        in_shardings=(shard,) * 11, out_shardings=shard)
    out["fused_commit_round"] = count_collectives(
        fused_commit_fn, elem_tables + (put(desc), put(blob)))
    return out


def assert_zero_collectives(audit: dict):
    """The acceptance form: every audited commit-path kernel compiled
    with zero cross-device collectives."""
    bad = {k: v for k, v in audit.items() if v}
    assert not bad, (
        f"sharded commit path compiled with collectives: {bad} — the "
        "doc axis is no longer communication-free")
