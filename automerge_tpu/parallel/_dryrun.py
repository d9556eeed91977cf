"""Multi-chip dry-run body: the full engine over a virtual (doc, elem) mesh.

Run via ``__graft_entry__.dryrun_multichip``, which execs this in a
subprocess whose environment sets the virtual CPU device count before
JAX initializes a backend (XLA reads the flag only then).
"""

from __future__ import annotations


def run(n_devices: int) -> None:
    """Run the REAL multi-doc engine over an n-device (doc, elem) mesh:
    stacked element tables sharded doc-data-parallel and elem-sequence-
    parallel, one vmapped SPMD program per round (ingest) plus one for
    materialization, with XLA inserting the ICI collectives. Executes a
    full merge + materialize on tiny shapes and checks the output."""
    import jax

    assert len(jax.devices()) >= n_devices, (
        f"need {n_devices} devices, have {jax.devices()}")

    from automerge_tpu.engine import DeviceTextDocSet, TextChangeBatch
    from automerge_tpu.parallel import make_mesh

    mesh = make_mesh(n_devices)
    n_docs = mesh.shape["doc"] * 2

    def typing(actor, seq, text, obj, start=1, after="_head", deps=None):
        ops, key = [], after
        for i, c in enumerate(text):
            ops += [{"action": "ins", "obj": obj, "key": key,
                     "elem": start + i},
                    {"action": "set", "obj": obj, "key":
                     f"{actor}:{start + i}", "value": c}]
            key = f"{actor}:{start + i}"
        return {"actor": actor, "seq": seq, "deps": deps or {}, "ops": ops}

    ids = [f"doc{i}" for i in range(n_docs)]
    ds = DeviceTextDocSet(ids, capacity=mesh.shape["elem"] * 16, mesh=mesh)
    # round 1: two concurrent writers per doc from the head
    ds.apply_batches({o: TextChangeBatch.from_changes(
        [typing("alice", 1, f"hi{i % 10}xxxx!", o),
         typing("bob", 1, "concurrent", o)], o)
        for i, o in enumerate(ids)})
    # round 2: alice continues her own run (chain continuation + breaks)
    ds.apply_batches({o: TextChangeBatch.from_changes(
        [typing("alice", 2, "++", o, start=9, after="alice:8")], o)
        for o in ids})
    texts = ds.texts()
    assert len(texts) == n_docs
    assert all(len(t) == 20 for t in texts.values()), texts
    assert all("concurrent" in t and "++" in t for t in texts.values())


if __name__ == "__main__":
    import sys

    from automerge_tpu._env import setup_compile_cache

    setup_compile_cache()
    run(int(sys.argv[1]) if len(sys.argv) > 1 else 8)
    print("dryrun_multichip: OK")
