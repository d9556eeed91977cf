"""Cross-doc planning smoke: parity + budget assert + schema-valid trace.

Usage: python -m benchmarks.cfg12t_smoke

The CI entry for the cross-doc columnar planning tier (engine/cross_doc
+ the batch-update range index, INTERNALS §16). One small serving-shaped
text population runs three ways:

1. AMTPU_CROSS_DOC_PLAN=1 + AMTPU_BATCH_INDEX=1 — the cross-doc path,
   with the stacked round budget AND the index bulk-update budget (one
   merge per doc per round) asserted, and the sharing stats checked
   (schedules/detections/ranks actually shared, not merely enabled);
2. AMTPU_CROSS_DOC_PLAN=0 + AMTPU_BATCH_INDEX=0 — the per-doc planner +
   sorted-insert comparator, committed state asserted byte-identical
   (text + clock + flattened index rows);
3. a traced cross-doc run: the plan/cross_doc, plan/detect_runs,
   plan/index_merge and plan/rank_resolve spans must export as
   schema-valid Chrome trace JSON (obs.export.validate_chrome_trace), so
   the cfg12t span-derived terms stay Perfetto-loadable.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")


N_DOCS = 24
N_ROUNDS = 3
OPS_PER_DOC = 8


def _run(cross: str, bidx: str):
    from automerge_tpu.engine import stacked
    from automerge_tpu.engine.text_doc import DeviceTextDoc
    from bench import _sharded_text_round

    os.environ["AMTPU_CROSS_DOC_PLAN"] = cross
    os.environ["AMTPU_BATCH_INDEX"] = bidx
    doc_ids = [f"sm-{i:03d}" for i in range(N_DOCS)]
    docs = {d: DeviceTextDoc(d, capacity=1024) for d in doc_ids}
    seed = _sharded_text_round(doc_ids, 1, 1, 64)
    st = stacked.apply_stacked([(docs[k], v) for k, v in seed.items()])
    assert st, "seed round fell off the stacked path"
    last = None
    for r in range(N_ROUNDS):
        chunk = _sharded_text_round(doc_ids, 2 + r,
                                    33 + r * (OPS_PER_DOC // 2),
                                    OPS_PER_DOC)
        last = stacked.apply_stacked([(docs[k], v)
                                      for k, v in chunk.items()])
        assert last, f"round {r} fell off the stacked path"
        stacked.assert_round_budget(last)
        assert last["index_merges"] == last["text_plans"] == N_DOCS, last
    state = {k: (d.text(), dict(d.clock),
                 tuple(r.tobytes() for r in d.index.rows()))
             for k, d in docs.items()}
    return state, last


def main(argv=None):
    from automerge_tpu import obs
    from automerge_tpu.obs.export import validate_chrome_trace

    state_on, st_on = _run("1", "1")
    cd = st_on["cross_doc"]
    assert cd["groups"] == 1 and cd["docs"] == N_DOCS, cd
    assert cd["sched_shared"] == N_DOCS - 1, cd
    assert cd["detect_shared"] == N_DOCS, cd
    assert cd["rank_seeded"] == N_DOCS, cd

    state_off, st_off = _run("0", "0")
    assert "cross_doc" not in st_off, st_off
    assert state_on == state_off, "cross-doc planner diverged"

    # traced run: the §16 spans must be schema-valid Chrome trace JSON
    obs.enable()
    try:
        _run("1", "1")
        path = os.environ.get("AMTPU_TRACE_OUT", "cfg12t_trace.json")
        obs.write_trace(path)
    finally:
        obs.disable()
    print("trace:", validate_chrome_trace(path))
    obj = json.load(open(path))
    events = obj["traceEvents"] if isinstance(obj, dict) else obj
    names = {(e.get("cat"), e.get("name")) for e in events
             if isinstance(e, dict)}
    for want in (("plan", "cross_doc"), ("plan", "detect_runs"),
                 ("plan", "index_merge"), ("plan", "rank_resolve")):
        assert want in names, (want, sorted(names)[:40])

    print(json.dumps({
        "smoke": "cfg12t", "docs": N_DOCS, "rounds": N_ROUNDS,
        "cross_doc": cd,
        "index_merges": st_on["index_merges"],
        "text_plans": st_on["text_plans"],
        "parity": "byte-identical",
    }))
    return 0


if __name__ == "__main__":
    from automerge_tpu._env import setup_compile_cache

    setup_compile_cache()
    sys.exit(main())
