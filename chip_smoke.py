"""The main path on one TPU v5e chip: CRDT merge + sharded serving.

Run from the root of a checkout, in one process that holds the chip:

    python chip_smoke.py              # one chip, phases a-d
    python chip_smoke.py --chips 4    # the four-chip serving path only
    python chip_smoke.py --rehearse   # same phases, tiny, on the CPU

Phases (each checked against a reference; a failure raises, the process
exits non-zero and the result line is never printed):

  a  oracle parity   the facade/oracle scenarios (merge_fanout,
                     conflict_registers, causal_rounds) through
                     DeviceTextDoc, compared element for element with the
                     backend/op_set.py oracle.
  b  headline merge  BASELINE.md config 5 at full shape: a 1M-character
                     base text plus 10,000 concurrent 1,000-op changes
                     through prepare_batch/commit_prepared with fused
                     rounds on, checked character for character against
                     the same delivery on the XLA comparator leg
                     (AMTPU_FUSED_ROUNDS=0) in this process.
  c  sharded round   the cfg12 population (5,120 text docs over 8 lanes)
                     through ShardedDocSet.deliver_rounds; every
                     mesh.capture(d) byte-equal to the per-object path
                     (AMTPU_STACKED_ROUNDS=0, one lane).
  d  device checks   platform "tpu", fused_mode() == "pallas", and a
                     tpu_custom_call in the compiled HLO of the fused
                     stacked round phase c ran.

``--chips 4`` runs only the four-chip serving tier: 4 lanes over
jax.devices()[:4] against an n_shards=1 mesh, byte-equal captures, every
doc's device tables on its lane's device, and zero collectives from
shard/audit.commit_path_collectives().

``--rehearse`` runs the same phases at tiny sizes on the CPU with the
Pallas interpreter (tier-1 runs it). Without it, any platform other than
"tpu" is an error. The last line of stdout is the JSON result and
nothing else.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# config 5 (bench.py) and the cfg12 serving population (bench.py
# measure_sharded: 8 shards x 640 docs), full size and rehearsal size
FULL = {"base": 1_000_000, "actors": 10_000, "ops": 1_000,
        "lanes": 8, "docs_per_lane": 640}
TINY = {"base": 2_000, "actors": 40, "ops": 100,
        "lanes": 4, "docs_per_lane": 6}
# slots per text doc: the largest bucket that keeps a lane of the
# four-chip population (1,280 docs) under the stacked path's cell gate
# (engine/stacked._max_cells: docs x 9 x cap <= 2^23)
DOC_CAPACITY = 512


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, Pallas interpret mode")
    return ap.parse_args(argv)


def log(msg):
    print(msg, flush=True)


def timed(name, fn, *a, **k):
    t0 = time.perf_counter()
    out = fn(*a, **k)
    log(f"phase {name}: {time.perf_counter() - t0:.3f} s")
    return out


# -- phase a: oracle parity --------------------------------------------------

def _check_parity(name, doc, eng):
    from test_engine_parity import oracle_view
    o_vals, o_ids, o_confs_raw = oracle_view(doc)
    o_confs = [{c["actor"]: c["value"] for c in (oc or [])}
               for oc in o_confs_raw]
    e_vals, e_ids = eng.values(), eng.elem_ids()
    e_confs = [eng.conflicts_at(i) or {} for i in range(len(e_vals))]
    for what, got, want in (("values", e_vals, o_vals),
                            ("elem_ids", e_ids, o_ids),
                            ("conflicts", e_confs, o_confs)):
        if got != want:
            k = next((i for i, (g, w) in enumerate(zip(got, want))
                      if g != w), min(len(got), len(want)))
            raise AssertionError(
                f"{name}/{what}: len {len(got)} vs {len(want)}, first "
                f"mismatch at {k}")
    log(f"  parity {name}: {len(e_vals)} elems")


def oracle_parity():
    import random

    import automerge_tpu as am
    from automerge_tpu import Text
    from automerge_tpu.engine import DeviceTextDoc
    from test_engine_parity import text_changes_of

    # merge_fanout: 30 actors splice runs + deletes into a shared base,
    # delivered as ONE bulk round (RGA sibling order, runs, tombstones)
    rng = random.Random(7)
    base = am.change(am.init("base"),
                     lambda d: d.__setitem__("t", Text("x" * 200)))
    merged = base
    for a in range(30):
        peer = am.merge(am.init(f"actor-{a:02d}"), base)
        ins_at, del_at = rng.randrange(0, 150), rng.randrange(0, 100)
        run = f"[{a:02d}:" + "ab" * 13 + "]"

        def edit(d, ins_at=ins_at, run=run, del_at=del_at):
            d["t"].insert_at(ins_at, *run)
            d["t"].delete_at(del_at, 3)
        merged = am.merge(merged, am.change(peer, edit))
    changes, obj_id = text_changes_of(merged)
    eng = DeviceTextDoc(obj_id)
    eng.apply_changes(changes)
    _check_parity("merge_fanout", merged, eng)

    # conflict_registers: 20 actors set the same positions -> LWW winner
    # + full conflict sets
    base = am.change(am.init("base"),
                     lambda d: d.__setitem__("t", Text("y" * 60)))
    merged = base
    for a in range(20):
        peer = am.merge(am.init(f"w{a:02d}"), base)
        merged = am.merge(merged, am.change(peer, lambda d, a=a: [
            d["t"].set(i, chr(ord("A") + (a + i) % 26)) for i in range(10)]))
    changes, obj_id = text_changes_of(merged)
    eng = DeviceTextDoc(obj_id)
    eng.apply_changes(changes)
    _check_parity("conflict_registers", merged, eng)

    # causal_rounds: round 2 delivered before round 1 queues, round 1
    # releases it
    doc = am.change(am.init("r1"),
                    lambda d: d.__setitem__("t", Text("hello world")))
    doc = am.change(doc, lambda d: d["t"].insert_at(5, *", dear"))
    doc = am.change(doc, lambda d: d["t"].delete_at(0, 2))
    changes, obj_id = text_changes_of(doc)
    eng = DeviceTextDoc(obj_id)
    eng.apply_changes(changes[2:])
    eng.apply_changes(changes[:2])
    _check_parity("causal_rounds", doc, eng)


# -- phase b: the config-5 headline merge ------------------------------------

def _merge_leg(batch, n_base, fused: bool):
    import bench as B
    from automerge_tpu.engine import DeviceTextDoc

    os.environ["AMTPU_FUSED_ROUNDS"] = "1" if fused else "0"
    try:
        doc = DeviceTextDoc("bench-text")
        doc.eager_materialize = True
        doc.apply_batch(B.base_batch("bench-text", n_base))
        doc.text()
        t0 = time.perf_counter()
        prepared = doc.prepare_batch(batch)
        t1 = time.perf_counter()
        doc.commit_prepared(prepared)
        doc._materialize(with_pos=False)
        n_vis = int(doc._scalars()[0])
        t2 = time.perf_counter()
        text = doc.text()
        t3 = time.perf_counter()
    finally:
        os.environ.pop("AMTPU_FUSED_ROUNDS", None)
    log(f"  {'fused' if fused else 'xla'} leg: prepare {t1 - t0:.3f} s, "
        f"commit+sync {t2 - t1:.3f} s, text pull {t3 - t2:.3f} s "
        "(first call at these shapes: compile included)")
    return n_vis, text


def headline_merge(size):
    import bench as B

    batch = B.merge_batch("bench-text", size["actors"], size["ops"],
                          size["base"])
    expect = size["base"] + size["actors"] * (size["ops"] // 2)
    n_fused, text_fused = _merge_leg(batch, size["base"], fused=True)
    n_xla, text_xla = _merge_leg(batch, size["base"], fused=False)
    assert n_fused == n_xla == expect, (n_fused, n_xla, expect)
    assert len(text_fused) == expect, (len(text_fused), expect)
    assert text_fused == text_xla, "fused and XLA legs disagree"
    log(f"  {batch.n_ops} ops merged, {expect} chars, fused == xla")


# -- phase c / --chips 4: the sharded serving tier ---------------------------

def _rounds(doc_ids):
    """The cfg12 text serving stream: a seeding run per doc, then one
    serving round appending to every doc (bench._sharded_text_round)."""
    import bench as B
    return [B._sharded_text_round(doc_ids, 1, 1, 64),
            B._sharded_text_round(doc_ids, 2, 33, 4)]


def _serve(n_shards, devices, doc_ids, rounds):
    from automerge_tpu.shard import ShardedDocSet
    mesh = ShardedDocSet(n_shards=n_shards, devices=devices,
                         doc_kind="text", capacity=DOC_CAPACITY)
    try:
        mesh.deliver_rounds(rounds)
    finally:
        mesh.close()
    return mesh


def _assert_captures_equal(mesh, ref, doc_ids):
    for d in doc_ids:
        if mesh.capture(d) != ref.capture(d):
            raise AssertionError(f"capture of {d} differs from reference")
    texts, ref_texts = mesh.texts(), ref.texts()
    assert texts == ref_texts and len(texts) == len(doc_ids)


def sharded_round(size, device):
    n_lanes = size["lanes"]
    doc_ids = [f"tdoc-{i:05d}" for i in range(n_lanes * size["docs_per_lane"])]
    rounds = _rounds(doc_ids)
    mesh = _serve(n_lanes, [device], doc_ids, rounds)
    stacked = sum(lane.stats["stacked_applies"] for lane in mesh.lanes)
    per_object = sum(lane.stats["per_object_applies"] for lane in mesh.lanes)
    assert stacked, "no lane took the stacked path"
    # a rehearsal lane may hold too few docs/ops for the stacked gates
    # (engine/stacked: >= 2 docs, >= 16 ops); at full size none does
    assert per_object == 0 or size is TINY, (stacked, per_object)
    os.environ["AMTPU_STACKED_ROUNDS"] = "0"
    try:
        ref = _serve(1, [device], doc_ids, rounds)
    finally:
        os.environ.pop("AMTPU_STACKED_ROUNDS", None)
    assert ref.lanes[0].stats["per_object_applies"], ref.lanes[0].stats
    _assert_captures_equal(mesh, ref, doc_ids)
    log(f"  {len(doc_ids)} docs on {n_lanes} lanes, "
        f"{mesh.stats['admitted_ops']} ops ({stacked} stacked, "
        f"{per_object} per-object lane applies), captures == per-object "
        "path")


def four_chip_serving(size):
    import jax

    from automerge_tpu.shard.audit import (assert_zero_collectives,
                                           commit_path_collectives,
                                           doc_mesh)
    devices = jax.devices()[:4]
    assert len(devices) == 4, f"--chips 4 needs 4 devices, have {devices}"
    doc_ids = [f"tdoc-{i:05d}" for i in range(4 * 2 * size["docs_per_lane"])]
    rounds = _rounds(doc_ids)
    mesh = timed("4-lane mesh", _serve, 4, devices, doc_ids, rounds)
    assert all(lane.stats["stacked_applies"] for lane in mesh.lanes)
    assert size is TINY or not any(
        lane.stats["per_object_applies"] for lane in mesh.lanes)
    ref = timed("1-shard mesh", _serve, 1, devices, doc_ids, rounds)
    timed("captures", _assert_captures_equal, mesh, ref, doc_ids)
    for lane in mesh.lanes:
        assert lane.docs, f"lane {lane.index} holds no docs"
        for doc_id, doc in lane.docs.items():
            for key, arr in doc._dev.items():
                if arr.devices() != {lane.device}:
                    raise AssertionError(
                        f"{doc_id}.{key} on {arr.devices()}, lane "
                        f"{lane.index} is {lane.device}")
    log(f"  {len(doc_ids)} docs: every table on its lane's device "
        f"({[len(lane.docs) for lane in mesh.lanes]} docs per lane)")
    audit = timed("collective audit", commit_path_collectives,
                  doc_mesh(4))
    assert_zero_collectives(audit)
    log(f"  commit-path collectives: {audit}")


# -- phase d: device checks --------------------------------------------------

class _Recorder:
    """Records the abstract arguments of every call to a kernel handle,
    so the HLO of exactly what ran can be compiled and read back."""

    def __init__(self, fn):
        self.fn, self.calls = fn, []

    def __call__(self, *args, **kwargs):
        import jax
        self.calls.append((
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args),
            kwargs))
        return self.fn(*args, **kwargs)


def device_checks(recorder, expect_platform, expect_mode):
    import jax

    from automerge_tpu.obs import device_truth
    from automerge_tpu.ops.fused_round import fused_mode
    dev = jax.devices()[0]
    assert dev.platform == expect_platform, dev.platform
    assert fused_mode() == expect_mode, fused_mode()
    assert recorder.calls, "phase c never ran the fused stacked round"
    args, kwargs = recorder.calls[-1]
    assert kwargs["mode"] == expect_mode, kwargs
    hlo = recorder.fn.lower(*args, **kwargs).compile().as_text()
    if expect_platform == "tpu":
        assert "tpu_custom_call" in hlo, "no Pallas kernel in the HLO"
        peaks = device_truth.peak_rates(dev.device_kind)
        log(f"  {dev.device_kind}: peaks {peaks}")
    log(f"  platform {dev.platform}, fused_mode {expect_mode}, "
        f"fused_stacked_round HLO {len(hlo)} chars")


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["AMTPU_FUSED_MODE"] = "interpret"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={args.chips}"])
    sys.path[:0] = [REPO, os.path.join(REPO, "tests")]

    import jax

    from automerge_tpu._env import setup_compile_cache
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX runs on {platform!r}); "
              "--rehearse runs the CPU rehearsal", file=sys.stderr)
        return 2
    log(f"devices: {len(devices)} x {devices[0].device_kind} ({platform})")
    log(f"compile cache: {setup_compile_cache()}")

    from automerge_tpu import native
    from automerge_tpu.obs import device_truth
    from automerge_tpu.ops import fused_round as F
    log(f"native codec loaded: {native.available()}")
    size = TINY if args.rehearse else FULL
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_serving(size)
    else:
        recorder = _Recorder(F.fused_stacked_round)
        F.fused_stacked_round = recorder
        timed("a oracle parity", oracle_parity)
        timed("b headline merge", headline_merge, size)
        timed("c sharded round", sharded_round, size, devices[0])
        timed("d device checks", device_checks, recorder,
              "cpu" if args.rehearse else "tpu",
              "interpret" if args.rehearse else "pallas")
    log(f"total {time.perf_counter() - t0:.3f} s, compiles "
        f"{device_truth.REGISTRY.compiles_total} "
        f"({device_truth.REGISTRY.compile_ns_total / 1e9:.3f} s), "
        f"persistent cache hits {device_truth.REGISTRY.pcache_hits} "
        f"misses {device_truth.REGISTRY.pcache_misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
