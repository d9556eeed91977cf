"""Stage + per-kernel profiling of the headline bench (not part of the suite).

Modes:
  python profile_bench.py           # wall timers per stage
  python profile_bench.py --trace   # jax.profiler device trace -> top ops
  python profile_bench.py --pallas  # A/B: XLA scan chain vs Pallas fused
                                    # kernel at bench shapes (real chip)
  python profile_bench.py --planned # A/B: self-contained vs host-planned
                                    # merge+materialize at bench shapes
  python profile_bench.py --int64   # A/B: int32 vs int64 sort/search/scan
                                    # at bench scale (the engine's all-int32
                                    # design assumption)

Stage wall times end in a data fetch (np.asarray), which waits for all
pending device work, so that work lands in the stage containing the
fetch. Per-kernel truth comes from the --trace mode.
"""
import glob
import gzip
import json
import os
import shutil
import sys
import time

import jax

from bench import (BASE_LEN, N_ACTORS, OPS_PER_CHANGE, base_batch,
                   merge_batch, run_once)
from automerge_tpu.engine import DeviceTextDoc

t = time.perf_counter
# profiler output stays inside the checkout (chiprun_out/ is gitignored
# and comes back from a chip call)
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "chiprun_out", "jxtrace")


def build():
    doc = DeviceTextDoc("bench-text")
    doc.apply_batch(base_batch("bench-text", BASE_LEN))
    doc.text()
    return doc


def stage_timers(batch):
    doc = build()
    t0 = t()
    prepared = doc.prepare_batch(batch)
    t1 = t()
    print(f"prepare (host plan + h2d staging): {(t1-t0)*1e3:8.1f} ms "
          f"({prepared.n_staged_bytes/1e6:.1f} MB staged)")
    doc.commit_prepared(prepared)
    t2 = t()
    print(f"commit dispatch (bookkeeping+enqueue): {(t2-t1)*1e3:6.1f} ms")
    doc._materialize(with_pos=False)
    t3 = t()
    print(f"materialize dispatch: {(t3-t2)*1e3:23.1f} ms")
    scal = doc._scalars()
    t4 = t()
    print(f"scalar fetch (flush+exec+sync): {(t4-t3)*1e3:13.1f} ms")
    print(f"TIMED REGION (commit..sync): {(t4-t1)*1e3:16.1f} ms")
    text = doc.text()
    t5 = t()
    print(f"text() d2h pull + decode (untimed): {(t5-t4)*1e3:9.1f} ms")
    assert len(text) == int(scal[0])


def device_trace(batch):
    doc = build()
    prepared = doc.prepare_batch(batch)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)
    t0 = t()
    doc.commit_prepared(prepared)
    doc._materialize(with_pos=False)
    scal = doc._scalars()
    dt = t() - t0
    jax.profiler.stop_trace()
    print(f"timed region: {dt*1e3:.1f} ms, n_vis={int(scal[0])}")
    for f in glob.glob(f"{TRACE_DIR}/**/*.trace.json.gz", recursive=True):
        with gzip.open(f, "rt") as fh:
            data = json.load(fh)
        events = data.get("traceEvents", [])
        pids = {e["pid"]: e["args"].get("name", "") for e in events
                if e.get("ph") == "M" and e.get("name") == "process_name"}
        by_name: dict = {}
        for e in events:
            if e.get("ph") == "X" and "TPU" in pids.get(e.get("pid"), ""):
                by_name[e["name"]] = by_name.get(e["name"], 0) + e["dur"]
        for name, dur in sorted(by_name.items(), key=lambda kv: -kv[1])[:20]:
            print(f"{dur/1e3:10.2f} ms  {name[:90]}")


def pallas_ab():
    """XLA stacked-cumsum scans (production path) vs the Pallas fused
    kernel, at headline-bench shapes, via the device profiler (per-kernel
    device time, not host wall time)."""
    import glob
    import gzip
    import json as _json

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform == "cpu":
        # compiled pallas_call is chip-only (CPU supports interpret mode
        # only, which measures nothing) — skip cleanly so a session
        # dry-run doesn't report a step failure that on-chip wouldn't have
        print("pallas_ab: chip-only A/B — skipped on cpu platform")
        return

    from automerge_tpu.ops.scan_pallas import fused_segment_scans

    C = 6_291_456
    n_elems = 6_000_000
    rng = np.random.default_rng(0)
    chain = jnp.asarray(rng.random(C) > (30_000 / C))
    has = jnp.asarray(np.ones(C, bool))

    @jax.jit
    def xla_scans(chain, has):
        idx = jnp.arange(C, dtype=jnp.int32)
        is_elem = (idx >= 1) & (idx <= n_elems)
        seg_start = is_elem & ~chain
        vis = has & is_elem
        two = jnp.cumsum(jnp.stack([seg_start.astype(jnp.int32),
                                    vis.astype(jnp.int32)]), axis=1)
        head = jax.lax.cummax(jnp.where(seg_start, idx, 0))
        return two[0], head, two[1]

    for name, fn in (("xla_scan_chain", lambda: xla_scans(chain, has)),
                     ("pallas_fused", lambda: fused_segment_scans(
                         chain, has, n_elems))):
        np.asarray(fn()[0])  # compile + drain
        shutil.rmtree(TRACE_DIR + "_ab", ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR + "_ab")
        out = fn()
        np.asarray(out[0])   # force flush+exec
        jax.profiler.stop_trace()
        total = 0
        for f in glob.glob(f"{TRACE_DIR}_ab/**/*.trace.json.gz",
                           recursive=True):
            with gzip.open(f, "rt") as fh:
                data = _json.load(fh)
            pids = {e["pid"]: e["args"].get("name", "")
                    for e in data.get("traceEvents", [])
                    if e.get("ph") == "M" and e.get("name") == "process_name"}
            total += sum(e["dur"] for e in data.get("traceEvents", [])
                         if e.get("ph") == "X"
                         and "TPU" in pids.get(e.get("pid"), ""))
        print(f"{name}: device total {total / 1e3:.2f} ms")


def planned_ab(batch, pairs: int = 4):
    """Timed-region A/B at bench shapes: host-planned segment linearization
    (engine/segments.py) vs the self-contained kernels (mirror disabled).
    Both run the same prepare/commit/sync protocol as bench.py.

    INTERLEAVED pairs (A,B,A,B,...): host load drifts on a seconds
    timescale, and a block design aliases that drift into the arm
    difference. Pairing puts both arms inside the same conditions and
    reports the per-pair delta distribution alongside min-of-arm, so one
    harness run says whether the difference is real."""
    def once(planned: bool):
        doc = DeviceTextDoc("bench-text")
        doc.eager_materialize = True
        if not planned:
            doc.seg_mirror = None
            doc.prefer_planned = False
        else:
            # both arms pinned explicitly so the A/B compares the real
            # alternatives regardless of the production default (which
            # this harness's results decide — text_doc.prefer_planned)
            doc.prefer_planned = True
        doc.apply_batch(base_batch("bench-text", BASE_LEN))
        doc.text()
        prepared = doc.prepare_batch(batch)
        t0 = t()
        doc.commit_prepared(prepared)
        doc._materialize(with_pos=False)
        scal = doc._scalars()
        dt = t() - t0
        assert int(scal[0]) == BASE_LEN + N_ACTORS * (OPS_PER_CHANGE // 2)
        if planned:
            # the planned materialization returns the 5-scalar pack
            # (n_vis, n_segs, chain-count + structural-hash verifiers
            # — text_doc._scalars); the self-contained kernel returns
            # 2. (Was ==4 from an older pack layout: the round-5
            # session dry-run caught it failing before any chip
            # window could.)
            assert len(scal) == 5, "planned kernel did not engage"
        return dt

    once(True)                   # warm-up: compiles for both arms
    once(False)
    self_ts, plan_ts = [], []
    for _ in range(pairs):
        self_ts.append(once(False))
        plan_ts.append(once(True))
    n_ops = batch.n_ops
    for name, ts in (("self-contained", self_ts), ("host-planned", plan_ts)):
        dt = min(ts)
        print(f"{name}: timed region {dt*1e3:8.1f} ms "
              f"({n_ops/dt/1e6:.1f}M ops/s)  "
              f"[{', '.join(f'{x*1e3:.1f}' for x in ts)}]")
    deltas = [p - s for s, p in zip(self_ts, plan_ts)]
    wins = sum(1 for d in deltas if d < 0)
    print(f"per-pair delta (planned - self) ms: "
          f"{', '.join(f'{d*1e3:+.1f}' for d in deltas)}  "
          f"(planned wins {wins}/{len(deltas)})")


def int64_ab(n: int = 1 << 23, reps: int = 3):
    """The engine keeps ALL device state int32 on the stated (round-2,
    never measured) assumption that 64-bit keys would pay severalfold on
    the TPU's 32-bit lanes. This measures exactly the primitives the
    kernels lean on — sort, searchsorted, cumsum — at bench scale (2^23
    ~ the 10M-op round) in both widths. Requires jax_enable_x64 (set
    below), or the int64 arm silently degrades to int32 and the A/B
    measures nothing: guarded by a dtype assert."""
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    base = rng.integers(0, 1 << 30, size=n)

    def bench_dtype(dtype):
        x = jnp.asarray(base, dtype=dtype)
        assert x.dtype == dtype, (x.dtype, dtype)   # x64 actually enabled
        xs = jnp.sort(x).block_until_ready()   # hoisted: timing searchsorted
        ops = {                                # must not re-measure sort
            "sort": lambda: jnp.sort(x),
            "searchsorted": lambda: jnp.searchsorted(xs, x),
            "cumsum": lambda: jnp.cumsum(x),
        }
        out = {}
        for name, fn in ops.items():
            fn().block_until_ready()                # compile + warm
            ts = []
            for _ in range(reps):
                t0 = t()
                np.asarray(fn())                    # fetch = real flush
                ts.append(t() - t0)
            out[name] = min(ts)
        return out

    r32 = bench_dtype(jnp.int32)
    r64 = bench_dtype(jnp.int64)
    for name in r32:
        print(f"{name:>12}: int32 {r32[name]*1e3:8.1f} ms   "
              f"int64 {r64[name]*1e3:8.1f} ms   "
              f"ratio {r64[name]/r32[name]:.2f}x")


if __name__ == "__main__":
    from automerge_tpu._env import setup_compile_cache

    setup_compile_cache()
    if "--int64" in sys.argv:
        int64_ab()
        sys.exit(0)
    if "--pallas" in sys.argv:
        pallas_ab()
        sys.exit(0)
    batch = merge_batch("bench-text", N_ACTORS, OPS_PER_CHANGE, BASE_LEN)
    if "--planned" in sys.argv:
        planned_ab(batch)
        sys.exit(0)
    run_once(batch)  # warm compiles
    if "--trace" in sys.argv:
        device_trace(batch)
    else:
        stage_timers(batch)
