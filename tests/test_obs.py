"""The unified tracing & metrics tier (automerge_tpu/obs, INTERNALS §11).

Pins the four contracts the flight recorder exists for (ISSUE 6):

1. **Disabled is free.** The span-emit fast path with tracing off is a
   module-flag check — measured per call AND bounded structurally: the
   records a cfg5-quick stream would emit, times the measured disabled
   per-call cost, must stay under a few percent of the stream's wall
   time.
2. **Wraparound keeps the newest.** The ring is a flight recorder:
   overflow drops the oldest records; counters stay exact regardless.
3. **Concurrent writers never tear.** The pipeline ring's worker and
   caller threads (and arbitrary extra threads) emit concurrently;
   every snapshot record is a whole, well-formed tuple attributed to
   its writer.
4. **Bench terms come from spans.** The serial-profile quantities
   (`prepare_s`, `commit_s`, pull) derived from recorded spans pin
   against legacy perf_counter pairs around the same calls — the parity
   that makes replacing the hand-placed timers safe.
"""

import threading
import time

import numpy as np
import pytest

import bench as B
from automerge_tpu import obs
from automerge_tpu.engine import DeviceTextDoc, PipelinedIngestor
from automerge_tpu.obs.export import (TraceValidationError,
                                      to_chrome_trace,
                                      validate_chrome_trace)
from automerge_tpu.obs.recorder import FlightRecorder


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled (module flag)."""
    obs.disable()
    yield
    obs.disable()


# -- the cfg5-quick-shaped stream used by the overhead + parity bars ------

QUICK = dict(base_n=20_000, n_batches=4, n_actors=200, ops=100)


def _quick_batches(prefix="ov"):
    return [B.merge_batch("obs-text", QUICK["n_actors"], QUICK["ops"],
                          QUICK["base_n"], seed=50 + k,
                          actor_prefix=f"{prefix}{k:02d}")
            for k in range(QUICK["n_batches"])]


def _quick_stream(batches):
    doc = DeviceTextDoc("obs-text")
    doc.eager_materialize = True
    doc.apply_batch(B.base_batch("obs-text", QUICK["base_n"]))
    doc.text()
    t0 = time.perf_counter()
    with PipelinedIngestor(doc) as pipe:
        pipe.run(batches)
    doc._materialize(with_pos=False)
    doc._scalars()
    dt = time.perf_counter() - t0
    doc.text()
    return dt


def test_disabled_overhead_within_noise_on_quick_stream():
    """The ISSUE 6 overhead bar: with tracing DISABLED, the whole span
    emit path costs a module-flag check per site. Bound it two ways:

    - measured: one disabled no-op emit (`obs.span` behind a false
      flag + the `obs.now() if obs.ENABLED else 0` idiom) costs well
      under a microsecond;
    - structural: (records an ENABLED quick stream emits) x (that
      per-call cost) must be <= 2% of the DISABLED stream's wall time —
      i.e. even if every emit site paid the full call, the stream
      wouldn't notice.
    """
    batches = _quick_batches()
    _quick_stream(batches)                       # warm-up (jit compiles)
    disabled_s = min(_quick_stream(batches) for _ in range(3))

    # how many records the same stream emits when tracing is ON
    with obs.tracing():
        rec = obs.recorder()
        rec.clear()
        _quick_stream(batches)
        n_records = rec.n_emitted
    assert n_records > 0

    # measured disabled fast path (the call-site idiom, flag off)
    assert not obs.ENABLED
    n_calls = 200_000
    t0 = time.perf_counter_ns()
    for _ in range(n_calls):
        t = obs.now() if obs.ENABLED else 0
        if obs.ENABLED:
            obs.span("x", "y", t)
    per_call_ns = (time.perf_counter_ns() - t0) / n_calls
    assert per_call_ns < 1_000, f"disabled emit path {per_call_ns:.0f}ns"

    worst_case_s = n_records * per_call_ns / 1e9
    assert worst_case_s <= 0.02 * disabled_s, (
        f"{n_records} emit sites x {per_call_ns:.0f}ns = "
        f"{worst_case_s * 1e3:.2f}ms vs stream {disabled_s * 1e3:.0f}ms")


def test_disabled_emit_is_strict_noop():
    """span()/event() with the flag off write nothing, even when a
    recorder exists from an earlier session."""
    with obs.tracing():
        pass                          # recorder now exists, flag off
    rec = obs.recorder()
    rec.clear()
    t = obs.now() if obs.ENABLED else 0
    if obs.ENABLED:
        obs.span("x", "y", t)
        obs.event("x", "z")
    assert rec.n_emitted == 0 and obs.snapshot() == []


def test_ring_wraparound_keeps_newest():
    rec = FlightRecorder(capacity=16, n_stripes=1)
    for i in range(100):
        rec.emit((i, 0, "c", "n", 0, {"i": i}))
    snap = rec.snapshot()
    assert len(snap) == 16
    assert [r[5]["i"] for r in snap] == list(range(84, 100))
    assert rec.n_emitted == 100 and rec.n_retained == 16


def test_counters_exact_across_wraparound(collector_paused):
    """metrics_snapshot counters aggregate outside the ring: emitting
    far more events than capacity loses ring records, never counts."""
    with obs.tracing(capacity=16):
        obs.clear()
        for _ in range(500):
            obs.event("chaos", "drop")
        snap = obs.metrics_snapshot()
    assert snap["counters"]["chaos.drop"] == 500
    assert snap["retained"] < snap["emitted"] == 500


def test_concurrent_writers_no_torn_records(collector_paused):
    """Writers on many threads (beyond the stripe count, so stripes are
    shared) emit concurrently; every snapshotted record is whole and
    attributed to exactly one writer, and nothing is lost below
    capacity."""
    n_threads, n_each = 12, 400
    with obs.tracing(capacity=n_threads * n_each):
        obs.clear()
        start = threading.Barrier(n_threads)

        def writer(w):
            start.wait()
            for i in range(n_each):
                t0 = obs.now()
                obs.span("t", f"w{w}", t0, args={"w": w, "i": i})

        threads = [threading.Thread(target=writer, args=(w,))
                   for w in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = obs.snapshot()
    assert len(snap) == n_threads * n_each
    per_writer = {}
    for r in snap:
        assert len(r) == 6
        ts, dur, cat, name, tid, args = r
        assert cat == "t" and name == f"w{args['w']}"
        assert isinstance(ts, int) and dur >= 0
        # a torn/interleaved record would mismatch name vs args payload
        per_writer.setdefault(args["w"], set()).add(args["i"])
    assert all(v == set(range(n_each)) for v in per_writer.values())


def test_ring_worker_and_caller_spans_are_consistent():
    """A real pipeline session with tracing on: the worker thread's
    ring.plan spans and the caller's ring.commit spans both land whole,
    slot-tagged, and one per batch."""
    batches = _quick_batches("rw")
    with obs.tracing():
        obs.clear()
        _quick_stream(batches)
        snap = obs.snapshot()
    plans = [r for r in snap if r[2] == "ring" and r[3] == "plan"]
    commits = [r for r in snap if r[2] == "ring" and r[3] == "commit"]
    assert len(plans) == len(batches)
    assert len(commits) == len(batches)
    assert sorted(r[5]["slot"] for r in commits) == list(range(len(batches)))
    # two distinct writer threads participated (worker + caller)
    assert len({r[4] for r in plans + commits}) >= 2


def test_span_terms_match_legacy_perf_counter():
    """The acceptance parity bar: span-derived prepare/commit/pull terms
    pin against legacy perf_counter pairs around the same calls on a
    seeded cfg5-quick-shaped run. The span is the inner measurement of
    the exact region the timer pair straddles, so they may differ only
    by call overhead."""
    doc = DeviceTextDoc("obs-text")
    doc.eager_materialize = True
    doc.apply_batch(B.base_batch("obs-text", QUICK["base_n"]))
    doc.text()
    batch = B.merge_batch("obs-text", QUICK["n_actors"], QUICK["ops"],
                          QUICK["base_n"], seed=7, actor_prefix="par")
    with obs.tracing():
        obs.clear()
        t0 = time.perf_counter()
        plan = doc.prepare_batch(batch)
        legacy_prepare = time.perf_counter() - t0
        t0 = time.perf_counter()
        doc.commit_prepared(plan)
        legacy_commit = time.perf_counter() - t0
        doc._materialize(with_pos=False)
        doc._scalars()
        t0 = time.perf_counter()
        doc.text()
        legacy_pull = time.perf_counter() - t0
        recs = obs.snapshot()
    span_prepare = obs.span_seconds(recs, "plan", "prepare_batch")
    span_commit = obs.span_seconds(recs, "commit", "batch")
    span_pull = obs.span_seconds(recs, "pull", "text")
    for legacy, derived, what in [(legacy_prepare, span_prepare, "prepare"),
                                  (legacy_commit, span_commit, "commit"),
                                  (legacy_pull, span_pull, "pull")]:
        assert derived > 0, what
        tol = max(0.02, 0.2 * legacy)
        assert abs(derived - legacy) <= tol, (
            f"{what}: span {derived:.4f}s vs legacy {legacy:.4f}s")


def test_bench_serial_profile_is_span_derived():
    """measure_pipeline's serial profile terms are exactly the recorded
    span sums: zero out the span store mid-derivation and the terms
    would vanish — here we assert the positive direction (terms present,
    consistent with an independent wall clock of the whole profile)."""
    rec = B.measure_pipeline(quick=True, reps=5)
    prof = rec["serial_profile"]
    for term in ("prepare_s", "commit_s", "device_wait_s", "final_sync_s"):
        assert term in prof and prof[term] >= 0, prof
    # on any platform the four terms sum to less than the stream count
    # times a generous bound — and prepare can no longer swallow device
    # execution: the dominant cpu term must be the explicit device wait
    # or the commit, never prepare by a 10x margin over both
    assert prof["prepare_s"] <= 10 * (prof["device_wait_s"]
                                      + prof["commit_s"] + 0.01), prof


def test_chrome_trace_export_and_validation():
    batches = _quick_batches("tr")
    with obs.tracing():
        obs.clear()
        with obs.span_ctx("bench", "stream", args={"rep": 0}):
            _quick_stream(batches)
        obs.event("chaos", "drop")
        snap = obs.snapshot()
        t0 = obs.recorder().t0_ns
    trace = to_chrome_trace(snap, t0_ns=t0)
    counts = validate_chrome_trace(trace, require_stream_nesting=True)
    assert counts["n_spans"] > 0 and counts["n_ring_spans"] > 0
    assert counts["n_streams"] >= 1 and counts["n_events"] >= 1
    # every exported span satisfies the schema the CI smoke enforces
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "X":
            assert ev["dur"] >= 0 and "cat" in ev and "ts" in ev


def test_trace_validation_rejects_empty_and_malformed():
    with pytest.raises(TraceValidationError):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(TraceValidationError):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "n", "cat": "c",
                              "ts": 0.0}]})      # missing dur
    # a ring span with no enclosing stream fails the nesting contract
    bad = {"traceEvents": [
        {"ph": "X", "name": "plan", "cat": "ring", "ts": 5.0, "dur": 1.0,
         "pid": 1, "tid": 1}]}
    with pytest.raises(TraceValidationError):
        validate_chrome_trace(bad, require_stream_nesting=True)
    validate_chrome_trace(bad)        # without the bench contract: fine


def test_tracing_scope_restores_outer_state():
    assert not obs.ENABLED
    with obs.tracing():
        assert obs.ENABLED
        with obs.tracing():
            assert obs.ENABLED
        assert obs.ENABLED            # inner exit keeps the outer session
    assert not obs.ENABLED


def test_metrics_snapshot_span_aggregates():
    with obs.tracing():
        obs.clear()
        for i in range(5):
            t0 = obs.now()
            time.sleep(0.001)
            obs.span("plan", "prepare_batch", t0)
        snap = obs.metrics_snapshot()
    agg = snap["spans"]["plan.prepare_batch"]
    assert agg["count"] == 5
    assert agg["total_ns"] >= 5 * 1_000_000
    assert agg["min_ns"] <= agg["max_ns"] <= agg["total_ns"]


def test_accounting_labeled_durations_ride_along():
    """Blocking syncs with a measured duration land in the labeled
    histogram: the staging barrier always carries one."""
    from automerge_tpu.engine import accounting
    with obs.tracing():
        before = accounting.labeled_snapshot()["sync"]
        doc = DeviceTextDoc("lbl")
        doc.eager_materialize = True
        doc.apply_batch(B.base_batch("lbl", 2000))
        doc.commit_prepared(doc.prepare_batch(
            B.merge_batch("lbl", 16, 20, 2000, seed=5)))
        after = accounting.labeled_snapshot()["sync"]
    d = after["stage_barrier"]["n"] - before.get(
        "stage_barrier", {"n": 0})["n"]
    assert d >= 1
    assert after["stage_barrier"]["ns"] > 0
