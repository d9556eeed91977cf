"""Unified tracing & metrics tier (INTERNALS §11).

One structured observability surface threaded through every hot layer —
host planning, the pipeline ring, device dispatch accounting, the
resilience tier, and the checkpoint writer — replacing nothing: the
existing stats dicts (`doc.dispatch_stats`, `PipelinedIngestor.stats`,
`ResilientChannel.stats`, ...) keep their shapes and are FED by the same
instrumentation points that emit here.

Contract for instrumented call sites (the hot-path discipline):

    from automerge_tpu import obs
    ...
    t0 = obs.now() if obs.ENABLED else 0
    ... the work ...
    if obs.ENABLED:
        obs.span("plan", "prepare_batch", t0,
                 args={"doc": self.obj_id, "n_ops": batch.n_ops})

``obs.ENABLED`` is a module attribute: when tracing is off, the whole
emit path is ONE module-dict lookup and a falsy branch — no call, no
allocation, no lock (the overhead bound is asserted in
tests/test_obs.py). Everything behind the flag goes to a bounded,
lock-striped ring-buffer flight recorder (`obs.recorder.FlightRecorder`)
whose newest records always survive and whose counters are exact across
wraparound.

Enable via ``AMTPU_TRACE=1`` in the environment, `obs.enable()`, or the
scoped ``with obs.tracing(): ...``. Export with `obs.write_trace(path)`
(Chrome trace-event JSON — load at https://ui.perfetto.dev) and read
aggregates with `obs.metrics_snapshot()`. `obs.anchor_profiler()` writes
the ring clock into a running ``jax.profiler`` trace, so ring records can
be placed on the device trace's timeline.

Category catalogue (full schema in docs/INTERNALS.md §11):

  plan    host planning: prepare_batch / admission / wire decode
  commit  commit_prepared (args carry n_rounds + dispatch/sync delta)
  device  dispatch/sync accounting (labeled kernel counters), waits;
          `compile`: one jitted-kernel compile or persistent-cache load
          (args kernel = <label>/<variant>)
  ring    PipelinedIngestor slot lifecycle (plan/commit spans,
          fallback/serial/abort events, gen + slot tags)
  pull    text materialization pulls (mode + byte counts)
  chan    ResilientChannel (retransmit / dup_drop / window_drop /
          backpressure / dead ...)
  chaos   ChaosLink fault injections (drop / dup / reorder / delay ...)
  quar    quarantine admits / evictions (incl. tenant-attributed
          evict_pressure + dead-peer evict_peer) / releases
  sync    hub snapshot bootstrap (snapshot_capture / serve_cached —
          the join-storm coalescing ratio)
  svc     service tier (INTERNALS §13): the `tick` span and its
          children, which cover it — `admit` (the admission loop),
          `deliver` (one per (room, doc) group; args room / n_changes /
          n_ops / fast, fast = the gate's binary wire fast lane took
          it), `sessions` (retransmit + health passes, evictions),
          `lag` (bounds + lag probe), `mesh` (residency paging, when
          on), `flush` (every room's deferred hub flush); one
          `inbox_wait` per admitted change message, from its enqueue
          to its admission (args room / doc / tick); shed / defer /
          suspect / evict / join / rejoin / protocol_error events
  backend `apply`: Backend.apply_changes inside one doc apply
  frontend `patch`: Frontend.apply_patch of that apply
  hub     `flush`: one room hub's change extraction + frame encode +
          sends (args room / peers / frames / bytes)
  host    `gc`: one garbage collection (args generation / collected),
          from a gc.callbacks hook registered only while tracing is on
  ckpt    checkpoint writer (grab spans, conflicts, degrades)
  bench   harness-side regions (stream reps, explicit device waits)
  lineage per-change provenance hops (obs/lineage.py, INTERNALS §18):
          origin / chan/send / chan/retransmit / hub/flush / svc/admit
          / svc/defer / svc/shed / quar/park / quar/release / quar/pen
          / plan/stacked / commit / ckpt/adopt — emitted here only when
          BOTH tracing and lineage sampling are on; the ledger itself
          is independent of the trace ring
"""

from __future__ import annotations

import gc
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional

from .recorder import (  # noqa: F401  (re-exported for consumers/tests)
    ARGS, CAT, DUR, EVENT_DUR, NAME, TID, TS, FlightRecorder,
    span_seconds, span_totals,
)
from .telemetry import Telemetry  # noqa: F401  (re-exported)

#: THE fast-path gate. Instrumented call sites read this module attribute
#: directly (`if obs.ENABLED:`) so a disabled process pays one dict
#: lookup per site and nothing else. Mutated only by enable()/disable().
ENABLED = False

_recorder: Optional[FlightRecorder] = None
_telemetry: Optional[Telemetry] = None

now = time.perf_counter_ns   # monotonic ns — the span clock


def enabled() -> bool:
    return ENABLED


def recorder() -> Optional[FlightRecorder]:
    """The live FlightRecorder (None when tracing never enabled)."""
    return _recorder


def telemetry() -> Optional[Telemetry]:
    """The live rolling-telemetry store (None when tracing never
    enabled). Created and cleared in lockstep with the recorder; fed at
    emit time by span()/event()/counter(), so its aggregates stay exact
    across trace-ring wraparound (INTERNALS §14)."""
    return _telemetry


def enable(capacity: Optional[int] = None) -> FlightRecorder:
    """Turn tracing on (idempotent). A recorder (and its telemetry
    sibling) is created on first enable and retained across disable()
    so late readers can still export; pass `capacity` (records per
    stripe) to size a fresh pair."""
    global ENABLED, _recorder, _telemetry
    if _recorder is None or capacity is not None:
        _recorder = FlightRecorder(capacity)
        _telemetry = Telemetry()
    elif _telemetry is None:
        _telemetry = Telemetry()
    ENABLED = True
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return _recorder


def disable():
    global ENABLED
    _emit_gc()
    ENABLED = False
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


_gc_t0 = 0
#: finished collections not yet in the ring: (t0, t1, generation,
#: collected). A collection can interrupt its thread inside the ring's or
#: the telemetry's lock, so the hook only queues; the next span(),
#: event(), snapshot() or disable() emits.
_gc_done: list = []


def _on_gc(phase: str, info: dict):
    """The ``gc.callbacks`` hook (registered by enable() only): one
    ``host/gc`` span per collection. Collections never overlap, so one
    start instant suffices."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = now() if ENABLED else 0
    elif _gc_t0:
        _gc_done.append((_gc_t0, now(), info["generation"],
                         info["collected"]))
        _gc_t0 = 0


def _emit_gc():
    while _gc_done:
        try:
            t0, t1, generation, collected = _gc_done.pop()
        except IndexError:      # another thread took the last one
            return
        _record_span("host", "gc", t0,
                     {"generation": generation, "collected": collected}, t1)


def anchor_profiler():
    """Write one ``obs.clock`` ``jax.profiler.TraceAnnotation`` into the
    running profiler trace, carrying this clock's reading at the
    annotation's entry as its ``perf_ns`` stat. Two anchors (one after
    ``start_trace``, one before ``stop_trace``) map ring timestamps onto
    the trace's timeline linearly. Imports jax only when called."""
    from jax.profiler import TraceAnnotation
    with TraceAnnotation("obs.clock", perf_ns=now()):
        pass


@contextmanager
def tracing(capacity: Optional[int] = None):
    """Scoped enable: tracing on inside the block, restored (not force-
    disabled) on exit — nesting under a process-wide AMTPU_TRACE=1 keeps
    the outer session running. Yields the recorder."""
    was = ENABLED
    rec = enable(capacity)
    try:
        yield rec
    finally:
        if not was:
            disable()


# ---------------------------------------------------------------------------
# emit side — call ONLY behind an `if obs.ENABLED:` check
# ---------------------------------------------------------------------------


def span(cat: str, name: str, t0_ns: int, args: Optional[dict] = None,
         t1_ns: Optional[int] = None):
    """Record a completed span started at `t0_ns` (from `obs.now()`).
    A zero `t0_ns` (tracing was off when the region started) is dropped —
    a half-observed region must not fabricate a duration."""
    if _gc_done:
        _emit_gc()
    _record_span(cat, name, t0_ns, args, t1_ns)


def _record_span(cat, name, t0_ns, args, t1_ns):
    rec = _recorder
    if rec is None or not t0_ns:
        return
    end = t1_ns if t1_ns is not None else time.perf_counter_ns()
    dur = max(0, end - t0_ns)
    rec.emit((t0_ns, dur, cat, name, threading.get_ident(), args))
    tel = _telemetry
    if tel is not None:
        tel.observe_span(cat, name, dur, ts_ns=t0_ns)


def event(cat: str, name: str, args: Optional[dict] = None, n: int = 1):
    """Record an instant event AND bump its wrap-proof counter."""
    if _gc_done:
        _emit_gc()
    rec = _recorder
    if rec is None:
        return
    ts = time.perf_counter_ns()
    rec.emit((ts, EVENT_DUR, cat, name, threading.get_ident(), args))
    rec.bump((cat, name), n)
    tel = _telemetry
    if tel is not None:
        tel.observe_count(cat, name, n, ts_ns=ts)


def counter(cat: str, name: str, n: int = 1):
    """Bump a counter without a ring record (per-dispatch call sites:
    exact totals, no ring pressure)."""
    rec = _recorder
    if rec is not None:
        rec.bump((cat, name), n)
        tel = _telemetry
        if tel is not None:
            tel.observe_count(cat, name, n)


@contextmanager
def span_ctx(cat: str, name: str, args: Optional[dict] = None):
    """Span context manager for NON-hot call sites (bench, soak, tests).
    Hot paths use the explicit now()/span() pair behind the flag."""
    t0 = now() if ENABLED else 0
    try:
        yield
    finally:
        if ENABLED and t0:
            span(cat, name, t0, args)


# ---------------------------------------------------------------------------
# read side
# ---------------------------------------------------------------------------


def snapshot(since_ns: int = 0) -> list:
    """All retained records (see recorder.snapshot); [] when never
    enabled."""
    _emit_gc()
    return [] if _recorder is None else _recorder.snapshot(since_ns)


def metrics_snapshot(since_ns: int = 0) -> dict:
    """Aggregate view of the session: exact counters (wrap-proof) plus
    per-(cat, name) span aggregates.

        {"counters": {"chaos.drop": 12, ...},
         "spans": {"plan.prepare_batch": {"count", "total_ns",
                                          "min_ns", "max_ns"}, ...},
         "emitted": <total records ever>, "retained": <in ring now>}

    Span aggregates come from the telemetry store (fed at emit time),
    so they stay EXACT after trace-ring wraparound — the ISSUE 9 bug
    class. A `since_ns` query falls back to the retained ring records
    (windowed queries belong to `telemetry().windows()`); the ring view
    is also always available directly via `span_totals(snapshot())`.
    """
    if _recorder is None:
        out = {"counters": {}, "spans": {}, "emitted": 0, "retained": 0}
        _merge_device_truth(out)
        return out
    if since_ns == 0 and _telemetry is not None:
        spans = {f"{c}.{n}": dict(agg) for (c, n), agg
                 in sorted(_telemetry.span_aggregates().items())}
    else:
        spans = {f"{c}.{n}": agg for (c, n), agg
                 in sorted(span_totals(_recorder.snapshot(since_ns))
                           .items())}
    out = {
        "counters": {f"{c}.{n}": v
                     for (c, n), v in sorted(_recorder.counters().items())},
        "spans": spans,
        "emitted": _recorder.n_emitted,
        "retained": _recorder.n_retained,
    }
    _merge_device_truth(out)
    return out


def _merge_device_truth(out: dict):
    """Attach the always-on device-truth aggregates (compile registry,
    footprint gauges, persistent-compile-cache state; INTERNALS §19)
    when the session touched a device — independent of the trace ring,
    like the lineage ledger."""
    from . import device_truth
    reg = device_truth.REGISTRY
    if reg.compiles_total or reg.peak_bytes or any(
            h.calls for h in reg._kernels.values()):
        out["device_truth"] = device_truth.summary()


def clear():
    if _recorder is not None:
        _recorder.clear()
    if _telemetry is not None:
        _telemetry.clear()


def write_trace(path: str, since_ns: int = 0) -> str:
    """Dump the retained records as Chrome trace-event JSON (Perfetto-
    loadable); returns `path`. See obs/export.py for the schema."""
    from .export import write_trace as _write
    return _write(path, snapshot(since_ns),
                  t0_ns=None if _recorder is None else _recorder.t0_ns)


# honor AMTPU_TRACE=1 at import: `AMTPU_TRACE=1 python bench.py --trace`
# needs no code path to remember to call enable() before the first span
if os.environ.get("AMTPU_TRACE", "0") not in ("", "0"):
    enable()

# the change-lineage tier (its own module flag + AMTPU_LINEAGE_RATE env
# bootstrap); imported last so `obs` is fully initialized when lineage's
# emit path reaches back for the trace-ring flag
from . import lineage  # noqa: E402,F401

# the device-truth tier (its own always-on module flag; INTERNALS §19):
# imported for the same reason — metrics_snapshot and write_trace reach
# into it, and ops/ingest.py re-binds its kernels through it at import
from . import device_truth  # noqa: E402,F401
