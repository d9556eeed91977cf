"""Host-side planning parallelism + the pipelined ingestion driver.

Round-5 profiling put host planning (`prepare_s`) and the d2h text pull
each above the device commit region of the headline merge, and the
in-process overlap schedule lost to serial. This module closes the
planning half of that gap:

- `planner_pool()` — one small shared ThreadPoolExecutor. Every heavy
  planning pass (the native run-detection walker, numpy column passes)
  releases the GIL, so sharding one batch's planning across a few
  threads runs at real parallelism on multicore hosts and costs nothing
  on one core (`AMTPU_PLAN_WORKERS=1` disables sharding).
- `stage_h2d()` — chunked, asynchronous host->device staging via
  `jax.device_put`. Large value blobs split into chunks so transfers
  start flowing while later planning still runs, instead of one
  monolithic copy at the end; the prepare-side completion barrier
  (engine/base.py prepare_batch) is unchanged and still guarantees the
  plan's buffers are resident before commit.
- `PipelinedIngestor` — the K-deep in-flight batch ring (INTERNALS §9):
  a worker thread prepares batch k+1 *chained onto* batch k's
  still-uncommitted plan (engine/base.py `prepare_batch(after=...)`)
  while the caller thread commits batch k and the device executes its
  kernels. `slots` PreparedBatch slots bound the speculation (default
  `AMTPU_PIPELINE_DEPTH`, 4): at depth K the worker can run K-1 chained
  plans ahead of the commit front, so a long stream of
  causally-independent batches keeps host planning, h2d staging, commit
  bookkeeping, and device execution ALL saturated — one slow phase no
  longer stalls the others (double buffering only hid one phase; the
  ring amortizes all of them). Every commit is generation-checked, and
  a mismatch (the document mutated outside the pipeline) falls back to
  a fresh inline prepare instead of corrupting state. `stats` reports
  how the session actually ran (chained vs serial prepares, fallbacks,
  committed batches) — `bench.py --pipeline` records them next to the
  throughput number.

Jiffy's batch-update/snapshot split and PAM's bulk-parallel map
construction (PAPERS.md) are the shape being reproduced: bulk-plan on
the host in parallel, commit as pure dispatch.

The ring is planner-agnostic: the columnar planner (INTERNALS §10,
`engine/wire_columns.py` + `base._schedule_columnar`) chains its
pre-grouped plans through `prepare_batch(after=...)` unchanged — the
worker thread just plans in column space (batch-level decode caches
shared across the stream), and `AMTPU_COLUMNAR_PLAN=0` runs the same
ring over the legacy per-change planner
(tests/test_columnar_plan.py::test_ring_integration_both_planners).

The same worker-thread/queue/overlap discipline, lifted from per-doc to
per-lane, is `shard/parallel.LaneExecutor` (INTERNALS §24): one
persistent worker per shard lane runs whole stacked ingest rounds under
the lane's device context while the caller pre-decodes the NEXT round's
wire payloads — the ring's "plan k+1 while k commits" seam at mesh
granularity. Both layers share :func:`device_ctx_factory` for device
pinning.
"""

from __future__ import annotations

import os
import queue
import threading

import numpy as np

from .. import obs

_POOL = None
_POOL_LOCK = threading.Lock()


def plan_workers() -> int:
    """Worker count for sharded planning. 1 disables sharding."""
    try:
        w = int(os.environ.get("AMTPU_PLAN_WORKERS", "0"))
    except ValueError:
        w = 0
    if w <= 0:
        w = min(4, os.cpu_count() or 1)
    return max(1, w)


def pipeline_depth() -> int:
    """Default in-flight slot count of the batch ring (K). K-1 chained
    plans can run ahead of the commit front; 4 keeps planning, staging,
    commit, and device execution all occupied without unbounded
    speculation (each slot pins its plan's staged device buffers until
    commit). AMTPU_PIPELINE_DEPTH overrides; 1 degrades to serial."""
    try:
        k = int(os.environ.get("AMTPU_PIPELINE_DEPTH", "0"))
    except ValueError:
        k = 0
    return k if k >= 1 else 4


def planner_pool():
    """The ONE shared planning pool (lazy; None when workers == 1)."""
    global _POOL
    if plan_workers() == 1:
        return None
    with _POOL_LOCK:
        if _POOL is None:
            from concurrent.futures import ThreadPoolExecutor
            _POOL = ThreadPoolExecutor(
                max_workers=plan_workers(),
                thread_name_prefix="amtpu-plan")
    return _POOL


def device_ctx_factory(device):
    """A zero-arg context-manager factory pinning work to `device`
    (``jax.default_device``), or a nullcontext factory when `device` is
    None. The one device-pinning idiom shared by the per-doc ring
    (:class:`PipelinedIngestor`) and the per-lane executor
    (shard/parallel, INTERNALS §24) — resolved once so the hot paths
    never re-import jax per call."""
    if device is None:
        import contextlib

        def _null():
            return contextlib.nullcontext()
        return _null
    import jax
    return lambda: jax.default_device(device)


def _chunk_elems(arr: np.ndarray) -> int:
    """Elements per staging chunk (env-tunable byte budget)."""
    try:
        mb = float(os.environ.get("AMTPU_STAGE_CHUNK_MB", "4"))
    except ValueError:
        mb = 4.0
    if mb <= 0:
        return 0
    return max(1, int(mb * (1 << 20)) // max(1, arr.dtype.itemsize))


def stage_h2d(arr: np.ndarray):
    """Asynchronously stage a host array to the default device.

    1-D arrays above the chunk budget ship as several `jax.device_put`
    calls reassembled with one device-side concatenate: each chunk's
    transfer is enqueued immediately (device_put does not block), so
    byte movement overlaps the remaining host planning instead of
    serializing after it. Small arrays and matrices ship whole. The
    caller still owns the completion barrier."""
    import jax
    import jax.numpy as jnp
    ce = _chunk_elems(arr)
    if arr.ndim != 1 or ce == 0 or len(arr) <= ce:
        return jax.device_put(arr)
    parts = [jax.device_put(arr[i: i + ce])
             for i in range(0, len(arr), ce)]
    return jnp.concatenate(parts)


class PipelineError(RuntimeError):
    """A background prepare failed; the original exception chains."""


_SERIAL = object()   # worker marker: batch not chainable, prepare inline


class PipelinedIngestor:
    """K-deep in-flight batch ring for one CausalDeviceDoc.

    Contract: while a pipeline session is open, the document is mutated
    ONLY through it. The worker thread prepares each fed batch chained
    onto the previous (still pending) plan's shadow state
    (`prepare_batch(after=...)`), so planning of batch k+1 overlaps both
    the caller's commit bookkeeping for batch k and the device's kernel
    execution; `slots` bounds the speculation depth (2 = classic double
    buffering; default AMTPU_PIPELINE_DEPTH, 4 — the sustained-streaming
    ring). Commits stay generation-checked: if the document moved
    under a pending plan (outside mutation, or a chained base that
    failed), `flush()` degrades that batch to a fresh inline
    prepare+commit — semantics are always exactly apply_batch's.

    `donate=True` additionally switches the document onto the donated
    commit kernels for the session (ops/ingest.py `*_donated`): XLA may
    write each round's output tables in place of the inputs, so
    steady-state device allocation is flat across the ring instead of
    holding K dead table generations. The flag is restored on close();
    see engine/base.py `donate_buffers` for why it is incompatible with
    the checkpoint writer's zero-copy grab.

    Batches whose actor interning would reorder existing ranks cannot be
    planned concurrently with an uncommitted base (the remap would
    invalidate the base plan's staged columns — see
    engine/base.py prepare_batch); the worker marks those and the caller
    prepares them serially after the preceding commit. Wide merge loads
    intern fresh actors in lexicographic append position, so the chained
    path is the common case.
    """

    def __init__(self, doc, slots: int = None, donate: bool = False,
                 device=None):
        self.doc = doc
        #: shard-lane pinning (INTERNALS §15): every prepare (worker
        #: thread h2d staging) and commit (caller thread dispatch) runs
        #: inside ``jax.default_device(device)``, so a per-lane ring
        #: keeps its document's tables and staged plan buffers on ITS
        #: lane's device. None = the process default, unchanged.
        self.device = device
        self._n_slots = max(1, pipeline_depth() if slots is None else slots)
        self._slots = threading.Semaphore(self._n_slots)
        self._in: "queue.Queue" = queue.Queue()
        self._out: "queue.Queue" = queue.Queue()
        self._n_fed = 0
        self._total_fed = 0
        self._cv = threading.Condition()
        self._n_committed = 0
        self._fallbacks = 0     # commits that degraded to a fresh prepare
        self._chained = 0       # background prepares chained onto a base
        self._serial = 0        # batches the caller had to prepare inline
        # running min/max of the per-commit device-interaction deltas
        # (doc.last_commit_stats): the ring's public budget surface, so
        # consumers never re-implement the drain loop to sample it
        self._budget = {"dispatches_min": None, "dispatches_max": 0,
                        "syncs_min": None, "syncs_max": 0}
        self._closing = False
        self._donate = donate
        self._donate_prior = getattr(doc, "donate_buffers", False)
        if donate:
            doc.donate_buffers = True
        # serializes prepare_batch calls between the worker and the
        # caller's degraded-path inline re-prepares (commit_next): two
        # concurrent UNCHAINED prepares could race actor interning
        self._prep_lock = threading.Lock()
        self._device_ctx = self._make_device_ctx()
        self._thread = threading.Thread(
            target=self._worker, name="amtpu-pipeline", daemon=True)
        self._started = False

    def _make_device_ctx(self):
        return device_ctx_factory(self.device)

    # -- context manager -------------------------------------------------
    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        # a clean exit commits everything still in flight — silently
        # dropping fed batches would violate the apply_batch-equivalence
        # contract; an exceptional exit just tears the worker down
        try:
            if exc_type is None:
                self.flush()
        finally:
            self.close()
        return False

    def close(self):
        """Terminal: a closed ingestor cannot be fed again (its worker
        thread is joined; start a new instance for a new session)."""
        with self._cv:
            self._closing = True
            self._cv.notify_all()       # unpark a quiescence wait
        if self._started:
            self._in.put(None)
            self._thread.join()
            self._started = False
        if self._donate:
            self.doc.donate_buffers = self._donate_prior

    @property
    def stats(self) -> dict:
        """How the session actually ran: ring depth, committed batches,
        chained vs caller-inline (serial) prepares, and degraded-path
        fallbacks. Carried in bench --pipeline records so a ring that
        silently degraded to serial planning cannot pass as pipelined."""
        with self._cv:
            return {"depth": self._n_slots,
                    "committed": self._n_committed,
                    "chained_prepares": self._chained,
                    "fresh_prepares": (self._n_committed - self._chained
                                       - self._serial),
                    "serial_prepares": self._serial,
                    "fallbacks": self._fallbacks,
                    "per_commit_budget": dict(self._budget)}

    # -- feeding / committing --------------------------------------------
    def feed(self, batch):
        """Queue a batch for background planning. At the `slots` bound,
        feed COMMITS the oldest in-flight batch inline instead of
        blocking — commits happen on the caller thread only, so waiting
        on the semaphore with a full pipeline would deadlock (nobody
        else can drain it)."""
        if self._closing:
            raise RuntimeError("PipelinedIngestor is closed")
        if not self._started:
            self._thread.start()
            self._started = True
        while not self._slots.acquire(blocking=False):
            self.commit_next()
        self._in.put((self._total_fed, batch))
        self._total_fed += 1
        self._n_fed += 1

    def commit_next(self):
        """Commit the oldest fed batch (blocking on its prepare)."""
        if self._n_fed <= 0:
            raise RuntimeError("commit_next with no batch fed")
        self._n_fed -= 1
        k, batch, plan, err = self._out.get()
        _t0 = obs.now() if obs.ENABLED else 0
        serial = fallback = False
        try:
            if err is not None:
                raise PipelineError(
                    "background prepare failed") from err
            if plan is _SERIAL:
                serial = True
                with self._cv:
                    self._serial += 1
                with self._prep_lock, self._device_ctx():
                    plan = self.doc.prepare_batch(batch)
            try:
                with self._device_ctx():
                    self.doc.commit_prepared(plan)
            except ValueError:
                # generation mismatch: the document moved under the
                # pending plan — re-plan against live state and commit
                # (the documented degraded path, never silent corruption).
                # Bump the fallback epoch so the worker abandons the now-
                # dead chain base instead of chaining onto it forever.
                fallback = True
                if obs.ENABLED:
                    obs.event("ring", "fallback",
                              args={"doc": self.doc.obj_id, "slot": k})
                with self._cv:
                    self._fallbacks += 1
                with self._prep_lock, self._device_ctx():
                    plan = self.doc.prepare_batch(batch)
                with self._device_ctx():
                    self.doc.commit_prepared(plan)
        finally:
            with self._cv:
                self._n_committed += 1
                self._cv.notify_all()
            self._slots.release()
            if obs.ENABLED:
                obs.span("ring", "commit", _t0, args={
                    "doc": self.doc.obj_id, "slot": k,
                    "gen": self.doc._gen, "serial": serial,
                    "fallback": fallback})
        # reached on successful commits only: fold the committed batch's
        # device-interaction delta into the public budget surface
        st = getattr(self.doc, "last_commit_stats", None)
        if st:
            with self._cv:
                b = self._budget
                for key in ("dispatches", "syncs"):
                    b[key + "_max"] = max(b[key + "_max"], st[key])
                    b[key + "_min"] = (st[key] if b[key + "_min"] is None
                                       else min(b[key + "_min"], st[key]))

    def flush(self):
        """Commit every batch still in flight; returns the document."""
        while self._n_fed:
            self.commit_next()
        return self.doc

    def run(self, batches):
        """Pipeline a whole sequence: feed + commit with `slots` lag."""
        for b in batches:
            self.feed(b)
            # drain down to (slots - 1) speculative plans so the worker
            # keeps its lookahead while feed() can never block on an
            # exhausted semaphore (slots=1 degrades to a serial schedule)
            while self._n_fed >= self._n_slots:
                self.commit_next()
        return self.flush()

    # -- worker ----------------------------------------------------------
    def _worker(self):
        base = None       # the previous (possibly uncommitted) plan
        seen_fallbacks = 0
        while True:
            item = self._in.get()
            if item is None:
                return
            k, batch = item
            plan = err = None
            try:
                with self._cv:
                    if self._fallbacks != seen_fallbacks:
                        # a commit degraded to a fresh inline prepare:
                        # any pending chain base is dead (its
                        # committed_gen will never match) — drop it and
                        # re-enter via the quiescence path
                        seen_fallbacks = self._fallbacks
                        base = None
                if base is None:
                    # no pending plan to chain onto: a live-state prepare
                    # must not race a commit still mutating the document,
                    # so wait until every earlier batch has committed
                    with self._cv:
                        self._cv.wait_for(
                            lambda: self._n_committed >= k
                            or self._closing)
                    if self._closing and self._n_committed < k:
                        # abandoned session: hand the batch back serial
                        if obs.ENABLED:
                            obs.event("ring", "abort", args={
                                "doc": self.doc.obj_id, "slot": k})
                        self._out.put((k, batch, _SERIAL, None))
                        continue
                try:
                    _t0 = obs.now() if obs.ENABLED else 0
                    with self._prep_lock, self._device_ctx():
                        plan = self.doc.prepare_batch(batch, after=base)
                    if obs.ENABLED:
                        obs.span("ring", "plan", _t0, args={
                            "doc": self.doc.obj_id, "slot": k,
                            "chained": base is not None})
                    if base is not None:
                        with self._cv:
                            self._chained += 1
                except ValueError:
                    # not chainable (actor remap / missing shadow):
                    # the caller prepares this one inline after the
                    # preceding commit lands
                    plan = _SERIAL
                    if obs.ENABLED:
                        obs.event("ring", "serial", args={
                            "doc": self.doc.obj_id, "slot": k})
            except BaseException as e:   # pragma: no cover - defensive
                err = e
                plan = None
            self._out.put((k, batch, plan, err))
            base = plan if plan not in (None, _SERIAL) else None
