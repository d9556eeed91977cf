"""Device-truth telemetry: compile, memory, and kernel-cost observability
for the XLA tier (INTERNALS §19).

Everything above the dispatch boundary is already exact — labeled
dispatch/sync counts (engine/accounting.py), span-derived bench terms
(INTERNALS §11), emit-time telemetry aggregates (§14). Below it the
device was a black box: the serial profile lumps all accelerator work
into one ``device_wait_s`` term, recompiles from static-shape bucket
churn are invisible, and nothing meters device-resident bytes. This
module extends the same discipline below the boundary, the way
`shard/audit.py` already proves the zero-collective claim from compiled
HLO:

- **Compiled-program registry.** Every jit entry point the engine
  dispatches through (the module-level jitted kernels in `ops/ingest.py`
  and `ops/linearize.py` — `engine/base.py`, `text_doc.py`,
  `map_doc.py`, `stacked.py` and the shard lanes all call these module
  attributes) is wrapped in a :class:`KernelHandle` at definition time.
  The wrapper costs one C++ ``_cache_size()`` probe (~60 ns) per launch:
  a cache-size growth IS a compile event, recorded with wall time, the
  traced shape signature, and the current default device; every other
  call is a cache hit. No call site changes — the handles are the same
  module attributes the engine always imported.
- **Cost/memory capture, once per compiled executable.** A compile
  event stores the argument tree abstracted to `ShapeDtypeStruct`s
  (never live buffers — a retained donated operand would be a deleted
  buffer AND a leak). ``analyses()`` lazily re-lowers from the abstract
  args and reads XLA ``cost_analysis()`` (flops, bytes accessed) +
  ``memory_analysis()`` (argument/output/temp/generated bytes); within
  a process the re-lower is served from jax's lowering cache, across
  processes from the persistent compile cache, so the probe never pays
  a second full backend compile in the steady state.
- **Recompile-storm detection.** Repeat compiles of one (label,
  variant) are attributed to their differing shape signatures
  (`recompile_report()`); :class:`steady_state` gives benches an
  in-run ``recompiles == 0`` assertion — the cfg15 acceptance bar.
  While obs tracing is on, each compile is also a ``device/compile``
  ring span (args: kernel) over its wall time.
- **Footprint metering.** `CausalDeviceDoc.device_footprint()` computes
  live table bytes from dtype x shape over the engine tables (9 for
  text, 5 for map) — parity with the live ``jax.Array`` buffer sizes is
  pinned in tests — and commits feed the per-doc gauge here
  (`note_footprint`); shard lanes aggregate per lane. `peak_bytes`
  tracks the high-water mark across the session. Exact h2d/d2h staged
  byte counters ride `engine/accounting.py` (``h2d_bytes`` /
  ``d2h_bytes`` in every `track()` delta).
- **Read side.** Always-on aggregates (`summary()`, merged into
  ``obs.metrics_snapshot()["device_truth"]``), Prometheus
  ``amtpu_device_*`` families on `SyncService.scrape()`
  (`families()`), Perfetto counter tracks stitched into every exported
  trace (`counter_events()` — "C"-phase samples fed at compile/footprint
  time), and the cost-model attribution helpers `attribute_device_time`
  / `roofline_seconds` that split a measured ``device_wait_s`` into
  per-kernel shares for the bench serial profile.

Like the lineage tier, the registry is independent of the trace ring:
``AMTPU_DEVICE_TRUTH=0`` disables the per-call probe (the wrapper then
forwards straight to the jitted callable; the overhead bound of the
disabled path is asserted in tests/test_device_truth.py per the PR-6
discipline), but the default is ON — compiles are rare and the probe is
two module loads and an int compare.

Label coverage contract: every ``label=`` an engine `_count_dispatch`
site carries must be a key of :data:`DISPATCH_LABEL_KERNELS` whose
kernels are all registered here, and every `_count_sync` label a member
of :data:`SYNC_LABELS` — the lint test walks the source tree, so a new
kernel cannot ship unmetered.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque

#: THE fast-path gate (same shape as obs.ENABLED / lineage.ENABLED):
#: instrumented kernels read this module attribute per call. Default ON —
#: the enabled path is one dict lookup, one 60 ns cache-size probe, and
#: two counter bumps; AMTPU_DEVICE_TRUTH=0 turns even that off.
ENABLED = os.environ.get("AMTPU_DEVICE_TRUTH", "1") not in ("", "0")

_LOCK = threading.Lock()

now = time.perf_counter_ns    # the shared trace clock (obs.now)

#: accounting dispatch label -> the registered kernel names that
#: implement it (several when a label covers plain/packed or legacy
#: comparator forms). The label-coverage lint asserts every
#: `_count_dispatch(label=...)` site in engine/ + ops/ appears here AND
#: that every named kernel is actually instrumented.
DISPATCH_LABEL_KERNELS = {
    "merge_materialize_planned": ("merge_and_materialize_dense_planned",),
    "merge_materialize_dense": ("merge_and_materialize_dense",),
    "apply_mixed_round": ("apply_mixed_round",),
    "apply_map_round": ("apply_map_round",),
    "scatter_registers": ("scatter_registers_packed", "scatter_registers"),
    "pack_rows": ("pack_rows",),
    "rga_linearize": ("rga_linearize",),
    # _run_materialize picks one of four per with_pos/prefer_planned;
    # the label covers all of them so cost attribution never zeroes out
    # on the planned (default) or with_pos shapes
    "materialize": ("materialize_codes", "materialize_text",
                    "materialize_codes_planned",
                    "materialize_text_planned"),
    "segment_visible_counts": ("segment_visible_counts",),
    "gather_spans": ("gather_spans",),
    "remap_actors": ("remap_actors",),
    "remap_ranks": ("remap_ranks",),
    "stacked_gather": ("stack_register_tables", "stack_element_tables"),
    "stacked_map_round": ("stacked_map_round",),
    "stacked_mixed_round": ("stacked_mixed_round",),
    "stacked_scatter": ("stacked_scatter_registers",),
    "stacked_unstack": ("unstack_rows",),
    "stacked_linearize": ("stacked_linearize",),
    "stacked_mirror_fetch": ("stacked_pack_rows",),
    # ISSUE-17 fused-round tier (ops/fused_round.py): the flag-free solo
    # mixed round, the both-lanes stacked megakernel, and the combined
    # slow-path scatter
    "fused_mixed_round": ("fused_mixed_round",),
    "fused_stacked_round": ("fused_stacked_round",),
    "fused_scatter": ("fused_scatter_registers",),
    # ISSUE-19 ring-commit tier: the pipelined ingestor's steady-state
    # dense merge+materialize commit as one fused-tier program (XLA
    # merge_and_materialize_dense* stays the comparator)
    "fused_commit_round": ("fused_commit_round",),
    "fused_commit_planned": ("fused_commit_round_planned",),
}

#: `_count_sync` labels: blocking d2h fetches and completion barriers.
#: These are transfers/waits, not program launches — they pair with the
#: dispatch label that produced the fetched buffer (or none, for pure
#: barriers). The lint asserts membership, and the d2h byte meter
#: (accounting.record_sync d2h_bytes=) is threaded through these sites.
SYNC_LABELS = frozenset({
    "stage_barrier",          # prepare-side h2d completion barrier
    "mirror_fetch",           # packed host-mirror fetch (pack_rows)
    "slow_info_fetch",        # the residual path's one packed round trip
    "scalars_fetch",          # read path's visible-count fetch
    "touched_fetch",          # the same, with the touched slots' rows
    "positions_fetch",        # RGA position pull
    "codes_pull",             # O(doc) codes buffer pull
    "segment_visible_counts",  # incremental pull per-segment counts
    "gather_spans",           # O(edits) span gather fetch
    "rga_linearize",          # position fetch after linearize
    "stacked_slow_info",      # stacked packed slow residue fetch
    "stacked_mirror_fetch",   # stacked packed mirror re-seed fetch
})


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def _sig_of(args: tuple, kwargs: dict) -> tuple:
    """The shape signature of one call: (dtype, shape) per array leaf,
    the repr of every non-array leaf (static flags, python scalars), and
    the current default device — so per-lane compiles of identical
    shapes (shard tier: same program, different device) attribute to
    the DEVICE axis, not to a phantom shape churn."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, tuple(sorted(kwargs.items()))))
    parts = []
    for leaf in leaves:
        dt = getattr(leaf, "dtype", None)
        shape = getattr(leaf, "shape", None)
        if dt is not None and shape is not None:
            parts.append((str(dt), tuple(shape)))
        else:
            parts.append(repr(leaf)[:64])
    dev = getattr(jax.config, "jax_default_device", None)
    return (str(dev), tuple(parts))


def _abstract(args: tuple, kwargs: dict):
    """(args, kwargs) with every array leaf replaced by a
    ShapeDtypeStruct — no device buffer survives into the registry (a
    retained donated operand would be a deleted buffer and a leak), and
    `fn.lower()` accepts the abstract tree verbatim."""
    import jax

    def one(leaf):
        dt = getattr(leaf, "dtype", None)
        shape = getattr(leaf, "shape", None)
        if dt is not None and shape is not None:
            return jax.ShapeDtypeStruct(tuple(shape), dt)
        return leaf

    return (jax.tree_util.tree_map(one, args),
            {k: jax.tree_util.tree_map(one, v) for k, v in kwargs.items()})


class KernelHandle:
    """One instrumented jitted callable, registered under (label,
    variant). Replaces the module attribute the engine imports; the
    call path is the jitted dispatch plus one cache-size probe."""

    __slots__ = ("_fn", "label", "variant", "calls", "compiles",
                 "compile_ns", "_size")

    def __init__(self, fn, label: str, variant: str = "plain"):
        self._fn = fn
        self.label = label
        self.variant = variant
        self.calls = 0
        self.compiles = 0
        self.compile_ns = 0
        self._size = 0

    def __call__(self, *args, **kwargs):
        fn = self._fn
        if not ENABLED:
            return fn(*args, **kwargs)
        t0 = time.perf_counter_ns()
        out = fn(*args, **kwargs)
        probe = getattr(fn, "_cache_size", None)
        sz = probe() if probe is not None else 0
        with _LOCK:
            self.calls += 1
            if probe is not None and sz != self._size:
                grew = sz > self._size
                self._size = sz
                if grew:
                    # wall time includes trace + compile + the first
                    # (async-enqueued) dispatch — dominated by compile,
                    # documented as such (§19.2)
                    wall = time.perf_counter_ns() - t0
                    self.compiles += 1
                    self.compile_ns += wall
                    REGISTRY._record_compile(self, wall, args, kwargs)
                # a shrink means jax.clear_caches() ran; resync silently
        return out

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return (f"<KernelHandle {self.label}/{self.variant} "
                f"calls={self.calls} compiles={self.compiles}>")


class DeviceTruthRegistry:
    """The per-process compiled-program registry + footprint meter.

    Jiffy-style read discipline: every read copies under the one lock,
    no reader can observe a torn compile event; bounded rings
    (compile events, counter-track samples) evict oldest while the
    aggregate counters stay exact across eviction (the PR-6 wraparound
    discipline)."""

    def __init__(self):
        self._kernels: "OrderedDict[tuple, KernelHandle]" = OrderedDict()
        self._events = deque(maxlen=_env_int("AMTPU_DEVICE_TRUTH_EVENTS",
                                             1024))
        # two SEPARATE counter-track sample rings: compile samples are
        # rare and precious (the recompile-diagnosis timeline), footprint
        # samples arrive per commit — sharing one ring would let a busy
        # session evict every compile sample in seconds
        self._samples = deque(maxlen=_env_int("AMTPU_DEVICE_TRUTH_SAMPLES",
                                              4096))
        self._fp_samples = deque(maxlen=_env_int(
            "AMTPU_DEVICE_TRUTH_SAMPLES", 4096))
        self._analyses: dict = {}     # (label, variant, sig) -> dict
        self._pending: "OrderedDict[tuple, tuple]" = OrderedDict()
        # (label, variant, sig) -> abstract (args, kwargs), uncomputed
        self.compiles_total = 0
        self.compile_ns_total = 0
        self._footprint: dict = {}    # (kind, key) -> bytes gauge
        self._doc_bytes_total = 0     # running sum of the doc gauges —
        # maintained by delta so the per-commit feed is O(1), never an
        # O(n_docs) re-sum under the process-global lock
        self.peak_bytes = 0
        self.pcache_hits = 0          # persistent compile cache, session
        self.pcache_misses = 0
        self._listener_installed = False

    # -- registration / compile events ---------------------------------

    def register(self, handle: KernelHandle):
        with _LOCK:
            self._kernels[(handle.label, handle.variant)] = handle
        self._install_cache_listener()
        return handle

    def _record_compile(self, handle: KernelHandle, wall_ns: int,
                        args: tuple, kwargs: dict):
        # called under _LOCK from KernelHandle.__call__
        from .. import obs
        if obs.ENABLED:
            end = time.perf_counter_ns()
            obs.span("device", "compile", end - wall_ns, t1_ns=end,
                     args={"kernel": f"{handle.label}/{handle.variant}"})
        sig = _sig_of(args, kwargs)
        self.compiles_total += 1
        self.compile_ns_total += wall_ns
        key = (handle.label, handle.variant, sig)
        if key not in self._analyses and key not in self._pending:
            self._pending[key] = _abstract(args, kwargs)
        self._events.append({
            "ts_ns": time.perf_counter_ns(), "label": handle.label,
            "variant": handle.variant, "wall_ns": wall_ns, "sig": sig,
            "n_for_label": handle.compiles})
        self._samples.append((time.perf_counter_ns(), {
            "compiles_total": self.compiles_total,
            "compile_seconds_total": self.compile_ns_total / 1e9}))

    def _install_cache_listener(self):
        """Session first-compile vs persistent-cache-served counts: jax
        monitoring emits /jax/compilation_cache/cache_{hits,misses} per
        backend compile when the persistent cache is configured
        (`_env.py` defaults it). Guarded — the private monitoring
        surface may move across jax versions, and device truth must
        degrade to 'unknown', never break the engine."""
        if self._listener_installed:
            return
        self._listener_installed = True
        try:
            from jax._src import monitoring

            def _on_event(name, **kw):
                if name == "/jax/compilation_cache/cache_hits":
                    with _LOCK:
                        self.pcache_hits += 1
                elif name == "/jax/compilation_cache/cache_misses":
                    with _LOCK:
                        self.pcache_misses += 1

            monitoring.register_event_listener(_on_event)
        except Exception:     # noqa: BLE001 — observability, not engine
            pass

    # -- compile read side ---------------------------------------------

    def kernels(self) -> dict:
        """{(label, variant): {"calls", "compiles", "compile_ns"}}."""
        with _LOCK:
            return {k: {"calls": h.calls, "compiles": h.compiles,
                        "compile_ns": h.compile_ns}
                    for k, h in self._kernels.items()}

    def registered_kernel_names(self) -> set:
        with _LOCK:
            return {label for (label, _v) in self._kernels}

    def compile_events(self) -> list:
        with _LOCK:
            return [dict(e) for e in self._events]

    def compile_snapshot(self) -> dict:
        """Point-in-time compile counters for delta taking (the
        steady-state assertion's input)."""
        with _LOCK:
            return {"compiles_total": self.compiles_total,
                    "by_kernel": {k: h.compiles
                                  for k, h in self._kernels.items()}}

    def compiles_since(self, snap: dict) -> dict:
        """{(label, variant): n_new_compiles} since `snap` (only nonzero
        entries; empty == steady state)."""
        cur = self.compile_snapshot()
        base = snap.get("by_kernel", {})
        return {k: n - base.get(k, 0)
                for k, n in cur["by_kernel"].items()
                if n - base.get(k, 0) > 0}

    def recompile_report(self) -> list:
        """Repeat compiles of one (label, variant), attributed to their
        differing shape signatures: [{label, variant, n_compiles,
        signatures: [sig...]}] for every kernel that compiled more than
        once — the recompile-storm diagnosis surface."""
        by_kernel: dict = {}
        for e in self.compile_events():
            by_kernel.setdefault((e["label"], e["variant"]),
                                 []).append(e["sig"])
        out = []
        with _LOCK:
            totals = {k: h.compiles for k, h in self._kernels.items()}
        for key, n in sorted(totals.items()):
            if n <= 1:
                continue
            sigs = by_kernel.get(key, [])
            out.append({"label": key[0], "variant": key[1],
                        "n_compiles": n,
                        "distinct_signatures": len(set(map(str, sigs))),
                        "signatures": [str(s) for s in sigs[-8:]]})
        return out

    # -- cost / memory analyses ----------------------------------------

    def analyses(self, compute: bool = True) -> dict:
        """{(label, variant): [{"sig", "flops", "bytes_accessed",
        "argument_bytes", "output_bytes", "temp_bytes",
        "generated_code_bytes"} ...]} — computed lazily, once per
        compiled executable, from the stored abstract args. `compute` =
        False returns only what's already materialized (hot paths must
        never trigger a lower)."""
        if compute:
            with _LOCK:
                todo = list(self._pending.items())
            for key, (a_args, a_kwargs) in todo:
                res = self._analyze_one(key, a_args, a_kwargs)
                with _LOCK:
                    self._pending.pop(key, None)
                    self._analyses[key] = res
        out: dict = {}
        with _LOCK:
            for (label, variant, sig), res in self._analyses.items():
                out.setdefault((label, variant), []).append(
                    {"sig": str(sig), **res})
        return out

    def _analyze_one(self, key, a_args, a_kwargs) -> dict:
        label, variant, _sig = key
        with _LOCK:
            handle = self._kernels.get((label, variant))
        if handle is None:
            return {"error": "kernel no longer registered"}
        try:
            compiled = handle._fn.lower(*a_args, **a_kwargs).compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            mem = compiled.memory_analysis()
            return {
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                "argument_bytes": int(getattr(
                    mem, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(
                    mem, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "generated_code_bytes": int(getattr(
                    mem, "generated_code_size_in_bytes", 0)),
            }
        except Exception as exc:   # noqa: BLE001 — capture, don't break
            return {"error": f"{type(exc).__name__}: {exc}"[:200]}

    def kernel_costs(self) -> dict:
        """Per-label cost model merged over variants/signatures:
        {label: {"calls", "flops_per_call", "bytes_per_call"}}. The MAX
        signature's cost is used — steady-state streams run one bucketed
        capacity, the largest compiled one (§19.4 caveats)."""
        an = self.analyses()
        with _LOCK:
            calls: dict = {}
            for (label, _v), h in self._kernels.items():
                calls[label] = calls.get(label, 0) + h.calls
        out = {}
        for label, n in calls.items():
            if n == 0:
                continue
            flops = bys = 0.0
            for (lab, _v), results in an.items():
                if lab != label:
                    continue
                for r in results:
                    if "error" in r:
                        continue
                    flops = max(flops, r["flops"])
                    bys = max(bys, r["bytes_accessed"])
            out[label] = {"calls": n, "flops_per_call": flops,
                          "bytes_per_call": bys}
        return out

    # -- footprint ------------------------------------------------------

    def note_footprint(self, kind: str, key: str, nbytes: int):
        """Feed one device-resident footprint gauge (kind in
        {"doc", "lane"}) and roll the peak-total high-water mark. O(1)
        per call: the doc total is maintained by gauge delta, and a
        counter-track sample lands only when the total actually moved."""
        nbytes = int(nbytes)
        with _LOCK:
            old = self._footprint.get((kind, key), 0)
            self._footprint[(kind, key)] = nbytes
            if kind != "doc" or nbytes == old:
                return
            self._doc_bytes_total += nbytes - old
            total = self._doc_bytes_total
            if total > self.peak_bytes:
                self.peak_bytes = total
            self._fp_samples.append((time.perf_counter_ns(), total))

    def drop_footprint(self, kind: str, key: str):
        with _LOCK:
            old = self._footprint.pop((kind, key), None)
            if kind == "doc" and old:
                self._doc_bytes_total -= old

    def footprint(self) -> dict:
        with _LOCK:
            return {"gauges": {f"{k}:{key}": v
                               for (k, key), v in sorted(
                                   self._footprint.items())},
                    "device_bytes_total": self._doc_bytes_total,
                    "peak_device_bytes": self.peak_bytes}

    # -- export surfaces ------------------------------------------------

    def summary(self) -> dict:
        """The always-on aggregate view merged into
        obs.metrics_snapshot()["device_truth"] and the bench records."""
        with _LOCK:
            kernels = {f"{label}/{v}": {"calls": h.calls,
                                        "compiles": h.compiles,
                                        "compile_ms": round(
                                            h.compile_ns / 1e6, 3)}
                       for (label, v), h in sorted(self._kernels.items())
                       if h.calls or h.compiles}
            out = {
                "compiles_total": self.compiles_total,
                "compile_seconds_total": round(
                    self.compile_ns_total / 1e9, 4),
                "kernels": kernels,
                "persistent_cache": {"hits": self.pcache_hits,
                                     "misses": self.pcache_misses},
            }
        out["recompiles"] = self.recompile_report()
        out["footprint"] = self.footprint()
        from .. import _env
        out["compile_cache"] = _env.compile_cache_state()
        from ..engine import accounting
        tot = accounting.snapshot()
        out["staged_bytes"] = {"h2d": tot.get("h2d_bytes", 0),
                               "d2h": tot.get("d2h_bytes", 0)}
        return out

    def families(self, prefix: str = "amtpu_device") -> list:
        """Prometheus exposition families — what `SyncService.scrape()`
        appends (always-on; validate_prom-clean by construction)."""
        with _LOCK:
            kernel_rows = [((label, v), h.calls, h.compiles,
                            h.compile_ns)
                           for (label, v), h in sorted(
                               self._kernels.items())]
            fp = dict(self._footprint)
            peak = self.peak_bytes
            hits, misses = self.pcache_hits, self.pcache_misses
            compiles_total = self.compiles_total
        from ..engine import accounting
        tot = accounting.snapshot()
        fams = [
            (f"{prefix}_kernel_calls_total", "counter",
             "Jitted kernel launches through the device-truth registry.",
             [({"kernel": k, "variant": v}, calls)
              for (k, v), calls, _c, _ns in kernel_rows if calls]),
            (f"{prefix}_compiles_total", "counter",
             "XLA compile events per kernel (cache-size growth at the "
             "jit boundary).",
             [({"kernel": k, "variant": v}, comp)
              for (k, v), _calls, comp, _ns in kernel_rows if comp]
             + [({"kernel": "_all", "variant": "_all"}, compiles_total)]),
            (f"{prefix}_compile_seconds_total", "counter",
             "Wall seconds spent in trace+compile per kernel.",
             [({"kernel": k, "variant": v}, ns / 1e9)
              for (k, v), _calls, _c, ns in kernel_rows if ns]),
            (f"{prefix}_persistent_cache_total", "counter",
             "Persistent XLA compile cache outcomes this session.",
             [({"outcome": "hit"}, hits), ({"outcome": "miss"}, misses)]),
            (f"{prefix}_staged_bytes_total", "counter",
             "Exact staged transfer bytes at the counted engine seams.",
             [({"direction": "h2d"}, tot.get("h2d_bytes", 0)),
              ({"direction": "d2h"}, tot.get("d2h_bytes", 0))]),
            (f"{prefix}_peak_footprint_bytes", "gauge",
             "High-water mark of summed per-doc device table bytes.",
             [({}, peak)]),
        ]
        if fp:
            fams.append((
                f"{prefix}_footprint_bytes", "gauge",
                "Device-resident table bytes per doc / shard lane "
                "(dtype x shape over the engine tables).",
                [({"kind": k, "key": key}, v)
                 for (k, key), v in sorted(fp.items())]))
        return fams

    def counter_events(self, t0_ns: int, pid: int = 1) -> list:
        """Chrome trace counter-track ("C"-phase) samples: compile
        totals (their own ring — rare, never evicted by commit traffic)
        and device-resident bytes over session time, stitched into
        every exported trace (obs/export.py)."""
        with _LOCK:
            samples = list(self._samples)
            fp_samples = list(self._fp_samples)
        out = []
        for ts, vals in samples:
            if ts < t0_ns:
                continue
            for name, v in vals.items():
                out.append({"ph": "C", "name": f"amtpu_device_{name}",
                            "cat": "device_truth", "pid": pid, "tid": 0,
                            "ts": (ts - t0_ns) / 1000.0,
                            "args": {"value": round(v, 3)}})
        for ts, total in fp_samples:
            if ts < t0_ns:
                continue
            out.append({"ph": "C",
                        "name": "amtpu_device_device_bytes_total",
                        "cat": "device_truth", "pid": pid, "tid": 0,
                        "ts": (ts - t0_ns) / 1000.0,
                        "args": {"value": total}})
        return out

    def clear_session(self):
        """Reset per-session counters/gauges (tests; the kernel handles
        themselves persist — they ARE the module attributes)."""
        with _LOCK:
            self._events.clear()
            self._samples.clear()
            self._fp_samples.clear()
            self._footprint.clear()
            self._doc_bytes_total = 0
            self.peak_bytes = 0


REGISTRY = DeviceTruthRegistry()


def registry() -> DeviceTruthRegistry:
    return REGISTRY


def instrument(fn, label: str, variant: str = "plain") -> KernelHandle:
    """Wrap one jitted callable; the handle replaces the module
    attribute, so every engine call site is intercepted without
    change."""
    return REGISTRY.register(KernelHandle(fn, label, variant))


def instrument_pair(pair, label: str):
    """(plain, donated) jit twins -> instrumented twins. Donation
    efficacy is countable from the two variants' call counters."""
    plain, donated = pair
    return (instrument(plain, label, "plain"),
            instrument(donated, label, "donated"))


def summary() -> dict:
    return REGISTRY.summary()


def families(prefix: str = "amtpu_device") -> list:
    return REGISTRY.families(prefix)


def donation_efficacy() -> dict:
    """Per-label donated-call share: {label: {"donated", "plain",
    "share"}} for labels with a donated twin — how much of the steady
    state actually rides donation (flat device allocation)."""
    ker = REGISTRY.kernels()
    donated_labels = {lab for (lab, v) in ker if v == "donated"}
    out: dict = {}
    for (label, variant), st in ker.items():
        if label not in donated_labels:
            continue
        e = out.setdefault(label, {"donated": 0, "plain": 0})
        e["donated" if variant == "donated" else "plain"] += st["calls"]
    return {label: {**e, "share": round(
        e["donated"] / max(1, e["donated"] + e["plain"]), 4)}
        for label, e in out.items()}


class steady_state:
    """Context manager asserting no kernel compiled inside the region:

        with device_truth.steady_state() as ss:
            ... the timed reps ...
        ss.assert_zero()        # or read ss.recompiles

    The cfg15 bench runs its timed region inside one; a bucket-churn
    recompile fails the run naming the kernel and both signatures."""

    def __init__(self):
        self.recompiles: dict = {}

    def __enter__(self):
        self._snap = REGISTRY.compile_snapshot()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.recompiles = REGISTRY.compiles_since(self._snap)
        return False

    def assert_zero(self):
        if self.recompiles:
            report = [r for r in REGISTRY.recompile_report()
                      if (r["label"], r["variant"]) in self.recompiles
                      or any(k[0] == r["label"]
                             for k in self.recompiles)]
            raise AssertionError(
                f"recompiles at steady state: {self.recompiles} — "
                f"shape churn attribution: {report}")


# ---------------------------------------------------------------------------
# cost-model attribution (the bench serial-profile split)
# ---------------------------------------------------------------------------


def _label_cost(label: str, costs: dict) -> tuple:
    """(flops_per_call, bytes_per_call) of one ACCOUNTING label,
    resolved to its registered kernels via DISPATCH_LABEL_KERNELS (an
    unmapped label falls back to its own name); several kernels under
    one label (plain/donated, packed/legacy) merge by max — variants of
    one logical program."""
    names = DISPATCH_LABEL_KERNELS.get(label, (label,))
    flops = max((costs.get(n, {}).get("flops_per_call", 0.0)
                 for n in names), default=0.0)
    bys = max((costs.get(n, {}).get("bytes_per_call", 0.0)
               for n in names), default=0.0)
    return flops, bys


def attribute_device_time(label_calls: dict, total_s: float) -> dict:
    """Split a measured device-wait total into per-kernel shares by
    cost-model weight (calls x bytes accessed — these kernels are
    memory-bound on every backend we run): {label: share_s}, summing to
    `total_s` by construction. Labels without a captured analysis weigh
    in at the population's median bytes/call so they are visible, never
    silently dropped (§19.4 records the attribution caveats)."""
    costs = REGISTRY.kernel_costs()
    known = [c["bytes_per_call"] for c in costs.values()
             if c["bytes_per_call"] > 0]
    fallback = sorted(known)[len(known) // 2] if known else 1.0
    weights = {}
    for label, n in label_calls.items():
        if n <= 0:
            continue
        _, per_call = _label_cost(label, costs)
        weights[label] = n * (per_call if per_call > 0 else fallback)
    wsum = sum(weights.values())
    if wsum <= 0:
        return {}
    return {label: round(total_s * w / wsum, 6)
            for label, w in sorted(weights.items())}


#: Published peak rates of one chip, keyed by JAX's ``device_kind``.
#: TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
#: 197 TFLOP/s bf16, 819 GB/s HBM bandwidth. A kind not listed here has
#: no roofline: `peak_rates` raises rather than assume one.
PEAK_RATES = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peak_rates(device_kind: str) -> dict:
    """{"flops_per_s", "bytes_per_s"} of `device_kind` from PEAK_RATES;
    an unknown kind raises ValueError."""
    try:
        return PEAK_RATES[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device kind {device_kind!r}; "
            "add them to obs/device_truth.PEAK_RATES with their "
            "source") from None


def roofline_seconds(label_calls: dict) -> dict:
    """Cost-model roofline floor for the given per-label call counts:
    sum over labels of calls x max(flops/peak_flops, bytes/peak_bw),
    with the peaks of this process's device kind (`peak_rates`). On the
    CPU there is no roofline — it is not a device metric — so
    ``seconds`` is None ("not measured") and ``per_label`` is empty."""
    import jax
    dev = jax.devices()[0]
    out = {"seconds": None, "per_label": {}, "peak_flops": None,
           "peak_bytes_per_s": None, "platform": dev.platform,
           "device_kind": dev.device_kind}
    if dev.platform == "cpu":
        return out
    peaks = peak_rates(dev.device_kind)
    peak_flops, peak_bw = peaks["flops_per_s"], peaks["bytes_per_s"]
    costs = REGISTRY.kernel_costs()
    total = 0.0
    per_label = {}
    for label, n in label_calls.items():
        flops, bys = _label_cost(label, costs)
        if n <= 0 or (flops == 0.0 and bys == 0.0):
            continue
        t = n * max(flops / peak_flops, bys / peak_bw)
        per_label[label] = round(t, 6)
        total += t
    out.update(seconds=round(total, 6), per_label=per_label,
               peak_flops=peak_flops, peak_bytes_per_s=peak_bw)
    return out


def compile_cache_snapshot() -> dict:
    """The persistent-compile-cache block for bench records: the
    `_env.py` defaulted state (dir, enabled, entries on disk) plus this
    session's first-compile vs cache-served counts."""
    from .. import _env
    state = _env.compile_cache_state()
    with _LOCK:
        state["session_cache_hits"] = REGISTRY.pcache_hits
        state["session_cache_misses"] = REGISTRY.pcache_misses
        state["session_compiles"] = REGISTRY.compiles_total
    return state
