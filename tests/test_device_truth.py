"""Device-truth telemetry (automerge_tpu/obs/device_truth.py,
INTERNALS §19).

Pins the tier's contracts (ISSUE 15):

1. **Compile events are real events.** A new shape signature through an
   instrumented kernel records exactly one compile event with its
   signature; a cache-hit call records none (the recompile detector's
   no-false-positive half).
2. **Recompile storms attribute to shape churn.** Repeat compiles of one
   kernel name their differing signatures; `steady_state` raises with
   that attribution when anything compiles inside the region.
3. **Cost capture holds no buffers.** Analyses come from
   ShapeDtypeStruct trees — flops/bytes are present, and no live
   jax.Array survives into the registry (donation safety + no leak).
4. **Footprint is dtype x shape truth.** `device_footprint()` equals the
   summed live jax.Array buffer sizes for text and map docs, and the
   exact h2d/d2h byte meters move when the engine stages/fetches.
5. **Export surfaces validate.** amtpu_device_* families are
   validate_prom-clean; counter tracks ride the Chrome trace and pass
   validate_chrome_trace; metrics_snapshot carries the summary.
6. **Disabled is cheap, enabled is bounded.** The AMTPU_DEVICE_TRUTH=0
   path is a flag check + direct call; the enabled per-call probe is
   bounded per the PR-6 discipline.
7. **Label coverage lint.** Every `_count_dispatch`/`_count_sync` label
   in engine/ + ops/ is registered (DISPATCH_LABEL_KERNELS /
   SYNC_LABELS) with every mapped kernel actually instrumented — a new
   kernel cannot ship unmetered.
"""

import os
import re
import time

import numpy as np
import pytest

import bench as B
from automerge_tpu import _env, obs
from automerge_tpu.engine import DeviceMapDoc, DeviceTextDoc, accounting
from automerge_tpu.obs import device_truth as dt
from automerge_tpu.obs import prom
from automerge_tpu.obs.export import to_chrome_trace, validate_chrome_trace

ENGINE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "automerge_tpu")


@pytest.fixture(autouse=True)
def _device_truth_on():
    """Every test runs with the flag in its default ON state and a
    clean per-session surface (gauges/events; kernel handles persist —
    they ARE the module attributes)."""
    was = dt.ENABLED
    dt.ENABLED = True
    yield
    dt.ENABLED = was


def _fresh_kernel(label, variant="plain", fn=None):
    import jax
    return dt.instrument(jax.jit(fn or (lambda x: x * 2 + 1)), label,
                         variant)


# -- 1/2: compile events + recompile attribution --------------------------


def test_compile_event_once_per_signature_cache_hit_no_event():
    import jax.numpy as jnp
    k = _fresh_kernel("t_sig_once")
    snap = dt.REGISTRY.compile_snapshot()
    k(jnp.ones(8))
    assert dt.REGISTRY.compiles_since(snap) == {("t_sig_once", "plain"): 1}
    k(jnp.ones(8))            # cache hit: same signature
    k(jnp.ones(8))
    assert dt.REGISTRY.compiles_since(snap) == {("t_sig_once", "plain"): 1}
    assert k.calls == 3 and k.compiles == 1
    evs = [e for e in dt.REGISTRY.compile_events()
           if e["label"] == "t_sig_once"]
    assert len(evs) == 1 and evs[0]["wall_ns"] > 0
    assert ("float32", (8,)) in evs[0]["sig"][1]


def test_recompile_attributed_to_shape_churn():
    import jax.numpy as jnp
    k = _fresh_kernel("t_churn")
    k(jnp.ones(4))
    k(jnp.ones(16))           # second shape -> recompile
    rep = [r for r in dt.REGISTRY.recompile_report()
           if r["label"] == "t_churn"]
    assert len(rep) == 1
    assert rep[0]["n_compiles"] == 2
    assert rep[0]["distinct_signatures"] == 2
    assert any("(4,)" in s for s in rep[0]["signatures"])
    assert any("(16,)" in s for s in rep[0]["signatures"])


def test_steady_state_clean_and_violated():
    import jax.numpy as jnp
    k = _fresh_kernel("t_steady")
    k(jnp.ones(4))            # warmup compile
    with dt.steady_state() as ss:
        for _ in range(5):
            k(jnp.ones(4))
    assert ss.recompiles == {}
    ss.assert_zero()          # no raise

    with dt.steady_state() as ss2:
        k(jnp.ones(32))       # fresh shape INSIDE the region
    assert ss2.recompiles == {("t_steady", "plain"): 1}
    with pytest.raises(AssertionError, match="t_steady"):
        ss2.assert_zero()


def test_disabled_flag_skips_probe_and_counts():
    import jax.numpy as jnp
    k = _fresh_kernel("t_flag_off")
    dt.ENABLED = False
    y = k(jnp.ones(4))
    assert float(y[0]) == 3.0     # the kernel itself still runs
    assert k.calls == 0 and k.compiles == 0
    dt.ENABLED = True
    k(jnp.ones(4))
    assert k.calls == 1 and k.compiles == 1  # compiled while off: the
    # cache-size resync records the first observed entry as a compile


# -- 3: cost/memory capture -----------------------------------------------


def test_analysis_captured_without_retaining_buffers():
    import jax
    import jax.numpy as jnp
    k = _fresh_kernel("t_cost", fn=lambda a, b: (a * b).sum())
    k(jnp.ones((64, 64)), jnp.ones((64, 64)))
    an = dt.REGISTRY.analyses()
    results = an[("t_cost", "plain")]
    assert len(results) == 1
    r = results[0]
    assert r["flops"] > 0 and r["bytes_accessed"] > 0
    assert r["argument_bytes"] == 2 * 64 * 64 * 4
    # no live jax.Array may survive into the registry (donation safety)
    with dt._LOCK:
        stored = list(dt.REGISTRY._pending.values())
    for a_args, a_kwargs in stored:
        for leaf in jax.tree_util.tree_leaves((a_args, a_kwargs)):
            assert not isinstance(leaf, jax.Array), leaf


def test_donated_twin_registers_as_variant():
    import jax
    import jax.numpy as jnp
    plain, donated = dt.instrument_pair(
        (jax.jit(lambda a: a + 1),
         jax.jit(lambda a: a + 1, donate_argnums=(0,))), "t_twin")
    plain(jnp.ones(4))
    donated(jnp.ones(4))
    donated(jnp.ones(4))
    ker = dt.REGISTRY.kernels()
    assert ker[("t_twin", "plain")]["calls"] == 1
    assert ker[("t_twin", "donated")]["calls"] == 2
    eff = dt.donation_efficacy()["t_twin"]
    assert eff == {"donated": 2, "plain": 1, "share": round(2 / 3, 4)}


# -- 4: footprint + byte meters -------------------------------------------


def _buffer_bytes(doc) -> int:
    total = 0
    for arr in doc._dev.values():
        n = 1
        for d in arr.shape:
            n *= int(d)
        total += n * np.dtype(arr.dtype).itemsize
    return total


def test_text_footprint_parity_with_live_buffers():
    doc = DeviceTextDoc("fp-text")
    doc.apply_batch(B.base_batch("fp-text", 2_000))
    doc.text()
    fp = doc.device_footprint()
    assert fp["n_tables"] == 9
    assert fp["table_bytes"] == _buffer_bytes(doc)
    # live jax.Array nbytes agree with the dtype x shape computation
    live = sum(int(a.nbytes) for a in doc._dev.values())
    assert fp["table_bytes"] == live
    assert fp["device_bytes"] >= fp["table_bytes"]
    assert fp["host"]["index_ranges"] >= 1


def test_map_footprint_parity_and_gauge_feed():
    from automerge_tpu.engine.columnar import MapChangeBatch
    dt.REGISTRY.clear_session()
    doc = DeviceMapDoc("fp-map")
    b = MapChangeBatch.from_changes([
        {"actor": "a", "seq": 1, "deps": {},
         "ops": [{"action": "set", "obj": "fp-map", "key": f"k{i}",
                  "value": i} for i in range(64)]}], "fp-map")
    doc.apply_batch(b)
    fp = doc.device_footprint()
    assert fp["n_tables"] == 5
    assert fp["table_bytes"] == _buffer_bytes(doc)
    g = dt.REGISTRY.footprint()
    assert g["gauges"].get("doc:fp-map") == fp["device_bytes"]
    assert g["peak_device_bytes"] >= fp["device_bytes"]


def test_byte_meters_move_and_are_exact_at_prepare():
    doc = DeviceTextDoc("meter-text")
    doc.apply_batch(B.base_batch("meter-text", 5_000))
    doc.text()
    batch = B.merge_batch("meter-text", 100, 100, 5_000, seed=7)
    with accounting.track() as t:
        plan = doc.prepare_batch(batch)
        doc.commit_prepared(plan)
        doc.text()
    assert t.stats["h2d_bytes"] >= plan.n_staged_bytes > 0
    assert t.stats["d2h_bytes"] > 0
    assert doc.dispatch_stats["h2d_bytes"] > 0
    assert doc.dispatch_stats["d2h_bytes"] > 0


def test_footprint_feed_is_o1_and_compile_samples_survive_commit_flood():
    """Review pins: (a) the per-commit gauge feed maintains a running
    doc total (no O(n_docs) re-sum — drop/refeed keeps it exact); (b)
    footprint samples live in their OWN ring, so a commit flood cannot
    evict the rare compile samples; (c) an unchanged gauge adds no
    sample."""
    import jax.numpy as jnp
    dt.REGISTRY.clear_session()
    k = _fresh_kernel("t_flood")
    k(jnp.ones(8))                       # one compile sample
    n_compile_samples = len(dt.REGISTRY._samples)
    assert n_compile_samples >= 1
    for i in range(5000):                # commit-flood the fp ring
        dt.REGISTRY.note_footprint("doc", f"d{i % 7}", 100 + i)
    assert len(dt.REGISTRY._samples) == n_compile_samples
    # running total == sum of the live gauges (delta maintenance exact)
    g = dt.REGISTRY.footprint()
    assert g["device_bytes_total"] == sum(
        v for key, v in g["gauges"].items() if key.startswith("doc:"))
    dt.REGISTRY.drop_footprint("doc", "d0")
    g2 = dt.REGISTRY.footprint()
    assert g2["device_bytes_total"] == sum(
        v for key, v in g2["gauges"].items() if key.startswith("doc:"))
    # unchanged refeed: no new sample
    before = len(dt.REGISTRY._fp_samples)
    dt.REGISTRY.note_footprint("doc", "d1", g2["gauges"]["doc:d1"])
    assert len(dt.REGISTRY._fp_samples) == before


def test_materialize_label_covers_all_four_kernels():
    """Review pin: `_run_materialize` launches one of four kernels per
    with_pos/prefer_planned — the label must map all of them, or cost
    attribution zeroes out on the default (planned) shapes."""
    assert set(dt.DISPATCH_LABEL_KERNELS["materialize"]) == {
        "materialize_codes", "materialize_text",
        "materialize_codes_planned", "materialize_text_planned"}


# -- 5: export surfaces ----------------------------------------------------


def test_prom_families_validate_clean():
    import jax.numpy as jnp
    k = _fresh_kernel("t_prom")
    k(jnp.ones(4))
    dt.REGISTRY.note_footprint("doc", "prom-doc", 12345)
    page = prom.expose(dt.families())
    res = prom.validate_prom(page)
    assert res["samples"] > 0
    assert "amtpu_device_compiles_total" in page
    assert 'kernel="t_prom"' in page
    assert 'amtpu_device_footprint_bytes{key="prom-doc",kind="doc"} 12345' \
        in page


def test_counter_tracks_ride_the_trace_and_validate():
    import jax.numpy as jnp
    with obs.tracing():
        obs.clear()
        t0 = obs.now()
        with obs.span_ctx("bench", "region"):
            k = _fresh_kernel("t_trace")
            k(jnp.ones(4))                   # compile event -> sample
            dt.REGISTRY.note_footprint("doc", "trace-doc", 999)
        recs = obs.snapshot()
    trace = to_chrome_trace(recs, t0_ns=t0)
    res = validate_chrome_trace(trace)
    assert res["n_counter_samples"] >= 2
    names = {ev["name"] for ev in trace["traceEvents"]
             if ev.get("ph") == "C"}
    assert "amtpu_device_compiles_total" in names
    assert "amtpu_device_device_bytes_total" in names


def test_counter_sample_schema_enforced():
    from automerge_tpu.obs.export import TraceValidationError
    bad = {"traceEvents": [
        {"ph": "X", "name": "s", "cat": "c", "ts": 0, "dur": 1},
        {"ph": "C", "name": "ctr", "cat": "c", "ts": 0,
         "args": {"value": "not-a-number"}}]}
    with pytest.raises(TraceValidationError, match="counter"):
        validate_chrome_trace(bad)


def test_metrics_snapshot_carries_device_truth():
    import jax.numpy as jnp
    k = _fresh_kernel("t_snapshot")
    k(jnp.ones(4))
    snap = obs.metrics_snapshot()
    assert "device_truth" in snap
    s = snap["device_truth"]
    assert s["compiles_total"] >= 1
    assert "t_snapshot/plain" in s["kernels"]
    assert s["compile_cache"]["dir"]
    assert {"hits", "misses"} <= set(s["persistent_cache"])


def test_compile_cache_state_is_observable_and_jax_free():
    state = _env.compile_cache_state()
    assert set(state) >= {"dir", "exists", "entries"}
    # overriding the env var is visible without touching jax
    state2 = _env.compile_cache_state(
        {"JAX_COMPILATION_CACHE_DIR": "/nonexistent-cache-dir"})
    assert state2["dir"] == "/nonexistent-cache-dir"
    assert state2["exists"] is False and state2["entries"] == 0
    snap = dt.compile_cache_snapshot()
    assert {"session_cache_hits", "session_cache_misses",
            "session_compiles"} <= set(snap)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PLACEMENT_PROBE = (
    "import sys; sys.path.insert(0, {repo!r}); "
    "from automerge_tpu._env import setup_compile_cache; "
    "import jax, jax.numpy as jnp; "
    "print(setup_compile_cache()); "
    "print(jax.config.jax_compilation_cache_dir); "
    "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()")


@pytest.mark.parametrize("env_set", [True, False],
                         ids=["env-set", "env-unset"])
def test_compile_cache_placement(tmp_path, env_set):
    """`setup_compile_cache` is the one place the cache is set: with
    JAX_COMPILATION_CACHE_DIR set the run writes its cache there; unset,
    the cache is <repo>/.jax_cache whatever the working directory."""
    import subprocess
    import sys

    from automerge_tpu._env import virtual_cpu_env
    env = virtual_cpu_env(1)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if env_set:
        want = str(tmp_path / "x")
        env["JAX_COMPILATION_CACHE_DIR"] = want
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    cwd = tmp_path / "elsewhere"
    cwd.mkdir()
    out = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_PROBE.format(repo=REPO)],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
    if env_set:
        assert any(not n.endswith("-atime") for n in os.listdir(want))
    assert not os.listdir(cwd)


def test_peak_rates_keyed_by_device_kind():
    v5e = dt.peak_rates("TPU v5 lite")
    assert v5e == {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    with pytest.raises(ValueError, match="no published peak rates"):
        dt.peak_rates("TPU v9 imaginary")


def test_roofline_not_measured_on_cpu():
    roof = dt.roofline_seconds({"merge_materialize_dense": 3})
    assert roof["seconds"] is None and roof["per_label"] == {}
    assert roof["platform"] == "cpu"


# -- 6: overhead bounds ----------------------------------------------------


def _per_call_ns(fn, x, n=1_000, rounds=5) -> float:
    """Best-of-rounds per-call cost — min, not mean: scheduler noise on
    a loaded CI box only ever ADDS time, so the minimum is the honest
    estimate of the path's own cost (the PR-6 overhead-bar method)."""
    best = None
    for _ in range(rounds):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            fn(x)
        dt_ns = (time.perf_counter_ns() - t0) / n
        best = dt_ns if best is None else min(best, dt_ns)
    return best


def test_disabled_path_overhead_bound():
    """AMTPU_DEVICE_TRUTH=0: the wrapper is one module-flag check and a
    tail call. Bound the per-call delta vs the raw jitted callable the
    PR-6 way — best-of-rounds, single-digit microseconds of margin so
    a loaded suite run cannot flake while a real regression (anything
    doing work on the off path) still fails by orders of magnitude."""
    import jax.numpy as jnp
    k = _fresh_kernel("t_overhead_off")
    x = jnp.ones(4)
    k(x)                                  # compile out of the loop
    dt.ENABLED = False
    wrapped = _per_call_ns(k, x)
    raw = _per_call_ns(k._fn, x)
    assert wrapped - raw < 5_000, (wrapped, raw)


def test_enabled_probe_overhead_bound():
    import jax.numpy as jnp
    k = _fresh_kernel("t_overhead_on")
    x = jnp.ones(4)
    k(x)
    wrapped = _per_call_ns(k, x)
    raw = _per_call_ns(k._fn, x)
    # cache-size probe + lock + two counter bumps: single-digit
    # microseconds against a ~10us jit dispatch; bound loosely enough
    # for CI noise, tightly enough that a lower() on the hot path fails
    assert wrapped - raw < 25_000, (wrapped, raw)


# -- 7: label-coverage lint -------------------------------------------------

_LABEL_RE = re.compile(
    r'(_count_dispatch|_count_sync|_count|record_dispatch|record_sync)'
    r'\s*\((?:[^)]*?)label="([a-z_0-9]+)"|'
    r'(_count|_count_sync)\s*\(\s*stats\s*,\s*"([a-z_0-9]+)"')


def _source_labels():
    """(dispatch_labels, sync_labels) actually present at call sites in
    engine/ + ops/ source."""
    dispatch, sync = set(), set()
    for sub in ("engine", "ops"):
        root = os.path.join(ENGINE_ROOT, sub)
        for name in sorted(os.listdir(root)):
            if not name.endswith(".py"):
                continue
            src = open(os.path.join(root, name)).read()
            for m in re.finditer(
                    r'_count_dispatch\([^)]*label="([a-z_0-9]+)"', src):
                dispatch.add(m.group(1))
            for m in re.finditer(
                    r'_count_sync\([^)]*label="([a-z_0-9]+)"', src):
                sync.add(m.group(1))
            # the stacked helpers: _count(stats, "x") / _count_sync(
            # stats, "x", ...)
            for m in re.finditer(
                    r'_count\(\s*stats\s*,\s*"([a-z_0-9]+)"', src):
                dispatch.add(m.group(1))
            for m in re.finditer(
                    r'_count_sync\(\s*stats\s*,\s*"([a-z_0-9]+)"', src):
                sync.add(m.group(1))
    return dispatch, sync


def test_label_coverage_every_dispatch_label_registered():
    """ISSUE 15 satellite: a kernel cannot ship unmetered — every
    dispatch label used in engine/ or ops/ must map to registered
    device-truth kernels, every sync label must be declared."""
    dispatch, sync = _source_labels()
    assert dispatch, "lint found no dispatch labels — regex rot"
    assert sync, "lint found no sync labels — regex rot"
    registered = dt.REGISTRY.registered_kernel_names()
    missing = {}
    for label in sorted(dispatch):
        kernels = dt.DISPATCH_LABEL_KERNELS.get(label)
        if kernels is None:
            missing[label] = "label not in DISPATCH_LABEL_KERNELS"
            continue
        unreg = [k for k in kernels if k not in registered]
        if unreg:
            missing[label] = f"kernels not instrumented: {unreg}"
    assert not missing, (
        "unmetered dispatch labels (add the kernel to "
        f"DISPATCH_LABEL_KERNELS + instrument it): {missing}")
    undeclared = sorted(sync - dt.SYNC_LABELS)
    assert not undeclared, (
        f"sync labels not declared in device_truth.SYNC_LABELS: "
        f"{undeclared}")


def test_label_map_has_no_stale_entries():
    """The inverse direction: every label in the map is actually used
    by some call site (a renamed label must update the map, not strand
    a stale alias that would green-light the lint forever)."""
    dispatch, sync = _source_labels()
    stale = sorted(set(dt.DISPATCH_LABEL_KERNELS) - dispatch)
    assert not stale, f"DISPATCH_LABEL_KERNELS entries unused: {stale}"
    stale_sync = sorted(dt.SYNC_LABELS - sync)
    assert not stale_sync, f"SYNC_LABELS entries unused: {stale_sync}"


# -- the cfg15 record shape (quick, in-process) ----------------------------


@pytest.mark.slow
def test_cfg15_quick_record_asserts_steady_state():
    rec = B.measure_device_truth(quick=True, reps=5)
    assert rec["recompiles_at_steady_state"] == 0
    assert rec["bytes_staged_per_op"] > 0
    assert rec["peak_device_bytes"] > 0
    assert rec["prom_families_validated"] is True
    assert rec["compile_cache"]["dir"]
