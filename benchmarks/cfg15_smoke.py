"""Device-truth smoke: traced cfg15 quick run + scrape validation.

Usage: python -m benchmarks.cfg15_smoke

The CI entry for the device-truth telemetry tier (obs/device_truth.py,
INTERNALS §19). One process, three checks:

1. the cfg15 quick record through `bench.measure_device_truth` — zero
   steady-state compile events asserted in-run, nonzero exact h2d/d2h
   byte meters, dtype x shape peak footprint, cost-model flops/bytes
   per op present;
2. the `amtpu_device_*` families on a LIVE SyncService scrape page —
   the full service exposition (with device families appended) must be
   validate_prom-clean, and the device families must actually carry
   kernel/compile/footprint samples from the run above;
3. the exported Chrome trace must hold device-truth "C"-phase counter
   samples and pass validate_chrome_trace (Perfetto counter tracks).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main():
    from automerge_tpu import obs
    from automerge_tpu.obs import device_truth as dt
    from automerge_tpu.obs import prom
    from automerge_tpu.obs.export import (to_chrome_trace,
                                          validate_chrome_trace)
    import bench as B

    # (1) the cfg15 quick record, traced so counter samples land
    with obs.tracing():
        t0 = obs.now()
        rec = B.measure_device_truth(quick=True, reps=5)
        recs = obs.snapshot()
    assert rec["recompiles_at_steady_state"] == 0, rec
    assert rec["compile_count"] > 0, rec
    assert rec["bytes_staged_per_op"] > 0, rec
    assert rec["d2h_bytes_per_op"] > 0, rec
    assert rec["peak_device_bytes"] > 0, rec
    assert rec["cost_model_bytes_per_op"] > 0, rec
    print(f"cfg15 quick: {rec['value']} ops/s, "
          f"{rec['compile_count']} warmup compiles, "
          f"{rec['bytes_staged_per_op']} staged B/op, "
          f"peak {rec['peak_device_bytes']} device B")

    # (2) the live scrape: service page + amtpu_device_* families
    from automerge_tpu.service import ServiceConfig, SyncService
    svc = SyncService(ServiceConfig())
    page = svc.scrape()
    res = prom.validate_prom(page)
    assert "amtpu_device_compiles_total" in page, "device families absent"
    assert "amtpu_device_peak_footprint_bytes" in page
    assert "amtpu_device_staged_bytes_total" in page
    assert 'direction="h2d"' in page
    n_dev = sum(1 for ln in page.splitlines()
                if ln.startswith("amtpu_device_"))
    assert n_dev >= 5, f"only {n_dev} device samples on the scrape"
    print(f"scrape: {res['families']} families, {res['samples']} samples "
          f"({n_dev} amtpu_device_*), validate_prom clean")

    # (3) counter tracks in the exported trace
    trace = to_chrome_trace(recs, t0_ns=t0)
    tres = validate_chrome_trace(trace)
    assert tres["n_counter_samples"] > 0, tres
    names = {e["name"] for e in trace["traceEvents"] if e.get("ph") == "C"}
    assert "amtpu_device_compiles_total" in names, names
    print(f"trace: {tres['n_spans']} spans, "
          f"{tres['n_counter_samples']} counter samples, schema valid")


if __name__ == "__main__":
    from automerge_tpu._env import setup_compile_cache

    setup_compile_cache()
    main()
