"""cfg4 stacked-rounds smoke: budget-asserted A/B + schema-valid trace.

Usage: python -m benchmarks.cfg4_smoke [--record-session]

The CI entry for the stacked multi-object tier (engine/stacked.py,
INTERNALS §12). One quick Trellis merge (the exact cfg4 generator,
benchmarks/run_all.trellis_changes) runs three ways:

1. AMTPU_STACKED_ROUNDS=1 — the stacked path, with the object-count-
   independent per-round dispatch budget ASSERTED
   (stacked.assert_round_budget) and the merge's dispatch count captured;
2. AMTPU_STACKED_ROUNDS=0 — the per-object comparator, same change set,
   committed state asserted identical (to_json + save), dispatch count
   captured for the A/B;
3. a traced stacked run: the plan/stack + commit/stacked_round spans and
   stacked kernel counters must export as schema-valid Chrome trace JSON
   (obs.export.validate_chrome_trace), so the new spans stay
   Perfetto-loadable.

`--record-session` appends the cpu A/B row to BENCH_SESSIONS.jsonl per
the PR-4 credibility rules (full JSON, git-sha-stamped, append-only).
On cpu the DISPATCH-COUNT delta is the headline — cpu e2e is
device-bound on the dev box and wall-clock A/Bs there are noise; the
wall-clock payoff lands where dispatch overhead is a real cost (a
chip's host<->device round trip).
"""

import json
import os
import sys


def _merge(saved: bytes, changes, flag: str):
    """One measured merge against a FRESH core (each am.apply_changes on
    a shared base state would fork the prior run's advanced core by
    replay, polluting the A/B's dispatch counts with replay work)."""
    import time

    import automerge_tpu as am
    from automerge_tpu.engine import accounting, stacked

    os.environ["AMTPU_STACKED_ROUNDS"] = flag
    base = am.load(saved)
    stacked.LAST_STATS.clear()
    t0 = time.perf_counter()
    with accounting.track() as tr:
        merged = am.apply_changes(base, changes)
    dt = time.perf_counter() - t0
    return merged, {
        "dispatches": tr.thread_stats["dispatches"],
        "syncs": tr.thread_stats["syncs"],
        "merge_s": round(dt, 4),
        "stacked": dict(stacked.LAST_STATS),
    }


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    import automerge_tpu as am
    from automerge_tpu import obs
    from automerge_tpu.engine import stacked
    from automerge_tpu.obs.export import validate_chrome_trace
    from benchmarks.run_all import trellis_changes

    n_actors = 100
    base, changes, n_ops = trellis_changes(n_actors)
    saved = am.save(base)

    # warm-up both paths once (pays the one-time jit compiles so the
    # recorded wall clocks compare like for like; the dispatch COUNTS —
    # the cpu headline — are identical cold or warm)
    _merge(saved, changes, "1")
    _merge(saved, changes, "0")

    # 1. stacked path: parity + asserted budget
    m1, stat1 = _merge(saved, changes, "1")
    assert stat1["stacked"], "stacked path did not engage on cfg4 --quick"
    stacked.assert_round_budget(stat1["stacked"])

    # 2. per-object comparator: byte-identical committed state
    m0, stat0 = _merge(saved, changes, "0")
    assert not stat0["stacked"]
    canon = lambda d: json.dumps(am.to_json(d), sort_keys=True,  # noqa: E731
                                 default=str)
    assert canon(m1) == canon(m0), "stacked/per-object state diverged"
    assert am.save(m1) == am.save(m0)
    assert stat1["dispatches"] < stat0["dispatches"], (
        "stacked merge did not reduce dispatch count: "
        f"{stat1['dispatches']} vs {stat0['dispatches']}")

    # 3. traced stacked run, schema-validated
    os.environ["AMTPU_STACKED_ROUNDS"] = "1"
    trace_path = os.environ.get("AMTPU_TRACE_OUT", "cfg4_trace.json")
    fresh = am.load(saved)
    with obs.tracing():
        am.apply_changes(fresh, changes)
        rec = obs.recorder()
        names = {(r[obs.CAT], r[obs.NAME]) for r in rec.snapshot()}
        obs.write_trace(trace_path)
    assert ("plan", "stack") in names, "plan/stack span missing"
    assert ("commit", "stacked_round") in names, \
        "commit/stacked_round span missing"
    summary = validate_chrome_trace(trace_path)

    st = stat1["stacked"]
    row = {
        "metric": f"cfg4_stacked_dispatch_ab_{n_actors}_actors",
        "unit": "dispatches/merge",
        "value": stat1["dispatches"],
        "n_ops": n_ops,
        "dispatch_per_op": round(stat1["dispatches"] / n_ops, 4),
        "per_object_dispatches": stat0["dispatches"],
        "dispatch_reduction": round(
            stat0["dispatches"] / max(1, stat1["dispatches"]), 1),
        "stacked": st,
        "merge_s_stacked": stat1["merge_s"],
        "merge_s_per_object": stat0["merge_s"],
        "trace": summary,
        "threshold": ("asserted in code: stacked dispatches <= "
                      f"{stacked.APPLY_DISPATCH_BASE} + "
                      f"{stacked.PASS_DISPATCH_BUDGET} per round-pass "
                      "(>= 1 pass per causal round), "
                      "object-count-independent; state byte-identical "
                      "to the per-object comparator"),
    }
    from benchmarks.common import _platform
    row["platform"] = _platform()
    print(json.dumps(row), flush=True)

    if "--record-session" in argv:
        import datetime

        import bench as B
        row["recorded_at_utc"] = datetime.datetime.now(
            datetime.timezone.utc).isoformat()
        row["git_sha"] = B._git_sha()
        try:
            import subprocess
            dirty = subprocess.run(
                ["git", "status", "--porcelain"], capture_output=True,
                text=True, timeout=10).stdout.strip()
            if dirty:
                # honest provenance for rows recorded before the commit
                # that introduces the measured code (sha = parent)
                row["git_dirty"] = True
        except Exception:
            pass
        row["timed_region"] = (
            "one cfg4 --quick Trellis merge (100 actors, ~21 objects) "
            "through am.apply_changes; dispatches counted via "
            "engine/accounting thread totals; A/B = same change set, "
            "AMTPU_STACKED_ROUNDS 1 vs 0. On cpu the dispatch-count "
            "delta is the headline (e2e is device-bound on this box).")
        B.append_session_log(row)
        print(f"# appended to {B.SESSION_LOG_PATH}", file=sys.stderr)


if __name__ == "__main__":
    from automerge_tpu._env import setup_compile_cache

    setup_compile_cache()
    main()
