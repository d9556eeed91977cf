"""Shared helpers for the BASELINE.md benchmark configs.

Each config prints one JSON line {"metric", "value", "unit", "vs_baseline"}.
The reference publishes no numbers (BASELINE.md), so vs_baseline is reported
against the driver's north-star rate where one is defined (configs tied to
the 100M ops/s target) and as 0.0/absent otherwise.
"""

import json
import os
import sys
import time

RESULTS: list = []  # every emit() of the run, for the per-round record file


def bench_platform() -> str:
    """The platform a measurement runs on: "tpu", or "cpu" when the run
    asked for the CPU explicitly (``JAX_PLATFORMS=cpu``). Anything else —
    a JAX that found no chip and fell back to the CPU on its own — raises,
    so a measurement never lands on the CPU unasked."""
    import jax
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return platform
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu":
        return platform
    raise RuntimeError(
        f"no TPU: JAX runs on {platform!r}; set JAX_PLATFORMS=cpu to "
        "measure the CPU on purpose")


def timed(fn, warmups: int = 1, reps: int = 2) -> float:
    """Best wall time over `reps` runs after `warmups` compile passes."""
    for _ in range(warmups):
        fn()
    return min(timed_once(fn) for _ in range(reps))


def timed_once(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _platform() -> str:
    """The platform every config in this process actually ran on — recorded
    in each result row so a CPU-fallback record can never masquerade as a
    chip measurement."""
    import jax
    return jax.devices()[0].platform


# marker for config rows whose absolute rate is platform-dependent and has
# no reference target: the row is tracked (cross-round, same-platform
# diffing) rather than asserted against a constant
TRACKING_ONLY = ("tracking-only: platform-dependent absolute rate with no "
                 "reference target; regressions caught by diffing "
                 "same-platform rows across round records")


def prior_committed_value(metric: str, platform: str, root: str = None):
    """Value of the latest committed record row for (metric, platform).

    Scans BENCH_CONFIGS_r*.json newest-first (by NUMERIC round — a
    lexicographic sort would rank r99 above r100 once rounds outgrow the
    2-digit padding) and returns the first matching row's value, or
    None. The committed records are the cross-round regression baseline
    the tracking-only methodology diffs against; this helper turns that
    diff into a machine check for the headline rows (CPU row >= its
    prior record -20%). `root` overrides the repo root (tests)."""
    import glob
    import re
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def round_no(path: str) -> int:
        m = re.search(r"_r(\d+)\.json$", path)
        return int(m.group(1)) if m else -1

    for path in sorted(glob.glob(os.path.join(root, "BENCH_CONFIGS_r*.json")),
                       key=round_no, reverse=True):
        try:
            with open(path) as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue
                    if (row.get("metric") == metric
                            and row.get("platform") == platform
                            and isinstance(row.get("value"), (int, float))):
                        return float(row["value"])
        except OSError:
            continue
    return None


def headline_cpu_floor(rec: dict, committed_metric: str,
                       slack: float = 0.8, root: str = None) -> dict:
    """Fold the cfg5/headline CPU floor into a bench record (in place).

    On cpu, the machine check is `value >= slack * latest committed cpu
    row` (chip rows carry `floor_met` against the 100M north star
    instead). The result is recorded, never silently dropped:
    `threshold_met` lands in the row and a miss prints to stderr so a
    regression of the one metric the project is judged on is loud in
    every sweep log."""
    if rec.get("platform") != "cpu":
        return rec
    prior = prior_committed_value(committed_metric, "cpu", root=root)
    if prior is None:
        rec["threshold"] += ("; cpu floor: no committed cpu row yet for "
                             f"{committed_metric} — this run seeds it")
        return rec
    bound = slack * prior
    met = bool(float(rec["value"]) >= bound)
    rec["threshold"] += (
        f"; cpu floor (machine-checked): value >= {slack:.0%} of the "
        f"latest committed cpu row ({round(prior)} {rec.get('unit', '')}, "
        f"{committed_metric}) -> threshold_met. The committed row may "
        "come from a DIFFERENT host: a miss with the device region "
        "untouched usually means the box changed, not the code — confirm "
        "with a same-box A/B before reading it as a regression")
    rec["threshold_met"] = met
    rec["threshold_prior_cpu"] = prior
    if not met:
        print(f"bench: HEADLINE CPU FLOOR MISS: {rec['metric']} = "
              f"{rec['value']} < {bound:.0f} (= {slack} x committed "
              f"{round(prior)}). Code regression OR host change — run a "
              "same-box A/B against the prior tree before concluding",
              file=sys.stderr)
    return rec


def emit(metric: str, value: float, unit: str,
         vs_baseline: float | None = None, **extra):
    # vs_baseline None -> json null: an honest "no defined target" instead
    # of a 0.0 placeholder
    rec = {"metric": metric, "value": round(value, 2), "unit": unit,
           "vs_baseline": (None if vs_baseline is None
                           else round(vs_baseline, 4)),
           **extra,
           # platform is stamped LAST so no extra kwarg can override
           # provenance
           "platform": _platform()}
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)


def write_record(path: str):
    """One JSON line per emitted config result (BENCH_CONFIGS_r<NN>.json).

    MERGE semantics per (metric, platform): an existing row is replaced
    only when THIS run re-emitted the same metric on the same platform.
    Cross-platform rows are always preserved (the chip session's sweep
    must not destroy the committed cpu rows the tracking-only regression
    methodology diffs against, and vice versa). Same-platform rows this
    run has NOT (yet) re-emitted are preserved too: the sweep calls this
    incrementally after every config, so a sweep that fails midway keeps
    the rows an earlier sweep recorded."""
    current = {(rec["metric"], rec["platform"]) for rec in RESULTS}
    kept = []
    if os.path.exists(path):
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    # a kill mid-rewrite may have truncated the final
                    # line of a previous record; a corrupt row must not
                    # wedge every future sweep
                    print(f"write_record: dropping unparsable line in "
                          f"{path}: {line[:80]!r}", file=sys.stderr)
                    continue
                if (rec.get("metric"), rec.get("platform")) not in current:
                    kept.append(rec)
    # atomic replace: a sweep killed mid-write must never leave a
    # half-written record
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        for rec in kept + RESULTS:
            fh.write(json.dumps(rec) + "\n")
    os.replace(tmp, path)
