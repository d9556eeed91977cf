"""Run one benchmark cell once.

    python3 -m loadbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, in one process that holds the chips. The
cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``loadbench/configs``) and a traffic mix (``loadbench/traffic``); the
mix names its generator. Set-up builds the service and its documents
from the seed, connects the clients and runs the warm-up; the window
then lasts ``--seconds``; a bounded drain follows; then the check.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read with the obs span recorder on and a
``jax.profiler`` trace over the window's first ``TRACE_S`` seconds. The
last stdout line is the JSON result and nothing else; the numbers the
check compared are the last lines of stderr and the last key of that
line.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result. ``--rehearse`` (not for measurement) runs the cell
at the tiny ``rehearse`` sizes of its files on the CPU with the Pallas
interpreter; ``--control`` adds the control's reading to the check's
output (the reference with siblings in arrival order put in the
engine's place).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _process_start() -> float:
    """This process's start on the ``time.perf_counter`` clock (Linux:
    from /proc; elsewhere: now, which leaves out interpreter start)."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


T_PROCESS = _process_start()

#: a traced run traces (profiler and obs spans) only this many seconds
#: at the start of its window: traces grow with the window, and writing
#: and reading them counts against the run's time limit
TRACE_S = 10.0


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def _peak_lookup(kind: str):
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)

    def peak(key):
        if kind not in table:
            raise KeyError(f"device kind {kind!r} is not in peaks.json")
        return float(table[kind][key])
    return peak


def _plan_ns(records, w0_ns: int, w1_ns: int):
    """Union per thread of the ``plan/*`` spans inside the window."""
    from loadbench.trace_reduce import clip, union
    by_tid: dict = {}
    for ts, dur, cat, _name, tid, _args in records:
        if cat == "plan" and dur >= 0:
            by_tid.setdefault(tid, []).append((ts, ts + dur))
    return sum(sum(e - s for s, e in union(clip(iv, w0_ns, w1_ns)))
               for iv in by_tid.values())


def _layer_context(c0, c1, offers, ticks, records, trace_dir, platform,
                   kind):
    """What the per-layer readers read over the traced span [c0, c1]:
    counter deltas, the generator's lateness, the tick durations, the
    plan spans and the reduced trace."""
    from loadbench import trace_reduce
    reduced = trace_reduce.reduce_dir(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    delta = {k: c1[k] - c0[k] for k in ("admitted_ops", "dispatches",
                                        "wire_bytes")}
    plan_ns = None if records is None else _plan_ns(
        records, int(c0["t"] * 1e9), int(c1["t"] * 1e9))
    # changes handed over inside the traced span: those due in it but
    # handed over after it waited for the profiler to stop, not for us
    lateness_ms = [(inj - due) * 1e3
                   for due, inj in zip(offers.due, offers.injected)
                   if c0["t"] <= due and inj <= c1["t"]]
    tick_ms = [d * 1e3 for t, d in ticks if c0["t"] <= t and t + d <= c1["t"]]
    return dict(delta, window_s=c1["t"] - c0["t"],
                compiles=_compiles(c0, c1), lateness_ms=lateness_ms,
                tick_ms=tick_ms, plan_ns=plan_ns, trace=reduced,
                platform=platform, peak=_peak_lookup(kind))


def _compiles(c0, c1) -> int:
    """Programs the engine's jitted kernels compiled (or loaded from the
    persistent cache) between two counter reads."""
    return (c1["compiles"]["compiles_total"]
            - c0["compiles"]["compiles_total"])


def _compiled(c0, c1) -> list:
    """The kernels behind ``_compiles``, each with its count."""
    a, b = c0["compiles"]["by_kernel"], c1["compiles"]["by_kernel"]
    return sorted(f"{k}: {n - a.get(k, 0)}" for k, n in b.items()
                  if n > a.get(k, 0))


def start(rehearse: bool, chips: int):
    """What every loadbench process does first: keep JAX's compile cache
    inside the checkout (every program, however fast it compiled, so that
    only a cell's first run in a checkout compiles), and find the chips.
    Returns the devices to use, or None, with the reason on stderr, when
    there is no TPU or fewer chips than ``chips``. ``rehearse`` runs on
    the CPU with the Pallas interpreter instead (not for measurement)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["AMTPU_FUSED_MODE"] = "interpret"
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        print(f"loadbench: no TPU (JAX runs on {platform!r})",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"loadbench: {chips} chips needed, JAX sees {len(devices)}",
              file=sys.stderr)
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from automerge_tpu._env import setup_compile_cache
    setup_compile_cache()
    return devices[:chips]


def make_traffic(cell, seed: int, seconds: float, rehearse: bool,
                 rate_steps=None):
    """The cell's configuration and its traffic drawn from ``seed``;
    ``rate_steps`` ([[seconds, rate], ...]) replaces the mix's rate."""
    from loadbench import spec
    config = spec.sized(cell.config, rehearse)
    mix = spec.sized(cell.mix, rehearse)
    if rate_steps is not None:
        mix["rate_steps"] = rate_steps
    return config, spec.generator(mix["kind"]).make(config, mix, seed,
                                                    seconds)


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    sys.path.insert(0, ROOT)
    from loadbench import checks, spec
    from loadbench.harness import CLOCK, Harness
    cell = spec.Cell(spec.benchmark(), args.workload)
    used = start(args.rehearse, cell.chips)
    if used is None:
        return 2
    import jax
    platform = used[0].platform

    config, traffic = make_traffic(cell, args.seed, args.seconds,
                                   args.rehearse)
    harness = Harness(config, traffic, trace=bool(args.trace))
    harness.build()
    # the documents and traffic set-up made live for the whole run: keep
    # the collector's full passes off them during warm-up and window
    gc.collect()
    gc.freeze()
    t_build = CLOCK()
    w0 = harness.warm_up()
    w1 = w0 + args.seconds

    trace_dir = os.path.join(ROOT, ".loadbench_trace", args.workload)
    if args.trace:
        from automerge_tpu import obs
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.enable(capacity=1 << 19)
        jax.profiler.start_trace(trace_dir)
    c0 = harness.counters()
    records = None
    if args.trace:
        with harness.annotate("window"):
            harness.run(min(w1, w0 + TRACE_S))
        c1 = harness.counters()
        jax.profiler.stop_trace()
        rec = obs.recorder()
        records = rec.snapshot() if rec.n_emitted == rec.n_retained \
            else None
        obs.disable()
    harness.run(w1)
    c_end = harness.counters()
    if not args.trace:
        c1 = c_end
    harness.settle(w1 + traffic.drain_s)
    t_drained = CLOCK()

    mem = [d.memory_stats() or {} for d in used]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    first, values, dict_changes, frames = harness.received()
    vis = checks.visibility(traffic, first)
    e2e = checks.end_to_end(traffic, vis, w0, w1, t_drained)
    rng = checks.seeded_rng(args.seed, 1)
    picks = checks.pick_docs(traffic, rng, int(config["docs_checked"]),
                             w0, w1)
    engine_raw = harness.engine_docs([traffic.rooms[r]["room"]
                                      for r in picks])
    engine = {r: engine_raw[traffic.rooms[r]["room"]] for r in picks}
    missing, altered = checks.fanout(traffic, first, values, dict_changes,
                                     frames, rng,
                                     int(config["frames_checked"]))
    ctx = None
    if args.trace:
        ctx = _layer_context(c0, c1, traffic.offers, harness.ticks,
                             records, trace_dir, platform,
                             used[0].device_kind)
    stats = {k: c1[k] - c0[k] for k in ("protocol_errors", "evictions")}
    harness_shapes = harness.shapes_warmed
    harness.close()
    del harness, first, values, dict_changes, frames
    gc.collect()

    room_changes = traffic.room_changes()
    compared = {
        "docs_mismatched": (checks.docs_mismatched(traffic, engine,
                                                   room_changes), 0),
        "fanout_missing": (missing, 0),
        "fanout_altered": (altered, 0),
    }
    correct = all(v <= lim for v, lim in compared.values())

    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            value = spec.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=w0 - T_PROCESS)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": correct, "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics,
              "device": {"platform": platform,
                         "kind": used[0].device_kind,
                         "count": len(used),
                         "memory_peak_bytes": memory_peak}}
    if args.trace:
        tr = ctx["trace"]
        result["device"]["busy_s"] = tr["busy_ns"] / 1e9
        result["device"]["window_s"] = tr["window_ns"] / 1e9
        result["breakdown"] = {
            "device_ops": [[n, ns / 1e9] for n, ns in tr["device_ops"]],
            "idle_gaps": [[n, ns / 1e9] for n, ns in tr["idle_gaps"]]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in compared.items()}

    info = {"setup_s": w0 - T_PROCESS, "build_s": t_build - T_PROCESS,
            "offered": len(traffic.offers), "drain_s": t_drained - w1,
            "compiles_in_window": _compiles(c0, c_end),
            "compiled_in_window": _compiled(c0, c_end),
            "shapes_warmed": harness_shapes, "docs_checked": len(engine),
            **stats}
    if args.control:
        info["control_docs_mismatched"] = checks.docs_mismatched(
            traffic, engine, room_changes, sibling_order="arrival")
    print("loadbench: " + json.dumps(info), file=sys.stderr)
    for k, (v, lim) in compared.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
