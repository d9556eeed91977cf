"""Find the highest rate an open-loop cell sustains: one process, one
set-up, then the cell's traffic at each rate of ``--rates`` for
``--step`` seconds each, back to back.

    python3 -m loadbench.sweep --workload rooms-typing --seed 7 \
        --rates 200,400,800,1600 --step 10

Prints one JSON line per rate: the changes offered, the wire ops that
became visible to every other online peer during the step per second,
visibility p50/p99 of the step's changes, and how many of them were
still not visible when the step ended (a backlog that grows from step to
step means the rate is past the knee). Not part of the benchmark's
command: the mix file holds the rate the sweep chose, as a number.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from loadbench import checks, spec
    from loadbench.harness import CLOCK, Harness
    from loadbench.run import make_traffic, start

    cell = spec.Cell(spec.benchmark(), args.workload)
    if start(args.rehearse, cell.chips) is None:
        return 2
    rates = [float(r) for r in args.rates.split(",")]
    warmup_s = float(cell.mix["warmup_s"])
    window = args.step * len(rates)
    config, traffic = make_traffic(
        cell, args.seed, window, args.rehearse,
        rate_steps=[[warmup_s, rates[0]]] + [[args.step, r] for r in rates])
    harness = Harness(config, traffic, trace=False)
    t_build = CLOCK()
    harness.build()
    gc.collect()
    gc.freeze()
    print(f"loadbench.sweep: build {CLOCK() - t_build:.1f} s",
          file=sys.stderr, flush=True)
    w0 = harness.warm_up()
    harness.run(w0 + window)
    harness.settle(CLOCK() + traffic.drain_s)
    first = harness.received()[0]
    vis = checks.visibility(traffic, first)
    for k, rate in enumerate(rates):
        a, b = w0 + k * args.step, w0 + (k + 1) * args.step
        e2e = checks.end_to_end(traffic, vis, a, b, CLOCK())
        pending = sum(1 for d, t in zip(traffic.offers.due, vis)
                      if a <= d < b and (t is None or t > b))
        print(json.dumps({"rate": rate, "offered": e2e["attempted"],
                          "ops_per_s": e2e["ops_per_s"],
                          "visibility_p50_ms": e2e["visibility_p50_ms"],
                          "visibility_p99_ms": e2e["visibility_p99_ms"],
                          "not_visible_at_step_end": pending}),
              flush=True)
    harness.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
