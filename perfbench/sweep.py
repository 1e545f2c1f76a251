#!/usr/bin/env python3
"""Offered-load sweep of an open-loop cell, to find the rate it sustains.

    python3 perfbench/sweep.py --workload <cell> --seed 5 --seconds 40 \\
        --rates 1.0,1.5,2.0,2.5,3.0

Builds and warms the cell once, then for each rate serves the mix's
untimed lead-in and a window of Poisson arrivals at that rate (the mix
file's rate replaced), draining the engine between rates.  One JSON line
per rate: requests due and completed, completions per second, TTFT
percentiles, the backlog (due but not yet admitted) at the close and its
peak.  The cell's rate is set once from
this, at four fifths of the highest rate whose backlog does not grow.
"""
from __future__ import annotations

import argparse
import json
import sys

import run
from harness import checks, drive, spec, traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.load(run.ROOT / "BENCHMARK.json", run.ROOT, args.workload)
    if cell.mix["loop"] != "open":
        raise SystemExit("the sweep is for open-loop mixes")
    run._use_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    # build and warm once, at the first rate
    first = run.Run(cell, args.seed, 0.01)
    eng = first.driver.eng
    steps = first.driver.steps
    for i, rate in enumerate(rates):
        while not eng.sched.done:
            eng.step()
            steps += 1
        mix = dict(cell.mix, rate_per_s=rate)
        gen = traffic.Generator(mix, args.seed, cell.config["vocab_size"],
                                rid_base=(i + 1) * 10**6)
        d = drive.Driver(eng, gen, mix)
        d.steps = steps
        backlog = []
        d.lead_in()
        t0, t1 = d.window(args.seconds, lambda e: backlog.append(
            len(eng.sched.pending)))
        steps = d.steps
        r = checks.Readings.collect(d, t0, t1, mix)
        due = [s for s in d.served.values() if t0 <= s.due < t1]
        done = [s for s in due if s.done and s.stamps[-1] <= t1]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "completed": len(done),
            "completed_per_s": len(done) / (t1 - t0),
            "output_tok_s": r.tokens / (t1 - t0),
            "ttft_p50_ms": drive.percentile(r.ttft_ms, 50),
            "ttft_p90_ms": drive.percentile(r.ttft_ms, 90),
            "itl_p95_ms": drive.percentile(r.itl_ms, 95),
            "backlog_end": backlog[-1], "backlog_max": max(backlog),
            "backlog_mid": backlog[len(backlog) // 2]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
