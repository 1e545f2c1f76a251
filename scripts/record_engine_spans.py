#!/usr/bin/env python3
"""Record the engine's phase spans on the chip, and time what they cost.

    python3 scripts/record_engine_spans.py \\
        --out tests/data/engine_spans.xplane.pb

Builds one benchmark cell as ``perfbench/run.py`` does (weights made from
the seed and packed, the engine warmed, the mix's traffic driven for a
short untraced window), then:

1. prints the window's step log: per phase, its mean milliseconds per
   step, and the host and wait milliseconds per step;
2. records a ``jax.profiler`` trace with the Python tracer off
   (``python_tracer_level=0``) around a few engine steps, one of which
   admits a request, and copies its ``.xplane.pb`` to ``--out``;
3. times one phase span (``Engine._phase``: the profiler annotation and
   the step-log record) entered and left in a loop, with the profiler
   off, on with the Python tracer off, and on at the profiler's default.

The last line of standard output is one JSON object with all of it.
``--root`` names another checkout holding ``BENCHMARK.json`` and
``perfbench/`` (the CPU tests' tiny copy, for instance).
"""
from __future__ import annotations

import argparse
import glob
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _phase_means(steps) -> dict:
    """Mean milliseconds per step of each phase, of host and of wait time,
    and the admission phases' milliseconds per admitted request."""
    n = len(steps)
    phases: dict[str, float] = {}
    for r in steps:
        for k, v in r.phases.items():
            phases[k] = phases.get(k, 0.0) + v
    admitted = sum(r.admitted for r in steps)
    adm = sum(r.phases.get(p, 0.0) for r in steps if r.admitted
              for p in ("admission", "prefill", "prefill.wait", "page_write"))
    return {"steps": n,
            "host_ms_per_step": 1e3 * sum(r.host_s for r in steps) / n,
            "wait_ms_per_step": 1e3 * sum(r.wait_s for r in steps) / n,
            "step_ms": 1e3 * sum(r.t_end - r.t_begin for r in steps) / n,
            "admitted": admitted,
            "admission_ms": 1e3 * adm / admitted if admitted else None,
            "phase_ms_per_step": {k: 1e3 * v / n for k, v in
                                  sorted(phases.items(), key=lambda kv: -kv[1])}}


def _record(driver, directory: pathlib.Path, n_steps: int,
            attempts: int) -> tuple[str, list]:
    """Trace ``n_steps`` engine steps with the Python tracer off, starting
    just before a sequence completes so that a later step admits; retried
    until the traced steps hold an admission."""
    import jax

    from harness import xplane
    from repro.serving.scheduler import SeqPhase

    eng = driver.eng
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    for _ in range(attempts):
        for _ in range(10_000):
            if any(s.phase is SeqPhase.DECODING and s.remaining <= 1
                   for s in eng.sched.active.values()):
                break
            driver.step()
        else:
            raise RuntimeError("no sequence came near completion")
        shutil.rmtree(directory, ignore_errors=True)
        first = len(eng.metrics.steps.records)
        jax.profiler.start_trace(str(directory), profiler_options=opts)
        with jax.profiler.TraceAnnotation(xplane.MARK_START):
            pass
        for _ in range(n_steps):
            driver.step()
        with jax.profiler.TraceAnnotation(xplane.MARK_STOP):
            pass
        jax.profiler.stop_trace()
        traced = list(eng.metrics.steps.records)[first:]
        if any(r.admitted for r in traced):
            (path,) = glob.glob(str(directory / "**" / "*.xplane.pb"),
                                recursive=True)
            return path, traced
    raise RuntimeError(f"no admission in {attempts} traced attempts")


def _span_cost_us(eng, n: int) -> float:
    """Microseconds one ``Engine._phase`` span adds, entered and left
    inside a step record, over an empty loop of the same length."""
    log = eng.metrics.steps
    with log.step(-1):
        t = time.perf_counter()
        for _ in range(n):
            with eng._phase("commit"):
                pass
        spans = time.perf_counter() - t
    log.records.pop()
    t = time.perf_counter()
    for _ in range(n):
        pass
    empty = time.perf_counter() - t
    return 1e6 * (spans - empty) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="internlm2-1.8b-tcsc30.decode-batch")
    ap.add_argument("--seed", type=int, default=2147483901)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="the untraced window before the recording")
    ap.add_argument("--steps", type=int, default=4,
                    help="engine steps in the recorded trace")
    ap.add_argument("--attempts", type=int, default=12)
    ap.add_argument("--loops", type=int, default=20000,
                    help="spans entered per cost reading")
    ap.add_argument("--out", default=None,
                    help="copy the recorded .xplane.pb here")
    ap.add_argument("--root", default=str(ROOT))
    args = ap.parse_args(argv)
    root = pathlib.Path(args.root).resolve()
    sys.path[:0] = [str(ROOT / "perfbench"), str(ROOT / "src")]

    import jax
    import run
    from harness import spec, xplane

    cell = spec.load(root / "BENCHMARK.json", root, args.workload,
                     root / "perfbench")
    run._use_compile_cache()
    r = run.Run(cell, args.seed, args.seconds)
    driver, eng = r.driver, r.driver.eng
    out = {"workload": args.workload, "seed": args.seed,
           "device": jax.devices()[0].device_kind,
           "window": _phase_means(eng.metrics.steps.window(
               r.t0, r.t0 + r.readings.window_s))}

    work = root / ".bench_out" / "engine_spans"
    path, traced = _record(driver, work / "trace", args.steps,
                           args.attempts)
    out["recorded"] = {"bytes": pathlib.Path(path).stat().st_size,
                       **_phase_means(traced)}
    if jax.devices()[0].platform == "tpu":
        t = xplane.reduce(path)
        out["recorded"].update(window_s=t.window_s, busy_s=t.busy_s,
                               idle_gaps=t.idle_gaps)
    if args.out:
        shutil.copy(path, args.out)

    from jax.profiler import ProfileData

    host = [e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]
    steps = host.count("engine.step") // 2       # the harness's and ours
    phases = sum(1 for h in host if h.startswith("engine.step."))
    per_step = (phases + steps) / steps
    cost = {"off": _span_cost_us(eng, args.loops)}
    for label, level in (("on_python_tracer_0", 0), ("on_default", None)):
        opts = jax.profiler.ProfileOptions()
        if level is not None:
            opts.python_tracer_level = level
        jax.profiler.start_trace(str(work / label), profiler_options=opts)
        cost[label] = _span_cost_us(eng, args.loops)
        jax.profiler.stop_trace()
    step_ms = out["window"]["step_ms"]
    out["spans"] = {"per_step": per_step, "us_per_span": cost,
                    "us_per_step": {k: v * per_step for k, v in cost.items()},
                    "share_of_step_pct": {k: 100 * v * per_step
                                          / (1e3 * step_ms)
                                          for k, v in cost.items()}}
    shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
