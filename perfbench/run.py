#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip this process finds.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json`` and in the files under ``perfbench/``.  The run makes the
weights on the device from ``--seed``, packs them with the program's
packer where the configuration states a packed format, builds the serving
engine through the adapter its ``model_type`` names
(``adapters/<model_type>.py``), warms every shape the mix can use, and
drives the engine for ``--seconds`` from its own loop.  With
``--trace 1`` it also records a profiler trace of a steady part of the
window and reports the cell's per-layer metrics instead of its end-to-end
ones.  After the window it frees the engine and checks a sample of the
served tokens against the configuration's plain float32 reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, last, ``checks``,
each number compared beside its limit.  The same checks are the last lines
of standard error.  Without a TPU of a known kind, or with fewer chips than
the cell asks for, the run prints no result and exits with status 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from harness import spec  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def _load_module(path: pathlib.Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _use_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _CompileCounter:
    """Counts executables JAX compiles or loads from the persistent cache
    (each one is a backend compile event)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Run:
    """One cell served for one window: set-up, the window, its readings."""

    def __init__(self, cell, seed: int, seconds: float, trace_root=None):
        import jax

        from harness import build, checks, device, drive, traffic
        from repro.models.model import LM

        self.cell = cell
        self.compiles = _CompileCounter()
        cfg, mix = cell.config, cell.mix
        self.adapter = adapter = _load_module(cell.adapter,
                                              "perfbench_adapter")
        self.mc = mc = adapter.model_config(cfg)
        model = LM(mc)
        self.ref = _load_module(cell.reference, "perfbench_reference")
        params, self.pack_s = build.build_params(
            adapter, model, cfg,
            lambda role, layer, *index: self.ref.checkpoint_matrix(
                cfg, seed, role, layer, *index))
        self.mats = build.matmuls(params, adapter, cfg)
        gen = traffic.Generator(mix, seed, cfg["vocab_size"])
        plens, outs = gen.lengths()
        lo, hi = int(plens.min()), int(plens.max())
        self.max_len = build.bucket(mc, mix, hi) + int(outs.max())
        if mix["loop"] == "closed":
            # a steady-state request's prompt holds what it served so far
            hi += int(outs.max()) - 1
            self.max_len = max(self.max_len, build.bucket(mc, mix, hi))
        n_pages = build.pool_pages(
            adapter.kv_page_bytes(mc, mix["page_size"]), mix, self.max_len,
            device.free_bytes())
        eng = build.engine(model, params, mix, self.max_len, n_pages)
        del params
        self.driver = driver = drive.Driver(eng, gen, mix)
        buckets = sorted({build.bucket(mc, mix, p) for p in range(lo, hi + 1)})
        driver.warm(buckets, cfg["vocab_size"])
        if mix["loop"] == "closed":
            driver.fill()
        driver.lead_in()
        # set-up's garbage (traced and compiled programs) is collected
        # now, and what survives is kept out of the window's collections
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - T_START
        log(setup_s=self.setup_s, pack_s=self.pack_s,
            compile_cache=jax.config.jax_compilation_cache_dir,
            kv_pages=n_pages, max_len=self.max_len, buckets=buckets)

        self.tracer = None
        if trace_root is not None:
            from harness import tracing

            self.tracer = tracing.Window(trace_root, seconds)
        before = self.compiles.n
        self.t0, self.t1 = driver.window(
            seconds, self.tracer.tick if self.tracer else None)
        late = driver.lateness or [0.0]
        recs = driver.window_records()
        log(compiles_in_window=self.compiles.n - before,
            generator_late_ms_p99=1e3 * drive.percentile(late, 99),
            steps_in_window=len(recs),
            admitted_in_window=sum(len(r.admitted) for r in recs),
            completed_in_window=sum(
                1 for s in driver.served.values()
                if s.done and self.t0 < s.stamps[-1] <= self.t1))
        if self.tracer is not None:
            self.tracer.close()
        self.peak_bytes = (device.memory_peak_bytes(cell.chips)
                           if jax.devices()[0].platform == "tpu" else 0)
        self.readings = r = checks.Readings.collect(driver, self.t0, self.t1,
                                                    mix)
        # the spread of the host-clock tails, for reading a run's noise
        log(ttft_ms_p50_p75_p90_max=[drive.percentile(r.ttft_ms, q)
                                     for q in (50, 75, 90, 100)]
            if r.ttft_ms else None, ttft_n=len(r.ttft_ms),
            itl_ms_p50_p95=[drive.percentile(r.itl_ms, q) for q in (50, 95)]
            if r.itl_ms else None, itl_n=len(r.itl_ms))

    def context(self, peaks):
        from harness import build

        mc, mix = self.mc, self.cell.mix
        return self.tracer.context(
            driver=self.driver, readings=self.readings, mats=self.mats,
            attention=self.adapter.attention(self.cell.config), peaks=peaks,
            mix=mix,
            pack_s=self.pack_s, bucket=lambda p: build.bucket(mc, mix, p))

    def sample_and_free(self, seed: int):
        """The requests compared; the engine and its pool are freed, so
        the reference that follows has the chip to itself."""
        from harness import checks

        sample = checks.sample(self.driver, seed)
        self.driver.eng = None
        gc.unfreeze()
        gc.collect()
        return sample

    @property
    def seq_len(self) -> int:
        return -(-self.max_len // 128) * 128


def log(**kw) -> None:
    print(json.dumps(kw), file=sys.stderr, flush=True)


def main(argv=None, *, require_tpu: bool = True, root: pathlib.Path = ROOT,
         base: pathlib.Path = HERE) -> int:
    """One run.  Tests pass ``require_tpu=False`` to drive it on the CPU,
    and their own ``root`` (holding ``BENCHMARK.json``) and ``base`` (the
    benchmark's files)."""
    args = parse_args(argv)
    cell = spec.load(root / "BENCHMARK.json", root, args.workload, base)

    import jax

    from harness import checks, device

    try:
        dev = (device.check(cell.chips) if require_tpu else
               {"platform": jax.devices()[0].platform,
                "kind": jax.devices()[0].device_kind, "count": cell.chips})
        peaks = device.peaks(dev["kind"] if require_tpu else "TPU v5 lite")
    except device.NoDevice as e:
        print(f"perfbench: {e}; nothing was run", file=sys.stderr)
        return 2
    _use_compile_cache()
    run = Run(cell, args.seed, args.seconds,
              root / ".bench_out" / "trace" if args.trace else None)

    metrics = {}
    ctx = None
    if args.trace:
        ctx = run.context(peaks)
        for m in cell.per_layer:
            reader = _load_module(cell.metric_reader(m["name"]),
                                  "perfbench_metric")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"setup_s": run.setup_s, **run.readings.end_to_end()}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    sample = run.sample_and_free(args.seed)
    result, correct, info = checks.compare(run.ref, cell.config, args.seed,
                                           sample, cell.limits, run.seq_len)
    log(compared=info)
    for name, c in result.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    dev = {**dev, "memory_peak_bytes": run.peak_bytes}
    if ctx is not None:
        dev.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
    # a request the engine refuses raises, and the run prints no result
    line = {"correct": correct, "attempted": run.readings.attempted,
            "failed": 0, "metrics": metrics, "device": dev}
    if ctx is not None:
        line["breakdown"] = ctx.breakdown()
        shutil.rmtree(run.tracer.dir, ignore_errors=True)
    line["checks"] = result
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
