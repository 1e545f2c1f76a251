"""The traced part of a ``--trace 1`` window and what it reduces to.

The profiler records a steady sub-window: it starts a quarter into the
window and lasts half of it, at most 8 seconds, always between two engine
steps, when the device is idle.  The harness marks each step on the host
with a ``TraceAnnotation`` so that idle gaps on the device can be told
apart by what the host was doing.  :class:`Context` holds everything a
per-layer metric reads: the trace's reduction, the engine steps that ran
inside it, the forward pass's matmuls and attention, and the chip's
peaks.
"""
from __future__ import annotations

import glob
import pathlib
import shutil
import sys
import time

from harness import workcount, xplane

TRACE_FROM = 0.25          # of the window
TRACE_SHARE = 0.5
TRACE_MAX_S = 8.0


class Window:
    def __init__(self, directory: pathlib.Path, seconds: float):
        self.dir = pathlib.Path(directory)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.begin = TRACE_FROM * seconds
        self.end = self.begin + min(TRACE_MAX_S, TRACE_SHARE * seconds)
        self.started = self.stopped = None

    def tick(self, elapsed: float) -> None:
        import jax

        if self.started is None and elapsed >= self.begin:
            jax.profiler.start_trace(str(self.dir))
            with jax.profiler.TraceAnnotation(xplane.MARK_START):
                self.started = time.perf_counter()
        elif (self.started is not None and self.stopped is None
              and elapsed >= self.end):
            self.close()

    def close(self) -> None:
        import jax

        if self.started is not None and self.stopped is None:
            with jax.profiler.TraceAnnotation(xplane.MARK_STOP):
                self.stopped = time.perf_counter()
            jax.profiler.stop_trace()

    def context(self, **kw) -> "Context":
        files = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace under {self.dir}, found "
                               f"{files}")
        trace = xplane.reduce(files[0])
        return Context(trace=trace, t_start=self.started,
                       t_stop=self.stopped, **kw)


class Context:
    """What per-layer metric readers see (``metrics/<name>.py``)."""

    def __init__(self, *, trace, t_start, t_stop, driver, readings, mats,
                 attention, peaks, mix, pack_s, bucket):
        self.trace = trace
        self.driver = driver
        self.readings = readings
        self.mats = mats
        self.attention = attention
        self.peaks = peaks
        self.mix = mix
        self.pack_s = pack_s
        self.bucket = bucket
        self.records = [r for r in driver.records
                        if r.start >= t_start and r.end <= t_stop]
        self.busy_s = trace.busy_s
        self.window_s = trace.window_s

    # -- programs and kernels ---------------------------------------------
    def program_calls(self) -> list[tuple[str, int]]:
        """(program, rows) of every forward the traced steps ran: one
        prefill per admission at its bucket, one decode per step that
        decoded, at all slots."""
        calls = []
        for r in self.records:
            calls += [("prefill", self.bucket(p)) for p in r.admitted]
            if r.decoded:
                calls.append(("decode", self.mix["slots"]))
        return calls

    def program_ms(self, program: str) -> float | None:
        n, s = self.trace.programs.get(program, (0, 0.0))
        return 1e3 * s / n if n else None

    def roofline(self, kernel: str) -> float | None:
        """Share (%) of the kernel's device time that the least time of
        its own matmuls in the calls made needs; None when the trace has
        no such kernel or its calls do not match the programs that ran."""
        n, s = self.trace.kernels.get(kernel, (0, 0.0))
        mats = [m for m in self.mats if m.kernel == kernel]
        calls = self.program_calls()
        if not n or not calls or not mats:
            return None
        if n != len(calls) * len(mats):
            print(f"roofline {kernel}: {n} kernel events in the trace, "
                  f"{len(calls)} programs x {len(mats)} {kernel} "
                  f"matmuls expected", file=sys.stderr)
            return None
        least = sum(workcount.program_least_time_s(m, mats, self.peaks)
                    for _, m in calls)
        return 100.0 * least / s

    def mfu(self) -> float | None:
        """Model operations of every token the window processed (prompt
        tokens at admission, then each served token) over the window, as
        a share (%) of the chip's bf16 peak."""
        t0 = self.driver.t0
        t1 = t0 + self.readings.window_s
        w = workcount.weight_ops(self.mats)
        ops = 0.0
        for s in self.driver.served.values():
            plen = len(s.req.prompt)
            for i, t in enumerate(s.stamps):
                if not t0 < t <= t1:
                    continue
                if i == 0:        # the prompt: positions 1..plen attended
                    ops += plen * w + workcount.prompt_attention_ops(
                        self.attention, plen)
                else:
                    ops += w + workcount.attention_ops(self.attention,
                                                       plen + i)
        if not ops:
            return None
        return 100.0 * ops / self.readings.window_s / self.peaks["bf16_flops"]

    def idle_share(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        return {"device_ops": self.trace.top_ops[:10],
                "idle_gaps": self.trace.idle_gaps[:10]}
