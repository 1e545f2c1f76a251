"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Part of the yardstick.  On a TPU the trace has one plane per chip,
``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per program
execution, named ``jit_<function>(<hash>)``) and a line ``XLA Ops`` (one
event per HLO operation; a ``while`` loop's event spans its body's events).
The host plane's lines hold the Python frames and the harness's
``TraceAnnotation`` spans, on the same clock.

* busy time: the union of the ``XLA Ops`` intervals, averaged over chips;
* kernels: the custom calls of each Pallas kernel, matched by the name the
  trace gives them (the jitted wrapper's name);
* programs: module executions by function name, with aliases for the
  engine's prefill and decode programs;
* idle gaps: the device's idle intervals, each named by the innermost
  serving or harness frame that was running on the host at its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

# kernel -> the name its custom calls carry in the trace
KERNELS = {"pallas_fused": "sod_matmul_pallas",
           "pallas_block": "block_matmul_pallas"}
# program alias -> the jitted function the engine runs for it
PROGRAMS = {"decode": "paged_decode_step", "prefill": "prefill_full",
            "page_write": "_pool_write_pages"}
# ops whose event encloses other ops' events
_CONTAINERS = ("%while", "%conditional", "%call")
# host frames that name an idle gap, innermost first wins
_HOST_FRAMES = re.compile(r"^(\$(engine|scheduler|pool)\.py:\d+ \w+|"
                          r"engine\.step|harness\.\w+)")
_MODULE = re.compile(r"^jit_(.+?)(\(\d+\))?$")
# host annotations the harness places just inside the traced span
MARK_START = "perfbench.trace_start"
MARK_STOP = "perfbench.trace_stop"


@dataclasses.dataclass
class Trace:
    window_s: float
    busy_s: float
    kernels: dict            # kernel -> (calls, device seconds)
    programs: dict           # program -> (executions, device seconds)
    top_ops: list            # [[op, device seconds], ...] longest first
    idle_gaps: list          # [[host activity, idle seconds], ...]


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0]


def _window(host, devices) -> tuple[float, float]:
    """The traced span: between the harness's ``perfbench.trace`` markers,
    else between the returns of ``start_trace`` and the call of
    ``stop_trace``, else the span of the device's events."""
    for first, last in ((MARK_START, MARK_STOP),
                        (" start_trace", " stop_trace")):
        lo = [e.end_ns for e in host if e.name.endswith(first)]
        hi = [e.start_ns for e in host if e.name.endswith(last)]
        if lo and hi:
            return min(lo), max(hi)
    ops = [e for lines in devices for e in lines.get("XLA Ops", [])]
    return min(e.start_ns for e in ops), max(e.end_ns for e in ops)


def reduce(path: str, window: tuple[float, float] | None = None) -> Trace:
    """Reduce one trace.  ``window`` is the traced span in the trace's own
    nanoseconds; by default the span :func:`_window` finds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append({line.name: list(line.events)
                            for line in plane.lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += list(line.events)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    if window is None:
        window = _window(host, devices)
    lo, hi = window
    window_s = (hi - lo) * 1e-9

    busy = 0.0
    kernels: dict[str, list] = {}
    programs: dict[str, list] = {}
    ops: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for lines in devices:
        op_events = [e for e in lines.get("XLA Ops", [])
                     if e.end_ns > lo and e.start_ns < hi]
        merged = _union((max(e.start_ns, lo), min(e.end_ns, hi))
                        for e in op_events)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for e in op_events:
            name = _op_name(e.name)
            if not name.startswith(_CONTAINERS):
                ops[name] = ops.get(name, 0.0) + e.duration_ns * 1e-9
            for kernel, prefix in KERNELS.items():
                if name.startswith(f"%{prefix}"):
                    k = kernels.setdefault(kernel, [0, 0.0])
                    k[0] += 1
                    k[1] += e.duration_ns * 1e-9
        for e in lines.get("XLA Modules", []):
            if e.end_ns <= lo or e.start_ns >= hi:
                continue
            m = _MODULE.match(e.name)
            fn = m.group(1) if m else e.name
            p = programs.setdefault(fn, [0, 0.0])
            p[0] += 1
            p[1] += e.duration_ns * 1e-9
    n = len(devices)
    for alias, fn in PROGRAMS.items():
        if fn in programs:
            programs[alias] = programs[fn]
    idle: dict[str, float] = {}
    frames = sorted((e for e in host if _HOST_FRAMES.match(e.name)),
                    key=lambda e: e.start_ns)
    starts = [e.start_ns for e in frames]
    for s, e in gaps:
        mid = (s + e) / 2
        label = "no host frame"
        best = None
        for ev in frames[:bisect.bisect_right(starts, mid)]:
            if ev.end_ns >= mid and (best is None
                                     or ev.start_ns >= best.start_ns):
                best = ev
        if best is not None:
            label = best.name
        idle[label] = idle.get(label, 0.0) + (e - s) * 1e-9 / n
    return Trace(
        window_s=window_s, busy_s=busy / n,
        kernels={k: (v[0] // n, v[1] / n) for k, v in kernels.items()},
        programs={k: (v[0] // n, v[1] / n) for k, v in programs.items()},
        top_ops=sorted(([k, v / n] for k, v in ops.items()),
                       key=lambda kv: -kv[1]),
        idle_gaps=sorted(([k, v] for k, v in idle.items()),
                         key=lambda kv: -kv[1]))
