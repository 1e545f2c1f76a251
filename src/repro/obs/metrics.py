"""Counters, gauges, log-bucket histograms and a per-step phase log.

The :class:`Metrics` registry is deliberately tiny: counters are a plain
insertion-ordered dict (so an existing ``stats`` dict can migrate onto
it via :meth:`Metrics.stats_view` without changing any key, value type,
or arithmetic), gauges are last-write-wins, and histograms use fixed
log-spaced buckets so percentile queries are O(buckets) with bounded
relative error.  :class:`StepLog` (``Metrics.steps``) keeps one
:class:`StepRecord` per engine step for the last few thousand steps:
when the step ran, where its host time went phase by phase, how long it
waited on the device, and what it admitted, decoded and completed.

Nothing here imports outside the stdlib; see ``docs/observability.md``
for the metric glossary.
"""

from __future__ import annotations

import bisect
import contextlib
import math
import time
from collections import deque
from collections.abc import Iterator, MutableMapping
from typing import Any

# Bucket edges grow by 2**(1/8) ≈ 1.09 per bucket, bounding the relative
# error of an interpolated percentile to roughly half a bucket (~5%).
_GROWTH = 2.0 ** 0.125


class Histogram:
    """Fixed log-bucket histogram of non-negative samples.

    Buckets span ``[0, lo)`` then log-spaced edges from ``lo`` to at
    least ``hi`` (growth factor ``growth``); samples beyond either end
    clamp into the boundary bucket.  Percentiles interpolate linearly
    within the selected bucket and are clamped to the observed min/max,
    which keeps them within ~half a bucket width of the exact
    (numpy-style) quantile.
    """

    def __init__(self, lo: float = 1e-6, hi: float = 1e4,
                 growth: float = _GROWTH):
        if not (lo > 0.0 and hi > lo and growth > 1.0):
            raise ValueError("need 0 < lo < hi and growth > 1")
        edges = [0.0, lo]
        while edges[-1] < hi:
            edges.append(edges[-1] * growth)
        self._edges = edges
        self._counts = [0] * (len(edges) - 1)
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def observe(self, value: float) -> None:
        """Record one sample (negative values clamp into the first bucket)."""
        v = float(value)
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        idx = bisect.bisect_right(self._edges, v) - 1
        self._counts[min(max(idx, 0), len(self._counts) - 1)] += 1

    def percentile(self, q: float) -> float:
        """Approximate the ``q``-th percentile (0..100) of the samples."""
        if self.count == 0:
            return 0.0
        target = (q / 100.0) * self.count
        cum = 0
        for i, c in enumerate(self._counts):
            if c and cum + c >= target:
                frac = (target - cum) / c
                lo_e, hi_e = self._edges[i], self._edges[i + 1]
                val = lo_e + frac * (hi_e - lo_e)
                return min(max(val, self.vmin), self.vmax)
            cum += c
        return self.vmax

    def summary(self) -> dict[str, float]:
        """Count/mean/min/max plus p50/p90/p99 as a JSON-ready dict."""
        if self.count == 0:
            return {"count": 0, "mean": 0.0, "min": 0.0, "max": 0.0,
                    "p50": 0.0, "p90": 0.0, "p99": 0.0}
        return {"count": self.count,
                "mean": self.total / self.count,
                "min": self.vmin,
                "max": self.vmax,
                "p50": self.percentile(50),
                "p90": self.percentile(90),
                "p99": self.percentile(99)}


class _StatsView(MutableMapping):
    """Mutable-mapping facade over a Metrics counter table.

    Behaves exactly like the dict it wraps — same keys, same value
    objects, same iteration order — so an engine can assign it to its
    ``stats`` attribute and keep every existing ``stats[...]`` read,
    write, ``update``, and ``dict(...)`` call bit-identical.
    """

    __slots__ = ("_table",)

    def __init__(self, table: dict[str, Any]):
        self._table = table

    def __getitem__(self, key):
        return self._table[key]

    def __setitem__(self, key, value):
        self._table[key] = value

    def __delitem__(self, key):
        del self._table[key]

    def __iter__(self):
        return iter(self._table)

    def __len__(self):
        return len(self._table)

    def __repr__(self):
        return repr(self._table)


class StepRecord:
    """One engine step.

    ``t_begin``/``t_end`` are ``time.perf_counter`` seconds.  ``phases``
    maps each phase that ran to its own seconds (time in phases nested
    inside it excluded), so the phases of a step sum to at most
    ``t_end - t_begin``; ``wait_s`` is the part spent in ``*.wait``
    phases, blocked on the device.  The counters say what the step did:
    ``decode_rows`` slots committed a token from its batched decode,
    ``admitted`` requests took a slot, ``completed`` finished,
    ``queue_ready`` admissible requests were left waiting after
    admission, ``free_pages`` pages were free at its end, and, for a model
    with held experts, ``expert_rows`` rows of its batched decode were
    routed to a held expert, summed over held experts and MoE layers.
    """

    __slots__ = ("step", "t_begin", "t_end", "phases", "wait_s",
                 "decode_rows", "admitted", "completed", "queue_ready",
                 "free_pages", "expert_rows")

    def __init__(self, step: int, t_begin: float):
        self.step = step
        self.t_begin = t_begin
        self.t_end = t_begin
        self.phases: dict[str, float] = {}
        self.wait_s = 0.0
        self.decode_rows = 0
        self.admitted = 0
        self.completed = 0
        self.queue_ready = 0
        self.free_pages = 0
        self.expert_rows = 0

    @property
    def host_s(self) -> float:
        """Seconds of the step not spent waiting on the device."""
        return self.t_end - self.t_begin - self.wait_s


class _Phase:
    """Context manager around one phase: enters ``span`` and adds the
    phase's own seconds to the log's current record."""

    __slots__ = ("_log", "_span", "_name", "_t")

    def __init__(self, log: "StepLog", span, name: str):
        self._log = log
        self._span = span
        self._name = name

    def __enter__(self):
        self._span.__enter__()
        self._log._open.append(0.0)
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t
        log = self._log
        own = dt - log._open.pop()
        if log._open:
            log._open[-1] += dt
        rec = log.current
        if rec is not None:
            name = self._name
            rec.phases[name] = rec.phases.get(name, 0.0) + own
            if name.endswith(".wait"):
                rec.wait_s += own
        self._span.__exit__(*exc)
        return False


class StepLog:
    """Bounded ring of the :class:`StepRecord` of the last ``capacity``
    steps.

    ``with log.step(i) as rec:`` opens step ``i``'s record; inside it,
    ``with log.phase(span, name):`` times a phase (``span`` is entered
    around it, typically a tracer span of the same name).  A phase whose
    name ends in ``.wait`` counts as waiting on the device.
    """

    def __init__(self, capacity: int = 8192):
        self.records: deque[StepRecord] = deque(maxlen=capacity)
        self.current: StepRecord | None = None
        self._open: list[float] = []   # nested seconds per open phase

    @contextlib.contextmanager
    def step(self, index: int) -> Iterator[StepRecord]:
        """Context manager that records step ``index``; yields its record."""
        rec = self.current = StepRecord(index, time.perf_counter())
        try:
            yield rec
        finally:
            rec.t_end = time.perf_counter()
            self.records.append(rec)
            self.current = None

    def phase(self, span, name: str) -> _Phase:
        """Context manager timing phase ``name`` of the current step."""
        return _Phase(self, span, name)

    def window(self, t0: float, t1: float) -> list[StepRecord]:
        """The records of steps that ran wholly inside ``[t0, t1]``."""
        return [r for r in self.records if t0 <= r.t_begin and r.t_end <= t1]

    def summary(self) -> dict[str, Any]:
        """Count, mean, p50 and p99 over the logged steps of the host and
        wait milliseconds per step, of each phase's milliseconds (over the
        steps it ran in), and of each counter."""
        def stats(values, scale: float = 1.0) -> dict[str, float]:
            h = Histogram(lo=1e-4, hi=1e6)
            for v in values:
                h.observe(scale * v)
            s = h.summary()
            return {k: s[k] for k in ("count", "mean", "p50", "p99")}

        recs = self.records
        phases: dict[str, list[float]] = {}
        for r in recs:
            for k, v in r.phases.items():
                phases.setdefault(k, []).append(v)
        return {"steps": len(recs),
                "host_ms": stats((r.host_s for r in recs), 1e3),
                "wait_ms": stats((r.wait_s for r in recs), 1e3),
                "phases_ms": {k: stats(v, 1e3) for k, v in phases.items()},
                "counters": {k: stats(getattr(r, k) for r in recs)
                             for k in ("decode_rows", "admitted", "completed",
                                       "queue_ready", "free_pages")}}


class Metrics:
    """Registry of named counters, gauges, histograms, and the step log."""

    def __init__(self):
        self._counters: dict[str, Any] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, Histogram] = {}
        self.steps = StepLog()

    def counter(self, name: str, inc: float = 1) -> None:
        """Add ``inc`` to counter ``name`` (created at 0)."""
        self._counters[name] = self._counters.get(name, 0) + inc

    def gauge(self, name: str, value: float) -> None:
        """Set gauge ``name`` to ``value`` (last write wins)."""
        self._gauges[name] = value

    def histogram(self, name: str, **kwargs) -> Histogram:
        """Return (creating on first use) the histogram named ``name``."""
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = Histogram(**kwargs)
        return h

    def observe(self, name: str, value: float) -> None:
        """Record ``value`` into histogram ``name``."""
        self.histogram(name).observe(value)

    def stats_view(self) -> _StatsView:
        """Dict-compatible live view of the counter table.

        The engine assigns this to ``self.stats`` so its pre-existing
        counter keys live in the registry while every access pattern
        stays unchanged.
        """
        return _StatsView(self._counters)

    def snapshot(self) -> dict[str, Any]:
        """JSON-ready snapshot: counters, gauges, histogram summaries, and
        the step log's summary when it holds steps."""
        out = {"counters": dict(self._counters),
               "gauges": dict(self._gauges),
               "histograms": {k: h.summary()
                              for k, h in self._hists.items()}}
        if self.steps.records:
            out["steps"] = self.steps.summary()
        return out
