"""The measured window: the harness's own loop around ``Engine.step``.

The harness submits each request when it is due by the wall clock, with
``arrival`` set to the engine's current step, so the engine admits it at
its next step.  It stamps every emitted token itself (``perf_counter``
after the step that returned it), and reads the engine's admission stamp
for the queue wait.  ``Engine.run`` and the step-clock traces are not
used.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

from harness.traffic import Generator, Req


@dataclasses.dataclass
class Served:
    """What the harness saw of one request."""

    req: Req
    due: float                       # wall clock, seconds
    admitted: float | None = None    # the engine's own admission stamp
    stamps: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.req.max_new


@dataclasses.dataclass
class StepRecord:
    """One engine step: when it ran, the prompt lengths it admitted, and
    how many tokens its batched decode emitted."""

    start: float
    end: float
    admitted: list
    decoded: int


class Driver:
    """Feeds one engine from one generator and keeps the stamps."""

    def __init__(self, eng, gen: Generator, mix: dict):
        self.eng = eng
        self.gen = gen
        self.mix = mix
        self.steps = 0                 # == the engine's own step counter
        self.served: dict[int, Served] = {}
        self.records: list[StepRecord] = []
        self.lateness: list[float] = []
        self._clients: dict[int, int] = {}    # rid -> client (closed loop)
        self._next_open: Req | None = None
        self.origin = 0.0              # open loop: arrivals count from here
        self.t0 = 0.0                  # the window's start

    # -- submission -------------------------------------------------------
    def _submit(self, req: Req, due: float, now: float) -> None:
        from repro.serving import Request

        with TraceAnnotation("harness.submit"):
            self.eng.submit(Request(rid=req.rid, tokens=req.prompt,
                                    max_new=req.max_new, arrival=self.steps))
        self.served[req.rid] = Served(req, due)
        self.lateness.append(now - due)

    def _submit_due(self, now: float) -> None:
        if self.mix["loop"] == "open":
            while True:
                if self._next_open is None:
                    self._next_open = self.gen.next()
                due = self.origin + self._next_open.due_s
                if due > now:
                    return
                self._submit(self._next_open, due, now)
                self._next_open = None

    def _next_due(self) -> float:
        if self._next_open is None:
            self._next_open = self.gen.next()
        return self.origin + self._next_open.due_s

    # -- stepping ---------------------------------------------------------
    def step(self) -> None:
        start = time.perf_counter()
        with TraceAnnotation("engine.step"):
            events = self.eng.step()
        end = time.perf_counter()
        self.steps += 1
        admitted = []
        for seq in self.eng.sched.active.values():
            s = self.served.get(seq.req.rid)
            if s is not None and s.admitted is None:
                s.admitted = seq.admitted_wall
        decoded = 0
        for rid, tok in events:
            s = self.served[rid]
            if not s.tokens:
                admitted.append(len(s.req.prompt))
            else:
                decoded += 1
            s.tokens.append(tok)
            s.stamps.append(end)
            if s.done and self.mix["loop"] == "closed":
                nxt = self.gen.next()
                self._clients[nxt.rid] = self._clients.pop(rid)
                self._submit(nxt, end, end)
        self.records.append(StepRecord(start, end, admitted, decoded))

    def warm(self, buckets: list[int], vocab: int) -> None:
        """Compile every program the window can call: one two-token
        request per prefill bucket, its prompt one short of the bucket so
        that it ends inside it, stepped to completion (set-up; their
        tokens are not counted)."""
        from repro.serving import Request

        rng = np.random.default_rng(0)
        for i, b in enumerate(buckets):
            self.eng.submit(Request(rid=-1 - i, max_new=2,
                                    tokens=rng.integers(0, vocab, b - 1),
                                    arrival=self.steps))
        while not self.eng.sched.done:
            self.eng.step()
            self.steps += 1

    def fill(self) -> None:
        """Closed loop: each client submits the request it holds in the
        loop's steady state (``Generator.in_flight``), and the engine
        admits all it can (set-up, before the window); requests the page
        pool cannot hold yet wait in the queue."""
        now = time.perf_counter()
        for c, req in enumerate(self.gen.in_flight(self.mix["clients"])):
            self._clients[req.rid] = c
            self._submit(req, now, now)
        while self.eng.sched.pending:
            waiting = len(self.eng.sched.pending)
            self.step()
            if len(self.eng.sched.pending) == waiting:
                break

    def lead_in(self) -> None:
        """The mix's traffic for ``warm_s`` seconds before the window,
        untimed (set-up): open-loop arrivals start here, so the window
        opens on a loaded engine, and every program has run a few times."""
        self.origin = time.perf_counter()
        self._serve(self.origin + self.mix["warm_s"])

    def window(self, seconds: float, on_tick=None) -> tuple[float, float]:
        """Drive the engine for ``seconds`` of wall clock; returns the
        window's (start, end).  ``on_tick(elapsed)`` runs between steps."""
        self.t0 = time.perf_counter()
        first = len(self.records)
        self._serve(self.t0 + seconds, on_tick)
        self.first_record = first
        return self.t0, self.records[-1].end if len(
            self.records) > first else time.perf_counter()

    def _serve(self, end: float, on_tick=None) -> None:
        while True:
            now = time.perf_counter()
            if on_tick is not None:
                on_tick(now - self.t0)
            if now >= end:
                return
            self._submit_due(now)
            if self.eng.sched.done:
                # open loop, nothing in flight: wait for the next arrival
                with TraceAnnotation("harness.wait_arrival"):
                    time.sleep(max(0.0, min(self._next_due(), end) - now))
                continue
            self.step()

    # -- readings ---------------------------------------------------------
    def window_records(self) -> list[StepRecord]:
        return self.records[self.first_record:]


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))
