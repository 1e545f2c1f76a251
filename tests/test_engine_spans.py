"""Engine phase spans on the profiler's clock and the engine's step log.

The engine's ``engine.step.<phase>`` spans are profiler annotations, so a
``jax.profiler`` trace holds them on the host plane even with the Python
tracer off; the same calls fill ``Engine.metrics.steps``, whose records
must agree with what the benchmark harness sees from outside.  A trace
recorded on one TPU v5e (``data/engine_spans.xplane.pb``) shows the spans
naming the device's idle time inside the engine's steps.
"""
from __future__ import annotations

import glob
import json
import pathlib
import types

import jax
import numpy as np
import pytest

from repro import configs, obs
from repro.models.model import build_model
from repro.serving import Engine, Request

REPO = pathlib.Path(__file__).resolve().parent.parent
BENCH = REPO / "perfbench"
CHIP_TRACE = REPO / "tests" / "data" / "engine_spans.xplane.pb"
# phases of a fused-prefill paged step, in pipeline order; the three
# after ``admission`` repeat once per admitted request, inside it
PIPELINE = ("admission", "prefill", "prefill.wait", "page_write",
            "capacity", "decode.prepare", "decode.dispatch", "decode.wait",
            "commit", "pool_sample")
CELLS = ("internlm2-1.8b-tcsc30.decode-batch",
         "internlm2-1.8b-tcsc30.chat-rate")
READERS = {"internlm2-1.8b-tcsc30.decode-batch": ("host_ms_per_step.batch",),
           "internlm2-1.8b-tcsc30.chat-rate": ("host_ms_per_step.chat",
                                               "admission_ms.chat")}


def _engine(tracer=None) -> Engine:
    cfg = configs.reduced(configs.get_config("llama3.2-1b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, max_slots=2, page_size=4, max_len=16,
                 tracer=tracer)
    for i in range(3):
        eng.submit(Request(rid=i, tokens=(np.arange(6, dtype=np.int32) * 7
                                          + i) % cfg.vocab,
                           max_new=4, arrival=i))
    return eng


def _drain(eng: Engine) -> dict:
    while not eng.sched.done:
        eng.step()
    return {rid: list(s.generated) for rid, s in eng._finished.items()}


def _host_events(path: str) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [e for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def test_phase_spans_sit_on_the_host_plane_in_pipeline_order(tmp_path):
    eng = _engine()
    eng.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _drain(eng)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = _host_events(path)
    assert not any(e.name.startswith("$") for e in events)  # no Python frames
    steps = sorted((e for e in events if e.name == "engine.step"),
                   key=lambda e: e.start_ns)
    phases = [e for e in events if e.name.startswith("engine.step.")]
    assert len(steps) == eng._step_idx
    seen = set()
    for step in steps:
        inside = sorted((e for e in phases
                         if step.start_ns <= e.start_ns
                         and e.end_ns <= step.end_ns),
                        key=lambda e: e.start_ns)
        names = [e.name[len("engine.step."):] for e in inside]
        # each step admits at most one request here, so the pipeline's
        # order holds without repeats
        order = [PIPELINE.index(n) for n in names]
        assert order == sorted(order) and len(set(order)) == len(order), \
            names
        seen.update(names)
    assert seen == set(PIPELINE)
    # every phase span lies inside one of the steps
    assert sum(1 for e in phases if any(
        s.start_ns <= e.start_ns and e.end_ns <= s.end_ns
        for s in steps)) == len(phases)


def test_step_records_add_up():
    eng = _engine()
    tokens = _drain(eng)
    recs = list(eng.metrics.steps.records)
    assert [r.step for r in recs] == list(range(eng._step_idx))
    for r in recs:
        span = r.t_end - r.t_begin
        assert 0 <= sum(r.phases.values()) <= span
        assert r.wait_s == pytest.approx(
            sum(v for k, v in r.phases.items() if k.endswith(".wait")))
        assert 0 <= r.host_s <= span
    emitted = sum(len(t) for t in tokens.values())
    assert sum(r.decode_rows for r in recs) == emitted - len(tokens)
    assert sum(r.admitted for r in recs) == len(tokens)
    assert sum(r.completed for r in recs) == len(tokens)
    assert recs[-1].free_pages == eng.page_pool.free_count
    # the third request is due at step 2 but both slots are busy then
    assert recs[2].queue_ready == 1 and recs[-1].queue_ready == 0
    assert eng.metrics.steps.window(recs[1].t_begin, recs[2].t_end) == \
        recs[1:3]


def test_step_log_nests_phases_and_is_bounded():
    log = obs.StepLog(capacity=3)
    null = obs.NULL_TRACER
    for i in range(5):
        with log.step(i) as rec:
            with log.phase(null.span("outer"), "outer"):
                with log.phase(null.span("inner.wait"), "inner.wait"):
                    pass
            rec.admitted = i
    assert [r.step for r in log.records] == [2, 3, 4]
    assert log.current is None
    for r in log.records:
        assert set(r.phases) == {"outer", "inner.wait"}
        assert r.wait_s == r.phases["inner.wait"]
        assert r.phases["outer"] + r.phases["inner.wait"] <= \
            r.t_end - r.t_begin
    summary = log.summary()
    assert summary["steps"] == 3
    assert set(summary["phases_ms"]) == {"outer", "inner.wait"}
    assert summary["phases_ms"]["outer"]["count"] == 3
    assert log.records[-1].admitted == 4


@pytest.mark.parametrize("mode", ["installed_tracer", "profiler_on"])
def test_tokens_identical_with_spans_recorded(tmp_path, mode):
    want = _drain(_engine())
    if mode == "installed_tracer":
        tracer = obs.install_tracer(obs.Tracer())
        try:
            got = _drain(_engine())
        finally:
            obs.install_tracer(None)
        names = {e["name"] for e in tracer._events}
        assert {"engine.step", "engine.step.decode.wait"} <= names
    else:
        eng = _engine()
        jax.profiler.start_trace(str(tmp_path))
        try:
            got = _drain(eng)
        finally:
            jax.profiler.stop_trace()
    assert got == want


def test_step_phases_all_in_observability_doc():
    """Every phase an engine step records has a row in the taxonomy of
    docs/observability.md."""
    doc = (REPO / "docs" / "observability.md").read_text()
    eng = _engine()
    _drain(eng)
    names = {k for r in eng.metrics.steps.records for k in r.phases}
    assert names == set(PIPELINE)
    assert "`engine.step`" in doc
    missing = [n for n in sorted(names) if f"`{n}`" not in doc]
    assert not missing, f"phases missing from docs/observability.md: {missing}"


def test_serve_metrics_json_summarises_the_step_log(tmp_path):
    from repro.launch import serve

    out = tmp_path / "m.json"
    serve.main(["--arch", "llama3.2-1b", "--reduced", "--engine",
                "--requests", "2", "--prompt-len", "6", "--gen", "3",
                "--max-slots", "2", "--page-size", "4",
                "--metrics-json", str(out)])
    steps = json.loads(out.read_text())["steps"]
    assert steps["steps"] > 0
    assert {"admission", "decode.wait", "commit"} <= set(steps["phases_ms"])
    for s in steps["phases_ms"].values():
        assert 0 <= s["p50"] <= s["p99"]
    assert steps["counters"]["completed"]["count"] == steps["steps"]
    assert steps["counters"]["completed"]["mean"] * steps["steps"] == \
        pytest.approx(2)


# ---------------------------------------------------------------------------
# the benchmark's view: the tiny CPU copy of the harness
# ---------------------------------------------------------------------------
@pytest.fixture()
def bench(tmp_path, monkeypatch):
    monkeypatch.syspath_prepend(str(BENCH / "tests"))
    monkeypatch.syspath_prepend(str(BENCH))
    import tiny

    return tiny.make(tmp_path / "root")


@pytest.mark.parametrize("cell", CELLS)
def test_step_log_agrees_with_the_harness(bench, cell):
    import run
    from harness import spec

    c = spec.load(bench / "BENCHMARK.json", bench, cell, bench / "perfbench")
    r = run.Run(c, 5, 3.0)
    log = r.driver.eng.metrics.steps
    steps = log.window(r.t0, r.t0 + r.readings.window_s)
    harness = r.driver.window_records()
    assert len(steps) == len(harness) > 0
    assert sum(s.decode_rows for s in steps) == r.readings.decode_tokens
    assert sum(s.admitted for s in steps) == sum(len(h.admitted)
                                                 for h in harness)
    ctx = types.SimpleNamespace(driver=r.driver, readings=r.readings)
    for name in READERS[cell]:
        reader = run._load_module(c.metric_reader(name), "perfbench_metric")
        value = reader.read(ctx)
        assert value is not None and value > 0, name
    # a program without a step log reads nothing, and does not raise
    r.driver.eng = types.SimpleNamespace(metrics=object())
    for name in READERS[cell]:
        reader = run._load_module(c.metric_reader(name), "perfbench_metric")
        assert reader.read(ctx) is None


def test_recorded_chip_trace_idle_is_named_by_engine_phases(monkeypatch):
    """On one v5e at internlm2-1.8b-tcsc30's widths, 64 slots, the Python
    tracer off: of the device's idle time inside the engine's steps, at
    least 90% falls in an ``engine.step.<phase>`` span."""
    monkeypatch.syspath_prepend(str(BENCH))
    from harness import xplane

    trace = xplane.reduce(str(CHIP_TRACE))
    idle = dict((k, v) for k, v in trace.idle_gaps)
    named = sum(v for k, v in idle.items() if k.startswith("engine.step."))
    inside = named + idle.get("engine.step", 0.0)
    assert inside > 0
    assert named >= 0.9 * inside, trace.idle_gaps
    assert trace.programs["decode"][0] >= 3
    assert trace.programs["prefill"][0] >= 1
    assert not any(k.startswith("$") for k in idle)
