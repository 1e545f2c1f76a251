"""Tracing + metrics layer: trace-event validity, histogram accuracy,
no-op-by-default guarantees, and tokens bit-identical with tracing on."""
import importlib.util
import json
import pathlib

import numpy as np
import pytest

from repro import configs, obs
from repro.models.model import build_model
from repro.serving import Engine, Request

import jax

KEY = jax.random.PRNGKey(0)
REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_trace_report():
    path = REPO / "scripts" / "trace_report.py"
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _validate_trace(data: dict) -> list[dict]:
    """Assert Chrome trace-event invariants; return the event list.

    * required keys on every event (string pid/tid are valid);
    * timestamps non-decreasing per (pid, tid) track;
    * ``B``/``E`` nest LIFO per tid — depth never negative, ends at 0;
    * counter (``C``) events carry numeric args only.
    """
    assert isinstance(data, dict) and "traceEvents" in data
    events = data["traceEvents"]
    last_ts: dict[tuple, float] = {}
    depth: dict[str, int] = {}
    for ev in events:
        for key in ("name", "ph", "ts", "pid", "tid"):
            assert key in ev, f"event missing {key!r}: {ev}"
        track = (str(ev["pid"]), str(ev["tid"]))
        assert ev["ts"] >= last_ts.get(track, 0.0), \
            f"ts went backwards on track {track}"
        last_ts[track] = ev["ts"]
        tid = str(ev["tid"])
        if ev["ph"] == "B":
            depth[tid] = depth.get(tid, 0) + 1
        elif ev["ph"] == "E":
            depth[tid] = depth.get(tid, 0) - 1
            assert depth[tid] >= 0, f"E without B on tid {tid}"
        elif ev["ph"] == "C":
            assert all(isinstance(v, (int, float))
                       for v in ev["args"].values()), ev
        elif ev["ph"] == "i":
            assert ev.get("s") == "t"
    assert all(d == 0 for d in depth.values()), f"unclosed spans: {depth}"
    return events


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
def test_tracer_export_valid_and_balanced(tmp_path):
    tr = obs.Tracer()
    with tr.span("outer", track="engine", step=0):
        with tr.span("inner", track="engine"):
            tr.instant("tick", track="lifecycle", rid=1)
        tr.counter("pool_pages", {"free": 3, "live": 5}, track="pool")
    tr.begin("dangling", track="engine")     # export must synthesize the E
    out = tr.export(tmp_path / "t.json")
    data = json.loads(pathlib.Path(out).read_text())
    events = _validate_trace(data)
    assert data["displayTimeUnit"] == "ms"
    by_ph = {e["ph"] for e in events}
    assert by_ph == {"B", "E", "i", "C"}
    names = [e["name"] for e in events if e["ph"] == "B"]
    assert names == ["outer", "inner", "dangling"]
    # args survive, non-JSON values are repr()'d not fatal
    outer = next(e for e in events if e["name"] == "outer")
    assert outer["args"] == {"step": 0}


def test_tracer_ring_buffer_bounds_memory():
    tr = obs.Tracer(capacity=10)
    for i in range(50):
        tr.instant(f"e{i}", track="engine")
    assert len(tr._events) == 10
    assert tr._events[0]["name"] == "e40"   # oldest dropped, newest kept


def test_null_tracer_is_inert(tmp_path):
    nt = obs.NULL_TRACER
    assert isinstance(nt, obs.NullTracer) and nt.enabled is False
    with nt.span("x", track="engine"):      # all entry points are no-ops
        nt.instant("y")
        nt.counter("z", {"a": 1})
    assert nt.export(tmp_path / "never.json") is None
    assert not (tmp_path / "never.json").exists()


def test_install_tracer_round_trip():
    assert obs.get_tracer() is obs.NULL_TRACER
    live = obs.Tracer()
    try:
        assert obs.install_tracer(live) is live
        assert obs.get_tracer() is live
    finally:
        assert obs.install_tracer(None) is obs.NULL_TRACER
    assert obs.get_tracer() is obs.NULL_TRACER


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(7)
    vals = rng.lognormal(mean=-4.0, sigma=1.5, size=4000)
    h = obs.Histogram()
    for v in vals:
        h.observe(float(v))
    for q in (50, 90, 99):
        exact = float(np.percentile(vals, q))
        approx = h.percentile(q)
        # log buckets grow by 2**(1/8) ~ 9%; interpolation keeps the
        # estimate within about half a bucket of the true quantile
        assert approx == pytest.approx(exact, rel=0.12), f"p{q}"
    s = h.summary()
    assert s["count"] == 4000
    assert s["min"] == pytest.approx(vals.min())
    assert s["max"] == pytest.approx(vals.max())
    assert s["mean"] == pytest.approx(vals.mean(), rel=1e-6)


def test_histogram_edge_cases():
    h = obs.Histogram()
    assert h.percentile(50) == 0.0          # empty: defined, not NaN
    h.observe(0.0123)
    for q in (1, 50, 99):                   # single value: clamped exact
        assert h.percentile(q) == pytest.approx(0.0123)
    h2 = obs.Histogram()
    h2.observe(0.0)                         # below lo lands in bucket 0
    h2.observe(1e9)                         # above hi clamps to last
    assert h2.summary()["count"] == 2
    assert h2.percentile(99) <= 1e9


def test_stats_view_is_a_real_dict_surface():
    m = obs.Metrics()
    view = m.stats_view()
    view["a"] = 1
    view.update({"b": 2.5, "c": 0})
    view["a"] += 4
    assert view["a"] == 5 and len(view) == 3
    assert dict(view) == {"a": 5, "b": 2.5, "c": 0}
    assert list(view) == ["a", "b", "c"]    # insertion order preserved
    del view["c"]
    assert "c" not in view
    m.counter("hits", 3)
    assert view["hits"] == 3                # registry and view share state
    assert m.snapshot()["counters"]["a"] == 5


def test_metrics_snapshot_shape():
    m = obs.Metrics()
    m.gauge("g", 7.0)
    m.observe("lat_s", 0.25)
    snap = m.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["lat_s"]["count"] == 1


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------
def _engine_run(tracer=None):
    cfg = configs.reduced(configs.get_config("llama3.2-1b"))
    model = build_model(cfg)
    params = model.init(KEY)
    reqs = [Request(rid=i, tokens=(np.arange(6, dtype=np.int32) * 7 + i)
                    % cfg.vocab, max_new=4, arrival=i)
            for i in range(3)]
    eng = Engine(model, params, max_slots=2, page_size=4, max_len=16,
                 tracer=tracer)
    return eng, eng.run(reqs)


def test_engine_tokens_identical_traced_vs_untraced(tmp_path):
    _, res_off = _engine_run()
    tr = obs.Tracer()
    eng, res_on = _engine_run(tracer=tr)
    assert res_on["tokens"] == res_off["tokens"]
    # every pre-existing stat is bit-identical; the latency percentiles
    # are timing-derived, so compare key sets only
    assert set(res_on["stats"]) == set(res_off["stats"])
    for k in ("completed", "steps", "preemptions", "cow_forks"):
        assert res_on["stats"][k] == res_off["stats"][k]

    out = tr.export(tmp_path / "engine.json")
    events = _validate_trace(json.loads(pathlib.Path(out).read_text()))
    tracks = {str(e["tid"]) for e in events}
    assert {"engine", "lifecycle", "pool"} <= tracks
    assert any(t.startswith("slot") for t in tracks)
    steps = [e for e in events
             if e["name"] == "engine.step" and e["ph"] == "B"]
    assert len(steps) == res_on["stats"]["steps"]
    reqs = {e["name"] for e in events if e.get("cat") == "request"}
    assert reqs == {"req0", "req1", "req2"}

    # the report tool parses it and attributes engine self-time
    trp = _load_trace_report()
    rep = trp.report(out, track="engine")
    assert rep["events"] == len(events)
    assert any(k.endswith(":engine.step") for k in rep["spans"])
    assert {r["request"] for r in rep["slowest_requests"]} == reqs
    assert trp.main([str(out), "--track", "engine"]) == 0


def test_engine_latency_stats_present_and_sane():
    eng, res = _engine_run(tracer=obs.Tracer())
    for k in ("queue_wait_p50_s", "queue_wait_p99_s", "ttft_p50_s",
              "ttft_p99_s", "tpot_p50_s", "tpot_p99_s"):
        assert k in res["stats"] and res["stats"][k] >= 0.0
    assert res["stats"]["ttft_p99_s"] >= res["stats"]["ttft_p50_s"]
    hists = eng.metrics.snapshot()["histograms"]
    assert hists["ttft_s"]["count"] == res["stats"]["completed"]


def test_serve_engine_trace_and_metrics_files(tmp_path):
    from repro.launch import serve

    trace = tmp_path / "serve.trace.json"
    mjson = tmp_path / "serve.metrics.json"
    summary = serve.main([
        "--arch", "llama3.2-1b", "--reduced", "--engine",
        "--sod", "tiled_csc", "--density", "0.4",
        "--requests", "2", "--prompt-len", "6", "--gen", "3",
        "--max-slots", "2", "--page-size", "4",
        "--trace", str(trace), "--metrics-json", str(mjson)])
    assert summary["trace"] == str(trace)
    _validate_trace(json.loads(trace.read_text()))
    snap = json.loads(mjson.read_text())
    assert snap["counters"]["completed"] == 2
    assert "ttft_s" in snap["histograms"]
    assert summary["kernel_dispatch"]        # impl[source] -> count
    assert obs.get_tracer() is obs.NULL_TRACER   # driver uninstalled it


def test_obs_metric_names_all_in_glossary():
    """Every gauge/histogram the engine's metrics registry emits must be
    documented in docs/observability.md — same gate style as the
    serving-stats glossary check."""
    doc = (REPO / "docs" / "observability.md").read_text()
    eng, _ = _engine_run(tracer=obs.Tracer())
    snap = eng.metrics.snapshot()
    names = list(snap["gauges"]) + list(snap["histograms"])
    assert names, "engine run recorded no gauges/histograms"
    missing = [n for n in names if f"`{n}`" not in doc]
    assert not missing, (
        f"metric names missing from docs/observability.md: {missing}")
