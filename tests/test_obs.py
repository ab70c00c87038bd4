"""Telemetry subsystem: metrics registry + exporters, JSONL events, span
tracing, solver convergence callbacks (the paper's monotone-descent
guarantee as a monitored invariant), and the BENCH_*.json snapshot
schema."""
import importlib.util
import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from repro.core import cox, solvers
from repro.data.synthetic import SyntheticSpec, make_correlated_survival
from repro.obs import TelemetryCallback, events, metrics, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run_for_tests", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("bench_run_for_tests", mod)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def sinks_off():
    """Guarantee both global sinks are off for the test, restore after."""
    events.configure(None)
    trace.configure(None)
    yield
    events.configure(None)
    trace.configure(None)


# ---------------------------------------------------------------------------
# Metrics: counters / gauges / histograms, snapshot, Prometheus text
# ---------------------------------------------------------------------------

def test_counter_labels_and_total():
    reg = metrics.Registry()
    c = reg.counter("reqs_total", "requests", ("kind",))
    c.inc(kind="a")
    c.inc(2, kind="b")
    assert c.value(kind="a") == 1.0
    assert c.value(kind="b") == 2.0
    assert c.total() == 3.0
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")
    with pytest.raises(ValueError):
        c.inc(kind="a", extra="nope")


def test_gauge_up_down():
    g = metrics.Registry().gauge("depth")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value() == 4.0


def test_histogram_bucketing_and_inf_bucket():
    reg = metrics.Registry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h._series()[()]
    assert s["counts"] == [1, 2, 1, 1]          # last bin is +Inf
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(56.05)


def test_registry_get_or_create_and_type_conflict():
    reg = metrics.Registry()
    assert reg.counter("c") is reg.counter("c")
    with pytest.raises(ValueError):
        reg.gauge("c")
    with pytest.raises(ValueError):
        reg.counter("c", label_names=("x",))


def test_prometheus_text_format():
    reg = metrics.Registry()
    reg.counter("served_total", "served", ("kind",)).inc(3, kind="risk")
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.to_prometheus()
    assert "# TYPE served_total counter" in text
    assert 'served_total{kind="risk"} 3' in text
    # cumulative le-buckets + the implicit +Inf
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text


def test_snapshot_satisfies_bench_schema():
    run = _load_bench_run()
    reg = metrics.Registry()
    reg.counter("a_total", "", ("k",)).inc(k="x")
    reg.gauge("g").set(2)
    reg.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    snap = reg.snapshot()
    assert run.validate_metrics_snapshot(snap) == []
    json.dumps(snap)                            # JSON-able end to end


def test_snapshot_schema_rejects_malformed():
    run = _load_bench_run()
    assert run.validate_metrics_snapshot([]) != []
    assert run.validate_metrics_snapshot({}) != []
    bad = {"counters": {"c": {"": "NaN-string"}}, "gauges": {},
           "histograms": {"h": {"buckets": [1.0],
                                "series": {"": {"counts": [1],  # wrong len
                                                "sum": 0.0, "count": 1}}}}}
    errs = run.validate_metrics_snapshot(bad)
    assert any("counters/c" in e for e in errs)
    assert any("histograms/h" in e for e in errs)


def test_serve_metrics_http_endpoint():
    reg = metrics.Registry()
    reg.counter("hits_total").inc(7)
    server = metrics.serve_metrics(port=0, registry=reg)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "hits_total 7" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5)
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# Events + spans
# ---------------------------------------------------------------------------

def test_event_sink_roundtrip(tmp_path, sinks_off):
    path = str(tmp_path / "events.jsonl")
    events.configure(path)
    try:
        events.emit("unit.test", a=1, arr=np.float32(2.5))
        assert events.enabled()
    finally:
        events.configure(None)
    recs = events.read_jsonl(path)
    assert len(recs) == 1
    assert recs[0]["kind"] == "unit.test"
    assert recs[0]["a"] == 1
    assert recs[0]["arr"] == 2.5               # numpy coerced, not crashed
    assert "ts" in recs[0]


def test_span_noop_when_disabled(sinks_off):
    assert not trace.enabled()
    sp = trace.span("x", attr=1)
    assert sp is trace.span("y")                # shared no-op singleton
    with sp as s:
        s.set(more=2)


def test_span_is_the_shared_noop_without_sink_or_profiler(sinks_off):
    """No sink and no profiler tracing: ``span()`` hands back the one
    shared no-op, so the hot path allocates nothing."""
    assert not trace._profiling()
    assert trace.span("service.step") is trace._NOOP
    assert trace.span("engine.score", b=3) is trace._NOOP


def test_held_spans_are_written_when_the_sink_is_reconfigured(
        tmp_path, sinks_off):
    """Spans wait in memory, not on disk, and none is lost when the sink
    is replaced: a full block is written as it fills, the rest on
    ``configure(None)``."""
    path = str(tmp_path / "trace.jsonl")
    trace.configure(path)
    try:
        with trace.span("first"):
            pass
        trace.emit_span("retro", 0.5)
        assert os.path.getsize(path) == 0       # held, not written
        n = events.HOLD_MAX + 10
        for i in range(n):
            with trace.span("service.dispatch", i=i):
                pass
        with open(path) as f:                   # one block written
            assert sum(1 for _ in f) == events.HOLD_MAX
    finally:
        trace.configure(None)
    recs = events.read_jsonl(path)
    assert len(recs) == n + 2
    assert sorted(r["attrs"]["i"] for r in recs if "attrs" in r) \
        == list(range(n))
    first = recs[0]
    assert first["name"] == "first"
    assert first["start_ts"] <= first["ts"]
    retro = next(r for r in recs if r["name"] == "retro")
    assert retro["ts"] - retro["start_ts"] == pytest.approx(0.5, abs=1e-3)


def test_a_span_file_read_while_its_sink_is_open_holds_every_span(
        tmp_path, sinks_off):
    """A reader in the same process, the sink still open (as
    ``examples/serve_risk_api.py`` renders its table), sees every finished
    span; closing the sink afterwards writes none of them twice."""
    from repro.analysis.report import latency_breakdown_table
    path = str(tmp_path / "trace.jsonl")
    trace.configure(path)
    try:
        with trace.span("service.step"):
            for _ in range(3):
                with trace.span("service.dispatch"):
                    pass
        trace.emit_span("service.request", 0.01)
        lines = latency_breakdown_table(path).splitlines()
        assert any(ln.startswith("| service.step | 1 ") for ln in lines)
        assert any(ln.startswith("| service.dispatch | 3 ") for ln in lines)
        assert any(ln.startswith("| service.request | 1 ") for ln in lines)
        assert len(events.read_jsonl(path)) == 5
    finally:
        trace.configure(None)
    assert len(events.read_jsonl(path)) == 5


def test_span_nesting_and_trace_ids(tmp_path, sinks_off):
    path = str(tmp_path / "trace.jsonl")
    trace.configure(path)
    try:
        with trace.span("root", tag="r") as root:
            with trace.span("child"):
                with trace.span("grandchild"):
                    pass
            trace.emit_span("retro", 0.25, rid=7)
        with trace.span("root2"):
            pass
    finally:
        trace.configure(None)
    spans = {r["name"]: r for r in events.read_jsonl(path)}
    assert len(spans) == 5
    tid = spans["root"]["trace_id"]
    for name in ("child", "grandchild", "retro"):
        assert spans[name]["trace_id"] == tid
    assert spans["child"]["parent_id"] == spans["root"]["span_id"]
    assert spans["grandchild"]["parent_id"] == spans["child"]["span_id"]
    assert spans["retro"]["parent_id"] == spans["root"]["span_id"]
    assert spans["retro"]["dur_s"] == 0.25
    assert spans["root"]["attrs"] == {"tag": "r"}
    assert spans["root2"]["trace_id"] != tid    # fresh root, fresh trace
    assert all(s["dur_s"] >= 0 for s in spans.values())
    assert root.trace_id == tid


def test_latency_breakdown_table_renders(tmp_path, sinks_off):
    from repro.analysis.report import latency_breakdown_table
    path = str(tmp_path / "trace.jsonl")
    trace.configure(path)
    try:
        with trace.span("service.step"):
            with trace.span("service.dispatch"):
                pass
            with trace.span("service.dispatch"):
                pass
    finally:
        trace.configure(None)
    table = latency_breakdown_table(path)
    lines = table.splitlines()
    assert lines[0].startswith("| stage ")
    assert any(ln.startswith("| service.step | 1 ") for ln in lines)
    assert any(ln.startswith("| service.dispatch | 2 ") for ln in lines)
    # empty file degrades to a hint row, not a crash
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert "no spans" in latency_breakdown_table(empty)


# ---------------------------------------------------------------------------
# Solver convergence telemetry
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_problem():
    x, t, delta, _ = make_correlated_survival(
        SyntheticSpec(n=200, p=15, k=3, rho=0.3, seed=4))
    return cox.prepare(x, t, delta)


def test_fit_cd_telemetry_matches_objective_and_no_violations(
        small_problem, sinks_off):
    import jax
    reg = metrics.Registry()
    tel = TelemetryCallback("cd_quad", registry=reg)
    res = solvers.fit_cd(small_problem, lam2=0.1, n_iters=20, telemetry=tel)
    res.beta.block_until_ready()
    jax.effects_barrier()
    assert tel.iterations == 20
    assert tel.violations == 0
    # recorded objectives are the solver's own per-iteration objectives
    np.testing.assert_allclose(tel.objectives,
                               np.asarray(res.objective), rtol=1e-5)
    assert np.all(np.diff(tel.objectives) <= tel.tol)
    assert reg.counter("solver_iterations_total",
                       label_names=("solver",)).value(solver="cd_quad") == 20


def test_fit_cd_tol_telemetry_counts_iterations(small_problem, sinks_off):
    import jax
    tel = TelemetryCallback("cd_tol", registry=metrics.Registry())
    solvers.fit_cd_tol(small_problem, 0.0, 0.1, max_iters=30,
                       telemetry=tel).beta.block_until_ready()
    jax.effects_barrier()
    assert 1 <= tel.iterations <= 30
    assert tel.violations == 0
    rec = tel.records[0]
    assert {"iter", "objective", "grad_norm", "step_norm",
            "active_set"} <= set(rec)


def test_broken_step_increments_violation_counter(sinks_off):
    tel = TelemetryCallback("broken", tol=1e-6,
                            registry=metrics.Registry())
    # a deliberately non-monotone objective sequence: 5 -> 4 -> 4.5 -> 3
    for it, obj in enumerate((5.0, 4.0, 4.5, 3.0)):
        tel._cb(it, obj, 0.0, 0.0, 0)
    assert tel.violations == 1
    assert tel.iterations == 4


def test_violation_check_is_arrival_order_independent(sinks_off):
    tel = TelemetryCallback("ooo", registry=metrics.Registry())
    # same broken sequence, callbacks landing out of order (unordered
    # jax.debug.callback semantics): each adjacent pair still checked once
    seq = {0: 5.0, 1: 4.0, 2: 4.5, 3: 3.0}
    for it in (2, 0, 3, 1):
        tel._cb(it, seq[it], 0.0, 0.0, 0)
    assert tel.violations == 1


def test_newton_without_line_search_is_caught(sinks_off):
    """The broken solver the paper critiques (Fig. 1a: raw Newton
    overshoots from beta=0 on rare heavy-tailed features) is exactly what
    the violation counter must flag — same data as
    test_solvers.test_exact_newton_blows_up_without_line_search."""
    import jax
    rng = np.random.default_rng(1)
    n, p = 120, 4
    x = ((rng.uniform(size=(n, p)) < 0.04)
         * rng.lognormal(1.5, 1.0, size=(n, p))).astype(np.float64)
    risk = np.clip(x @ np.array([3.0, -3.0, 2.0, -2.0]), -30, 30)
    t = (-np.log(rng.uniform(1e-12, 1, n)) / np.exp(risk)) ** 0.3
    delta = (rng.uniform(size=n) < 0.8).astype(np.float64)
    data = cox.prepare(x, t, delta)
    tel = TelemetryCallback("newton_raw", registry=metrics.Registry())
    solvers.fit_newton(data, lam2=0.0, n_iters=12, line_search=False,
                       telemetry=tel).beta.block_until_ready()
    jax.effects_barrier()
    assert tel.violations >= 1


def test_telemetry_none_is_free(small_problem):
    # telemetry=None must stage no callback: same jit cache entry count
    # behaviour as the pre-telemetry solver, and no iterations recorded
    res = solvers.fit_cd(small_problem, lam2=0.1, n_iters=5, telemetry=None)
    assert np.isfinite(float(res.objective[-1]))


def test_solver_events_emitted(tmp_path, small_problem, sinks_off):
    import jax
    path = str(tmp_path / "solver_events.jsonl")
    events.configure(path)
    try:
        tel = TelemetryCallback("evt", registry=metrics.Registry())
        solvers.fit_cd(small_problem, lam2=0.1, n_iters=5,
                       telemetry=tel).beta.block_until_ready()
        jax.effects_barrier()
    finally:
        events.configure(None)
    iters = [r for r in events.read_jsonl(path)
             if r["kind"] == "solver.iter"]
    assert len(iters) == 5
    assert all(r["solver"] == "evt" for r in iters)


# ---------------------------------------------------------------------------
# Bench embedding: the instrumented smoke-fit record
# ---------------------------------------------------------------------------

def test_telemetry_record_validates_and_counts_zero_violations(sinks_off):
    run = _load_bench_run()
    rec = run._telemetry_record("cpu", tuned={}, git_rev="test",
                                n_iters=10)
    assert run.validate_records([rec]) == []
    assert run.validate_metrics_snapshot(rec["metrics"]) == []
    assert rec["value"] == 0.0
    assert run._solver_violations(rec["metrics"]) == 0.0
    cs = rec["metrics"]["counters"]
    assert "solver_iterations_total" in cs
