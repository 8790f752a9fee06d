"""Observability subsystem (asyncrl_tpu/obs/, ISSUES 5+7): span rings,
trace export/validation, the stall-attribution report, the counters/
gauges/histograms registry, the flight recorder, and the run-health
telemetry layer (time-series store, detectors, /metrics + /healthz
exposition, obs doctor) — unit-level plus fault-injected pipeline runs
proving the crash-forensics and health paths end to end.
"""

import glob
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from asyncrl_tpu.obs import (
    export,
    flightrec,
    health,
    registry,
    report,
    timeseries,
    trace,
)
from asyncrl_tpu.obs import spans as span_names
from asyncrl_tpu.obs.http import ObsHTTPServer, render_prometheus
from asyncrl_tpu.obs.trace import SpanRing, Tracer


def _get(url, timeout=5.0):
    """(status, parsed body) for a local GET — 4xx/5xx included."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts and ends with tracing/flightrec disarmed and a
    fresh registry (all three are process-global, like utils.faults)."""
    trace.configure(False)
    flightrec.disarm()
    registry.registry().reset()
    yield
    trace.configure(False)
    flightrec.disarm()
    registry.registry().reset()


# ------------------------------------------------------------------ tracing


def test_disabled_span_is_one_shared_noop():
    """The disabled fast path allocates nothing: every call site gets the
    SAME no-op context manager and no thread ring is ever registered."""
    assert not trace.enabled()
    s1 = trace.span("actor.env_step")
    s2 = trace.span("learner.update")
    assert s1 is s2  # shared singleton — zero allocation per call
    with s1:
        pass
    assert trace.stats() == {}
    assert trace.snapshots() == []


def test_ring_overflow_drops_oldest_and_counts():
    ring = SpanRing(8, "t0", "g0")
    for i in range(20):
        ring.record(f"s{i}", float(i), float(i) + 0.5)
    snap = ring.snapshot()
    assert snap["recorded"] == 20
    assert snap["dropped"] == 12
    names = [s[0] for s in snap["spans"]]
    # Drop-oldest: only the newest survive (the snapshot conservatively
    # excludes one more slot — the one a concurrent writer could be
    # mid-store on).
    assert names == [f"s{i}" for i in range(13, 20)]


def test_spans_record_and_nest():
    tracer = trace.configure(True, capacity=64)
    with tracer.span("outer"):
        with tracer.span("inner"):
            time.sleep(0.002)
    (snap,) = tracer.snapshots()
    spans = {name: (start, end) for name, start, end in snap["spans"]}
    assert set(spans) == {"outer", "inner"}
    oi, oo = spans["inner"], spans["outer"]
    assert oo[0] <= oi[0] and oi[1] <= oo[1]  # containment
    stats = trace.stats()
    assert stats["trace_spans"] == 2 and stats["trace_dropped_spans"] == 0


def test_thread_groups_map_and_tag_override():
    trace.configure(True, capacity=32)

    def actor_work():
        with trace.span("actor.env_step"):
            pass

    t = threading.Thread(target=actor_work, name="actor-3")
    t.start()
    t.join()
    trace.tag_thread("learner")
    with trace.span("learner.update"):
        pass
    groups = {s["thread"]: s["group"] for s in trace.snapshots()}
    assert groups["actor-3"] == "actor"
    assert groups[threading.current_thread().name] == "learner"


def test_wait_classification_and_taxonomy():
    assert span_names.is_wait(span_names.LEARNER_QUEUE_WAIT)
    assert span_names.is_wait("anything.custom_wait")  # suffix convention
    assert not span_names.is_wait(span_names.ACTOR_ENV_STEP)
    # Every declared wait span has a causal reading for the report.
    for name in span_names.WAIT_SPANS:
        assert name in span_names.WAIT_CAUSES


def test_dead_threads_rings_are_retained():
    """A crashed/retired thread's spans stay in the export: rings are
    registered append-only (never keyed on the recyclable thread.ident),
    so a restarted actor cannot evict its predecessor's forensics."""
    trace.configure(True, capacity=32)

    def work(i):
        with trace.span("actor.env_step"):
            pass

    for i in range(3):  # sequential: idents are maximally reusable
        t = threading.Thread(target=work, args=(i,), name=f"actor-{i}")
        t.start()
        t.join()
    snaps = trace.snapshots()
    assert len(snaps) == 3
    assert all(len(s["spans"]) == 1 for s in snaps)
    assert trace.stats()["trace_spans"] == 3


def test_env_arming_rearms_fresh_tracer_per_setup(monkeypatch, tmp_path):
    """ASYNCRL_TRACE=1: each obs.setup still gets a FRESH tracer — a
    second agent's stats/export must not include a predecessor's spans,
    and the handle stays bound to ITS tracer even after a later re-arm."""
    import asyncrl_tpu.obs as obs_pkg
    from asyncrl_tpu.utils.config import Config

    monkeypatch.setenv(trace.ENV_VAR, "1")
    cfg = Config(trace=False, run_dir=str(tmp_path / "a"))
    h1 = obs_pkg.setup(cfg)
    assert h1.enabled  # env wins over config.trace=False
    with trace.span("actor.env_step"):
        pass
    assert h1.window()["trace_spans"] == 1

    h2 = obs_pkg.setup(cfg.replace(run_dir=str(tmp_path / "b")))
    assert h2.window()["trace_spans"] == 0  # fresh rings
    # h1 still reads (and would export) its own rings, not h2's.
    assert h1.window()["trace_spans"] == 1
    path = h1.export_trace()
    doc = json.load(open(path))
    assert sum(1 for e in doc["traceEvents"] if e["ph"] == "X") == 1


# ------------------------------------------------------------------- export


def _traced_two_threads():
    tracer = trace.configure(True, capacity=128)

    def actor_work():
        for _ in range(3):
            with trace.span(span_names.ACTOR_ENV_STEP):
                time.sleep(0.001)

    t = threading.Thread(target=actor_work, name="actor-0")
    t.start()
    trace.tag_thread("learner")
    with trace.span(span_names.LEARNER_QUEUE_WAIT):
        t.join()
    return tracer


def test_export_schema_and_validator(tmp_path):
    _traced_two_threads()
    doc = export.export_document()
    assert export.validate_trace(doc) == []
    path = export.write_trace(str(tmp_path / "sub" / "trace.json"))
    on_disk = json.load(open(path))
    assert export.validate_trace(on_disk) == []
    # Thread metadata + both groups present.
    meta = [e for e in on_disk["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["group"] for m in meta} >= {"actor", "learner"}
    # The validator actually catches breakage (the trace_smoke gate).
    broken = json.loads(json.dumps(doc))
    for ev in broken["traceEvents"]:
        ev.pop("ts", None)
    assert export.validate_trace(broken)
    assert export.validate_trace({"schema": "wrong"})


def test_export_none_when_disabled():
    assert export.export_document() is None
    assert export.write_trace("/tmp/should-not-exist.json") is None


# ------------------------------------------------------------------- report


def _synthetic_doc():
    """1s window: learner waits 600ms on the queue and computes 350ms;
    one actor steps envs 900ms."""
    events = [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "MainThread", "group": "learner"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "actor-0", "group": "actor"}},
    ]
    for i in range(6):
        events.append({"ph": "X", "name": "learner.queue_wait", "pid": 1,
                       "tid": 1, "ts": i * 165_000.0, "dur": 100_000.0})
        events.append({"ph": "X", "name": "learner.update", "pid": 1,
                       "tid": 1, "ts": i * 165_000.0 + 105_000.0,
                       "dur": 58_000.0})
    for i in range(9):
        events.append({"ph": "X", "name": "actor.env_step", "pid": 1,
                       "tid": 2, "ts": i * 110_000.0, "dur": 100_000.0})
    return {"schema": export.SCHEMA, "traceEvents": events}


def test_report_stall_attribution_table():
    analysis = report.analyze(_synthetic_doc())
    text = report.render(analysis)
    # Per-stage table rows + wait/compute kinds.
    assert "learner.queue_wait" in text and "wait" in text
    assert "actor.env_step" in text and "compute" in text
    # Stall attribution names the dominant wait with its cause.
    share, group, name, _ = analysis["waits"][0]
    assert (group, name) == ("learner", "learner.queue_wait")
    assert 0.55 < share < 0.70
    assert "dominant stall: learner.queue_wait" in text
    assert "learner starved for fragments" in text


def test_report_self_time_subtracts_children():
    events = [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "actor-0", "group": "actor"}},
        {"ph": "X", "name": "actor.lease_wait", "pid": 1, "tid": 1,
         "ts": 0.0, "dur": 100_000.0},
        {"ph": "X", "name": "staging.reuse_wait", "pid": 1, "tid": 1,
         "ts": 10_000.0, "dur": 80_000.0},
    ]
    analysis = report.analyze({"schema": export.SCHEMA, "traceEvents": events})
    by_name = {s.name: s for s in analysis["stages"]}
    assert by_name["staging.reuse_wait"].self_us == pytest.approx(80_000.0)
    # Parent keeps only its own 20ms — the nested wait is not re-counted.
    assert by_name["actor.lease_wait"].self_us == pytest.approx(20_000.0)


# ----------------------------------------------------------------- registry


def test_registry_counters_histograms_window():
    reg = registry.registry()
    reg.counter("widgets").inc()
    reg.counter("widgets").inc(2.0)
    h = reg.histogram("lat_ms")
    for v in (0.5, 1.0, 2.0, 4.0, 100.0):
        h.observe(v)
    window = registry.window()
    assert window["widgets"] == 3.0
    assert window["lat_ms_count"] == 5.0
    assert window["lat_ms_max"] == 100.0
    assert window["lat_ms_p50"] <= window["lat_ms_p95"]
    reg.reset()
    assert registry.window() == {}


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        registry.Histogram("bad", buckets=(2.0, 1.0))


def test_registry_gauge_is_last_value_and_resets():
    g = registry.gauge("queue_depth")
    g.set(3.0)
    g.set(1.5)  # a LEVEL, not a count: the last set wins
    assert registry.window()["queue_depth"] == 1.5
    registry.registry().reset()
    assert "queue_depth" not in registry.window()


def test_slo_gate_feeds_breach_gauges():
    """serve/slo.py feeds its rolling-p95 breach state to the health
    detectors through registry gauges, refreshed where the rolling
    window recomputes."""
    from asyncrl_tpu.serve.slo import BREACH_GAUGE, P95_GAUGE, SLOGate

    gate = SLOGate(p95_target_ms=10.0)
    gate.admit()
    gate.finished(50.0)  # p95 window = [50] -> breached
    window = registry.window()
    assert window[P95_GAUGE] == 50.0
    assert window[BREACH_GAUGE] == 1.0
    # Recovery: enough fast completions pull the rolling p95 back under.
    for _ in range(200):
        gate.admit()
        gate.finished(1.0)
    window = registry.window()
    assert window[P95_GAUGE] <= 10.0
    assert window[BREACH_GAUGE] == 0.0


# --------------------------------------------------------------- timeseries


def test_timeseries_ring_overflow_and_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "run" / timeseries.FILENAME)
    store = timeseries.TimeSeriesStore(
        capacity=8, persist_path=path, meta={"env_id": "unit", "seed": 3}
    )
    for i in range(20):
        store.append({"env_steps": i, "fps": float(100 + i)})
    store.annotate({"detector": "fps_collapse", "window_idx": 19})
    # Ring: drop-oldest, newest retained (the snapshot conservatively
    # excludes one more slot — the SpanRing copy-window discipline).
    snap = store.snapshot()
    assert [s["env_steps"] for s in snap] == list(range(13, 20))
    assert store.dropped == 12
    assert store.latest()["fps"] == 119.0
    assert store.series("fps", last_n=3) == [
        [s["t"], s["fps"]] for s in snap[-3:]
    ]
    assert "fps" in store.keys() and "env_steps" in store.keys()
    store.close()

    # JSONL: meta line + EVERY sample (persistence is unbounded even
    # though the ring dropped 12) + the event annotation.
    run = timeseries.read_jsonl(path)
    assert run["meta"] == {"env_id": "unit", "seed": 3}
    assert len(run["samples"]) == 20
    assert [s["env_steps"] for s in run["samples"]] == list(range(20))
    assert run["events"] == [
        {"detector": "fps_collapse", "window_idx": 19,
         "t": run["events"][0]["t"]}
    ]


def test_timeseries_tolerates_torn_tail_and_drops_nonscalars(tmp_path):
    path = str(tmp_path / timeseries.FILENAME)
    store = timeseries.TimeSeriesStore(capacity=8, persist_path=path)
    import numpy as np

    sample = store.append(
        {"fps": np.float32(2.0), "bad": object(), "status": "ok"}
    )
    assert sample["fps"] == 2.0 and "bad" not in sample
    store.close()
    with open(path, "a") as f:
        f.write('{"kind": "sample", "wind')  # a crashed writer's tail
    run = timeseries.read_jsonl(path)
    assert len(run["samples"]) == 1
    assert run["samples"][0]["status"] == "ok"


def test_timeseries_jsonl_is_strict_json_and_roundtrips_nonfinite(tmp_path):
    """A diverging run's loss=NaN must survive the JSONL round-trip AND
    leave the file strictly RFC-parseable (json.dumps' bare NaN literal
    is a Python dialect jq/JS/Go reject): non-finite floats encode as
    strings on disk and decode back to floats on read."""
    import math

    path = str(tmp_path / timeseries.FILENAME)
    store = timeseries.TimeSeriesStore(capacity=8, persist_path=path)
    store.append({"loss": float("nan"), "grad_norm": float("inf")})
    store.annotate({"detector": "nonfinite_loss",
                    "data": {"value": float("-inf")}})
    store.close()

    def reject_constants(name):  # bare NaN/Infinity literal => not strict
        raise AssertionError(f"non-strict JSON constant {name!r} on disk")

    rows = [
        json.loads(line, parse_constant=reject_constants)
        for line in open(path)
    ]
    assert rows[1]["window"]["loss"] == "NaN"
    run = timeseries.read_jsonl(path)
    assert math.isnan(run["samples"][0]["loss"])
    assert run["samples"][0]["grad_norm"] == float("inf")
    assert run["events"][0]["data"]["value"] == float("-inf")
    # The in-memory ring keeps the raw float; /timeseries skips the
    # unplottable point instead of serving invalid JSON.
    assert store.series("loss") == []


def test_timeseries_reused_run_dir_returns_last_segment(tmp_path):
    """A reused run_dir appends one meta line per run; read_jsonl returns
    the LAST segment, so an earlier run's samples are never replayed
    under a later run's thresholds and recorded events always align with
    the samples' window indices (doctor dedup correctness)."""
    path = str(tmp_path / timeseries.FILENAME)
    first = timeseries.TimeSeriesStore(
        capacity=8, persist_path=path, meta={"seed": 1}
    )
    first.append({"env_steps": 100})
    first.annotate({"detector": "fps_collapse", "window_idx": 1})
    first.close()
    second = timeseries.TimeSeriesStore(
        capacity=8, persist_path=path, meta={"seed": 2}
    )
    second.append({"env_steps": 7})
    second.close()
    run = timeseries.read_jsonl(path)
    assert run["meta"] == {"seed": 2}
    assert [s["env_steps"] for s in run["samples"]] == [7]
    assert run["events"] == []


# ------------------------------------------------------------------- health


def _monitor(tmp_path=None, thresholds=None, tracer=None, emit=False):
    store = timeseries.TimeSeriesStore(
        capacity=64,
        persist_path=(
            str(tmp_path / timeseries.FILENAME) if tmp_path else None
        ),
    )
    return health.HealthMonitor(
        thresholds=thresholds or health.Thresholds(window_ttl=2),
        store=store, tracer=tracer, emit=emit,
    )


def test_detector_nan_loss_is_critical_and_flips_healthz():
    monitor = _monitor()
    assert monitor.on_window({"env_steps": 100, "loss": 0.5}) == []
    assert monitor.verdict()["status"] == "ok"
    (event,) = monitor.on_window(
        {"env_steps": 200, "loss": float("nan")}
    )
    assert (event.detector, event.severity) == ("nonfinite_loss", "critical")
    verdict = monitor.verdict()
    assert verdict["status"] == "critical"
    assert verdict["components"]["learner"] == "critical"
    # Recovery: window_ttl=2 quiet windows later the verdict is ok again.
    monitor.on_window({"env_steps": 300, "loss": 0.4})
    monitor.on_window({"env_steps": 400, "loss": 0.4})
    assert monitor.verdict()["status"] == "ok"


def test_detector_stall_attribution_names_the_bottleneck_stage():
    """The learner_stall verdict reuses the WAIT_SPANS causal table: with
    the dominant wait being learner.queue_wait, the event names that
    stage, carries its causal reading, and blames the ACTORS component
    (the learner starving means its feeders are the bottleneck)."""
    tracer = trace.configure(True, capacity=64)
    ring = tracer.span("x")._ring  # materialize this thread's ring
    now = time.perf_counter()
    ring.record(span_names.LEARNER_QUEUE_WAIT, now - 0.5, now - 0.1)
    ring.record(span_names.LEARNER_H2D_WAIT, now - 0.09, now - 0.08)
    monitor = _monitor(tracer=tracer)
    (event,) = monitor.on_window(
        {"env_steps": 100, "learner_stall_frac": 0.97}
    )
    assert event.detector == "learner_stall"
    assert event.data["stage"] == span_names.LEARNER_QUEUE_WAIT
    assert event.component == "actors"
    assert "learner starved for fragments" in event.message


def test_detector_fps_collapse_vs_trailing_median():
    monitor = _monitor()
    for i in range(5):
        assert monitor.on_window({"env_steps": i, "fps": 1000.0}) == []
    (event,) = monitor.on_window({"env_steps": 6, "fps": 100.0})
    assert event.detector == "fps_collapse"
    assert event.data["trailing_median"] == 1000.0
    # The collapsed window joins the history; a RECOVERED window is quiet.
    assert monitor.on_window({"env_steps": 7, "fps": 900.0}) == []


def test_detector_restart_storm_and_admission_and_slo_persistence():
    monitor = _monitor()
    base = {"env_steps": 0, "actor_restarts": 0.0, "server_restarts": 0.0}
    assert monitor.on_window(dict(base)) == []
    # One restart in a window: churn, not storm proximity.
    assert monitor.on_window(
        dict(base, env_steps=1, actor_restarts=1.0)
    ) == []
    (storm,) = monitor.on_window(
        dict(base, env_steps=2, actor_restarts=3.0)
    )
    assert (storm.detector, storm.severity, storm.component) == (
        "restart_storm", "critical", "actors"
    )
    # Admission-gate saturation: overload counter grew this window.
    (sat,) = monitor.on_window(
        dict(base, env_steps=3, actor_restarts=3.0, server_overload=5.0)
    )
    assert (sat.detector, sat.component) == (
        "admission_saturation", "serve-core"
    )
    # SLO breach fires on PERSISTENCE (2+ consecutive breached windows).
    sample = dict(base, env_steps=4, actor_restarts=3.0,
                  server_overload=5.0, serve_slo_breached=1.0)
    assert monitor.on_window(dict(sample)) == []
    events = monitor.on_window(dict(sample, env_steps=5))
    assert [e.detector for e in events] == ["slo_breach"]


def test_detector_eval_regression_threshold():
    monitor = _monitor(
        thresholds=health.Thresholds(eval_drop=5.0, window_ttl=2)
    )
    assert monitor.on_window({"env_steps": 0, "eval_return": 10.0}) == []
    assert monitor.on_window({"env_steps": 1, "eval_return": 8.0}) == []
    (event,) = monitor.on_window({"env_steps": 2, "eval_return": 2.0})
    assert event.detector == "eval_regression"
    assert event.data["best"] == 10.0


def test_health_event_triggers_flightrec_dump(tmp_path):
    """The pinned anomaly->forensics path: a firing detector (emit=True)
    counts into the registry AND triggers a flight dump with
    reason=health.<detector>."""
    trace.configure(True, capacity=32)
    rec = flightrec.arm(str(tmp_path), min_interval_s=0.0)
    monitor = _monitor(emit=True)
    monitor.on_window({"env_steps": 1, "loss": 0.1})
    monitor.on_window({"env_steps": 2, "loss": float("inf")})
    assert rec.drain(10.0)
    (path,) = glob.glob(str(tmp_path / "*health.nonfinite_loss*.json"))
    doc = json.load(open(path))
    assert doc["reason"] == "health.nonfinite_loss"
    assert doc["extra"]["health_event"]["detector"] == "nonfinite_loss"
    window = registry.window()
    assert window["health_events_total"] == 1.0
    assert window["health_nonfinite_loss"] == 1.0


def test_health_forensics_stay_bound_to_the_armed_recorder(tmp_path):
    """The PipelineObs isolation contract extends to health telemetry: a
    monitor bound to ITS setup's recorder keeps dumping there after a
    later agent re-arms the global flight recorder, and a monitor whose
    setup armed none (recorder=None) never dumps into another agent's
    run_dir even while the global is armed."""
    rec_a = flightrec.arm(str(tmp_path / "a"), min_interval_s=0.0)
    monitor = health.HealthMonitor(store=None, emit=True, recorder=rec_a)
    silent = health.HealthMonitor(store=None, emit=True, recorder=None)
    flightrec.arm(str(tmp_path / "b"), min_interval_s=0.0)  # agent B
    monitor.on_window({"env_steps": 1, "loss": float("nan")})
    silent.on_window({"env_steps": 1, "loss": float("nan")})
    assert rec_a.drain(10.0) and flightrec.active().drain(10.0)
    assert glob.glob(str(tmp_path / "a" / "*health.nonfinite_loss*"))
    assert not glob.glob(str(tmp_path / "b" / "*"))


def test_broken_detector_degrades_to_counter():
    def boom(monitor, sample):
        raise RuntimeError("buggy detector")

    monitor = health.HealthMonitor(
        detectors=[health.Detector("boom", "pipeline", "warn", boom)],
        emit=True,
    )
    assert monitor.on_window({"env_steps": 1}) == []
    assert registry.window()["health_detector_errors"] == 1.0


# ----------------------------------------------------------- http endpoint


def test_http_metrics_healthz_timeseries_and_routes():
    registry.counter("widgets").inc(3.0)
    monitor = _monitor()
    monitor.on_window({"env_steps": 100, "fps": 1000.0, "loss": 0.5})
    server = ObsHTTPServer(port=0, store=monitor.store, monitor=monitor)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        # /metrics: Prometheus text exposition from registry + latest
        # window (TYPE line per metric; strings skipped).
        code, body = _get(f"{base}/metrics")
        text = body.decode()
        assert code == 200
        assert "# TYPE asyncrl_widgets gauge\nasyncrl_widgets 3" in text
        assert "asyncrl_fps 1000" in text
        assert "health_status" not in text  # categorical -> /healthz only

        code, body = _get(f"{base}/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"

        # A firing detector flips the verdict AND the status code — and
        # the body stays STRICT JSON even though the causing sample holds
        # a NaN loss (bare NaN literals would break RFC consumers).
        monitor.on_window({"env_steps": 200, "loss": float("nan")})
        code, body = _get(f"{base}/healthz")
        doc = json.loads(
            body,
            parse_constant=lambda name: pytest.fail(
                f"non-strict JSON constant {name!r} on /healthz"
            ),
        )
        assert code == 503
        assert doc["status"] == "critical"
        assert doc["components"]["learner"] == "critical"
        assert doc["recent_events"][0]["detector"] == "nonfinite_loss"

        code, body = _get(f"{base}/timeseries?key=fps&n=10")
        points = json.loads(body)["points"]
        assert code == 200 and [p[1] for p in points] == [1000.0]
        code, body = _get(f"{base}/timeseries")
        assert code == 200 and "fps" in json.loads(body)["keys"]
        code, _ = _get(f"{base}/nope")
        assert code == 404
    finally:
        server.stop()
        server.stop()  # idempotent
    # Zero threads once stopped (and the port is closed).
    assert "obs-http" not in [t.name for t in threading.enumerate()]
    with pytest.raises(OSError):
        urllib.request.urlopen(f"http://127.0.0.1:{server.port}/metrics",
                               timeout=0.5)


def test_render_prometheus_sanitizes_names():
    text = render_prometheus(
        {"fault_actor.step": 2, "health_status": "ok", "flag": True}
    )
    assert "asyncrl_fault_actor_step 2" in text
    assert "health_status" not in text and "flag" not in text


# ------------------------------------------------------------------ doctor


def _fixture_run_dir(tmp_path, fps=1000.0, nan_window=False):
    run_dir = tmp_path / "run"
    store = timeseries.TimeSeriesStore(
        capacity=64,
        persist_path=str(run_dir / timeseries.FILENAME),
        meta={"env_id": "CartPole-v1", "algo": "a3c", "backend": "sebulba",
              "platform": "cpu",
              "thresholds": {"window_ttl": 2, "fps_collapse": 0.5}},
    )
    for i in range(8):
        sample = {"env_steps": 100 * i, "fps": fps, "loss": 0.1}
        if nan_window and i == 5:
            sample["loss"] = float("nan")
        store.append(sample)
    store.close()
    return str(run_dir)


def test_doctor_timeline_attribution_and_exit_code(tmp_path, capsys):
    """The acceptance bar: doctor prints a detector timeline + bottleneck
    attribution, exits 0 on a clean run and nonzero when a detector
    fired, and no longer takes a run log to judge speed against."""
    from asyncrl_tpu.obs.__main__ import main as obs_main

    run_dir = _fixture_run_dir(tmp_path, fps=1000.0, nan_window=True)
    rc = obs_main(["doctor", run_dir])
    out = capsys.readouterr().out
    assert rc == 1
    assert "detector timeline" in out
    assert "nonfinite_loss" in out and "replayed" in out
    assert "bottleneck attribution" in out
    assert "regression verdict" not in out and "DEGRADED" in out

    clean_dir = _fixture_run_dir(tmp_path / "clean", fps=1000.0)
    assert obs_main(["doctor", clean_dir]) == 0
    assert "CLEAN (0 health event(s))" in capsys.readouterr().out

    with pytest.raises(SystemExit):
        obs_main(["doctor", run_dir, "--bench-history", "x.json"])


def test_doctor_errors_on_unrecorded_run_dir(tmp_path, capsys):
    from asyncrl_tpu.obs.__main__ import main as obs_main

    assert obs_main(["doctor", str(tmp_path / "missing")]) == 2
    assert "no readable timeseries" in capsys.readouterr().err


# --------------------------------------------------------------- flightrec


def test_flightrec_dump_and_debounce(tmp_path):
    trace.configure(True, capacity=64)
    with trace.span(span_names.ACTOR_ENV_STEP):
        pass
    rec = flightrec.arm(str(tmp_path), window_s=5.0, min_interval_s=60.0,
                        config={"env_id": "unit"})
    assert flightrec.record("fault.actor.step", detail="first")
    assert not flightrec.record("fault.actor.step", detail="debounced")
    assert flightrec.record("supervisor.actor_restart")
    assert rec.drain(10.0)
    paths = sorted(glob.glob(str(tmp_path / "flightrec-*.json")))
    assert len(paths) == 2  # the middle record was debounced
    doc = json.load(open(paths[0]))
    assert doc["schema"] == flightrec.SCHEMA
    assert doc["reason"] == "fault.actor.step"
    assert doc["config"] == {"env_id": "unit"}
    assert doc["counters"]["flightrec_dumps"] >= 1.0
    assert export.validate_trace(doc["trace"]) == []
    # The debounce was counted by the time the LAST dump snapshotted.
    last = json.load(open(paths[-1]))
    assert last["reason"] == "supervisor.actor_restart"
    assert last["counters"]["flightrec_suppressed"] >= 1.0


def test_flightrec_record_is_noop_when_unarmed(tmp_path):
    assert flightrec.active() is None
    assert not flightrec.record("fault.actor.step")


def test_setup_disabled_disarms_predecessor_flightrec(tmp_path):
    """A trace=False agent must not dump forensics into a PREVIOUS
    agent's run_dir with the old config embedded: setup() disarms the
    inherited recorder (the faults.arm('') precedent)."""
    import asyncrl_tpu.obs as obs_pkg
    from asyncrl_tpu.utils.config import Config

    h1 = obs_pkg.setup(Config(trace=True, run_dir=str(tmp_path / "a")))
    assert h1.enabled and flightrec.active() is not None
    obs_pkg.setup(Config(trace=False))
    assert flightrec.active() is None
    assert not flightrec.record("fault.actor.step")
    assert not glob.glob(str(tmp_path / "a" / "flightrec-*.json"))


def test_quiet_window_flightrec_dump_validates(tmp_path):
    """A dump whose lookback window holds no spans is correctly recorded,
    not malformed: the validator accepts it with require_spans=False (the
    CLI's flightrec path), while a span-less RUN export still fails."""
    trace.configure(True, capacity=16)
    with trace.span("actor.env_step"):
        pass
    time.sleep(0.05)
    rec = flightrec.arm(str(tmp_path), window_s=0.01)  # window excludes it
    assert flightrec.record("fault.actor.step")
    assert rec.drain(10.0)
    (path,) = glob.glob(str(tmp_path / "flightrec-*.json"))
    doc = json.load(open(path))["trace"]
    assert export.validate_trace(doc, require_spans=False) == []
    assert export.validate_trace(doc)  # the run-export gate still bites
    from asyncrl_tpu.obs.__main__ import main as obs_main

    assert obs_main(["validate", path]) == 0


# ------------------------------------------------------- pipeline end-to-end


def _traced_crash_config(tmp_path):
    from asyncrl_tpu.utils.config import Config

    return Config(
        env_id="CartPole-v1", algo="a3c", backend="sebulba",
        host_pool="jax", num_envs=16, actor_threads=2, unroll_len=4,
        precision="f32", log_every=2, seed=5,
        trace=True, trace_ring=2048, run_dir=str(tmp_path / "run"),
        inference_server=True,
        fault_spec="actor.step:crash:1:0:max=1",
    )


def test_traced_crash_run_dumps_flightrec_and_exports(tmp_path):
    """The acceptance path: a fault-injected run produces a flight dump
    with spans from >= 3 distinct thread groups, the Perfetto export
    validates, the report renders a stall-attribution table, and the
    obs window keys flow through the metric windows."""
    from asyncrl_tpu import make_agent

    cfg = _traced_crash_config(tmp_path)
    agent = make_agent(cfg)
    try:
        history = agent.train(total_env_steps=256)
    finally:
        agent.close()
    window = history[-1]
    assert window["actor_restarts"] >= 1
    assert window["fault_actor.step"] == 1
    # Registry/trace keys drained into the window (the unified plumbing).
    assert window["trace_spans"] > 0
    assert window["flightrec_dumps"] >= 1.0
    assert "h2d_wait_ms_p95" in window

    run_dir = cfg.run_dir
    dumps = sorted(glob.glob(f"{run_dir}/flightrec-*.json"))
    assert dumps, "no flight-recorder dump written on the injected crash"
    reasons = set()
    group_sets = []
    for path in dumps:
        doc = json.load(open(path))
        reasons.add(doc["reason"])
        group_sets.append(set(doc["thread_groups"]))
    assert "fault.actor.step" in reasons
    assert "supervisor.actor_restart" in reasons
    # The acceptance bar: a dump holding spans from >= 3 distinct thread
    # groups. (The fault dump itself can fire before the learner thread
    # completed its first span — the supervisor's restart dump, taken
    # once the drain noticed, always has all three.)
    assert any(len(g) >= 3 for g in group_sets), group_sets

    (trace_path,) = glob.glob(f"{run_dir}/trace-*.json")
    doc = json.load(open(trace_path))
    assert export.validate_trace(doc) == []
    text = report.render(report.analyze(doc))
    assert "stall attribution" in text
    assert "dominant stall:" in text


def test_trace_disabled_run_keeps_window_clean(tmp_path):
    """trace=False (the default): no run dir, no trace keys, no health
    layer, no obs-http thread, and the shared no-op span means the hot
    loop never registers a ring."""
    from asyncrl_tpu import make_agent

    cfg = _traced_crash_config(tmp_path).replace(
        trace=False, fault_spec="", inference_server=False
    )
    agent = make_agent(cfg)
    try:
        assert agent._obs.store is None and agent._obs.http is None
        assert "obs-http" not in [t.name for t in threading.enumerate()]
        history = agent.train(total_env_steps=128)
    finally:
        agent.close()
    window = history[-1]
    assert "trace_spans" not in window
    assert "health_status" not in window
    assert not glob.glob(str(tmp_path / "run" / "*"))
    # Registry instruments still drain (the unconditional metrics path).
    assert "h2d_wait_ms_count" in window


def test_live_run_serves_healthz_and_persists_timeseries(tmp_path):
    """The ISSUE 7 acceptance path: a traced run with the exposition
    endpoint on and an injected crash storm — /healthz degrades while the
    storm is inside the verdict TTL and recovers after, /metrics scrapes
    in Prometheus format mid-run, the window sample carries the health
    verdict (the ONE shared snapshot), timeseries.jsonl persists the full
    history, and the firing detector leaves a health.* flight dump."""
    from asyncrl_tpu import make_agent

    cfg = _traced_crash_config(tmp_path).replace(
        inference_server=False,
        obs_http_port=-1,  # ephemeral bind, read back from the handle
        health_window_ttl=2,
        fault_spec="actor.step:crash:1:0:max=2",  # both actors' first step
    )
    agent = make_agent(cfg)
    scrapes = []

    def scrape(window):
        base = f"http://127.0.0.1:{agent._obs.http.port}"
        code, body = _get(f"{base}/healthz")
        scrapes.append((code, json.loads(body)["status"]))
        if len(scrapes) == 1:
            code, body = _get(f"{base}/metrics")
            assert code == 200
            assert "# TYPE asyncrl_fps gauge" in body.decode()

    try:
        history = agent.train(total_env_steps=1024, callback=scrape)
    finally:
        agent.close()
    # Degraded while the storm was fresh; recovered once it aged out.
    assert (503, "critical") in scrapes, scrapes
    after = scrapes.index((503, "critical"))
    assert (200, "ok") in scrapes[after:], scrapes
    assert history[0]["health_events"] >= 1.0  # the storm window
    assert history[0]["health_status"] in ("degraded", "critical")
    # The per-detector counter registers at the firing window's close, so
    # it rides every LATER window's registry drain (cumulative).
    assert history[-1]["health_restart_storm"] >= 1.0
    # Endpoint gone after close(): zero threads, socket closed.
    assert "obs-http" not in [t.name for t in threading.enumerate()]
    run = timeseries.read_jsonl(
        str(tmp_path / "run" / timeseries.FILENAME)
    )
    assert run["meta"]["env_id"] == "CartPole-v1"
    assert len(run["samples"]) == len(history)
    assert any(
        e["detector"] == "restart_storm" for e in run["events"]
    ), run["events"]
    assert glob.glob(str(tmp_path / "run" / "*health.restart_storm*"))
