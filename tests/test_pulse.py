"""fluid-pulse (round 13): live health plane over real HTTP.

Covers the tentpole contract: /metrics parses under the STRICT
exposition grammar, /healthz flips ok -> unready when a detector trips,
start_pulse is refused while the observe flag is off, the pulse thread
never leaks across observe.reset_all() (the autouse fixture), the
detector catalog fires and clears on synthetic series, and the memory
observatory estimates against the cost model and degrades cleanly on a
backend without device memory stats (this CPU mesh).
"""

import json
import math
import threading
import time
import urllib.error
import urllib.request
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.observe import flight, health, memory, metrics, pulse
from paddle_tpu.observe.health import TimeSeries
from paddle_tpu.observe.metrics import parse_prometheus_text


def _get(port, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _get_json(port, path):
    code, body = _get(port, path)
    return code, json.loads(body)


def _start():
    fluid.set_flag("observe", True)
    return observe.start_pulse(0)


# ---------------------------------------------------------------------------
# the pulse endpoint
# ---------------------------------------------------------------------------

def test_start_pulse_refused_while_observe_off():
    fluid.set_flag("observe", False)
    with pytest.raises(RuntimeError, match="observe"):
        observe.start_pulse(0)
    assert pulse.get_pulse() is None


def test_pulse_binds_port0_idempotent_and_stops_clean():
    port = _start()
    assert port > 0
    assert observe.start_pulse(0) == port   # second call: same server
    assert any(t.name == f"pulse@{port}" for t in threading.enumerate())
    observe.reset_all()                     # the fixture's teardown path
    assert pulse.get_pulse() is None
    assert not any(t.name.startswith("pulse")
                   for t in threading.enumerate())
    # restartable after a reset
    fluid.set_flag("observe", True)
    port2 = observe.start_pulse(0)
    assert port2 > 0


def test_live_metrics_scrape_parses_under_strict_grammar():
    port = _start()
    # hostile label values: every character the exposition spec escapes
    metrics.counter("pulse_t_requests_total", "help with \\ and\nnewline") \
        .inc(3, cmd='a"b\\c\nd')
    metrics.gauge("pulse_t_level").set(float("inf"), src="x")
    metrics.histogram("pulse_t_us", "lat").observe(5.0, phase="p")
    code, body = _get(port, "/metrics")
    assert code == 200
    doc = parse_prometheus_text(body.decode())   # raises on ANY bad line
    (name, labels, value), = doc["pulse_t_requests_total"]["samples"]
    assert labels == {"cmd": 'a"b\\c\nd'} and value == 3
    assert doc["pulse_t_requests_total"]["help"] == \
        "help with \\ and\nnewline"
    assert doc["pulse_t_requests_total"]["kind"] == "counter"
    assert doc["pulse_t_level"]["samples"][0][2] == float("inf")
    # histogram family: buckets cumulative, +Inf bucket == count
    hsamples = doc["pulse_t_us"]["samples"]
    infb = [v for n, l, v in hsamples
            if n == "pulse_t_us_bucket" and l.get("le") == "+Inf"]
    cnt = [v for n, l, v in hsamples if n == "pulse_t_us_count"]
    assert infb == cnt == [1]


def test_healthz_flips_unready_when_detector_trips():
    """The acceptance scrape: ok over real HTTP, then a NaN loss lands
    on the watched series (via the registry emit path) and the verdict
    flips to 503/unready with a structured alert."""
    port = _start()
    code, doc = _get_json(port, "/healthz")
    assert (code, doc["status"]) == (200, "ok")
    assert "detectors" in doc["checks"]
    metrics.gauge("trainer_last_loss").set(2.5)
    code, doc = _get_json(port, "/healthz")
    assert (code, doc["status"]) == (200, "ok")

    metrics.gauge("trainer_last_loss").set(float("nan"))
    code, doc = _get_json(port, "/healthz")
    assert (code, doc["status"]) == (503, "unready")
    rules = {a["rule"] for a in doc["alerts"]}
    assert "non_finite_loss" in rules
    a = next(x for x in doc["alerts"] if x["rule"] == "non_finite_loss")
    assert a["metric"] == "train_loss" and a["threshold"] == "finite"
    # the alert was metered and black-boxed with the series' last points
    assert metrics.counter(health.ALERTS_METRIC).value(
        rule="non_finite_loss") == 1
    evs = flight.get_flight().events("alert")
    assert evs and evs[-1]["rule"] == "non_finite_loss"
    assert evs[-1]["points"], "alert must carry the triggering points"


def test_readyz_scopes_to_ready_checks():
    port = _start()
    eng = health.get_engine()
    eng.register_check("always_sad", lambda: (False, {"why": "testing"}),
                       ready=False)
    code, doc = _get_json(port, "/healthz")
    assert (code, doc["status"]) == (503, "unready")
    assert doc["checks"]["always_sad"]["detail"]["why"] == "testing"
    code, doc = _get_json(port, "/readyz")   # non-ready check excluded
    assert (code, doc["status"]) == (200, "ok")
    eng.unregister_check("always_sad")
    code, doc = _get_json(port, "/healthz")
    assert (code, doc["status"]) == (200, "ok")


def test_status_and_flight_endpoints():
    port = _start()
    metrics.counter("pulse_t_total").inc()
    flight.note("drill", detail=1)
    code, doc = _get_json(port, "/status")
    assert code == 200
    for key in ("pid", "process", "ts", "metrics", "steps", "recompiles",
                "memory", "alerts"):
        assert key in doc, key
    assert "pulse_t_total" in doc["metrics"]
    code, fdoc = _get_json(port, "/flight")
    assert code == 200
    assert any(e["kind"] == "drill" for e in fdoc["events"])
    assert "memory" in fdoc
    code, doc = _get_json(port, "/nope")
    assert code == 404


def test_concurrent_scrapes():
    port = _start()
    metrics.counter("pulse_t_total", "x").inc(cmd="y")
    errors = []

    def scrape():
        try:
            for path in ("/metrics", "/status", "/healthz"):
                code, _ = _get(port, path)
                if code != 200:
                    errors.append((path, code))
        except Exception as e:   # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=scrape) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors


# ---------------------------------------------------------------------------
# TimeSeries + detectors
# ---------------------------------------------------------------------------

def test_timeseries_bounded_rate_derivative():
    ts = TimeSeries(capacity=8)
    t0 = 1000.0
    for i in range(20):
        ts.append(float(i), ts=t0 + i)
    assert len(ts) == 8                       # capped
    assert ts.values() == [float(i) for i in range(12, 20)]
    s, n = ts.window_sum(3.0, now=t0 + 19)    # points at t+17..19
    assert n == 3 and s == 17 + 18 + 19
    assert ts.rate(3.0, now=t0 + 19) == pytest.approx(s / 3.0)
    assert ts.derivative() == pytest.approx(1.0)


def test_spike_detector_fires_and_clears():
    eng = health.HealthEngine()
    det = health.SpikeDetector(series="g", window=32, k=10, min_points=8)
    eng.add_detector(det)
    for _ in range(16):
        eng.feed("g", 1.0 + np.random.RandomState(0).rand() * 0.01)
    assert eng.evaluate() == []
    eng.feed("g", 50.0)                       # >> median + 10*MAD
    assert [a.rule for a in eng.evaluate()] == ["grad_norm_spike"]
    eng.feed("g", 1.0)
    assert eng.evaluate() == []               # cleared


def test_rate_collapse_detector():
    eng = health.HealthEngine()
    det = health.RateCollapseDetector(recent_s=5.0, trailing_s=30.0,
                                      frac=0.25, min_trailing=20)
    eng.add_detector(det)
    now = time.time()
    # healthy trailing window: 30 steps, then silence in the recent 5s
    for i in range(30):
        eng.feed("steps", 1.0, ts=now - 35 + i)
    assert [a.rule for a in eng.evaluate(now=now)] == \
        ["throughput_collapse"]
    # traffic back in the recent window -> clears
    for i in range(10):
        eng.feed("steps", 1.0, ts=now - 4 + i * 0.3)
    assert eng.evaluate(now=now) == []


def test_retry_storm_rides_the_registry_emit_path():
    """The counter -> TimeSeries plumbing: increments of the client
    retry counter (labels and all) land on the engine's series without
    any poll loop."""
    eng = health.get_engine()
    fluid.set_flag("observe", True)
    eng.install_default_detectors()
    for i in range(10):
        metrics.counter("pserver_client_retries_total").inc(
            endpoint=f"127.0.0.1:{i}", cmd="push_grad")
    rules = {a.rule for a in eng.evaluate()}
    assert "ps_retry_storm" in rules
    assert len(eng.series("ps_retries")) == 10


def test_recompile_detector_sticky_after_grace():
    from paddle_tpu.observe import steplog
    eng = health.HealthEngine()
    det = health.RecompileDetector(grace_steps=5)
    eng.add_detector(det)
    # warmup era: an unexpected event inside the grace window becomes
    # baseline, not an alert
    steplog.observatory().record(1, "feed_shape", "executor")
    assert eng.evaluate() == []
    for _ in range(10):
        steplog.get_steplog().record(
            steplog.StepStats(1, "executor", time.time(),
                              {"device_compute": 1e-6}),
            emit_metrics=False, emit_trace=False)
    assert eng.evaluate() == []               # no NEW unexpected events
    steplog.observatory().record(1, "feed_shape", "executor")
    assert [a.rule for a in eng.evaluate()] == ["steady_state_recompile"]
    # sticky: stays active even though nothing new happened
    assert [a.rule for a in eng.evaluate()] == ["steady_state_recompile"]


def test_queue_saturation_detector():
    eng = health.HealthEngine()
    eng.add_detector(health.QueueSaturationDetector(frac=0.9))
    metrics.gauge("serve_queue_depth").set(250, model="m")
    metrics.gauge("serve_queue_capacity").set(256, model="m")
    assert [a.rule for a in eng.evaluate()] == ["serve_queue_saturation"]
    metrics.gauge("serve_queue_depth").set(10, model="m")
    assert eng.evaluate() == []


def test_compression_collapse_detector():
    eng = health.HealthEngine()
    det = health.CompressionCollapseDetector(window_s=30.0,
                                             min_bytes=1000.0)
    eng.add_detector(det)
    t0 = time.time()
    eng.feed("wire_raw_bytes", 100_000.0, ts=t0)
    eng.feed("wire_encoded_bytes", 25_000.0, ts=t0)
    assert eng.evaluate(now=t0) == []          # 4x established, healthy
    t1 = t0 + 120                              # old window drained
    eng.feed("wire_raw_bytes", 100_000.0, ts=t1)
    eng.feed("wire_encoded_bytes", 100_000.0, ts=t1)
    assert [a.rule for a in eng.evaluate(now=t1)] == \
        ["wire_compression_collapse"]


def test_clear_alerts_acknowledges_sticky_detectors():
    """The operator remediation path: clear_alerts() must not let the
    SAME old evidence (the NaN still on the ring) re-fire on the next
    evaluate — but a NEW non-finite point is a new incident."""
    eng = health.HealthEngine()
    eng.add_detector(health.NonFiniteDetector(series="s"))
    eng.feed("s", float("nan"))
    assert [a.rule for a in eng.evaluate()] == ["non_finite_loss"]
    eng.clear_alerts()
    assert eng.evaluate() == []               # old NaN acknowledged
    assert eng.evaluate() == []
    time.sleep(0.01)
    eng.feed("s", float("inf"))               # fresh incident
    assert [a.rule for a in eng.evaluate()] == ["non_finite_loss"]
    assert metrics.counter(health.ALERTS_METRIC).value(
        rule="non_finite_loss") == 2


def test_alert_fires_once_per_transition():
    eng = health.HealthEngine()
    eng.add_detector(health.NonFiniteDetector(series="s"))
    eng.feed("s", float("nan"))
    eng.evaluate()
    eng.evaluate()
    eng.evaluate()
    assert metrics.counter(health.ALERTS_METRIC).value(
        rule="non_finite_loss") == 1
    assert len(flight.get_flight().events("alert")) == 1
    assert len(eng.history()) == 1


# ---------------------------------------------------------------------------
# memory observatory
# ---------------------------------------------------------------------------

def _small_train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        pred = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def test_peak_hbm_estimate_within_band_of_cost_model():
    """The documented band (docs/OBSERVABILITY.md §memory): the param
    component EQUALS CostReport.param_bytes (same walk, split by
    optimizer-slot ownership), and the peak estimate sits in
    [1x, 10x] param bytes on a small-batch training program."""
    from paddle_tpu.analysis import cost_model
    main, _, _ = _small_train_program()
    feeds = {"x": (8, 16), "y": (8, 1)}
    rep = cost_model.estimate_cost(main, feeds)
    est = cost_model.estimate_peak_hbm(main, feeds)
    assert est["param_bytes"] + est["optimizer_slot_bytes"] == \
        pytest.approx(rep.param_bytes)
    assert est["grad_bytes"] > 0 and est["activation_bytes"] > 0
    ratio = est["peak_bytes"] / rep.param_bytes
    assert 1.0 <= ratio <= 10.0, ratio


def test_memory_observatory_cpu_degrades_estimate_only_silently():
    obs = memory.get_observatory()
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # ANY warning fails the test
        for _ in range(5):                    # no per-call spam either
            live = obs.live_device_stats()
    assert live is None                       # CPU mesh: no memory stats
    assert obs.live_available() is False
    rep = obs.report()
    assert rep["live"] is False
    assert "devices" not in rep


def test_executor_compile_path_feeds_memory_observatory():
    fluid.set_flag("observe", True)
    main, startup, loss = _small_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
    rng = np.random.RandomState(0)
    prepared.run({"x": rng.randn(8, 16).astype(np.float32),
                  "y": rng.randint(0, 4, (8, 1)).astype(np.int64)})
    obs = memory.get_observatory()
    progs = obs.programs()
    assert progs, "compile path must register estimates while observing"
    assert all(r["peak_bytes"] > 0 for r in progs.values())
    assert obs.segment_peak() >= max(r["peak_bytes"]
                                     for r in progs.values())
    # a per-segment read: drain and start fresh
    peak = obs.segment_peak(reset=True)
    assert peak > 0 and obs.segment_peak() == 0.0
    # re-running the same shapes compiles nothing and adds nothing
    n = len(progs)
    prepared.run({"x": rng.randn(8, 16).astype(np.float32),
                  "y": rng.randint(0, 4, (8, 1)).astype(np.int64)})
    assert len(obs.programs()) == n


def test_flight_snapshot_carries_memory_section():
    fluid.set_flag("observe", True)
    snap = flight.get_flight().snapshot(reason="test")
    assert "memory" in snap
    assert "estimate_peak_bytes" in snap["memory"]


# ---------------------------------------------------------------------------
# exposition hardening details
# ---------------------------------------------------------------------------

def test_parse_prometheus_text_rejects_malformed():
    with pytest.raises(ValueError):
        parse_prometheus_text('bad{unclosed="x} 1\n')
    with pytest.raises(ValueError):
        parse_prometheus_text("name 1 2 3\n")
    with pytest.raises(ValueError):
        parse_prometheus_text("# FROB x y\n")
    # an UNescaped quote inside a label value cannot round-trip
    with pytest.raises(ValueError):
        parse_prometheus_text('m{l="a"b"} 1\n')


def test_prometheus_help_backslash_n_round_trips():
    """An escaped backslash followed by a LITERAL `n` must not come back
    as a newline (sequential-replace unescape would corrupt it)."""
    metrics.counter("pulse_t_help_total", "path C:\\new style").inc()
    doc = parse_prometheus_text(metrics.default_registry().to_prometheus())
    assert doc["pulse_t_help_total"]["help"] == "path C:\\new style"


def test_prometheus_special_float_values():
    metrics.gauge("pulse_t_inf").set(float("-inf"))
    metrics.gauge("pulse_t_nan").set(float("nan"))
    text = metrics.default_registry().to_prometheus()
    assert "pulse_t_inf -Inf" in text
    assert "pulse_t_nan NaN" in text
    doc = parse_prometheus_text(text)
    assert doc["pulse_t_inf"]["samples"][0][2] == float("-inf")
    assert math.isnan(doc["pulse_t_nan"]["samples"][0][2])
