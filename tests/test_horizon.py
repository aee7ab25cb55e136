"""fluid-horizon: fleet-wide tracing + the scraping observatory.

Pins the round-21 contracts (docs/OBSERVABILITY.md §fluid-horizon):

* trace context rides EVERY control-plane framing — fleet router →
  replica → sparse PSClient → pserver under ONE trace id with correct
  parentage and zero orphans (the e2e tree test), master client ↔
  master service, and the asynchronous replication streams (an update
  record carries the traceparent of the request that CAUSED it, so the
  backup's apply span joins the trainer's trace);
* baggage: bounded str→str annotations that ride the whole trace and
  the wire;
* causal stitching: cross-process flow events, RTT-midpoint clock-skew
  correction with BFS propagation, `trace_tree` queries, and the
  hardened `merge_chrome_traces` failure modes (empty/malformed file,
  strict span-count hard-fail, cross-host pid collisions);
* the observatory: bounded TimeSeriesStore query semantics
  (reset-aware rate, bucket-interpolated percentile, windowed mean),
  the live-pulse scrape loop whose answers must agree with the
  workload's own accounting, and the /trace pulse route;
* metric-catalog discipline: tools/metrics_lint.py as a repo gate
  (every emitted metric documented; stale rows warn-only);
* flight-recorder dump-path hygiene (never the working directory).

The true 3-process fleet drill (subprocess router + replica + pserver,
stitched across real pids) is the slow wrapper at the bottom.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import fleet, observe, serve
from paddle_tpu.master import Master, MasterClient
from paddle_tpu.observe import scrape, stitch, xray
from paddle_tpu.observe.tracer import load_chrome_trace, merge_chrome_traces
from paddle_tpu.pserver import ParameterServer, PSClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def observe_on():
    fluid.set_flag("observe", True)
    observe.get_tracer().clear()
    yield
    fluid.set_flag("observe", False)


# ---------------------------------------------------------------------------
# baggage
# ---------------------------------------------------------------------------

def test_baggage_rides_children_and_wire():
    root = xray.child_of().with_baggage(tenant="t0", kind="infer")
    child = root.child()
    assert child.baggage == {"tenant": "t0", "kind": "infer"}
    # wire round trip keeps identity AND baggage
    back = xray.from_wire(xray.to_wire(child))
    assert back.trace_id == child.trace_id
    assert back.span_id == child.span_id
    assert back.baggage == child.baggage
    # no baggage -> no baggage key on the wire (legacy-identical frames)
    bare = xray.child_of()
    assert set(xray.to_wire(bare)) == {"traceparent"}


def test_baggage_is_bounded_and_stringified():
    bag = {f"k{i}": i for i in range(40)}
    ctx = xray.child_of().with_baggage(**bag)
    wired = xray.from_wire(xray.to_wire(ctx))
    assert len(wired.baggage) <= 16
    assert all(isinstance(v, str) for v in wired.baggage.values())


def test_trace_flag_disarms_spans_and_wire_meta(observe_on):
    """The `trace` kill switch:
    observe stays on, but span creation no-ops and outbound frames go
    legacy-shaped — no ids allocated, nothing recorded."""
    fluid.set_flag("trace", False)
    try:
        assert xray.child_of() is None
        with xray.span("gone", cat="t") as ctx:
            assert ctx is None
        xray.record_span("also_gone", None, 0.0, 1.0)
        assert observe.get_tracer().events() == []
        # an rpc round under trace-off records no spans either side
        m = Master("127.0.0.1:0", timeout_dur=60).start()
        c = MasterClient(m.endpoint)
        try:
            c.set_dataset(["a"], chunks_per_task=1)
        finally:
            c.close()
            m.stop()
        assert not [e for e in observe.get_tracer().events()
                    if e.name.startswith("master_")]
    finally:
        fluid.set_flag("trace", True)
    with xray.span("back", cat="t") as ctx:     # switch flips back live
        assert ctx is not None
    assert [e.name for e in observe.get_tracer().events(cat="t")] \
        == ["back"]


def test_ambient_baggage_accessor():
    assert xray.baggage() == {}
    with xray.activate(xray.child_of().with_baggage(drill="s1")):
        assert xray.baggage("drill") == "s1"
        with xray.span("inner"):           # children inherit
            assert xray.baggage("drill") == "s1"
    assert xray.baggage("drill") is None


# ---------------------------------------------------------------------------
# stitch: edges, skew, flow events, tree queries
# ---------------------------------------------------------------------------

def _ev(pid, name, trace, span, parent=None, ts=0, dur=100):
    args = {"trace_id": trace, "span_id": span}
    if parent:
        args["parent_span_id"] = parent
    return {"ph": "X", "pid": pid, "tid": 1, "name": name,
            "ts": ts, "dur": dur, "cat": "rpc", "args": args}


def test_cross_process_edges_ignore_same_pid_links():
    evs = [
        _ev(1, "call", "t" * 32, "a" * 16),
        _ev(1, "attempt", "t" * 32, "b" * 16, "a" * 16),   # same pid
        _ev(2, "server", "t" * 32, "c" * 16, "b" * 16),    # cross pid
    ]
    edges = stitch.cross_process_edges(evs)
    assert len(edges) == 1
    assert edges[0][0]["name"] == "attempt"
    assert edges[0][1]["name"] == "server"


def test_skew_estimate_recovers_planted_offset():
    # pid 2's clock runs 5000 us AHEAD: its spans appear 5000 us later
    # than truth. The client midpoint (pid 1) vs server midpoint (pid 2)
    # observes exactly -5000.
    tr = "t" * 32
    evs = []
    for i in range(5):
        base = i * 10_000
        evs.append(_ev(1, "client", tr, f"c{i:015d}", ts=base, dur=1000))
        evs.append(_ev(2, "server", tr, f"s{i:015d}", f"c{i:015d}",
                       ts=base + 5000 + 200, dur=600))
    offsets = stitch.estimate_skew_us(evs)
    # pid 1 has as many spans; reference resolves deterministically and
    # the RELATIVE correction is what matters
    rel = offsets.get(2, 0.0) - offsets.get(1, 0.0)
    assert rel == pytest.approx(-5000, abs=1.0)


def test_skew_propagates_transitively_via_bfs(tmp_path):
    # chain 1 -> 2 -> 3: no direct edge between 1 and 3, pid 3's offset
    # must combine both hops (+2000 and +3000 of planted skew)
    tr = "t" * 32
    evs = []
    for i in range(3):
        b = i * 10_000
        evs += [
            _ev(1, "a", tr, f"a{i:015d}", ts=b, dur=1000),
            _ev(2, "b", tr, f"b{i:015d}", f"a{i:015d}",
                ts=b + 2000 + 300, dur=400),
            _ev(2, "c", tr, f"c{i:015d}", ts=b + 2000 + 100, dur=800),
            _ev(3, "d", tr, f"d{i:015d}", f"c{i:015d}",
                ts=b + 2000 + 3000 + 300, dur=200),
        ]
    # make pid 1 the reference (most spans)
    evs.append(_ev(1, "extra", tr, "e" * 16, ts=0, dur=1))
    evs.append(_ev(1, "extra2", tr, "f" * 16, ts=0, dur=1))
    offsets = stitch.estimate_skew_us(evs, reference_pid=1)
    assert offsets[2] == pytest.approx(-2000, abs=150)
    assert offsets[3] == pytest.approx(-5000, abs=300)


def _write_trace(path, events, pname=None):
    evs = list(events)
    if pname:
        evs.insert(0, {"ph": "M", "pid": events[0]["pid"], "tid": 0,
                       "name": "process_name", "args": {"name": pname}})
    with open(path, "w") as f:
        json.dump({"traceEvents": evs}, f)
    return str(path)


def test_stitch_emits_flow_events_and_corrects_skew(tmp_path):
    tr = "t" * 32
    client = [_ev(1, "client", tr, f"c{i:015d}", ts=i * 10_000, dur=1000)
              for i in range(3)]
    server = [_ev(2, "server", tr, f"s{i:015d}", f"c{i:015d}",
                  ts=i * 10_000 + 7000 + 200, dur=600)   # +7ms skew
              for i in range(3)]
    p1 = _write_trace(tmp_path / "a.json", client, "router")
    p2 = _write_trace(tmp_path / "b.json", server, "replica")
    out = str(tmp_path / "stitched.json")
    doc, stats = stitch.stitch_traces([p1, p2], out_path=out)
    assert stats["edges"] == 3 and stats["orphans"] == 0
    assert stats["skew_us"], "skew correction must report the shift"
    flows = [e for e in doc["traceEvents"] if e.get("cat") == "xray_flow"]
    assert len(flows) == 6                        # s+f per edge
    assert {e["ph"] for e in flows} == {"s", "f"}
    # after correction every server span STARTS inside its client span
    spans = {e["args"]["span_id"]: e for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    for i in range(3):
        c, s = spans[f"c{i:015d}"], spans[f"s{i:015d}"]
        assert c["ts"] <= s["ts"] <= c["ts"] + c["dur"]
    # the artifact on disk is the same doc
    assert load_chrome_trace(out)["traceEvents"]


def test_trace_tree_roots_children_orphans():
    tr, other = "t" * 32, "u" * 32
    evs = [
        _ev(1, "root", tr, "a" * 16),
        _ev(1, "mid", tr, "b" * 16, "a" * 16),
        _ev(2, "leaf", tr, "c" * 16, "b" * 16),
        _ev(2, "lost", tr, "d" * 16, "9" * 16),      # parent nowhere
        _ev(3, "foreign", other, "e" * 16),          # different trace
    ]
    tree = stitch.trace_tree(evs, tr)
    assert [e["name"] for e in tree["roots"]] == ["root"]
    assert [e["name"] for e in tree["orphans"]] == ["lost"]
    assert tree["pids"] == {1, 2}
    assert [e["name"] for e in tree["children"]["a" * 16]] == ["mid"]


# ---------------------------------------------------------------------------
# merge_chrome_traces failure modes
# ---------------------------------------------------------------------------

def test_merge_empty_file_raises_value_error_naming_file(tmp_path):
    good = _write_trace(tmp_path / "ok.json",
                        [_ev(1, "a", "t" * 32, "a" * 16)])
    empty = tmp_path / "empty.json"
    empty.write_text("")
    with pytest.raises(ValueError, match="empty.json"):
        merge_chrome_traces([good, str(empty)])


def test_merge_malformed_json_raises_value_error(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(ValueError, match="bad.json"):
        merge_chrome_traces([str(bad)])


def test_merge_doc_without_trace_events_raises(tmp_path):
    bad = tmp_path / "noevents.json"
    bad.write_text(json.dumps({"displayTimeUnit": "ms"}))
    with pytest.raises(ValueError, match="noevents.json"):
        merge_chrome_traces([str(bad)])


def test_merge_strict_hard_fails_on_span_count_mismatch(tmp_path,
                                                        monkeypatch):
    """The spans_out gate exists to catch a FUTURE merge change that
    silently filters events; simulate one with a loader whose events
    list shrinks after the counting pass."""
    from paddle_tpu.observe import tracer as tracer_mod

    class _Shrinking(list):
        def __init__(self, events):
            super().__init__(events)
            self._iters = 0
            self._all = list(events)

        def __iter__(self):
            self._iters += 1
            if self._iters >= 3:     # count pass, pname pass, transform
                return iter(self._all[:-1])
            return iter(self._all)

    events = [_ev(1, "a", "t" * 32, "a" * 16),
              _ev(1, "b", "t" * 32, "b" * 16)]
    monkeypatch.setattr(
        tracer_mod, "load_chrome_trace",
        lambda path: {"traceEvents": _Shrinking(events)})
    with pytest.raises(RuntimeError, match="merge dropped spans"):
        merge_chrome_traces(["whatever.json"], strict=True)
    # non-strict: same drop is only reported via stats
    _doc, stats = merge_chrome_traces(["whatever.json"], strict=False)
    assert stats["spans_out"] == stats["spans_in"] - 1


def test_merge_remaps_pid_collision_across_hosts(tmp_path):
    """Two HOSTS can legitimately hand the merge the same pid; distinct
    process names force a synthetic-pid remap with zero span loss."""
    tr = "t" * 32
    a = _write_trace(tmp_path / "h1.json",
                     [_ev(4242, "a", tr, "a" * 16)], pname="host1/router")
    b = _write_trace(tmp_path / "h2.json",
                     [_ev(4242, "b", tr, "b" * 16, "a" * 16)],
                     pname="host2/pserver")
    doc, stats = merge_chrome_traces([a, b], strict=True)
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert stats["spans_in"] == stats["spans_out"] == 2
    pids = {e["pid"] for e in spans}
    assert len(pids) == 2, "colliding pids must be remapped apart"
    # and the stitcher still links them causally via span ids
    assert len(stitch.cross_process_edges(spans)) == 1


# ---------------------------------------------------------------------------
# observatory store: query semantics
# ---------------------------------------------------------------------------

def test_store_latest_aggregates_and_empty_is_none():
    s = scrape.TimeSeriesStore()
    s.add("g", {"job": "a"}, 3.0, ts=100.0)
    s.add("g", {"job": "b"}, 5.0, ts=100.0)
    assert s.latest("g", agg="sum") == 8.0
    assert s.latest("g", agg="max") == 5.0
    assert s.latest("g", match={"job": "a"}, agg="sum") == 3.0
    assert s.latest("missing", agg="sum") is None    # no data != 0


def test_store_increase_and_rate_are_reset_aware():
    s = scrape.TimeSeriesStore()
    now = 1000.0
    for ts, v in ((now - 30, 10.0), (now - 20, 25.0), (now - 10, 4.0),
                  (now - 5, 9.0)):
        s.add("c_total", {"job": "a"}, v, ts=ts)
    # 10->25 (+15), restart to 4 (+4 post-reset), 4->9 (+5)
    assert s.increase("c_total", window_s=60, now=now) == 24.0
    # rate divides by the OBSERVED span (25 s), not the asked window
    assert s.rate("c_total", window_s=60, now=now) == \
        pytest.approx(24.0 / 25.0)


def test_store_rate_clamps_to_window_and_sums_series():
    s = scrape.TimeSeriesStore()
    now = 1000.0
    for ts in range(0, 100, 10):
        s.add("c_total", {"job": "a"}, float(ts), ts=now - 95 + ts)
        s.add("c_total", {"job": "b"}, float(ts * 2), ts=now - 95 + ts)
    r = s.rate("c_total", window_s=30.0, now=now)
    # within the last 30 s both series tick 1/s and 2/s
    assert r == pytest.approx(3.0, rel=0.25)


def test_store_percentile_interpolates_bucket_increases():
    s = scrape.TimeSeriesStore()
    now = time.time()        # percentile windows against the real clock
    # 100 events: 50 land <= 10, 90 <= 100, all <= +Inf
    for le, v0, v1 in (("10", 0, 50), ("100", 0, 90), ("+Inf", 0, 100)):
        s.add("lat_us_bucket", {"le": le, "job": "a"}, v0, ts=now - 20)
        s.add("lat_us_bucket", {"le": le, "job": "a"}, v1, ts=now - 1)
    p50 = s.percentile("lat_us", 0.50, window_s=60)
    p99 = s.percentile("lat_us", 0.99, window_s=60)
    assert p50 == pytest.approx(10.0, rel=0.05)       # exactly at bound
    assert 100.0 <= p99 <= 100.0 + 1e-6               # clamped to last
    assert s.percentile("lat_us", 0.5, window_s=0.25) is None  # no events


def test_store_mean_from_sum_and_count():
    s = scrape.TimeSeriesStore()
    now = time.time()        # mean windows against the real clock
    s.add("h_count", {"job": "a"}, 10.0, ts=now - 20)
    s.add("h_count", {"job": "a"}, 30.0, ts=now - 1)
    s.add("h_sum", {"job": "a"}, 100.0, ts=now - 20)
    s.add("h_sum", {"job": "a"}, 500.0, ts=now - 1)
    assert s.mean("h", window_s=60) == pytest.approx(20.0)
    assert s.mean("missing") is None


def test_store_bounds_points_and_sheds_series():
    s = scrape.TimeSeriesStore(max_points=5, max_series=2)
    for i in range(10):
        s.add("a", {"i": "0"}, float(i), ts=float(i))
    assert len(s.series("a")[0][1]) == 5              # ring per series
    s.add("b", {"i": "1"}, 1.0, ts=0.0)
    s.add("c", {"i": "2"}, 1.0, ts=0.0)               # past max_series
    assert len(s) == 2
    assert s.dropped_series() == 1


# ---------------------------------------------------------------------------
# observatory: live scrape against a real pulse endpoint
# ---------------------------------------------------------------------------

def test_live_scrape_matches_workload_accounting(observe_on):
    port = observe.start_pulse(0)
    try:
        c = observe.counter("serve_requests_total", "t")
        h = observe.histogram("serve_request_latency_us", "t")
        sc = scrape.Scraper([("replica0", port)], interval_s=0.05)
        n_first, n_total = 40, 100
        for _ in range(n_first):
            c.inc(model="m", outcome="ok")
            h.observe(500.0, model="m")
        t0 = time.time()
        sc.poll_once()
        for _ in range(n_total - n_first):
            c.inc(model="m", outcome="ok")
            h.observe(1500.0, model="m")
        time.sleep(0.25)
        # a round is stamped when it starts: the store's span runs from
        # the first poll's start to the second's, not to its end (a fetch
        # takes tens of ms beside five busy workers)
        elapsed = time.time() - t0
        sc.poll_once()

        inc = sc.store.increase("serve_requests_total", window_s=60)
        assert inc == n_total - n_first
        want_rate = (n_total - n_first) / elapsed
        got_rate = sc.store.rate("serve_requests_total", window_s=60)
        assert got_rate == pytest.approx(want_rate, rel=0.10)
        # percentile over the window's bucket increases: all 60 post-
        # baseline samples were 1500 us -> p99 lands in 1500's bucket
        p99 = sc.store.percentile("serve_request_latency_us", 0.99,
                                  window_s=60)
        assert p99 is not None and 1000.0 <= p99 <= 10_000.0
        up = sc.store.latest(scrape.UP_SERIES, agg="sum")
        assert up == 1.0
        ov = sc.fleet_overview(window_s=60)
        assert ov["targets"] == 1 and ov["targets_up"] == 1
        assert ov["serve_qps"] == pytest.approx(want_rate, rel=0.10)
        snap = sc.snapshot(window_s=60)
        assert "serve_requests_total" in snap["series"]
    finally:
        observe.stop_pulse()


def test_scrape_dead_target_scores_up_zero_and_never_raises():
    sc = scrape.Scraper([("ghost", "127.0.0.1:1")], timeout_s=0.2)
    res = sc.poll_once()
    (info,) = res.values()
    assert not info["ok"] and info["error"]
    assert sc.store.latest(scrape.UP_SERIES, agg="sum") == 0.0
    ov = sc.fleet_overview()
    assert ov["targets_up"] == 0


def test_scrape_loop_thread_has_guard_and_stops(observe_on):
    port = observe.start_pulse(0)
    try:
        sc = scrape.Scraper([("p", port)], interval_s=0.02).start()
        deadline = time.time() + 5
        while sc.rounds() < 2:
            assert time.time() < deadline
            time.sleep(0.01)
        sc.stop()
        r = sc.rounds()
        time.sleep(0.1)
        assert sc.rounds() == r, "poll loop must stop with stop()"
    finally:
        observe.stop_pulse()


def test_pulse_trace_route_serves_the_ring(observe_on):
    port = observe.start_pulse(0)
    try:
        with xray.span("horizon_probe", cat="t"):
            pass
        doc = scrape.fetch_trace(port)
        names = {e.get("name") for e in doc["traceEvents"]}
        assert "horizon_probe" in names
    finally:
        observe.stop_pulse()


# ---------------------------------------------------------------------------
# e2e: one fleet infer = one causally-complete trace
# ---------------------------------------------------------------------------

def _build_mlp_dir(dirname):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        pred = fluid.layers.fc(input=x, size=8, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    fluid.io.save_inference_model(dirname, ["x"], [pred], exe,
                                  main_program=main, scope=scope)


F, NVOCAB, K, D = 4, 300, 6, 3


def _build_deepfm_sparse_dir(dirname, eps):
    from paddle_tpu.models import deepfm
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 5
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _feeds, outs = deepfm.build(num_fields=F, sparse_feature_dim=NVOCAB,
                                    embedding_size=K, dense_dim=D,
                                    hidden_sizes=(8, 8), distributed=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    fleet.save_sparse_inference_model(
        dirname, ["dense_input", "sparse_input"], [outs["predict"]], exe,
        main_program=main, scope=scope, cap=64)


def test_fleet_infer_traces_end_to_end_through_pserver(tmp_path,
                                                       observe_on):
    """THE round-21 pin: one fleet `infer` = ONE trace id whose span
    tree runs router -> wire call -> replica -> serving batch -> sparse
    PSClient -> pserver handler with correct parentage and zero
    orphans. In-process here (every hop still crosses a real TCP frame
    + thread boundary); the 3-process version is the slow drill."""
    servers = [ParameterServer("127.0.0.1:0").start() for _ in range(2)]
    eps = [s.endpoint for s in servers]
    client = PSClient(eps)
    for wname, width in (("fm_v", K), ("fm_w", 1)):
        client.init_table(wname, NVOCAB, width, "float32", -0.05, 0.05,
                          seed=1337, opt_type="sgd", lr=0.1, attrs={})
    router = None
    srv = None
    try:
        d = os.path.join(str(tmp_path), "dfm")
        _build_deepfm_sparse_dir(d, eps)
        srv = serve.InferenceServer(
            fluid.CPUPlace(), serve.ServeConfig(batch_timeout_ms=1.0))
        srv.add_model("dfm", d, ladder=serve.BucketLadder(rows=(1, 2)),
                      sparse=fleet.SparseServeConfig(eps, cache_rows=512))
        rep = fleet.ReplicaServer(srv, replica_id="r0")
        router = fleet.FleetRouter(fleet.RouterConfig(
            lease_s=2.0, poll_interval_s=0.1)).start()
        rep.router_endpoint = None
        rep.start()
        router.add_replica(rep.endpoint, replica_id="r0")
        deadline = time.time() + 20
        while not router.ready_members("dfm"):
            assert time.time() < deadline, router.members()
            time.sleep(0.05)

        observe.get_tracer().clear()    # drop warmup/init spans
        rng = np.random.RandomState(3)
        feed = {"dense_input": rng.randn(2, D).astype(np.float32),
                "sparse_input": rng.randint(
                    10, NVOCAB, size=(2, F)).astype(np.int64)}
        res = router.infer("dfm", feed)
        assert res.outs is not None

        events = observe.get_tracer().chrome_events()
        roots = [e for e in events
                 if e.get("ph") == "X" and e["name"] == "fleet:infer"]
        assert len(roots) == 1
        trace_id = roots[0]["args"]["trace_id"]
        tree = stitch.trace_tree(events, trace_id)
        assert len(tree["roots"]) == 1
        assert tree["orphans"] == [], \
            [e["name"] for e in tree["orphans"]]
        names = {e["name"] for e in tree["spans"].values()}
        # the full causal chain, each hop present IN THIS ONE TRACE
        for want in ("fleet:infer", "fleet_call:infer", "replica:infer",
                     "serve_request", "serve_batch",
                     "ps_call:prefetch", "rpc_client:prefetch",
                     "rpc_server:prefetch"):
            assert want in names, f"missing {want}: {sorted(names)}"

        # parentage edges of the backbone
        by_name = {}
        for e in tree["spans"].values():
            by_name.setdefault(e["name"], e)

        def parent_of(name):
            pid_ = by_name[name]["args"].get("parent_span_id")
            return tree["spans"].get(pid_, {}).get("name")

        assert parent_of("fleet_call:infer") == "fleet:infer"
        assert parent_of("replica:infer") == "fleet_call:infer"
        assert parent_of("rpc_server:prefetch") == "rpc_client:prefetch"
        # every span of the trace shares the one trace id (tree is
        # already filtered; pin the count is plural and multi-hop)
        assert len(tree["spans"]) >= 8
    finally:
        if router is not None:
            router.close()
        if srv is not None:
            srv.close()
        client.close()
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# master client <-> service propagation
# ---------------------------------------------------------------------------

def test_master_rpc_spans_share_trace_and_parentage(observe_on):
    m = Master("127.0.0.1:0", timeout_dur=60).start()
    c = MasterClient(m.endpoint)
    try:
        with xray.span("trainer_bootstrap", cat="t") as root:
            c.set_dataset(["a", "b"], chunks_per_task=1)
    finally:
        c.close()
        m.stop()
    evs = {e.name: e for e in observe.get_tracer().events()}
    cl = evs["master_client:set_dataset"]
    sv = evs["master_server:set_dataset"]
    assert cl.args["trace_id"] == sv.args["trace_id"] == root.trace_id
    assert cl.args["parent_span_id"] == root.span_id
    assert sv.args["parent_span_id"] == cl.args["span_id"]
    assert cl.args["status"] == "ok"


def test_master_rpc_without_observe_sends_legacy_frames():
    fluid.set_flag("observe", False)
    m = Master("127.0.0.1:0", timeout_dur=60).start()
    c = MasterClient(m.endpoint)
    try:
        c.set_dataset(["a"], chunks_per_task=1)
        status, task = c.get_task()
        assert status == "ok" and task["task_id"] is not None
    finally:
        c.close()
        m.stop()
    assert not [e for e in observe.get_tracer().events()
                if e.name.startswith("master_")]


# ---------------------------------------------------------------------------
# replication streams: the apply span parents under the CAUSING request
# ---------------------------------------------------------------------------

def test_haven_backup_apply_span_joins_the_pusher_trace(observe_on):
    backup = ParameterServer("127.0.0.1:0").start()
    backup.start_standby(lease_s=0.6)
    primary = ParameterServer("127.0.0.1:0").start()
    primary.start_replication(backup.endpoint, lease_s=0.6)
    client = PSClient([primary.endpoint])
    try:
        # let the fresh pair finish its first full sync FIRST — a record
        # cut into the initial snapshot ships as state, not a replayed
        # log record, and would never earn an apply span
        deadline = time.time() + 10
        while primary._haven.log.lag() > 0:
            assert time.time() < deadline, "initial sync never drained"
            time.sleep(0.02)
        with xray.span("trainer_push", cat="t") as root:
            client.init_param(primary.endpoint, "w",
                              np.ones(4, np.float32), "sgd", 0.1, {})
        while not any(e.name == "haven_apply:init_param"
                      for e in observe.get_tracer().events(cat="ha")):
            assert time.time() < deadline, "replication never drained"
            time.sleep(0.02)
    finally:
        client.close()
        primary.stop()
        backup.stop()
    evs = [e for e in observe.get_tracer().events()
           if e.args.get("trace_id") == root.trace_id]
    by_name = {e.name: e for e in evs}
    assert "rpc_server:init_param" in by_name
    apply_ev = by_name.get("haven_apply:init_param")
    assert apply_ev is not None, sorted(by_name)
    # the backup's apply span parents under the PRIMARY'S handler span —
    # the request that caused the record, across the async stream
    assert apply_ev.args["parent_span_id"] == \
        by_name["rpc_server:init_param"].args["span_id"]


def test_update_log_batch_carries_trace_and_tolerates_legacy():
    log = fluid.haven.UpdateLog(window=8) if hasattr(fluid, "haven") \
        else __import__("paddle_tpu.haven",
                        fromlist=["UpdateLog"]).UpdateLog(window=8)
    log.rebase()   # fresh pair synced at seq 0: records are retained
    log.append("push_grad", {"name": "w"}, trace="00-" + "a" * 32 +
               "-" + "b" * 16 + "-01")
    log.append("push_grad", {"name": "v"})          # untraced
    recs = log.batch()
    assert [tr for _s, _c, _p, tr in recs] == \
        ["00-" + "a" * 32 + "-" + "b" * 16 + "-01", None]
    # legacy 3-tuple records replay fine (the *rest unpack contract)
    for seq, cmd, payload, *rest in [(1, "x", {}), (2, "y", {}, "tp")]:
        assert (rest[0] if rest else None) in (None, "tp")


# ---------------------------------------------------------------------------
# metrics-catalog lint: repo gate + behavior fixture
# ---------------------------------------------------------------------------

def test_metrics_catalog_gate_repo_is_clean():
    """Every metric the codebase can emit has a catalog row in
    docs/OBSERVABILITY.md (the race_lint-style repo gate)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_lint.py")],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 missing" in out.stdout


def test_metrics_lint_fails_on_undocumented_and_warns_on_stale(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'counter("documented_total", "h").inc()\n'
        'gauge(\n    "rogue_gauge", "h").set(1)\n'
        'MY_METRIC = "const_total"\n')
    doc = tmp_path / "OBS.md"
    doc.write_text("# x\n\n## Metric catalog\n\n"
                   "| metric | kind | source | what |\n|---|---|---|---|\n"
                   "| `documented_total` | counter | mod.py | d |\n"
                   "| `const_total` | counter | mod.py | d |\n"
                   "| `ghost_total` | counter | gone.py | stale |\n")
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metrics_lint", os.path.join(REPO, "tools", "metrics_lint.py"))
    ml = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ml)
    # undocumented rogue_gauge -> fail
    assert ml.main(["--pkg", str(pkg), "--doc", str(doc)]) == 1
    # document it -> stale ghost_total only warns
    doc.write_text(doc.read_text() +
                   "| `rogue_gauge` | gauge | mod.py | d |\n")
    assert ml.main(["--pkg", str(pkg), "--doc", str(doc)]) == 0
    assert ml.main(["--pkg", str(pkg), "--doc", str(doc),
                    "--strict"]) == 1


# ---------------------------------------------------------------------------
# flight-recorder dump-path hygiene
# ---------------------------------------------------------------------------

def test_flight_default_dump_path_never_cwd(monkeypatch, tmp_path):
    from paddle_tpu.observe import flight

    monkeypatch.delenv(flight.DUMP_PATH_ENV, raising=False)
    p = flight.default_dump_path()
    assert os.path.isabs(p)
    assert os.path.dirname(p) != os.getcwd()
    assert f"flight_recorder.{os.getpid()}" in os.path.basename(p)
    # env override wins
    want = str(tmp_path / "fr.json")
    monkeypatch.setenv(flight.DUMP_PATH_ENV, want)
    assert flight.default_dump_path() == want
    flight.note("probe", k=1)
    out = flight.dump(reason="test")
    assert out == want and os.path.exists(want)
    with open(want) as f:
        assert json.load(f)["reason"] == "test"


# ---------------------------------------------------------------------------
# observatory CLI plumbing
# ---------------------------------------------------------------------------

def test_observatory_cli_parse_targets_and_json(observe_on, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import observatory
    finally:
        sys.path.pop(0)
    ts = observatory.parse_targets(["r0=8471", "9000", "ps=h:1"])
    assert ts == [("r0", "8471"), ("target1", "9000"), ("ps", "h:1")]
    with pytest.raises(SystemExit):
        observatory.parse_targets([])

    port = observe.start_pulse(0)
    try:
        observe.counter("serve_requests_total", "t").inc()
        rc = observatory.main([f"replica0={port}", "--rounds", "1",
                               "--interval", "0.01", "--json"])
        assert rc == 0
        snap = json.loads(capsys.readouterr().out)
        assert snap["overview"]["targets_up"] == 1
        assert "serve_requests_total" in snap["series"]
    finally:
        observe.stop_pulse()


def test_observatory_cli_dump_trace_stitches_live_rings(observe_on,
                                                        tmp_path,
                                                        capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import observatory
    finally:
        sys.path.pop(0)
    port = observe.start_pulse(0)
    try:
        with xray.span("cli_probe", cat="t"):
            pass
        out = str(tmp_path / "fleet.json")
        rc = observatory.main([f"p0={port}", "--dump-trace", out])
        assert rc == 0
        doc = load_chrome_trace(out)
        assert any(e.get("name") == "cli_probe"
                   for e in doc["traceEvents"])
    finally:
        observe.stop_pulse()


# ---------------------------------------------------------------------------
# slow: the REAL 3-process fleet trace drill
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_three_process_fleet_trace_stitches_across_pids(tmp_path):
    """Router (this process) + replica subprocess + pserver subprocess:
    the stitched capture must hold ONE trace spanning >= 3 real pids
    with causal flow edges and zero orphans — the ISSUE's acceptance
    drill."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ps_out = str(tmp_path / "ps_out")
    ps_trace = os.path.join(ps_out, "trace_pserver0.json")
    rep_trace = str(tmp_path / "trace_rep.json")

    ps_proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "ps_worker.py"),
         "--name", "pserver0", "--out", ps_out],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        line = (ps_proc.stdout.readline() or "").strip()
        assert line.startswith("ENDPOINT "), line
        ep = line.split()[1]

        fluid.set_flag("observe", True)
        xray.set_process_name("router0")
        client = PSClient([ep])
        for wname, width in (("fm_v", K), ("fm_w", 1)):
            client.init_table(wname, NVOCAB, width, "float32",
                              -0.05, 0.05, seed=1337, opt_type="sgd",
                              lr=0.1, attrs={})
        d = os.path.join(str(tmp_path), "dfm")
        _build_deepfm_sparse_dir(d, [ep])
        client.close()

        router = fleet.FleetRouter(fleet.RouterConfig(
            lease_s=3.0, poll_interval_s=0.2)).start()
        rep_proc = subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "tools", "fleet_replica.py"),
             "--model-dir", d, "--name", "dfm", "--replica-id", "r0",
             "--router", router.control_endpoint,
             "--buckets", "1,2", "--sparse-endpoints", ep,
             "--sparse-cache-rows", "512", "--trace-out", rep_trace],
            stdout=subprocess.PIPE, text=True, env=env)
        try:
            for line in rep_proc.stdout:
                if line.startswith("READY"):
                    break
            deadline = time.time() + 60
            while not router.ready_members("dfm"):
                assert time.time() < deadline, router.members()
                time.sleep(0.1)

            observe.get_tracer().clear()
            rng = np.random.RandomState(3)
            feed = {"dense_input": rng.randn(2, D).astype(np.float32),
                    "sparse_input": rng.randint(
                        10, NVOCAB, size=(2, F)).astype(np.int64)}
            res = router.infer("dfm", feed)
            assert res.outs is not None
            router_trace = str(tmp_path / "trace_router.json")
            observe.get_tracer().export_chrome(router_trace)
        finally:
            rep_proc.terminate()
            rep_proc.wait(timeout=30)
            router.close()
    finally:
        ps_proc.terminate()
        ps_proc.wait(timeout=30)

    _doc, stats = stitch.stitch_traces(
        [router_trace, rep_trace, ps_trace],
        out_path=str(tmp_path / "stitched.json"), strict=True)
    events = _doc["traceEvents"]
    roots = [e for e in events
             if e.get("ph") == "X" and e.get("name") == "fleet:infer"]
    assert len(roots) == 1
    tree = stitch.trace_tree(events, roots[0]["args"]["trace_id"])
    assert len(tree["pids"]) >= 3, tree["pids"]
    assert tree["orphans"] == [], \
        [e["name"] for e in tree["orphans"]]
    assert stats["edges"] >= 2, stats
    names = {e["name"] for e in tree["spans"].values()}
    assert {"fleet:infer", "replica:infer",
            "rpc_server:prefetch"} <= names, sorted(names)
