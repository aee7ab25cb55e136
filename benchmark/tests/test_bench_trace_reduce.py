"""trace_reduce on a hand-made event list; every expected number below is
worked out by hand in the comments."""

import trace_reduce as tr
from trace_reduce import Event

OPS = tr.OPS_LINE
D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"

EVENTS = [
    # device 0: fusion.1 [0,100), convolution.2 [50,150) overlaps it,
    # a gap [150,200), all-reduce.3 [200,260), fusion.1 again [260,300)
    Event(D0, OPS, "fusion.1", 0, 100),
    Event(D0, OPS, "convolution.2", 50, 100),
    Event(D0, OPS, "all-reduce.3", 200, 60),
    Event(D0, OPS, "fusion.1", 260, 40),
    # another line of the same plane is not an op track
    Event(D0, "XLA Modules", "jit_step", 0, 300),
    Event(D0, "Steps", "0", 0, 300),
    # device 1: one op [10,110), a gap [110,290), one op [290,300)
    Event(D1, OPS, "fusion.1", 10, 100),
    Event(D1, OPS, "copy.4", 290, 10),
    # host: the benchmark's spans and somebody else's
    Event(HOST, "python3", "bench:dispatch inside exe.run", 140, 30),
    Event(HOST, "python3", "bench:wait in block_until_ready", 170, 130),
    Event(HOST, "python3", "PjitFunction(step)", 141, 20),
]


def test_busy_union_merges_overlaps_and_keeps_gaps():
    merged, busy = tr.busy_union([(0, 100), (50, 150), (200, 260),
                                  (260, 300)])
    assert merged == [[0, 150], [200, 300]]
    assert busy == 250


def test_device_summary_two_devices_a_gap_and_an_overlap():
    s = tr.device_summary(EVENTS)
    assert sorted(s) == [0, 1]
    # device 0: union [0,150) + [200,300) = 250 of a 300 window
    assert s[0]["window_ns"] == 300 and s[0]["busy_ns"] == 250
    assert s[0]["gaps"] == [(150, 200)]
    assert s[0]["n_ops"] == 4          # the Modules and Steps lines are out
    # per-name sums are plain sums: fusion.1 100 + 40
    assert s[0]["by_name"] == {"fusion.1": 140, "convolution.2": 100,
                               "all-reduce.3": 60}
    # device 1: [10,110) + [290,300) = 110 of a 290 window
    assert s[1]["window_ns"] == 290 and s[1]["busy_ns"] == 110
    assert s[1]["gaps"] == [(110, 290)]
    assert tr.busiest(s) == 0
    idle_pct = 100.0 * (1 - s[0]["busy_ns"] / s[0]["window_ns"])
    assert abs(idle_pct - 100.0 / 6) < 1e-9      # 50 of 300


def test_sum_matching_by_pattern():
    by_name = tr.device_summary(EVENTS)[0]["by_name"]
    assert tr.sum_matching(by_name, r"^all-reduce") == (60, ["all-reduce.3"])
    assert tr.sum_matching(by_name, r"^(fusion|convolution)") == (
        240, ["convolution.2", "fusion.1"])
    assert tr.sum_matching(by_name, r"^flash") == (0, [])


def test_gaps_are_named_after_the_host_span_that_covers_most():
    spans = tr.host_spans(EVENTS, "bench:")
    assert [s[2] for s in spans] == ["bench:dispatch inside exe.run",
                                     "bench:wait in block_until_ready"]
    # gap [150,200): dispatch covers [150,170) = 20, wait [170,200) = 30
    assert tr.attribute_gaps([(150, 200)], spans) == [
        ("bench:wait in block_until_ready", 50)]
    # a gap no span touches
    assert tr.attribute_gaps([(400, 450)], spans) == [("between calls", 50)]


def test_no_device_plane_gives_an_empty_summary():
    host_only = [e for e in EVENTS if e.plane == HOST]
    assert tr.device_summary(host_only) == {}
    assert tr.busiest({}) is None
