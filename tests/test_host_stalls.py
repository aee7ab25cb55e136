"""The record of the host's stalls (observe/steplog.py: `Pace`, `is_long`,
`stall_record`, `RecompilationObservatory.stalls()`): every step interval of a
compiled entry that runs long is kept with the part of it the host was in and
what the thread and the process did meanwhile; a steady step keeps nothing.
The clock, the thread's usage and the process's CPU seconds are the tests'
own (`Host`): nothing here sleeps."""

import gc
import json
import logging
import sys
import threading
import types

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.observe import steplog

# the ledger's step (PR 59, nemotron_3_nano_30b_a3b.s2048)
STEP = 0.0609
# a steady step's phases, seconds: 0.35 ms inside run()
PHASES = ((steplog.FEED_CONVERT, 1e-5), (steplog.STATE_GATHER, 2e-5),
          (steplog.JIT_CALL, 3e-4), (steplog.WRITE_BACK, 2e-5))


class Host:
    """What a run samples, as the test sets it."""

    def __init__(self, monkeypatch):
        self.t, self.cpu = 100.0, 1.0
        self.usage = types.SimpleNamespace(
            ru_utime=0.5, ru_stime=0.25, ru_nvcsw=10, ru_nivcsw=3,
            ru_majflt=0, ru_minflt=100)
        monkeypatch.setattr(steplog, "_now", lambda: self.t)
        monkeypatch.setattr(steplog, "_process_cpu", lambda: self.cpu)
        self.samples = 0
        monkeypatch.setattr(steplog, "_usage", self._sample)

    def _sample(self):
        self.samples += 1
        return types.SimpleNamespace(**vars(self.usage))

    def run(self, pace, step, interval=STEP, phases=PHASES, uid=7,
            compiles=False, meanwhile=None):
        """One run() of `phases`, then the caller's time until the next run
        starts `interval` after this one did; `meanwhile`: what the thread
        and the process use up between the two starts."""
        start = self.t
        with steplog.RunSpans(uid, "executor", step, pace) as spans:
            for which, seconds in phases:
                spans.phase(which)
                self.t += seconds
            if compiles:
                spans.keep = True       # what `_taker` does for a compile
        self.t = max(self.t, start + interval)
        if meanwhile:
            self.cpu += meanwhile.pop("cpu", 0.0)
            for key, more in meanwhile.items():
                setattr(self.usage, key, getattr(self.usage, key) + more)

    def steady(self, pace, runs, first=0, **kwargs):
        for step in range(first, first + runs):
            self.run(pace, step, **kwargs)
        return first + runs


@pytest.fixture
def host(monkeypatch):
    return Host(monkeypatch)


def _full(host, pace=None):
    """A pace whose ring has been full once, at `STEP`, and the next step."""
    pace = pace or steplog.Pace()
    step = host.steady(pace, steplog.PACE_RING + 1)
    assert pace.median == pytest.approx(STEP) and pace.at == 0
    return pace, step


def _stalls():
    return observe.observatory().stalls()


@pytest.mark.parametrize("interval, median, long", [
    (0.0609 + 0.0395, 0.0609, True),    # the ledger's one, PR 59
    (0.231 + 0.100, 0.231, True),       # a late return on the longest step
    (0.0500 + 0.100, 0.0500, True),     # and on the shortest
    (0.0609 * 1.002, 0.0609, False),    # steady intervals agree within 0.2%
    (0.231 * 1.2, 0.231, False),        # under a quarter of the median
    (0.0001 + 0.004, 0.0001, False),    # under 5 ms: a step of microseconds
])
def test_the_one_rule_for_long(interval, median, long):
    assert steplog.is_long(interval, median) is long


def _fc_stepper():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(input=x, size=2))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)

    def step(rows=8):
        return exe.run(main, feed={"x": np.ones((rows, 4), np.float32)},
                       fetch_list=[loss], scope=scope, return_numpy=False)

    def pace():
        entry, = [e for k, e in exe._cache.items()
                  if k.program_uid == main._uid]
        return entry.pace
    return step, pace


def test_a_steady_sequence_keeps_nothing_and_leaves_the_phases_as_they_were(
        host):
    step, pace = _fc_stepper()
    step()                      # binds and compiles: the set-up store's
    before = [p.as_dict() for p in observe.observatory().phases()]
    assert before
    for i in range(200):
        host.t += STEP * (1 + 0.001 * (i % 3 - 1))     # within 0.2%
        step()
    assert _stalls() == []
    assert [p.as_dict() for p in observe.observatory().phases()] == before
    assert observe.get_steplog().phase_summary()["steps"] == 0
    assert observe.get_flight().events("stall") == []
    assert pace().median == pytest.approx(STEP, rel=2e-3)


def test_a_long_wait_between_two_runs_is_kept_as_outside_run(host):
    pace, step = _full(host)
    host.run(pace, step, interval=2.1341, meanwhile={
        "ru_nvcsw": 2, "ru_minflt": 12, "ru_utime": 0.003,
        "ru_stime": 0.001, "cpu": 0.005})
    assert _stalls() == []      # an interval ends where the next starts
    host.run(pace, step + 1)
    record, = _stalls()
    assert record["where"] == "outside_run"
    assert (record["program_uid"], record["source"], record["step"]) \
        == (7, "executor", step)
    assert record["end"] - record["start"] == pytest.approx(2.1341)
    assert record["interval_s"] == pytest.approx(2.1341)
    assert record["median_s"] == pytest.approx(STEP)
    parts = record["parts_s"]
    assert sum(parts.values()) == pytest.approx(record["interval_s"])
    assert parts["outside_run"] == pytest.approx(2.1341 - 3.5e-4)
    assert parts["jit_call"] == pytest.approx(3e-4)
    assert parts["other_runs"] == pytest.approx(0.0, abs=1e-9)
    assert "bind" not in parts and "fetch" not in parts
    # the usage from the entry's last sample, one steady step before
    assert record["usage_over_s"] == pytest.approx(STEP + 2.1341)
    assert record["thread_cpu_s"] == pytest.approx(0.004)
    assert record["process_cpu_s"] == pytest.approx(0.005)
    assert (record["voluntary_switches"], record["involuntary_switches"],
            record["major_faults"], record["minor_faults"]) == (2, 0, 0, 12)
    assert record["gc_collections"] == [0, 0, 0] and record["compiles"] == 0
    # the next interval is a steady one again
    host.steady(pace, 5, first=step + 2)
    assert len(_stalls()) == 1


def test_the_usage_is_sampled_every_eighth_run_and_anew_at_a_stall(host):
    pace = steplog.Pace()
    step = host.steady(pace, 5 * steplog.USAGE_EVERY)
    assert host.samples == 5
    step = host.steady(pace, 5, first=step)             # runs 40-44: 6
    host.run(pace, step, interval=1.0, meanwhile={"ru_stime": 0.25})
    assert host.samples == 6
    host.run(pace, step + 1)        # the stall's own sample, kept as the last
    assert host.samples == 7
    record, = _stalls()
    assert record["usage_over_s"] == pytest.approx(5 * STEP + 1.0)
    assert record["thread_cpu_s"] == pytest.approx(0.25)
    host.steady(pace, steplog.USAGE_EVERY - 1, first=step + 2)
    assert host.samples == 7
    host.run(pace, step + 9)
    assert host.samples == 8


def test_a_long_jit_call_names_the_phase(host):
    pace, step = _full(host)
    slow = tuple((w, 0.25 if w is steplog.JIT_CALL else s) for w, s in PHASES)
    host.run(pace, step, interval=0.2505, phases=slow)
    host.run(pace, step + 1)
    record, = _stalls()
    assert record["where"] == "jit_call"
    assert record["parts_s"]["jit_call"] == pytest.approx(0.25)
    assert record["parts_s"]["outside_run"] == pytest.approx(0.0005 - 5e-5)


def test_a_wait_for_the_reader_is_the_runs_entry(host):
    pace, step = _full(host)
    start = host.t
    with steplog.RunSpans(7, "executor", step, pace) as spans:
        host.t += 0.4           # py_reader.next_feed(), before any phase
        spans.phase(steplog.FEED_CONVERT)
    host.t = start + 0.4
    host.run(pace, step + 1)
    record, = _stalls()
    assert record["where"] == "run_entry"
    assert record["parts_s"]["run_entry"] == pytest.approx(0.4)


def test_a_run_of_another_program_between_two_steps_is_counted_apart(host):
    pace, step = _full(host)
    test_pace = steplog.Pace()
    evaluate = ((steplog.FEED_CONVERT, 1e-5), (steplog.JIT_CALL, 1e-4),
                (steplog.FETCH, 0.5))
    # an evaluation of half a second after every fourth training step, the
    # evaluation program's ring full too
    for k in range(steplog.PACE_RING + 4):
        step = host.steady(pace, 3, first=step)
        start = host.t
        host.run(pace, step, interval=0.0)
        host.run(test_pace, k, interval=0.0, phases=evaluate, uid=8)
        host.t = start + STEP + 0.5002
        step += 1
    # its own pace: the cycle less what lay inside the four training runs
    assert test_pace.median == pytest.approx(4 * STEP + 0.5002 - 4 * 3.5e-4)
    assert _stalls() == []
    # a wait beside it is still a stall, and the evaluation is not in it
    start = host.t
    host.run(pace, step, interval=0.0)
    host.run(test_pace, 99, interval=0.0, phases=evaluate, uid=8)
    host.t = start + 0.5001 + 1.0
    host.run(pace, step + 1)
    record, = _stalls()
    assert record["program_uid"] == 7 and record["where"] == "outside_run"
    assert record["parts_s"]["other_runs"] == pytest.approx(0.50011)
    assert record["parts_s"]["outside_run"] == pytest.approx(
        1.0 - 3.5e-4, rel=1e-3)
    assert sum(record["parts_s"].values()) == pytest.approx(1.5001)


def test_runs_that_bind_or_compile_are_neither_kept_nor_in_the_ring(host):
    step, pace = _fc_stepper()
    step()                              # binds, compiles
    host.t += 3.0                       # the first run opens no interval
    for _ in range(steplog.PACE_RING + 1):
        step()
        host.t += STEP
    ring = pace()
    assert ring.median == pytest.approx(STEP) and ring.at == 0
    step(rows=16)                       # jax retraces inside the jitted call
    assert ring.at == 1                 # the interval before it was steady
    host.t += 3.0
    step(rows=16)
    assert ring.at == 1 and ring.prev is not None
    host.t += STEP
    step(rows=16)
    assert ring.at == 2
    assert _stalls() == []


def test_a_run_that_compiles_opens_no_interval(host):
    pace, step = _full(host)
    host.run(pace, step, interval=5.0, compiles=True)
    assert pace.prev is None
    host.run(pace, step + 1)
    assert _stalls() == [] and pace.at == 1


def test_a_run_that_raises_opens_no_interval(host):
    pace, step = _full(host)
    with pytest.raises(ZeroDivisionError):
        with steplog.RunSpans(7, "executor", step, pace):
            1 / 0
    assert pace.prev is None
    host.t += 5.0
    host.steady(pace, 3, first=step + 1)
    assert _stalls() == []


def test_nothing_is_kept_before_the_ring_is_full(host):
    pace = steplog.Pace()
    step = host.steady(pace, 10)
    host.run(pace, step, interval=2.0)
    host.steady(pace, steplog.PACE_RING - 11, first=step + 1)
    assert pace.median is None and _stalls() == []
    # the thirty-second interval fills it; the stall is one of 32 and does
    # not move the median
    host.run(pace, 99)
    assert pace.median == pytest.approx(STEP) and pace.at == 0


def test_a_run_on_another_thread_closes_no_interval(host):
    pace, step = _full(host)
    host.run(pace, step, interval=1.0)
    worker = threading.Thread(target=host.run, args=(pace, step + 1))
    worker.start()
    worker.join(timeout=30)
    assert not worker.is_alive()
    host.run(pace, step + 2)
    assert _stalls() == [] and pace.at == 1     # the steady one before them


def test_a_pace_that_changes_for_good_is_the_median_within_two_rings(host):
    pace, step = _full(host)
    step = host.steady(pace, 2 * steplog.PACE_RING, first=step,
                       interval=2 * STEP)
    assert pace.median == pytest.approx(2 * STEP)
    kept = len(_stalls())
    assert 0 < kept <= 2 * steplog.PACE_RING
    host.steady(pace, 40, first=step, interval=2 * STEP)
    assert len(_stalls()) == kept


def _stall_every_other(host, pace, step, stalls):
    for _ in range(stalls):
        host.run(pace, step, interval=1.0)
        host.run(pace, step + 1)
        step += 2
    return step


def test_the_store_stays_at_64_and_keeps_the_newest(host):
    pace, step = _full(host)
    first = step
    _stall_every_other(host, pace, step, steplog.STALLS_KEPT + 6)
    kept = _stalls()
    assert len(kept) == steplog.STALLS_KEPT == 64
    assert [r["step"] for r in kept] == list(
        range(first + 12, first + 2 * (steplog.STALLS_KEPT + 6), 2))


def test_the_seventeenth_stall_logs_nothing(host, caplog):
    pace, step = _full(host)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.observe"):
        _stall_every_other(host, pace, step, 20)
    lines = [r for r in caplog.records if r.name == "paddle_tpu.observe"]
    assert len(lines) == steplog.STALL_LINES == 16
    assert all(r.levelno == logging.WARNING for r in lines)
    assert len(_stalls()) == 20
    assert [r.getMessage() for r in lines] \
        == [steplog.stall_line(r) for r in _stalls()[:16]]


def test_the_line_holds_every_number(host):
    pace, step = _full(host)
    host.run(pace, step, interval=2.13413, meanwhile={
        "ru_nvcsw": 2, "ru_minflt": 12, "ru_utime": 0.0042, "cpu": 0.005})
    host.run(pace, step + 1)
    assert steplog.stall_line(_stalls()[0]) == (
        f"paddle_tpu: step interval 2134.1 ms at run {step} of program 7 "
        "(median 60.9): 2133.8 outside run(), jit_call 0.3; over 2195.0 ms "
        "thread CPU 4.2 ms, process CPU 5.0 ms; switches +2 voluntary +0 "
        "involuntary; "
        "faults 0 major 12 minor; gc 0+0+0 by generation (0.0 ms); compiles "
        "0, cache hits 0 misses 0")


def test_a_collection_inside_the_interval_shows_in_the_record(host):
    pace, step = _full(host)
    host.run(pace, step, interval=0.0)
    gc.collect()                        # the hook is the interpreter's own
    steplog._process.on_gc("start", {"generation": 1})
    host.t += 0.75
    steplog._process.on_gc("stop", {"generation": 1, "collected": 0,
                                    "uncollectable": 0})
    host.usage.ru_utime += 0.74
    host.run(pace, step + 1)
    record, = _stalls()
    assert record["gc_collections"][2] >= 1
    assert record["gc_collections"][1] >= 1
    assert record["gc_s"][1] == pytest.approx(0.75)
    assert record["thread_cpu_s"] == pytest.approx(0.74)
    assert "by generation (750.0 ms)" in steplog.stall_line(record)


def test_a_compile_between_two_runs_is_counted(host):
    pace, step = _full(host)
    host.run(pace, step, interval=0.0)
    jax.jit(lambda v: v * 17 + 3)(np.ones((3, 7), np.float32))   # the caller's
    host.t += 0.3
    host.run(pace, step + 1)
    record, = _stalls()
    assert record["compiles"] == 1
    assert observe.observatory().events() == []      # and given no cause


def test_the_record_round_trips_through_json_and_the_flight_ring_holds_it(
        host):
    pace, step = _full(host)
    host.run(pace, step, interval=1.0)
    host.run(pace, step + 1)
    doc = observe.observatory().as_dict()
    assert json.loads(json.dumps(doc))["stalls"] == doc["stalls"] == _stalls()
    assert observe.summary()["recompiles"]["stalls"] == _stalls()
    noted, = observe.get_flight().events("stall")
    assert {k: noted[k] for k in doc["stalls"][0]} == doc["stalls"][0]
    observe.observatory().clear()
    assert _stalls() == [] and observe.observatory().as_dict()["stalls"] == []


def test_without_resource_the_record_holds_nones_and_the_run_still_works(
        monkeypatch):
    monkeypatch.setitem(sys.modules, "resource", None)   # its import raises
    assert steplog._thread_usage() is None
    host = Host(monkeypatch)
    monkeypatch.setattr(steplog, "_usage", steplog._thread_usage())
    step, pace = _fc_stepper()
    for _ in range(steplog.PACE_RING + 2):
        step()
        host.t += STEP
    host.t += 1.0
    loss, = step()
    assert np.isfinite(np.asarray(loss)).all()
    record, = _stalls()
    assert record["where"] == "outside_run"
    for key in ("thread_cpu_s", "voluntary_switches", "involuntary_switches",
                "major_faults", "minor_faults"):
        assert record[key] is None
    assert record["process_cpu_s"] == 0.0
    assert "ms thread CPU n/a ms" in steplog.stall_line(record)
    assert "switches n/a voluntary n/a involuntary" in \
        steplog.stall_line(record)
    json.dumps(record)


def test_telemetry_dump_prints_the_kept_stalls(host, capsys):
    sys.path.insert(0, "tools")
    try:
        import telemetry_dump
    finally:
        sys.path.remove("tools")
    pace, step = _full(host)
    host.run(pace, step, interval=1.0)
    host.run(pace, step + 1)
    telemetry_dump.print_stalls(observe.observatory().as_dict())
    out = capsys.readouterr().out
    assert "step intervals that ran long (1 kept):" in out
    assert "  " + steplog.stall_line(_stalls()[0]) in out
    # a dump from an older process has no such key
    telemetry_dump.print_stalls({"counts": {}, "events": [], "phases": []})
    telemetry_dump.print_stalls({"stalls": []})
    assert capsys.readouterr().out == ""
