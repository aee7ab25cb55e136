"""Set-up from the inside (observe/steplog.py::Phase): the body of a
`program_guard` and `minimize` as phases on `time.perf_counter()`, the first
runs' intervals kept at default flags, compiles outside the jitted call
counted where they fell, and nothing at all from a steady step."""

import glob
import json
import time

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.core import registry
from paddle_tpu.observe import steplog

RUN_CHILDREN = ["paddle_tpu:feed_convert", "paddle_tpu:bind",
                "paddle_tpu:state_gather", "paddle_tpu:jit_call",
                "paddle_tpu:write_back", "paddle_tpu:fetch"]


def _build(opt=True):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(input=x, size=2))
        if opt:
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _phases(name=None, uid=None):
    return [p for p in observe.observatory().phases()
            if (name is None or p.name == name)
            and (uid is None or p.program_uid == uid)]


def _started(main, startup, loss):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return lambda feed, rn=False: exe.run(
        main, feed=feed, fetch_list=[loss], scope=scope, return_numpy=rn)


def test_program_guard_leaves_one_build_phase_of_the_main_program():
    t0 = time.perf_counter()
    main, startup, _ = _build()
    t1 = time.perf_counter()
    build, = _phases(steplog.PROGRAM_BUILD)
    assert build.program_uid == main._uid and build.parent is None
    assert t0 <= build.start <= build.end <= t1     # the one clock
    d = build.detail
    assert d["startup_uid"] == startup._uid
    assert d["main"] == {
        "ops": len(main.global_block().ops),
        "variables": len(main.global_block().vars),
        "parameters": len(main.global_block().all_parameters())}
    assert d["startup"]["ops"] == len(startup.global_block().ops)
    assert d["main"]["parameters"] == d["startup"]["parameters"] == 2


def test_minimize_is_nested_in_the_build_by_parent():
    main, _, _ = _build()
    build, = _phases(steplog.PROGRAM_BUILD)
    minimize, = _phases(steplog.MINIMIZE)
    assert minimize.parent == build.id and minimize.program_uid == main._uid
    assert build.start <= minimize.start <= minimize.end <= build.end
    # outside a guard it is a phase of its own, of the loss's program
    loss = layers.mean(layers.fc(
        input=layers.data(name="x", shape=[4], dtype="float32"), size=2))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    alone = _phases(steplog.MINIMIZE)[-1]
    assert alone.parent is None
    assert alone.program_uid == fluid.default_main_program()._uid


def test_infer_shapes_are_clocked_on_the_open_build(monkeypatch):
    calls = []
    inner = registry.infer_op_shapes

    def counting(*args, **kwargs):
        calls.append(args[0])
        return inner(*args, **kwargs)

    monkeypatch.setattr(registry, "infer_op_shapes", counting)
    main, _, _ = _build()
    build, = _phases(steplog.PROGRAM_BUILD)
    assert build.detail["infer_shapes_calls"] == len(calls) > 0
    # grad ops are written by append_backward without inferring
    assert not [c for c in calls if c.endswith("_grad")]
    assert any(op.type.endswith("_grad") for op in main.global_block().ops)
    assert 0 < build.detail["infer_shapes_s"] <= build.end - build.start
    # minimize holds its own share, if its ops infer at all
    minimize, = _phases(steplog.MINIMIZE)
    assert minimize.detail.get("infer_shapes_calls", 0) < len(calls)
    # with no phase open the call is not clocked
    n = len(observe.observatory().phases())
    registry.infer_op_shapes("relu", {}, {"X": [((3, 4), "float32")]})
    assert len(observe.observatory().phases()) == n
    assert build.detail["infer_shapes_calls"] == len(calls) - 1


def test_nested_guards_nest_their_builds():
    outer_main, inner_main = fluid.Program(), fluid.Program()
    with fluid.program_guard(outer_main, fluid.Program()):
        with fluid.program_guard(inner_main, fluid.Program()):
            layers.fc(input=layers.data(name="x", shape=[4],
                                        dtype="float32"), size=2)
    inner, = _phases(steplog.PROGRAM_BUILD, inner_main._uid)
    outer, = _phases(steplog.PROGRAM_BUILD, outer_main._uid)
    assert inner.parent == outer.id
    # a call is summed on every open phase
    assert inner.detail["infer_shapes_calls"] \
        == outer.detail["infer_shapes_calls"] > 0
    assert steplog.open_phase() is None


def test_a_first_run_leaves_its_run_and_phases():
    main, startup, loss = _build()
    step = _started(main, startup, loss)
    t0 = time.perf_counter()
    step({"x": np.ones((8, 4), np.float32)}, True)
    t1 = time.perf_counter()
    for uid, fed in ((startup._uid, False), (main._uid, True)):
        run, = _phases(steplog.RUN, uid)
        assert run.detail["source"] == "executor" and run.detail["step"] == 0
        assert run.parent is None
        event = observe.observatory().latest(uid)
        assert run.event is event and event.cause == "first_call"
        kids = sorted((p for p in _phases(uid=uid) if p.parent == run.id),
                      key=lambda p: p.start)
        assert [k.name for k in kids] == RUN_CHILDREN
        # leaves that tile the run from its first phase to its end
        assert run.start <= kids[0].start and kids[-1].end == run.end
        assert all(a.end == b.start for a, b in zip(kids, kids[1:]))
        # every compile stage of the step lies inside the jitted call
        jit = kids[RUN_CHILDREN.index("paddle_tpu:jit_call")]
        stages = event.stage_intervals()
        assert {"trace", "lower", "backend"} <= set(stages)
        for spans in stages.values():
            assert all(jit.start <= s <= e <= jit.end for s, e in spans)
    main_run, = _phases(steplog.RUN, main._uid)
    assert t0 <= main_run.start <= main_run.end <= t1


def test_a_steady_step_leaves_nothing():
    main, startup, loss = _build()
    step = _started(main, startup, loss)
    feed = {"x": np.ones((8, 4), np.float32)}
    step(feed)
    step(feed)
    store = observe.observatory()
    n, events = len(store.phases()), len(store.events())
    for _ in range(1000):
        step(feed)
    assert len(store.phases()) == n and len(store.events()) == events
    assert observe.get_steplog().phase_summary()["steps"] == 0
    assert len(observe.get_tracer()) == 0       # no second store of spans


def test_a_later_compile_keeps_that_run_on_the_entrys_event():
    main, startup, loss = _build()
    step = _started(main, startup, loss)
    step({"x": np.ones((8, 4), np.float32)})
    # at default flags the executor sees no new shape: jax compiles again
    # inside the jitted call, and the run that paid is kept
    step({"x": np.ones((16, 4), np.float32)})
    first, second = _phases(steplog.RUN, main._uid)
    assert first.event is second.event
    assert second.detail["step"] == 1 and second.eager is None
    assert second.event.as_dict()["backend_compiles"] == 2
    names = [p.name for p in _phases(uid=main._uid) if p.parent == second.id]
    assert "paddle_tpu:bind" not in names and "paddle_tpu:jit_call" in names


def test_a_compile_outside_the_jitted_call_is_counted_not_attributed():
    event = observe.observatory().record(99, "first_call", "executor", {})
    with steplog.RunSpans(99, "executor", 0) as spans:
        spans.event = event
        spans.phase(steplog.FEED_CONVERT)
        jax.jit(lambda v: v * 3 + 1)(np.ones((3, 5), np.float32))     # host code
        spans.phase(steplog.JIT_CALL)
        jax.jit(lambda v: v * 5 + 2)(np.ones((3, 5), np.float32))     # "the step"
        spans.phase(steplog.WRITE_BACK)
        jax.jit(lambda v: v * 7 + 3)(np.ones((3, 5), np.float32))
    run, = _phases(steplog.RUN, 99)
    d = run.as_dict()["detail"]
    assert d["eager_compiles"] == 2 and d["eager_compile_s"] > 0
    assert d["eager_where"] == {"feed_convert": 1, "write_back": 1}
    assert sum(d["eager_names"].values()) == 2
    # the event keeps meaning the step's own builds
    assert event.as_dict()["backend_compiles"] == 1
    assert event.cause == "first_call"


def test_a_compile_while_a_program_is_built_goes_on_the_build():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        jax.jit(lambda v: v * 11 + 5)(np.ones((2, 7), np.float32))
    build, = _phases(steplog.PROGRAM_BUILD, main._uid)
    d = build.as_dict()["detail"]
    assert d["eager_compiles"] == 1
    assert d["eager_where"] == {steplog.PROGRAM_BUILD: 1}
    # with nothing open it is a user's own: no record takes it
    n = len(observe.observatory().phases())
    jax.jit(lambda v: v * 13 + 7)(np.ones((2, 7), np.float32))
    assert len(observe.observatory().phases()) == n
    assert observe.observatory().events() == []


def test_a_cache_miss_reads_zero_seconds_of_retrieval():
    event = observe.observatory().record(98, "first_call", "executor", {})
    steplog._on_event("/jax/compilation_cache/cache_misses")
    assert event.cache_misses == 1
    assert event.stages_s == {"cache_retrieval": 0.0}
    steplog._on_event("/jax/compilation_cache/cache_hits")
    steplog._on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                         0.25)
    assert event.cache_hits == 1
    assert event.stages_s["cache_retrieval"] == pytest.approx(0.25)


def test_a_run_that_raises_is_not_kept():
    main, startup, loss = _build()
    step = _started(main, startup, loss)
    with pytest.raises(Exception):
        step({"nope": np.ones((8, 4), np.float32)})
    assert _phases(steplog.RUN, main._uid) == []
    assert steplog._building.run is None


def test_clear_and_reset_all_empty_the_store():
    _build()
    assert observe.observatory().phases()
    observe.observatory().clear()
    assert observe.observatory().phases() == []
    _build()
    observe.reset_all()
    assert observe.observatory().phases() == []


def test_the_store_is_bounded():
    store = steplog.RecompilationObservatory(phase_capacity=4)
    store.note_phases([steplog.Phase("p", i, start=0.0, end=1.0)
                       for i in range(9)])
    assert [p.program_uid for p in store.phases()] == [5, 6, 7, 8]


def test_summary_carries_the_phases():
    main, startup, loss = _build()
    _started(main, startup, loss)({"x": np.ones((8, 4), np.float32)})
    doc = json.loads(json.dumps(observe.summary()["recompiles"]))
    names = [p["name"] for p in doc["phases"]]
    assert names.count(steplog.PROGRAM_BUILD) == 1
    assert names.count(steplog.RUN) == 2
    build = next(p for p in doc["phases"]
                 if p["name"] == steplog.PROGRAM_BUILD)
    assert set(build) == {"id", "name", "program_uid", "parent", "start",
                          "end", "detail"}
    assert build["detail"]["infer_shapes_calls"] > 0
    event = next(e for e in doc["events"] if e["program_uid"] == main._uid)
    run = next(p for p in doc["phases"] if p["name"] == steplog.RUN
               and p["program_uid"] == main._uid)
    (start, end), = event["stage_intervals"]["backend"]
    assert run["start"] <= start <= end <= run["end"]


def test_telemetry_dump_prints_the_timeline(capsys):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import telemetry_dump
    finally:
        sys.path.pop(0)
    main, startup, loss = _build()
    _started(main, startup, loss)({"x": np.ones((8, 4), np.float32)})
    telemetry_dump.print_setup_timeline(observe.summary()["recompiles"])
    out = capsys.readouterr().out
    assert "set-up timeline" in out
    for name in (steplog.PROGRAM_BUILD, steplog.MINIMIZE, steplog.RUN,
                 "paddle_tpu:jit_call", "first_call backend"):
        assert name in out, name


def test_the_phases_are_in_a_profile_under_their_names(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        main, _, _ = _build()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = {e.name: (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in data.planes for line in plane.lines
             for e in line.events if e.name.startswith("paddle_tpu:")}
    build, minimize = spans[steplog.PROGRAM_BUILD], spans[steplog.MINIMIZE]
    assert build[2]["program"] == minimize[2]["program"] == main._uid
    assert build[0] <= minimize[0] <= minimize[1] <= build[1]
    # and in the store, as without a profile
    assert len(_phases(steplog.PROGRAM_BUILD)) == 1
