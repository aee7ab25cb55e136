"""The span layer of the training path (observe/steplog.py::RunSpans): host
spans on the profiler's clock at default flags, StepStats on the same
boundaries when observing, Fluid op scopes in the lowered step, and stage
durations on compile events."""

import contextlib
import glob

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe

PHASES = {"feed_convert", "state_gather", "jit_call", "write_back"}


def _fc_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(input=x, size=2))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _stepper(kind):
    """step(feed, return_numpy) through Executor.run or ParallelExecutor.run
    on the virtual CPU mesh, the startup program already run."""
    main, startup, loss = _fc_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    if kind == "executor":
        return main, lambda feed, rn: exe.run(
            main, feed=feed, fetch_list=[loss], scope=scope, return_numpy=rn)
    from paddle_tpu.parallel.mesh import make_mesh
    pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                scope=scope,
                                mesh=make_mesh([4], ["dp"], jax.devices()[:4]))
    return main, lambda feed, rn: pe.run(fetch_list=[loss.name], feed=feed,
                                         return_numpy=rn)


def _profiled(tmp_path, body):
    """Run `body()` under a jax.profiler trace; the `paddle_tpu:` host
    events as (name, start_ns, end_ns, stats), in order of start."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in data.planes for line in plane.lines
             for e in line.events if e.name.startswith("paddle_tpu:")]
    return sorted(spans, key=lambda s: s[1])


def _children(spans, run):
    return [s for s in spans
            if s is not run and run[1] <= s[1] and s[2] <= run[2]]


@pytest.mark.parametrize("kind", ["executor", "parallel"])
def test_run_spans_in_a_profile_at_default_flags(kind, tmp_path):
    main, step = _stepper(kind)
    feed = {"x": np.ones((8, 4), np.float32)}

    def body():
        for _ in range(3):
            step(feed, False)
        step(feed, True)

    spans = _profiled(tmp_path, body)
    runs = [s for s in spans if s[0] == "paddle_tpu:run"]
    assert [r[3]["step"] for r in runs] == [0, 1, 2, 3]
    assert {r[3]["program"] for r in runs} == {main._uid}
    assert {r[3]["source"] for r in runs} == {kind}
    for i, run in enumerate(runs):
        names = [c[0].split(":")[1] for c in _children(spans, run)]
        want = set(PHASES)
        if i == 0:
            want.add("bind")        # only the step that binds
        if i == 3:
            want.add("fetch")       # absent with return_numpy=False
        assert set(names) == want and len(names) == len(want), (i, names)
        # leaves in program order that tile the run without overlap
        kids = _children(spans, run)
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        assert names.index("feed_convert") < names.index("state_gather") \
            < names.index("jit_call") < names.index("write_back")
    # every span lies in a run: nothing is opened outside one
    assert all(any(r[1] <= s[1] and s[2] <= r[2] for r in runs)
               for s in spans)
    # the zero-registry-writes contract: no StepStats at default flags
    assert observe.get_steplog().phase_summary()["steps"] == 0


@pytest.mark.parametrize("kind", ["executor", "parallel"])
def test_step_stats_share_the_span_boundaries_when_observing(kind, tmp_path):
    _, step = _stepper(kind)
    feed = {"x": np.ones((8, 4), np.float32)}
    fluid.set_flag("observe", True)
    step(feed, True)                    # the step that binds and compiles
    observe.get_steplog().clear()
    spans = _profiled(tmp_path, lambda: [step(feed, True) for _ in range(7)])
    runs = [s for s in spans if s[0] == "paddle_tpu:run"]
    stats = observe.get_steplog().recent()
    assert len(runs) == len(stats) == 7
    ratios = []
    for run, st in zip(runs, stats):
        assert set(st.phases) == {"feed_convert", "state_gather",
                                  "device_compute", "write_back", "fetch"}
        assert st.source == kind
        ratios.append(st.total * 1e9 / (run[2] - run[1]))
    # the phases tile the run: their sum is the run span, less the few
    # microseconds before the first phase and after the last
    assert 0.8 <= sorted(ratios)[3] <= 1.05, ratios


@pytest.mark.parametrize("return_numpy", [False, True])
def test_both_executors_run_the_same_spans_and_note_the_same_detail(
        return_numpy, tmp_path):
    """One run loop (core/executor.py::PreparedProgram.run): the span
    names of a binding and of a steady step, in order, and the keys of the
    compile event's detail do not depend on who owns the handle."""
    seen = {}
    for kind in ("executor", "parallel"):
        main, step = _stepper(kind)
        feed = {"x": np.ones((8, 4), np.float32)}
        spans = _profiled(tmp_path / kind, lambda: [
            step(feed, return_numpy) for _ in range(2)])
        event, = [e for e in observe.observatory().events()
                  if e.program_uid == main._uid]
        assert event.source == kind
        seen[kind] = ([s[0] for s in spans], sorted(event.detail))
    assert seen["executor"] == seen["parallel"]
    names, keys = seen["executor"]
    steady = ["paddle_tpu:run", "paddle_tpu:feed_convert",
              "paddle_tpu:state_gather", "paddle_tpu:jit_call",
              "paddle_tpu:write_back"] + ["paddle_tpu:fetch"] * return_numpy
    assert names == steady[:2] + ["paddle_tpu:bind"] + steady[2:] + steady
    assert {"version", "feeds", "fetches", "parameters"} <= set(keys)


def _attention_program():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[2, 8, 4], dtype="float32")
        q = layers.fc(input=x, size=4, num_flatten_dims=3, bias_attr=False)
        out = layers.fused_attention(q, x, x, causal=True, sm_scale=4 ** -0.5)
        loss = layers.mean(out)
        fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _attention_losses_and_text():
    main, startup, loss = _attention_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.random.RandomState(0).rand(3, 2, 8, 4).astype(np.float32)}
    losses = [exe.run(main, feed=feed, fetch_list=[loss], scope=scope)[0]
              for _ in range(3)]
    compiled, = [c for c in exe._cache.values() if c.program is main]
    text = compiled._step.lower(
        feed, {n: scope.find_var(n) for n in compiled.mut_names},
        {n: scope.find_var(n) for n in compiled.const_names},
        np.uint32(0)).as_text(debug_info=True)
    return np.asarray(losses), text


def test_fluid_op_scopes_in_the_lowered_step_change_no_numerics(monkeypatch):
    losses, text = _attention_losses_and_text()
    # the forward op's rule, the same rule re-traced inside its grad op
    # (the generic vjp path), and an optimizer op, each under its own type
    for scope in ("/fused_attention/", "/fused_attention_grad/", "/adam/"):
        assert scope in text, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_losses, bare_text = _attention_losses_and_text()
    assert "/fused_attention" not in bare_text
    assert losses.tobytes() == bare_losses.tobytes()
    assert len({x.tobytes() for x in losses}) == 3     # it trained


def _compile_events(observing):
    """Compile events of an fc program run at one shape twice, then at a
    new shape; and the last event's stages after an unrelated jax compile."""
    main, startup, loss = _fc_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    fluid.set_flag("observe", observing)
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
    prepared.run({"x": np.ones((4, 4), np.float32)})
    prepared.run({"x": np.ones((4, 4), np.float32)})    # steady: no event
    prepared.run({"x": np.ones((6, 4), np.float32)})    # forced recompile
    events = [e for e in observe.observatory().events()
              if e.program_uid == main._uid]
    before = dict(events[-1].stages_s)
    jax.jit(lambda a: a * 3 + 1)(np.ones(5, np.float32))
    return events, before


@pytest.mark.parametrize("observing,causes,builds", [
    # shape tracking is flag-gated: with it a new shape is its own event
    (True, ["first_call", "feed_shape"], [1, 1]),
    # without it jax compiles and the executor saw no cause: the program's
    # event takes the cost of the second build, no cause is invented
    (False, ["first_call"], [2])])
def test_compile_events_carry_stage_durations(observing, causes, builds):
    events, before = _compile_events(observing)
    assert [e.cause for e in events] == causes
    assert [e.as_dict()["backend_compiles"] for e in events] == builds
    for e in events:
        stages = e.as_dict()["stages_s"]
        assert {"trace", "lower", "backend"} <= set(stages), stages
        assert all(stages[k] > 0 for k in ("trace", "lower", "backend"))
        # a traced function reports the jitted ones it calls inside its
        # own duration: the union, not the sum, so never more than the wall
        assert sum(stages[k] for k in ("trace", "lower", "backend")) < 60
    # nothing stays open to collect a later, unrelated compile, and a
    # compile outside any run() is not the executor's to record
    assert events[-1].stages_s == before
    assert len(observe.observatory().events()) == len(events) + 1  # startup
