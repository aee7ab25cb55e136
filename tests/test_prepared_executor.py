"""Prepared-program fast path (round 6): `Executor.prepare()` handles
must be bit-identical to `Executor.run()` — same fetches, same RNG
stream, same scope semantics — while skipping the per-step host dispatch
work (reference Executor::Prepare / RunPreparedContext,
executor.cc:294-366)."""

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.core.executor import StepKey, resolve_compiler_options
from paddle_tpu.observe.steplog import CAUSE_OF_FIELD
from paddle_tpu.parallel.mesh import make_mesh


def _build_mlp(seed=None, dropout=True):
    """Small seeded MLP (+ optional dropout so the RNG stream is load-
    bearing) built into fresh programs."""
    main, startup = fluid.Program(), fluid.Program()
    if seed is not None:
        main.random_seed = seed
        startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=0.3)
        pred = fluid.layers.fc(input=h, size=1, act=None)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _batches(n, bs=16):
    rng = np.random.RandomState(7)
    return [{"x": rng.randn(bs, 8).astype(np.float32),
             "y": rng.randn(bs, 1).astype(np.float32)} for _ in range(n)]


def test_prepared_matches_run_bit_identical():
    """Seeded multi-step training: the prepared handle's trajectory must
    equal exe.run()'s bit for bit (same compiled step, same counters)."""
    main, startup, loss = _build_mlp(seed=90)
    feeds = _batches(6)

    ref = []
    scope_a = fluid.Scope()
    exe_a = fluid.Executor(fluid.CPUPlace())
    exe_a.run(startup, scope=scope_a)
    for f in feeds:
        out, = exe_a.run(main, feed=f, fetch_list=[loss], scope=scope_a)
        ref.append(np.asarray(out))

    scope_b = fluid.Scope()
    exe_b = fluid.Executor(fluid.CPUPlace())
    exe_b.run(startup, scope=scope_b)
    prepared = exe_b.prepare(main, fetch_list=[loss], scope=scope_b)
    for f, r in zip(feeds, ref):
        out, = prepared.run(f)
        np.testing.assert_array_equal(np.asarray(out), r)


def test_prepared_and_run_interleave_one_rng_stream():
    """Alternating exe.run()/prepared.run() steps on ONE executor must
    advance the SAME per-program run counter — the trajectory equals an
    all-run() trajectory exactly."""
    main, startup, loss = _build_mlp(seed=33)
    feeds = _batches(6)

    ref = []
    scope_a = fluid.Scope()
    exe_a = fluid.Executor(fluid.CPUPlace())
    exe_a.run(startup, scope=scope_a)
    for f in feeds:
        out, = exe_a.run(main, feed=f, fetch_list=[loss], scope=scope_a)
        ref.append(np.asarray(out))

    scope_b = fluid.Scope()
    exe_b = fluid.Executor(fluid.CPUPlace())
    exe_b.run(startup, scope=scope_b)
    prepared = exe_b.prepare(main, fetch_list=[loss], scope=scope_b)
    for i, (f, r) in enumerate(zip(feeds, ref)):
        if i % 2 == 0:
            out, = exe_b.run(main, feed=f, fetch_list=[loss], scope=scope_b)
        else:
            out, = prepared.run(f)
        np.testing.assert_array_equal(np.asarray(out), r)


def test_unseeded_rng_stream_parity():
    """Unseeded programs draw from an executor-local stream (program
    ordinal + per-program counter); a fresh executor driving the handle
    must reproduce a fresh executor driving run()."""
    main, startup, loss = _build_mlp(seed=None)
    feeds = _batches(4)

    ref = []
    scope_a = fluid.Scope()
    exe_a = fluid.Executor(fluid.CPUPlace())
    exe_a.run(startup, scope=scope_a)
    for f in feeds:
        out, = exe_a.run(main, feed=f, fetch_list=[loss], scope=scope_a)
        ref.append(np.asarray(out))

    scope_b = fluid.Scope()
    exe_b = fluid.Executor(fluid.CPUPlace())
    exe_b.run(startup, scope=scope_b)
    prepared = exe_b.prepare(main, fetch_list=[loss], scope=scope_b)
    for f, r in zip(feeds, ref):
        out, = prepared.run(f)
        np.testing.assert_array_equal(np.asarray(out), r)


def test_scope_mutation_between_steps_is_observed():
    """set_var between prepared steps must invalidate the cached state
    gather — the next step computes with the NEW value exactly."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        pred = fluid.layers.fc(input=x, size=2, act=None,
                               bias_attr=False)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    prepared = exe.prepare(main, fetch_list=[pred], scope=scope)

    xs = np.arange(8, dtype=np.float32).reshape(2, 4)
    w_name = [n for n in scope.local_var_names() if ".w" in n][0]
    out0, = prepared.run({"x": xs})

    w_new = np.full(np.asarray(scope.find_var(w_name)).shape, 0.5,
                    np.float32)
    scope.set_var(w_name, w_new)
    out1, = prepared.run({"x": xs})
    np.testing.assert_allclose(np.asarray(out1), xs @ w_new, rtol=1e-6)
    assert not np.allclose(out0, out1)


def test_return_numpy_false_returns_device_array():
    main, startup, loss = _build_mlp(seed=1, dropout=False)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
    out, = prepared.run(_batches(1)[0], return_numpy=False)
    assert isinstance(out, jax.Array)
    out_run, = exe.run(main, feed=_batches(1)[0], fetch_list=[loss],
                       scope=scope, return_numpy=False)
    assert isinstance(out_run, jax.Array)


def test_prepared_handle_rejects_mutated_program():
    main, startup, loss = _build_mlp(seed=2, dropout=False)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    prepared = exe.prepare(main, fetch_list=[loss], scope=scope)
    prepared.run(_batches(1)[0])
    main._bump()  # any mutation invalidates the bound handle
    with pytest.raises(RuntimeError, match="mutated after prepare"):
        prepared.run(_batches(1)[0])


def test_program_mutation_evicts_stale_cache_entries():
    """Re-running a mutated program must REPLACE its compile-cache and
    prepared-memo entries, not accrete one per version (advisor r5)."""
    main, startup, loss = _build_mlp(seed=3, dropout=False)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    f = _batches(1)[0]
    exe.run(main, feed=f, fetch_list=[loss], scope=scope)
    n_cache, n_prepared = len(exe._cache), len(exe._prepared)
    for _ in range(3):
        main._bump()  # simulate program mutation between runs
        exe.run(main, feed=f, fetch_list=[loss], scope=scope)
    assert len(exe._cache) == n_cache
    assert len(exe._prepared) == n_prepared
    stale = [k for k in exe._cache if k.program_uid == main._uid
             and k.program_version != main._version]
    assert not stale


def test_malformed_compiler_options_raise_with_entry_name():
    """A missing '=' in an xla_compiler_options entry must raise a
    ValueError naming the malformed entry, not the opaque dict-update
    crash (advisor r5)."""
    fluid.flags.set_flag("xla_compiler_options", "a=1,no_equals_here,b=2")
    try:
        with pytest.raises(ValueError, match="no_equals_here"):
            resolve_compiler_options("cpu")
    finally:
        fluid.flags.set_flag("xla_compiler_options", "auto")


def test_run_still_fast_pathed_after_flag_flip():
    """A set_flag flip must take effect on the next run() (new handle)
    without recompiling unchanged steps (compile cache reuse)."""
    main, startup, loss = _build_mlp(seed=4, dropout=False)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    f = _batches(1)[0]
    out0, = exe.run(main, feed=f, fetch_list=[loss], scope=scope)
    n_cache = len(exe._cache)
    fluid.flags.set_flag("trace", False)  # unrelated flag: new memo key
    try:
        out1, = exe.run(main, feed=f, fetch_list=[loss], scope=scope)
    finally:
        fluid.flags.set_flag("trace", True)
    assert len(exe._cache) == n_cache  # no recompile


def test_donation_dropped_while_compile_cache_configured_on_cpu():
    """Regression pin for the former ~1-in-6 flake of
    test_wire.py::test_comm_quant_parallel_executor_zero_recompiles_and_band:
    on this jaxlib, a warm persistent-cache hit of a donate_argnums
    executable loses its input-output aliasing on the CPU backend
    (donated-buffer use-after-free — bus errors, segfaults, or silent
    state corruption under identical seeds). The runtime makes the
    unsound combination unrepresentable: donation_safe() must be False
    exactly when a compilation-cache dir is configured on a CPU
    backend, and True the moment the cache is off. The dir itself is the
    one the package's cache rule placed (paddle_tpu/__init__.py)."""
    from paddle_tpu.core.executor import donation_safe

    prev = jax.config.jax_compilation_cache_dir
    try:
        assert prev, "the package rule configures a cache for every process"
        assert jax.default_backend() == "cpu"
        assert donation_safe() is False
        # no cache dir -> full donation is sound again
        jax.config.update("jax_compilation_cache_dir", None)
        assert donation_safe() is True
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# -- one way from a Program to a running step -------------------------------
# Executor and ParallelExecutor bind, build and run through the same code
# (core/executor.py): what a step bakes in is one record (`StepKey`), each
# field of which names the cause of a compile that it alone brought about.

KINDS = ["executor", "parallel"]


def _share_program():
    """An expert layer under a share (its rules note `moe_row_buffer_rows`
    and tally `moe_share_bounded_moves` on the compile event) and a second
    thing to fetch."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[8], dtype="float32")
        routing = layers.moe_router(x, num_experts=4, k=2)
        y = layers.moe_experts(x, routing, num_experts=4, expert_size=8,
                               first_expert=0, experts_held=2)
        loss = layers.mean(y)
        other = layers.mean(x)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss, other


def _rig(kind, main, startup, scope=None, amp=False, ndev=4):
    """A new executor of `kind` over `main` with the startup program run:
    (step(feed, fetch_list), its compile cache, itself)."""
    scope = scope or fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace(), amp=amp)
    exe.run(startup, scope=scope)
    if kind == "executor":
        return (lambda feed, fetch: exe.run(main, feed=feed, fetch_list=fetch,
                                            scope=scope)), exe._cache, exe
    strategy = fluid.BuildStrategy()
    strategy.amp = amp
    pe = fluid.ParallelExecutor(
        main_program=main, scope=scope, build_strategy=strategy,
        mesh=make_mesh([ndev], ["dp"], jax.devices()[:ndev]))
    return (lambda feed, fetch: pe.run(fetch_list=fetch, feed=feed)), \
        pe._exe._cache, pe


def _events(main):
    return [e for e in observe.observatory().events()
            if e.program_uid == main._uid]


@pytest.mark.parametrize("field", StepKey._fields[1:])
def test_every_field_of_the_record_names_a_cause(field):
    """A field added to the record without a cause fails here."""
    assert sorted(CAUSE_OF_FIELD) == sorted(StepKey._fields[1:])
    base = StepKey(program_uid=10 ** 9, program_version=1, feeds=("x",),
                   fetches=("loss",), scope_uid=2, amp=False,
                   check_nan_inf=False, copts=None, seed=None, mesh=None)
    other = dict(program_version=2, feeds=("x", "y"), fetches=("acc",),
                 scope_uid=3, amp=True, check_nan_inf=True,
                 copts=(("a", "1"),), seed=5, mesh="a mesh")
    obs = observe.observatory()
    detail = {"version": 1}
    first = obs.note_entry_build(base, "executor", detail)
    assert first.cause == "first_call" and first.detail is detail
    again = obs.note_entry_build(base._replace(**{field: other[field]}),
                                 "executor", {})
    assert again.cause == CAUSE_OF_FIELD[field]
    # every value seen before: a second executor building the same step
    assert obs.note_entry_build(base, "parallel", {}).cause \
        == "options_change"


# `seed` is missing: setting a program's random_seed bumps its version
_REACHABLE = ["program_version", "copts", "feeds", "fetches", "scope_uid",
              "amp", "check_nan_inf"]


@pytest.mark.parametrize("kind,field", [(k, f) for k in KINDS
                                        for f in _REACHABLE]
                         + [("parallel", "mesh")])
def test_changing_one_setting_builds_one_entry_named_for_it(kind, field):
    main, startup, loss, other = _share_program()
    scope = fluid.Scope()
    step, cache, _ = _rig(kind, main, startup, scope)
    feed, fetch = {"x": np.ones((8, 8), np.float32)}, [loss.name]
    step(feed, fetch)
    (base,) = [k for k in cache if k.program_uid == main._uid]
    flag = {"copts": ("xla_compiler_options", "auto",
                      "xla_backend_optimization_level=0"),
            "check_nan_inf": ("check_nan_inf", False, True)}.get(field)
    if field == "program_version":
        main._bump()
    elif field == "feeds":
        feed = dict(feed, extra=np.ones((8, 1), np.float32))
    elif field == "fetches":
        fetch = [loss.name, other.name]
    elif field == "scope_uid":
        step, cache, _ = _rig(kind, main, startup)
    elif field == "amp":
        step, cache, _ = _rig(kind, main, startup, scope, amp=True)
    elif field == "mesh":
        step, cache, _ = _rig(kind, main, startup, scope, ndev=2)
    else:
        fluid.set_flag(flag[0], flag[2])
    try:
        step(feed, fetch)
        step(feed, fetch)               # steady: no third event
    finally:
        if flag:
            fluid.set_flag(flag[0], flag[1])
    assert [e.cause for e in _events(main)] \
        == ["first_call", CAUSE_OF_FIELD[field]]
    (new,) = [k for k in cache if k.program_uid == main._uid and k != base]
    assert {f for f in StepKey._fields
            if getattr(new, f) != getattr(base, f)} == {field}


@pytest.mark.parametrize("kind", KINDS)
def test_a_rules_facts_land_on_the_event_of_the_entry_it_traces(kind):
    """Two entries of one program; the first is traced again (a new batch)
    after the second was bound: what its rules note is on ITS event."""
    main, startup, loss, other = _share_program()
    step, _, _ = _rig(kind, main, startup)
    step({"x": np.ones((8, 8), np.float32)}, [loss.name])
    step({"x": np.ones((8, 8), np.float32)}, [loss.name, other.name])
    first, second = _events(main)
    rows = 8 * 2 + 2 * 128              # tokens x k + experts held x tile
    assert first.detail["moe_row_buffer_rows"] == rows
    assert second.detail["moe_row_buffer_rows"] == rows
    step({"x": np.ones((16, 8), np.float32)}, [loss.name])
    assert first.detail["moe_row_buffer_rows"] == rows + 8 * 2
    assert second.detail["moe_row_buffer_rows"] == rows
    # and so is what the second build cost (no cause is invented for it)
    assert [e.as_dict()["backend_compiles"] for e in (first, second)] \
        == [2, 1]
    # a tally counts an op once however often its step is traced, per entry
    assert first.detail["moe_share_bounded_moves"] \
        == second.detail["moe_share_bounded_moves"] == 3
    assert [e.cause for e in _events(main)] == ["first_call", "fetch_set"]


@pytest.mark.parametrize("kind", KINDS)
def test_check_nan_inf_holds_on_a_mesh_as_on_one_chip(kind):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[3], dtype="float32")
        loss = layers.mean(layers.log(x))       # negative input -> NaN
    step, _, _ = _rig(kind, main, startup)
    bad = {"x": -np.ones((4, 3), np.float32)}
    assert not np.isfinite(step(bad, [loss.name])[0]).all()  # off: silent
    fluid.set_flag("check_nan_inf", True)
    try:
        with pytest.raises(RuntimeError, match=r"NaN/Inf.*'log'"):
            step(bad, [loss.name])
        out, = step({"x": np.ones((4, 3), np.float32)}, [loss.name])
    finally:
        fluid.set_flag("check_nan_inf", False)
    assert np.isfinite(out).all()


@pytest.mark.parametrize("runs,picked", [("a,ab", "ab"), ("a,ab,a", "a"),
                                         ("ab,a,ab", "ab")])
def test_compiled_text_is_of_the_entry_the_last_run_used(runs, picked):
    main, startup, loss, other = _share_program()
    step, _, pe = _rig("parallel", main, startup)
    feed = {"x": np.ones((8, 8), np.float32)}
    fetch = {"a": [loss.name], "ab": [loss.name, other.name]}
    for r in runs.split(","):
        step(feed, fetch[r])
    entry, _ = pe._entry_for(feed, "compiled_text")
    assert entry.fetch_names == fetch[picked]
    assert pe.compiled_text(feed) == entry._hlo_text
    assert "HloModule" in pe.lowered_text(feed) or "module" in \
        pe.lowered_text(feed)


@pytest.mark.parametrize("kind", KINDS)
def test_a_py_reader_program_runs_without_a_feed(kind):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        reader, (xv,) = fluid.reader.py_reader(
            capacity=4, shapes=[[-1, 4]], dtypes=["float32"])
        loss = layers.mean(layers.fc(input=xv, size=2))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    reader.decorate_tensor_provider(lambda: (
        {xv.name: np.full((8, 4), i, np.float32)} for i in range(3)))
    step, _, _ = _rig(kind, main, startup)
    reader.start()
    losses = [step(None, [loss.name])[0].item() for _ in range(3)]
    with pytest.raises(fluid.EOFException):
        step(None, [loss.name])
    reader.reset()
    assert len(set(losses)) == 3
