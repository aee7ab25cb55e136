"""Prepared-program fast path (round 6): `Executor.prepare()` handles
must be bit-identical to `Executor.run()` — same fetches, same RNG
stream, same scope semantics — while skipping the per-step host dispatch
work (reference Executor::Prepare / RunPreparedContext,
executor.cc:294-366)."""

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.executor import resolve_compiler_options


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
    stale = [k for k in exe._cache
             if k[0] == main._uid and k[1] != main._version]
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
