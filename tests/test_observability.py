"""Observability + hygiene: NaN/Inf check mode, flags registry, profiler
table/timeline, PE feed divisibility, prune with sub-blocks, clone
metadata (reference: FLAGS_check_nan_inf operator.cc:622, gflags forwarding
fluid/__init__.py, profiler.cc:448 table, tools/timeline.py)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers


def test_check_nan_inf_names_the_offending_op():
    x = layers.data(name="x", shape=[3], dtype="float32")
    h = layers.log(x)           # negative input -> NaN
    loss = layers.mean(h)
    exe = fluid.Executor(fluid.CPUPlace(), check_nan_inf=True)
    exe.run(fluid.default_startup_program())
    with pytest.raises(RuntimeError, match=r"NaN/Inf.*'log'"):
        exe.run(feed={"x": np.array([[-1.0, 2.0, 3.0]], np.float32)},
                fetch_list=[loss])
    # clean inputs pass
    out, = exe.run(feed={"x": np.array([[1.0, 2.0, 3.0]], np.float32)},
                   fetch_list=[loss])
    assert np.isfinite(np.asarray(out)).all()


def test_check_nan_inf_off_by_default():
    x = layers.data(name="x", shape=[2], dtype="float32")
    loss = layers.mean(layers.log(x))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    out, = exe.run(feed={"x": np.array([[-1.0, 1.0]], np.float32)},
                   fetch_list=[loss])  # NaN flows through silently
    assert not np.isfinite(np.asarray(out)).all()


def test_flags_registry():
    assert fluid.get_flag("check_nan_inf") in (True, False)
    fluid.set_flag("check_nan_inf", True)
    assert fluid.get_flag("check_nan_inf") is True
    fluid.set_flag("check_nan_inf", False)
    with pytest.raises(KeyError):
        fluid.get_flag("not_a_flag")


def test_profiler_host_table_and_timeline(tmp_path):
    import time
    from paddle_tpu import profiler as prof
    prof.reset_profiler()
    with prof.record_event("phase_a"):
        time.sleep(0.01)
    with prof.record_event("phase_b"):
        time.sleep(0.005)
    rows = prof.print_host_events()
    names = [r[0] for r in rows]
    assert "phase_a" in names and "phase_b" in names
    path = str(tmp_path / "timeline.json")
    prof.export_chrome_tracing(path)
    trace = json.load(open(path))
    evs = {e["name"]: e for e in trace["traceEvents"]}
    assert evs["phase_a"]["dur"] >= 9000  # >= ~10ms in us
    assert evs["phase_a"]["ph"] == "X"


def test_pe_rejects_non_divisible_batch():
    x = layers.data(name="x", shape=[4], dtype="float32")
    loss = layers.mean(layers.fc(input=x, size=2))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name)
    with pytest.raises(ValueError, match="not divisible"):
        pe.run(feed={"x": np.random.randn(7, 4).astype(np.float32)},
               fetch_list=[loss.name])


def test_prune_keeps_subblock_external_producers():
    """A While body reading a global-block var must keep that var's
    producer through _prune (regression: sub-block reads were invisible)."""
    x = layers.data(name="x", shape=[2], dtype="float32")
    gain = layers.fc(input=x, size=2, act=None, bias_attr=False)  # producer
    i = layers.fill_constant([1], "float32", 0.0)
    limit = layers.fill_constant([1], "float32", 3.0)
    acc = layers.fill_constant_batch_size_like(x, [-1, 2], "float32", 0.0)
    cond = layers.less_than(i, limit)
    w = layers.While(cond, max_iters=5)
    with w.block():
        layers.assign(layers.elementwise_add(acc, gain), acc)
        layers.increment(i, 1.0)
        layers.less_than(i, limit, cond=cond)
    pruned = fluid.default_main_program().clone(for_test=True)._prune(
        [acc.name])
    kept_types = [op.type for op in pruned.global_block().ops]
    assert "mul" in kept_types, kept_types  # the fc survived the prune
    # and the pruned program actually runs
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    out, = exe.run(pruned, feed={"x": np.ones((2, 2), np.float32)},
                   fetch_list=[acc])
    assert np.asarray(out).shape == (2, 2)


def test_clone_preserves_parameter_metadata():
    x = layers.data(name="x", shape=[4], dtype="float32")
    layers.fc(input=x, size=2,
              param_attr=fluid.ParamAttr(name="meta_w",
                                         sharding=("mp", None),
                                         learning_rate=0.5))
    clone = fluid.default_main_program().clone(for_test=True)
    w = clone.global_block().vars["meta_w"]
    assert w.sharding == ("mp", None)
    assert w.trainable is True
    assert w.optimize_attr["learning_rate"] == 0.5


def test_executor_cache_uid_survives_gc():
    """id() recycling must not alias compiled programs (the cache key uses
    process-unique uids now)."""
    import gc
    exe = fluid.Executor(fluid.CPUPlace())
    seen = set()
    for _ in range(3):
        p = fluid.Program()
        seen.add(p._uid)
        del p
        gc.collect()
    assert len(seen) == 3


def test_check_nan_inf_with_control_flow():
    """Flags recorded inside a lax.while body would be leaked tracers;
    interior ops are covered at the while op's boundary instead
    (regression: UnexpectedTracerError on any looped program)."""
    x = layers.data(name="x", shape=[2], dtype="float32")
    i = layers.fill_constant([1], "float32", 0.0)
    limit = layers.fill_constant([1], "float32", 4.0)
    acc = layers.fill_constant_batch_size_like(x, [-1, 2], "float32", 0.0)
    cond = layers.less_than(i, limit)
    w = layers.While(cond)
    with w.block():
        layers.assign(layers.elementwise_add(acc, x), acc)
        layers.increment(i, 1.0)
        layers.less_than(i, limit, cond=cond)
    loss = layers.mean(acc)
    exe = fluid.Executor(fluid.CPUPlace(), check_nan_inf=True)
    exe.run(fluid.default_startup_program())
    out, = exe.run(feed={"x": np.ones((2, 2), np.float32)},
                   fetch_list=[loss])
    assert np.allclose(np.asarray(out), 4.0)
    # NaN fed through the loop is caught at the boundary
    with pytest.raises(RuntimeError, match="NaN/Inf"):
        exe.run(feed={"x": np.full((2, 2), np.nan, np.float32)},
                fetch_list=[loss])


def test_check_nan_inf_covers_grad_ops():
    """A finite forward with an inf backward must be caught (regression:
    grad ops returned before recording flags)."""
    x = layers.data(name="x", shape=[2], dtype="float32")
    x.stop_gradient = False
    loss = layers.mean(layers.sqrt(x))
    grads = fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace(), check_nan_inf=True)
    exe.run(fluid.default_startup_program())
    with pytest.raises(RuntimeError, match=r"NaN/Inf.*grad"):
        exe.run(feed={"x": np.zeros((1, 2), np.float32)},  # d sqrt/dx -> inf
                fetch_list=[loss, "x@GRAD"])


def test_set_flag_takes_effect_after_executor_construction():
    x = layers.data(name="x", shape=[2], dtype="float32")
    loss = layers.mean(layers.log(x))
    exe = fluid.Executor(fluid.CPUPlace())  # constructed BEFORE the flip
    exe.run(fluid.default_startup_program())
    fluid.set_flag("check_nan_inf", True)
    try:
        with pytest.raises(RuntimeError, match="NaN/Inf"):
            exe.run(feed={"x": np.array([[-1.0, 1.0]], np.float32)},
                    fetch_list=[loss])
    finally:
        fluid.set_flag("check_nan_inf", False)


def test_pe_replicates_non_data_feeds():
    """Non-divisible feeds that are not data vars (lr schedules etc.) are
    replicated, not rejected."""
    x = layers.data(name="x", shape=[4], dtype="float32")
    lr = fluid.default_main_program().global_block().create_var(
        name="lr_feed", shape=[1], dtype="float32")
    h = layers.fc(input=x, size=2)
    loss = layers.elementwise_mul(layers.mean(h), lr)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name)
    ndev = pe.device_count
    out, = pe.run(feed={"x": np.random.randn(2 * ndev, 4).astype(np.float32),
                        "lr_feed": np.array([0.5], np.float32)},
                  fetch_list=[loss.name])
    assert np.isfinite(np.asarray(out)).all()


def test_record_event_survives_exception():
    from paddle_tpu import profiler as prof
    prof.reset_profiler()
    with pytest.raises(ValueError):
        with prof.record_event("failing_phase"):
            raise ValueError("boom")
    rows = prof.print_host_events()
    assert any(r[0] == "failing_phase" for r in rows)


def test_debugger_pprint_and_graphviz(tmp_path):
    """reference debugger.py analogs: program pseudo-code + DOT dump."""
    from paddle_tpu import debugger
    x = layers.data(name="x", shape=[4], dtype="float32")
    h = layers.fc(input=x, size=3, act="relu")
    loss = layers.mean(h)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog = fluid.default_main_program()
    code = debugger.pprint_program_codes(prog)
    assert "= mul(" in code and "relu" in code
    assert "_grad" not in code  # backward hidden by default
    code_bwd = debugger.pprint_program_codes(prog, show_backward=True)
    assert "_grad" in code_bwd
    p = str(tmp_path / "g.dot")
    dot = debugger.draw_block_graphviz(prog.global_block(),
                                      highlights=[r"mean"], path=p)
    assert dot.startswith("digraph G {") and 'shape=box' in dot
    assert open(p).read() == dot
    assert "fillcolor=red" in dot      # highlighted var
    assert "fillcolor=lightblue" in dot  # parameter node
