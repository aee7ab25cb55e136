"""AMP dtype-flow and dropout-path regressions from the MFU work.

The round-3 profile showed three silent performance bugs (reference for the
behavior contract: contrib/float16/float16_transpiler.py's program-wide fp16
rewrite): (1) a mixed bf16/f32 elementwise op promoted the whole downstream
stream to f32, (2) plain softmax was f32-listed and doubled attention-score
traffic, (3) dropout stored full masks as vjp residuals. These tests pin the
fixed behavior on the CPU backend (dtype flow is backend-independent).
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import lowering as L


def _dtype_spy(op_types):
    seen = {}
    orig = L.BlockLowerer._run_op

    def spy(self, block, op, op_idx, env, key):
        orig(self, block, op, op_idx, env, key)
        if op.type in op_types:
            for n in op.output_arg_names[:1]:
                v = env.get(n)
                if hasattr(v, "dtype"):
                    seen.setdefault(op.type, []).append(str(v.dtype))
    return spy, seen, orig


def test_amp_downcasts_mixed_elementwise_and_keeps_softmax_bf16():
    x = layers.data(name="x", shape=[-1, 8, 8], dtype="float32",
                    append_batch_size=False)
    q = layers.fc(input=x, size=8, num_flatten_dims=2, bias_attr=False)
    scores = layers.matmul(q, q, transpose_y=True, alpha=0.35)
    mask = layers.fill_constant([8, 8], "float32", -1e9)
    masked = layers.elementwise_add(scores, mask)   # bf16 + f32 feed
    w = layers.softmax(masked)
    out = layers.mean(layers.matmul(w, q))

    spy, seen, orig = _dtype_spy({"elementwise_add", "softmax", "matmul"})
    L.BlockLowerer._run_op = spy
    try:
        exe = fluid.Executor(fluid.CPUPlace(), amp=True)
        exe.run(fluid.default_startup_program())
        exe.run(feed={"x": np.random.randn(2, 8, 8).astype(np.float32)},
                fetch_list=[out])
    finally:
        L.BlockLowerer._run_op = orig
    # the masked-score add must NOT promote to f32 (downcast policy) and
    # softmax must stay bf16 (not f32-listed any more)
    assert seen["elementwise_add"][0] == "bfloat16", seen
    assert seen["softmax"][0] == "bfloat16", seen
    assert all(d == "bfloat16" for d in seen["matmul"]), seen


def test_dropout_fallback_statistics_and_grad_mask_consistency():
    """uint8 bit-compare dropout: keep rate ~ (1-p) at 1/256 resolution,
    and the regenerated backward mask equals the forward mask."""
    x = layers.data(name="x", shape=[-1, 256], dtype="float32",
                    append_batch_size=False)
    x.stop_gradient = False
    y = layers.dropout(x, dropout_prob=0.3,
                       dropout_implementation="upscale_in_train")
    loss = layers.mean(y)
    fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.ones((64, 256), np.float32)
    out, grad = exe.run(feed={"x": xv}, fetch_list=[y, "x@GRAD"])
    out, grad = np.asarray(out), np.asarray(grad)
    keep = (out != 0)
    assert abs(keep.mean() - 0.7) < 0.02
    # kept entries are upscaled by exactly 1/(1-p)
    np.testing.assert_allclose(out[keep], 1.0 / 0.7, rtol=1e-5)
    # backward regenerates the same mask from the same per-op key
    np.testing.assert_array_equal(grad != 0, keep)


def test_dropout_deterministic_per_seed_and_varies_per_step():
    x = layers.data(name="x", shape=[-1, 128], dtype="float32",
                    append_batch_size=False)
    y = layers.dropout(x, dropout_prob=0.5,
                       dropout_implementation="upscale_in_train")
    prog = fluid.default_main_program()
    prog.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.ones((8, 128), np.float32)
    a = np.asarray(exe.run(prog, feed={"x": xv}, fetch_list=[y])[0])
    b = np.asarray(exe.run(prog, feed={"x": xv}, fetch_list=[y])[0])
    assert not np.array_equal(a, b)  # step counter folds into the key
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(fluid.default_startup_program())  # align the run counter
    a2 = np.asarray(exe2.run(prog, feed={"x": xv}, fetch_list=[y])[0])
    np.testing.assert_array_equal(a, a2)  # same seed+step => same mask


def test_unseeded_programs_draw_decorrelated_masks():
    """Two distinct UNSEEDED dropout programs run through one executor
    must not draw identical key sequences (round-4 advisor: the
    per-program run counters alone would give both fold_in(key(0), 0..n));
    the executor folds in its per-program ordinal. Seeded programs keep
    pure-counter derivation (previous test)."""
    outs = []
    exe = fluid.Executor(fluid.CPUPlace())
    progs = []
    for _ in range(2):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = layers.data(name="x", shape=[-1, 128], dtype="float32",
                            append_batch_size=False)
            y = layers.dropout(x, dropout_prob=0.5,
                               dropout_implementation="upscale_in_train")
        progs.append((main, y))
    xv = np.ones((8, 128), np.float32)
    for main, y in progs:
        outs.append(np.asarray(exe.run(main, feed={"x": xv},
                                       fetch_list=[y])[0]))
    assert not np.array_equal(outs[0], outs[1]), \
        "unseeded programs drew identical dropout masks"


def test_batch_norm_amp_dtype():
    """BN keeps X's dtype on Y while computing f32 stats (conv models)."""
    x = layers.data(name="x", shape=[-1, 8, 4, 4], dtype="float32",
                    append_batch_size=False)
    c = layers.conv2d(input=x, num_filters=8, filter_size=3, padding=1,
                      bias_attr=False)
    b = layers.batch_norm(input=c)
    out = layers.mean(b)
    spy, seen, orig = _dtype_spy({"batch_norm", "conv2d"})
    L.BlockLowerer._run_op = spy
    try:
        exe = fluid.Executor(fluid.CPUPlace(), amp=True)
        exe.run(fluid.default_startup_program())
        exe.run(feed={"x": np.random.randn(2, 8, 4, 4).astype(np.float32)},
                fetch_list=[out])
    finally:
        L.BlockLowerer._run_op = orig
    assert seen["conv2d"][0] == "bfloat16"
    assert seen["batch_norm"][0] == "bfloat16"


def test_dropout_edge_rates_and_true_mask():
    """p=1.0 must not divide by zero; p=0.999 must not overflow uint8; the
    Mask output is the true keep mask even when X contains zeros."""
    x = layers.data(name="x", shape=[-1, 128], dtype="float32",
                    append_batch_size=False)
    y_all = layers.dropout(x, dropout_prob=1.0,
                           dropout_implementation="upscale_in_train")
    y_hi = layers.dropout(x, dropout_prob=0.999,
                          dropout_implementation="upscale_in_train")
    y = layers.dropout(x, dropout_prob=0.4,
                       dropout_implementation="upscale_in_train")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.ones((16, 128), np.float32)
    xv[:, ::2] = 0.0  # half the inputs are exact zeros (post-ReLU shape)
    prog = fluid.default_main_program()
    mask_name = prog.global_block().ops[-1].outputs["Mask"][0]
    a, h, o, m = exe.run(prog, feed={"x": xv},
                         fetch_list=[y_all, y_hi, y, mask_name])
    assert np.all(np.asarray(a) == 0.0)          # p=1: all dropped, no crash
    assert np.isfinite(np.asarray(h)).all()      # p=.999: no uint8 overflow
    o, m = np.asarray(o), np.asarray(m)
    # true mask: ~60% kept regardless of X's own zeros
    assert abs(m.mean() - 0.6) < 0.05, m.mean()
    # Out is nonzero exactly where mask kept AND input was nonzero
    np.testing.assert_array_equal(o != 0, (m != 0) & (xv != 0))
