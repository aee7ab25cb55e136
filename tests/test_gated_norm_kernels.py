"""The gated norm's two Pallas kernels (`ops/decoder_block.py`:
`gated_norm_fwd`, `gated_norm_bwd`) under the Pallas interpreter on the CPU:
against the jnp form the op keeps outside the kernels' envelope
(`_gated_norm_xla`) and its `jax.vjp`; the plan's table by shape; the op
through a Program with and without the kernels; and that a Qwen3-Next's step
holds each kernel once a delta-rule layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models
from paddle_tpu.core import registry
from paddle_tpu.core.lowering import FWD_OP_ATTR
from paddle_tpu.ops import _kernels
from paddle_tpu.ops import decoder_block as db

from attention_program import kernel_calls, step_text
from decoder_case import run_piece, tiny_args

TINY = tiny_args("qwen3_next")

EPS = 1e-6
# (leading dims, tokens, heads, head): the published head of 128 at 2 and 32
# heads, token counts that are one block, several, and several loop steps
SHAPES = {
    "two_heads_three_blocks": ((1,), 48, 2, 128),
    "two_heads_two_batches": ((2,), 512, 2, 128),
    "all_32_heads": ((1,), 96, 32, 128),
    "all_32_heads_one_block": ((1,), 256, 32, 128),
    "head_of_256": ((1,), 64, 4, 256),
    "no_leading_dim": ((), 32, 2, 128),
}


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _operands(case, dtype, seed=0):
    lead, T, H, D = SHAPES[case]
    rng = np.random.RandomState(seed)
    x, gate, d_y = (jnp.asarray(scale * rng.randn(*lead, T, H, D), dtype)
                    for scale in (2.0, 1.0, 1.0))
    return x, gate, jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32), d_y


def _xla(x, gate, w):
    return db._gated_norm_xla(x, gate, w, EPS)


def _close(got, want, dtype, places):
    """Within `places` last places of `dtype` at the largest value."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    bits = 8 if dtype == "bfloat16" else 24
    top = 2.0 ** np.ceil(np.log2(np.max(np.abs(want))))
    return np.max(np.abs(got - want)) <= places * top * 2.0 ** -bits


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(SHAPES))
def test_the_forward_kernel_is_the_jnp_form(case, dtype, interpreted):
    """Float32 statistics, the normed value rounded to X's dtype, the
    float32 gate product, one rounding at the end: the same arithmetic in
    the same order, so Y comes out bitwise."""
    x, gate, w, _ = _operands(case, dtype)
    assert db._gated_norm_plan(x.shape, x.dtype) == "kernel"
    y = db._gated_norm_call(x, gate, w, EPS)
    want = _xla(x, gate, w)
    assert y.shape == x.shape and y.dtype == x.dtype
    assert np.array_equal(np.asarray(y, np.float32),
                          np.asarray(want, np.float32))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(SHAPES))
def test_the_backward_kernel_is_the_vjp_of_the_jnp_form(case, dtype,
                                                        interpreted):
    """dX, dGate in their operands' dtypes and dScale in float32 against
    `jax.vjp` of the jnp form. `astype`'s vjp rounds the normed value's
    cotangent to X's dtype on its way back, which the kernel, all float32
    inside, does not: dX is within two last places in bf16."""
    x, gate, w, d_y = _operands(case, dtype)
    dx, dgate, dw = db._gated_norm_call(x, gate, w, EPS, d_y)
    want_x, want_gate, want_w = jax.vjp(_xla, x, gate, w)[1](d_y)
    assert dx.shape == dgate.shape == x.shape and dw.shape == w.shape
    assert dx.dtype == dgate.dtype == x.dtype and dw.dtype == jnp.float32
    assert np.any(np.asarray(dx, np.float32))
    assert _close(dx, want_x, dtype, 2 if dtype == "bfloat16" else 64)
    assert _close(dgate, want_gate, dtype, 2 if dtype == "bfloat16" else 64)
    # a sum over tokens x heads of bf16-rounded terms (float32: of its order)
    rows = x.size // x.shape[-1]
    assert np.max(np.abs(np.asarray(dw) - np.asarray(want_w))) <= (
        2.0 ** -8 * rows ** 0.5 * 8 if dtype == "bfloat16"
        else 1e-6 * rows)


def test_the_backward_kernel_is_the_float32_gradient(interpreted):
    """Against `jax.grad` of the rule written without its rounding, in
    float32: the three gradients to float32's last places."""
    x, gate, w, d_y = _operands("two_heads_three_blocks", "float32", seed=2)

    def plain(x, gate, w):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return jnp.sum(x * jax.lax.rsqrt(ms + EPS) * w * jax.nn.silu(gate)
                       * d_y)

    want = jax.grad(plain, (0, 1, 2))(x, gate, w)
    got = db._gated_norm_call(x, gate, w, EPS, d_y)
    for a, b in zip(got, want):
        assert np.allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,dtype,plan", [
    ((1, 4096, 32, 128), "bfloat16", "kernel"),     # the cell's operand
    ((1, 4096, 32, 128), "float32", "kernel"),
    ((2, 16, 2, 256), "bfloat16", "kernel"),
    ((48, 2, 128), "float32", "kernel"),
    ((3, 2, 6), "float32", "xla"),           # tests/test_op_autosweep.py
    ((2, 7, 4, 8), "float32", "xla"),        # tests/test_qwen3_next.py
    ((1, 128, 4, 8), "bfloat16", "xla"),     # the tiny model's heads
    ((1, 128, 4, 96), "bfloat16", "xla"),
    ((1, 128, 4, 130), "bfloat16", "xla"),
    ((1, 24, 4, 128), "bfloat16", "xla"),    # 24 tokens: no whole tile
    ((1, 128, 4, 128), "float16", "xla"),
    ((32, 128), "float32", "xla"),           # no token axis
])
def test_plan_reads_shape_and_dtype_alone(shape, dtype, plan):
    assert db._gated_norm_plan(shape, jnp.dtype(dtype)) == plan


def test_a_cpu_backend_takes_the_kernels_only_when_interpreted(monkeypatch):
    cell = ((1, 4096, 32, 128), jnp.dtype("bfloat16"))
    monkeypatch.setattr(_kernels, "interpret", lambda: False)
    assert not db._gated_norm_kernels_run(*cell)
    monkeypatch.setattr(_kernels, "interpret", lambda: True)
    assert db._gated_norm_kernels_run(*cell)
    assert not db._gated_norm_kernels_run((2, 7, 4, 8), jnp.dtype("float32"))


@pytest.mark.parametrize("T,H,D,itemsize,fwd,bwd", [
    (4096, 32, 128, 2, (128, 32), (256, 16)),       # the cell's
    (4096, 32, 128, 4, (128, 16), (256, 8)),
    (48, 2, 128, 2, (16, 2), (16, 2)), (96, 32, 128, 4, (32, 32), (32, 32)),
    (64, 4, 256, 2, (64, 4), (64, 4)), (512, 7, 128, 2, (128, 7), (256, 7)),
    # a head whose float32 tile is past half a MiB at that height takes
    # fewer rows (PR 65: at 128 rows of 2048 float32 lanes the kernels' ten
    # tiles are 10 MiB beside 10 MiB of blocks, past the scoped VMEM; the
    # first published shape that wide is Granite 4.0-H's group of 4096)
    (128, 6, 2048, 4, (64, 2), (64, 2)),
    (2048, 1, 4096, 2, (32, 1), (32, 1))])
def test_a_block_is_whole_heads_of_at_most_a_mebibyte(T, H, D, itemsize, fwd,
                                                      bwd):
    assert db._gated_norm_blocks(T, H, D, itemsize, False) == fwd
    assert db._gated_norm_blocks(T, H, D, itemsize, True) == bwd


# -- the op through a Program ---------------------------------------------------

def _counted(monkeypatch):
    calls = []
    call = db._gated_norm_call

    def counted(X, Gate, Scale, eps, d_y=None):
        calls.append("fwd" if d_y is None else "bwd")
        return call(X, Gate, Scale, eps, d_y)

    monkeypatch.setattr(db, "_gated_norm_call", counted)
    return calls


def _layer(x, z, w):
    return run_piece(
        lambda d: [layers.gated_rms_norm(
            d["x"], d["z"], param_attr=fluid.ParamAttr(name="w"))],
        {"x": x, "z": z}, {"w": w})


@pytest.mark.parametrize("shape", [(2, 32, 2, 128), (1, 48, 32, 128)],
                         ids=["two_heads", "all_32_heads"])
def test_the_op_gives_the_same_numbers_with_and_without_the_kernels(
        shape, monkeypatch):
    """One op, one grad op (`gated_rms_norm_grad`): the kernels where the
    backend takes them, the jnp form and its `jax.vjp` where it does not."""
    rng = np.random.RandomState(3)
    x, z = (rng.randn(*shape).astype(np.float32) for _ in range(2))
    w = rng.uniform(0.5, 1.5, shape[-1]).astype(np.float32)
    calls = _counted(monkeypatch)
    (xla,), xla_grads, _ = _layer(x, z, w)
    assert calls == []
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    (kernel,), kernel_grads, _ = _layer(x, z, w)
    assert sorted(set(calls)) == ["bwd", "fwd"]
    assert np.allclose(kernel, xla, atol=1e-6)
    assert sorted(kernel_grads) == sorted(xla_grads) == ["w", "x", "z"]
    for name in ("x", "z", "w"):
        assert np.any(xla_grads[name])
        # dScale: a float32 sum over tokens x heads in another order
        assert np.allclose(kernel_grads[name], xla_grads[name], rtol=1e-5,
                           atol=1e-4 if name == "w" else 1e-5), name


@pytest.mark.parametrize("shape", [(3, 2, 6), (2, 7, 4, 8), (1, 24, 2, 128)],
                         ids=["autosweep", "tiny_head", "24_tokens"])
def test_outside_the_envelope_the_grad_op_is_the_vjp_of_the_jnp_form(
        shape, interpreted, monkeypatch):
    """No kernel either way, whatever the backend would take."""
    calls = _counted(monkeypatch)
    rng = np.random.RandomState(4)
    x, z = (rng.randn(*shape).astype(np.float32) for _ in range(2))
    w = rng.uniform(0.5, 1.5, shape[-1]).astype(np.float32)
    (out,), grads, probe = _layer(x, z, w)
    assert calls == []
    want, vjp = jax.vjp(_xla, x, z, w)
    assert np.allclose(out, want, atol=1e-6)
    for name, g in zip(("x", "z", "w"), vjp(jnp.asarray(probe))):
        assert np.allclose(grads[name], g, atol=1e-5), name


def test_append_backward_emits_the_registered_grad():
    """The backward of a `gated_rms_norm` op is one `gated_rms_norm_grad`
    op, and the lowering hands it to the registered rule, which reads X,
    Gate, Scale and dY: not to the generic vjp of the forward rule, which
    would lower the forward kernel again inside the backward pass."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[1, 32, 2, 128], dtype="float32",
                        append_batch_size=False, stop_gradient=False)
        y = layers.gated_rms_norm(x, x, param_attr=fluid.ParamAttr(name="w"))
        fluid.append_backward(layers.reduce_sum(y))
    grad, = [op for op in main.global_block().ops
             if op.type == "gated_rms_norm_grad"]
    assert grad.attrs[FWD_OP_ATTR]["type"] == "gated_rms_norm"
    assert sorted(grad.attrs[FWD_OP_ATTR]["inputs"]) == ["Gate", "Scale", "X"]
    assert registry.get_op_def("gated_rms_norm").grad_lower \
        is db._gated_rms_norm_grad


def test_a_step_holds_each_kernel_once_a_layer(interpreted):
    """A Qwen3-Next whose value heads fall in the envelope (heads of 128,
    128 tokens; the key heads of 8 keep the rule and the convolution on
    their jnp forms), one training step traced: three delta-rule layers, so
    `gated_norm_fwd` three times and `gated_norm_bwd` three times. A grad
    op left to the generic vjp would show a fourth, fifth and sixth
    `gated_norm_fwd` (a jaxpr keeps what XLA would later merge)."""
    kw = dict(TINY, value_dim=128)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = models.qwen3_next.build(**kw)[1]["loss"]
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {n: rng.randint(0, kw["vocab_size"], (1, kw["seq_len"]))
            .astype("int64") for n in ("tokens", "labels")}
    first, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    second, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert np.isfinite(first) and second < first
    text = step_text(exe, main, scope, feed)
    assert kernel_calls(text, "gated_norm_fwd") == 3
    assert kernel_calls(text, "gated_norm_bwd") == 3
    assert kernel_calls(text, "gdn_fwd") == 0
