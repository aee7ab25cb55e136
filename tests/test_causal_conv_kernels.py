"""The causal convolution's two Pallas kernels (`ops/linear_attention.py`:
`causal_conv_fwd`, `causal_conv_bwd`) under the Pallas interpreter on the
CPU, at channel counts that fill lanes: against the jnp form the op keeps
outside the kernels' envelope and its `jax.vjp`, over time blocks and the
row chunks inside one, so that the K - 1 rows a tap reaches back cross a
chunk, a block, the sequence's start (zeros) and its end (nothing after);
causality both ways; the plan's table; the op through a Program with and
without the kernels; and that a step holds each kernel once a layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models
from paddle_tpu.ops import _kernels
from paddle_tpu.ops import linear_attention as la

import qwen3_next_reference as ref
from attention_program import kernel_calls, step_text
from decoder_case import RTOL, frob, run_piece, tiny_args

TINY = tiny_args("qwen3_next")

B, C = 2, 128
# T -> (time block, rows a loop step takes), by `_conv_blocks`: one block of
# one chunk; three blocks; one block of two chunks; three blocks of eight
BLOCKS = {16: (16, 16), 48: (16, 16), 128: (128, 64), 1536: (512, 64)}


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _inputs(t, k, dtype, c=C, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, t, c), dtype)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (c, k)), jnp.float32)
    d_out = jnp.asarray(rng.randn(B, t, c), dtype)
    return x, w, d_out


def _xla(x, w, d_out, silu=True):
    want, vjp = jax.vjp(lambda x, w: la._conv_xla(x, w, silu), x, w)
    return (want,) + vjp(d_out)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("t", sorted(BLOCKS))
def test_kernels_match_the_jnp_form_and_its_vjp(t, k, dtype, interpreted):
    """Out, dX and dW (summed over the batch, the time blocks and the
    chunks). Both sides sum in float32 and round once at the end, so in
    bf16 they differ by last bits of a few elements."""
    assert la._conv_blocks(t, C) == (BLOCKS[t][0], C, BLOCKS[t][1])
    x, w, d_out = _inputs(t, k, dtype)
    out = la._conv_forward(x, w, True)
    dx, dw = la._conv_backward(x, w, d_out, True)
    want, dx_want, dw_want = _xla(x, w, d_out)
    assert out.dtype == dx.dtype == x.dtype and out.shape == dx.shape
    assert dw.dtype == jnp.float32 and dw.shape == (C, k)
    tol = RTOL if dtype == "float32" else 1e-3
    assert frob(out, want) < tol
    assert frob(dx, dx_want) < tol
    assert frob(dw, dw_want) < RTOL


@pytest.mark.parametrize("t", [48, 128])
def test_without_an_activation_the_kernels_are_the_bare_convolution(
        t, interpreted):
    x, w, d_out = _inputs(t, 4, "float32", c=256)
    out = la._conv_forward(x, w, False)
    dx, dw = la._conv_backward(x, w, d_out, False)
    want, dx_want, dw_want = _xla(x, w, d_out, silu=False)
    assert frob(out, want) < RTOL
    assert frob(dx, dx_want) < RTOL and frob(dw, dw_want) < RTOL
    padded = np.pad(np.asarray(x), ((0, 0), (3, 0), (0, 0)))
    by_hand = sum(padded[:, j:j + t] * np.asarray(w)[:, j] for j in range(4))
    assert frob(out, by_hand) < RTOL


def test_the_weight_gradient_is_summed_over_the_batch(interpreted):
    x, w, d_out = _inputs(48, 4, "float32")
    _, dw = la._conv_backward(x, w, d_out, True)
    each = [la._conv_backward(x[b:b + 1], w, d_out[b:b + 1], True)[1]
            for b in range(B)]
    assert frob(dw, sum(each)) < RTOL
    assert frob(each[0], each[1]) > 0.1


# t0: inside a chunk, a block's last row, a block's first row, a chunk's
# first row inside a block
@pytest.mark.parametrize("t,t0", [(48, 7), (48, 31), (48, 32), (128, 64),
                                  (128, 66)])
@pytest.mark.parametrize("k", [2, 4])
def test_an_output_reads_no_later_input_and_a_gradient_no_earlier_one(
        t, t0, k, interpreted):
    x, w, d_out = _inputs(t, k, "float32")
    out = np.asarray(la._conv_forward(x, w, True))
    moved = np.asarray(la._conv_forward(x.at[:, t0].add(1.0), w, True))
    assert np.array_equal(moved[:, :t0], out[:, :t0])
    assert np.array_equal(moved[:, t0 + k:], out[:, t0 + k:])
    assert not np.allclose(moved[:, t0:t0 + k], out[:, t0:t0 + k])
    dx = np.asarray(la._conv_backward(x, w, d_out, True)[0])
    moved = np.asarray(la._conv_backward(
        x, w, d_out.at[:, t0].add(1.0), True)[0])
    assert np.array_equal(moved[:, t0 + 1:], dx[:, t0 + 1:])
    assert np.array_equal(moved[:, :t0 - (k - 1)], dx[:, :t0 - (k - 1)])
    assert not np.allclose(moved[:, t0 - (k - 1):t0 + 1],
                           dx[:, t0 - (k - 1):t0 + 1])


@pytest.mark.parametrize("t,c,k,plan", [
    (4096, 8192, 4, "kernel"), (16, 128, 2, "kernel"), (48, 384, 9, "kernel"),
    (4096, 8192, 1, "kernel"), (6, 3, 4, "xla"), (4096, 8192, 10, "xla"),
    (4096, 8200, 4, "xla"), (4104, 8192, 4, "xla"), (128, 96, 4, "xla"),
    (32, 6, 4, "xla")])
def test_plan_reads_the_shape_alone(t, c, k, plan):
    assert la._conv_plan(t, c, k) == plan


def test_a_cpu_backend_takes_the_kernels_only_when_interpreted(monkeypatch):
    monkeypatch.setattr(_kernels, "interpret", lambda: False)
    assert not la._conv_kernels_run(4096, 8192, 4)
    monkeypatch.setattr(_kernels, "interpret", lambda: True)
    assert la._conv_kernels_run(4096, 8192, 4)
    assert not la._conv_kernels_run(6, 3, 4)


# -- the op through a Program ---------------------------------------------------

def _layer(x, w, k=4):
    return run_piece(
        lambda d: [layers.causal_conv1d(
            d["x"], k, param_attr=fluid.ParamAttr(name="w"))],
        {"x": x}, {"w": w})


def _counted(monkeypatch):
    """Count the calls of both kernels' wrappers from here on."""
    calls = {"fwd": 0, "bwd": 0}
    forward, backward = la._conv_forward, la._conv_backward

    def fwd(*a):
        calls["fwd"] += 1
        return forward(*a)

    def bwd(*a):
        calls["bwd"] += 1
        return backward(*a)

    monkeypatch.setattr(la, "_conv_forward", fwd)
    monkeypatch.setattr(la, "_conv_backward", bwd)
    return calls


def test_the_op_gives_the_same_numbers_with_and_without_the_kernels(
        monkeypatch):
    """One op, one grad op (`causal_conv1d_grad`): the kernels where the
    backend takes them, the jnp form and its `jax.vjp` where it does not;
    both are the reference's convolution."""
    rng = np.random.RandomState(3)
    x = rng.randn(B, 48, C).astype(np.float32)
    w = rng.uniform(-0.5, 0.5, (C, 4)).astype(np.float32)
    calls = _counted(monkeypatch)
    (xla,), xla_grads, probe = _layer(x, w)
    assert calls == {"fwd": 0, "bwd": 0}
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    (kernel,), kernel_grads, _ = _layer(x, w)
    assert calls["fwd"] >= 1 and calls["bwd"] >= 1
    gx, gw = jax.grad(lambda a, b: jnp.sum(ref.causal_conv_silu(a, b)
                                           * probe), (0, 1))(x, w)
    for out, grads in ((xla, xla_grads), (kernel, kernel_grads)):
        assert frob(out, ref.causal_conv_silu(x, w)) < RTOL
        assert sorted(grads) == ["w", "x"]
        assert frob(grads["x"], gx) < RTOL and frob(grads["w"], gw) < RTOL


def test_outside_the_envelope_the_grad_op_is_the_vjp_of_the_jnp_form(
        interpreted, monkeypatch):
    """`X (2, 6, 3)`, as `tests/test_op_autosweep.py` has it: no kernel
    either way, whatever the backend would take."""
    calls = _counted(monkeypatch)
    rng = np.random.RandomState(4)
    x = rng.randn(2, 6, 3).astype(np.float32)
    w = rng.uniform(-0.5, 0.5, (3, 4)).astype(np.float32)
    (out,), grads, probe = _layer(x, w)
    assert calls == {"fwd": 0, "bwd": 0}
    want, vjp = jax.vjp(lambda a, b: la._conv_xla(a, b, True), x, w)
    gx, gw = vjp(jnp.asarray(probe))
    assert np.array_equal(out, want)
    assert np.array_equal(grads["x"], gx) and np.array_equal(grads["w"], gw)


def test_a_step_holds_each_kernel_once_a_layer(interpreted):
    """A Qwen3-Next whose convolutions fall in the envelope (128 channels,
    128 tokens), one training step traced: three delta-rule layers, so
    `causal_conv_fwd` three times and `causal_conv_bwd` three times. A grad
    op that traced the forward again would show a fourth `causal_conv_fwd`
    (a jaxpr keeps what XLA would later merge)."""
    kw = dict(TINY, key_dim=16, value_dim=16)       # 2*2*16 + 4*16 = 128
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = models.qwen3_next.build(**kw)[1]["loss"]
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {"tokens": rng.randint(0, kw["vocab_size"], (1, kw["seq_len"])),
            "labels": rng.randint(0, kw["vocab_size"], (1, kw["seq_len"]))}
    feed = {n: v.astype("int64") for n, v in feed.items()}
    first, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    second, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert np.isfinite(first) and second < first
    text = step_text(exe, main, scope, feed)
    assert kernel_calls(text, "causal_conv_fwd") == 3
    assert kernel_calls(text, "causal_conv_bwd") == 3
