"""The rotary op's Pallas kernel (`ops/decoder_block.py`: `rotary_fwd`,
`rotary_bwd`) under the Pallas interpreter on the CPU, at every regime the
five decoder models run it in: against the jnp form the op keeps outside the
kernel's envelope (`_rotary_xla`) and its `jax.vjp`; the plan's table by
shape; the op through a Program with and without the kernel; and that a
one-layer Mellum2's step holds each kernel twice (q and k) and counts the
four ops on its compile event."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models, observe
from paddle_tpu.ops import _kernels
from paddle_tpu.ops import decoder_block as db

from attention_program import kernel_calls, step_text
from decoder_case import TINY_YARN, run_piece, tiny_args

TINY = tiny_args("mellum2")

YARN = {"factor": 8.0, "original_max_position_embeddings": 64}
# the regimes: (leading dims, tokens, head, interleaved, scaling)
REGIMES = {
    "whole_head_q": ((1, 4), 1024, 128, False, None),       # two token blocks
    "whole_head_yarn": ((1, 4), 128, 128, False, YARN),
    "whole_head_k_4_heads": ((2, 4), 48, 128, False, None),     # three blocks
    "whole_head_one_head": ((1, 1), 64, 128, False, None),
    "head_of_256": ((1, 2), 32, 256, False, None),
    "interleaved_q": ((1, 8), 128, 64, True, None),
    "interleaved_k_1_head": ((1, 1), 1024, 64, True, None),
    "interleaved_three_dims": ((3,), 16, 64, True, None),
}


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _tables(T, R, scaling=None, theta=10000.0):
    return db.rotary_tables(T, *db.rotary_frequencies(R, theta, scaling))


def _operands(regime, dtype, seed=0):
    lead, T, D, interleaved, scaling = REGIMES[regime]
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(*lead, T, D), dtype)
    g = jnp.asarray(rng.randn(*lead, T, D), dtype)
    return x, g, interleaved, _tables(T, D, scaling)


def _ulps(got, want, dtype):
    """The largest difference in units of `want`'s last place in `dtype`."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    bits = 8 if dtype == "bfloat16" else 24
    exponent = np.floor(np.log2(np.maximum(np.abs(want), 1e-30)))
    return np.max(np.abs(got - want) / 2.0 ** (exponent - (bits - 1)))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_kernel_is_the_jnp_form_and_its_grad_the_vjp(regime, dtype,
                                                         interpreted):
    """`Out` and dX within one rounding of X's dtype of `_rotary_xla` and of
    its `jax.vjp`: both multiply and add in float32 against the same float32
    tables and round once (bf16 comes out bitwise; in float32 the two differ
    where one side fuses the multiply into the add)."""
    x, g, interleaved, (cos, sin) = _operands(regime, dtype)
    D = x.shape[-1]
    assert db._rotary_plan(x.shape, x.dtype, D, interleaved) != "xla"
    out = db._rotary_call(x, cos, sin, interleaved, False)
    dx = db._rotary_call(g, cos, sin, interleaved, True)
    want, vjp = jax.vjp(
        lambda x: db._rotary_xla(x, D, interleaved, cos, sin), x)
    dx_want, = vjp(g)
    assert out.shape == dx.shape == x.shape
    assert out.dtype == dx.dtype == x.dtype
    assert _ulps(out, want, dtype) <= 1.0
    # a float32 sum that cancels is off by a last place of its TERMS
    assert np.max(np.abs(np.asarray(dx, np.float64)
                         - np.asarray(dx_want, np.float64))) <= (
        2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -20)
    assert _ulps(dx, dx_want, dtype) <= (1.0 if dtype == "bfloat16" else 64)
    if dtype == "bfloat16":
        assert np.array_equal(np.asarray(out, np.float32),
                              np.asarray(want, np.float32))


@pytest.mark.parametrize("regime", ["whole_head_q", "interleaved_q"])
def test_the_grad_is_the_inverse_rotation(regime, interpreted):
    """A rotation's transpose is its inverse: `rotary_bwd` of `rotary_fwd`
    gives x back (for interleaved pairs in x's own layout, the
    `[evens | odds]` permutation undone), from dOut alone."""
    x, _, interleaved, (cos, sin) = _operands(regime, "float32")
    back = db._rotary_call(db._rotary_call(x, cos, sin, interleaved, False),
                           cos, sin, interleaved, True)
    assert np.allclose(back, x, atol=1e-5)


def test_yarn_scales_both_tables_inside_the_kernel(interpreted):
    """With YaRN's `attention_factor` the rotation is no longer orthogonal:
    forward then grad multiplies by the factor squared."""
    x, _, interleaved, (cos, sin) = _operands("whole_head_yarn", "float32")
    factor = 0.1 * np.log(YARN["factor"]) + 1.0
    back = db._rotary_call(db._rotary_call(x, cos, sin, interleaved, False),
                           cos, sin, interleaved, True)
    assert np.allclose(back, np.asarray(x) * factor ** 2, atol=1e-5)


@pytest.mark.parametrize("shape,dtype,R,interleaved,plan", [
    # the five cells' operands
    ((1, 32, 8192, 128), "bfloat16", 128, False, "roll"),    # Mellum2 q
    ((1, 4, 8192, 128), "bfloat16", 128, False, "roll"),     # Mellum2 k
    ((1, 16, 4096, 128), "bfloat16", 128, False, "roll"),    # Ouro, OLMoE
    ((1, 32, 4096, 64), "bfloat16", 64, True, "dot"),        # Kanana-2 q
    ((1, 1, 4096, 64), "bfloat16", 64, True, "dot"),         # Kanana-2 k
    ((1, 16, 4096, 256), "bfloat16", 64, False, "xla"),      # Qwen3-Next q
    ((1, 2, 4096, 256), "bfloat16", 64, False, "xla"),       # Qwen3-Next k
    ((1, 32, 8192, 128), "float32", 128, False, "roll"),
    # the CPU tests' shapes
    ((2, 4, 256, 16), "float32", 16, False, "xla"),          # tiny Mellum2
    ((2, 2, 6, 8), "float32", 8, False, "xla"),
    ((2, 2, 6, 8), "float32", 4, True, "xla"),
    ((2, 4, 24, 64), "float32", 64, False, "xla"),   # rotate-half at 64
    ((2, 4, 24, 128), "float32", 128, False, "xla"),     # 24 tokens
    ((2, 4, 32, 128), "float32", 128, True, "xla"),  # interleaved at 128
    ((2, 4, 32, 128), "float16", 128, False, "xla"),
    ((32, 128), "float32", 128, False, "xla"),           # no leading dim
])
def test_plan_reads_shape_and_dtype_alone(shape, dtype, R, interleaved,
                                          plan):
    assert db._rotary_plan(shape, jnp.dtype(dtype), R, interleaved) == plan


def test_a_cpu_backend_takes_the_kernel_only_when_interpreted(monkeypatch):
    q = ((1, 32, 8192, 128), jnp.dtype("bfloat16"), 128, False)
    monkeypatch.setattr(_kernels, "interpret", lambda: False)
    assert not db._rotary_kernel_runs(*q)
    monkeypatch.setattr(_kernels, "interpret", lambda: True)
    assert db._rotary_kernel_runs(*q)
    assert not db._rotary_kernel_runs((2, 2, 6, 8), jnp.dtype("float32"), 8,
                                      False)


@pytest.mark.parametrize("N,T,D,itemsize,blocks", [
    (32, 8192, 128, 2, (8, 512)), (4, 8192, 128, 2, (4, 512)),
    (16, 4096, 128, 2, (8, 512)), (32, 4096, 64, 2, (16, 512)),
    (1, 4096, 64, 2, (1, 512)), (6, 48, 128, 4, (6, 16)),
    (7, 512, 128, 4, (1, 512))])
def test_a_block_is_whole_heads_of_at_most_512_tokens(N, T, D, itemsize,
                                                      blocks):
    assert db._rotary_blocks(N, T, D, itemsize) == blocks


# -- the op through a Program ---------------------------------------------------

def _counted(monkeypatch):
    calls = []
    call = db._rotary_call

    def counted(X, cos, sin, interleaved, backward):
        calls.append("bwd" if backward else "fwd")
        return call(X, cos, sin, interleaved, backward)

    monkeypatch.setattr(db, "_rotary_call", counted)
    return calls


@pytest.mark.parametrize("kw,shape", [
    (dict(theta=1e4), (2, 4, 32, 128)),
    (dict(theta=1e6, scaling=YARN), (1, 4, 32, 128)),
    (dict(theta=1e4, interleaved=True), (2, 4, 32, 64)),
    (dict(theta=1e4, interleaved=True), (2, 1, 32, 64))],
    ids=["plain", "yarn", "interleaved_q", "interleaved_k"])
def test_the_op_gives_the_same_numbers_with_and_without_the_kernel(
        kw, shape, monkeypatch):
    """One op, one grad op (`rotary_embedding_grad`): the kernel where the
    backend takes it, the jnp form and its `jax.vjp` where it does not."""
    x = np.random.RandomState(3).randn(*shape).astype(np.float32)
    calls = _counted(monkeypatch)
    build = lambda d: [layers.rotary_embedding(d["x"], **kw)]
    (xla,), xla_grads, probe = run_piece(build, {"x": x})
    assert calls == []
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    (kernel,), kernel_grads, _ = run_piece(build, {"x": x})
    assert sorted(set(calls)) == ["bwd", "fwd"]
    assert np.allclose(kernel, xla, atol=1e-5)
    assert np.allclose(kernel_grads["x"], xla_grads["x"], atol=1e-5)
    assert np.any(xla_grads["x"])


@pytest.mark.parametrize("kw,shape", [
    (dict(theta=1e4), (2, 2, 6, 8)),
    (dict(theta=1e4, rotary_dim=64), (1, 2, 32, 256)),
    (dict(theta=1e4, rotary_dim=4, interleaved=True), (2, 2, 6, 8))],
    ids=["tiny_head", "a_part_of_the_head", "tiny_interleaved_part"])
def test_outside_the_envelope_the_grad_op_is_the_vjp_of_the_jnp_form(
        kw, shape, interpreted, monkeypatch):
    """No kernel either way, whatever the backend would take: a rotary part
    inside a wider head (Qwen3-Next's 64 of 256) and the tiny heads of the
    other CPU tests."""
    calls = _counted(monkeypatch)
    x = np.random.RandomState(4).randn(*shape).astype(np.float32)
    (out,), grads, probe = run_piece(
        lambda d: [layers.rotary_embedding(d["x"], **kw)], {"x": x})
    assert calls == []
    R = kw.get("rotary_dim", shape[-1])
    cos, sin = _tables(shape[-2], R)
    want, vjp = jax.vjp(lambda x: db._rotary_xla(
        x, R, kw.get("interleaved", False), cos, sin), jnp.asarray(x))
    assert np.allclose(out, want, atol=1e-6)
    assert np.allclose(grads["x"], vjp(jnp.asarray(probe))[0], atol=1e-6)


@pytest.mark.parametrize("kind,scaling", [("sliding_attention", None),
                                          ("full_attention", TINY_YARN)])
def test_a_one_layer_mellum2_counts_its_four_rotary_kernels(
        kind, scaling, interpreted):
    """A Mellum2 layer whose heads fall in the envelope (heads of 128, 128
    tokens), one training step: q and k each take `rotary_fwd` once and
    `rotary_bwd` once, and the four ops count themselves on the compile
    event (`rotary_kernel_ops`). A grad op left to the generic vjp would
    trace a third `rotary_fwd` for each."""
    kw = dict(TINY, n_layer=1, layer_types=[kind], head_dim=128, n_head=2,
              n_kv_head=1, seq_len=128, sliding_window=32,
              rope_scaling=scaling)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = models.mellum2.build(**kw)[1]["loss"]
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {n: rng.randint(0, kw["vocab_size"], (1, kw["seq_len"]))
            .astype(np.int32) for n in ("tokens", "labels")}
    first, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    second, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert np.isfinite(first) and second < first
    assert observe.observatory().latest(main._uid).detail[
        "rotary_kernel_ops"] == 4
    text = step_text(exe, main, scope, feed)
    assert kernel_calls(text, "rotary_fwd") == 2
    assert kernel_calls(text, "rotary_bwd") == 2


def test_without_the_kernel_the_compile_event_counts_none():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[1, 2, 32, 128], dtype="float32",
                        append_batch_size=False, stop_gradient=False)
        fluid.append_backward(layers.reduce_sum(
            layers.rotary_embedding(x, theta=1e4)))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed={"x": np.ones((1, 2, 32, 128), np.float32)},
            fetch_list=["x@GRAD"], scope=scope)
    assert "rotary_kernel_ops" not in observe.observatory().latest(
        main._uid).detail
