"""`moe_token_sum` (`ops/moe.py`): a share's token-side sums as one Pallas
call, under the interpreter on the CPU, against the loop it replaces on the
chip (`_token_sum_loop`, which stays the CPU path and the path of a shape
outside `_token_sum_plan`). Both add a token's rows in row order, in
float32, and round once, so they agree bitwise; but for float32 rows times
a router weight, where XLA:CPU contracts the kernel body's product and sum
into one fused multiply-add (one rounding where the loop, whose product and
scatter-add are two instructions, makes two): a float32 rounding a row.
The chip has no such instruction and agrees bitwise there too
(`tests/test_kernel_names_tpu.py`)."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import moe

from attention_program import kernel_calls, step_text

N, K, EXPERTS, FIRST, HELD, TILE = 64, 4, 16, 4, 4, 8


def _even(rng):
    return np.stack([rng.permutation(EXPERTS)[:K] for _ in range(N)])


def _one_expert(rng):
    """Every assignment on one held expert: all N * K rows are used."""
    return np.full((N, K), FIRST + 1)


def _none_held(rng):
    return np.stack([rng.permutation(FIRST)[:K] for _ in range(N)])


def _past_the_used_rows(rng):
    """24 + 16 + 8 used rows of 288: the second of the 32-row chunks is the
    last, and three quarters of it lie behind the used rows."""
    index = _none_held(rng)
    index[:24, 0], index[:16, 1], index[:8, 2] = FIRST, FIRST + 1, FIRST + 3
    return index


def _padded_groups(rng):
    """Groups of 13, 5, 0 and 27 assignments in tiles of 8: padding rows
    inside every group that has rows."""
    index = _none_held(rng)
    index[:13, 0], index[20:25, 1], index[30:57, 2] = \
        FIRST, FIRST + 1, FIRST + 3
    return index


ROUTINGS = {"even": (_even, None), "one_expert": (_one_expert, N * K),
            "none_held": (_none_held, 0),
            "past_the_used_rows": (_past_the_used_rows, 48),
            "padded_groups": (_padded_groups, 16 + 8 + 32)}


def _layout(index, width, dtype, rng):
    """`_dispatch_share`'s `Source` and `GroupSizes` for a routing, and rows
    to move: NaN in every padding row and in every row behind the used
    ones."""
    counts = np.bincount(index.reshape(-1), minlength=EXPERTS)
    out = moe._dispatch_share(
        jnp.zeros((N, width), dtype), jnp.asarray(index, jnp.int32),
        jnp.asarray(counts, jnp.int32), TILE, FIRST, HELD)
    source, sizes = out["Source"], out["GroupSizes"]
    moved = rng.randn(source.shape[0], width).astype(np.float32)
    moved[np.asarray(source) < 0] = np.nan
    return jnp.asarray(moved, dtype), source, sizes


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_kernel_is_the_loop(routing, scaled, dtype, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(3)
    make, used = ROUTINGS[routing]
    width = 256
    moved, source, sizes = _layout(make(rng), width, dtype, rng)
    rows = moved.shape[0]
    assert rows == N * K + HELD * TILE
    if used is not None:
        assert int(sizes.sum()) == used
    scale = jnp.asarray(rng.rand(N * K), jnp.float32) if scaled else None
    plan = moe._token_sum_plan(N, K, rows, width, dtype)
    assert plan == (256, 32, 64)
    got = moe._token_sum_call(moved, source, K, N, sizes, dtype, plan, scale)
    want = moe._token_sum_loop(moved, source, K, N, sizes, scale) \
        .astype(dtype)
    assert got.dtype == want.dtype and got.shape == (N, width)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    if scaled and dtype == jnp.float32:
        # the interpreter's fused multiply-add: a rounding a row, k rows
        np.testing.assert_allclose(got, want, rtol=0, atol=K * 2.0 ** -22)
    else:
        np.testing.assert_array_equal(got, want)
    if used == 0:
        assert not got.any()
    # the dispatcher takes the same call
    np.testing.assert_array_equal(got, np.asarray(moe._tokens_from_rows(
        moved, source, K, N, sizes, dtype, scale), np.float32))


def test_several_column_blocks_and_a_result_of_another_dtype(monkeypatch):
    """Two column blocks (an accumulator budget of half the width) each
    start from zeros and write their own columns; float32 out of bf16
    rows is the sums unrounded."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(moe, "_TOKEN_SUM_ACC_BYTES", N * 128 * 4)
    rng = np.random.RandomState(4)
    moved, source, sizes = _layout(_even(rng), 256, jnp.bfloat16, rng)
    plan = moe._token_sum_plan(N, K, moved.shape[0], 256, jnp.bfloat16)
    assert plan == (128, 32, 64)
    scale = jnp.asarray(rng.rand(N * K), jnp.float32)
    got = moe._token_sum_call(moved, source, K, N, sizes, jnp.float32, plan,
                              scale)
    want = moe._token_sum_loop(moved, source, K, N, sizes, scale)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=K * 2.0 ** -22)


@pytest.mark.parametrize("n,k,rows,width,dtype,plan", [
    # the four cells: one column block each
    (4096, 10, 45056, 2048, jnp.bfloat16, (2048, 512, 512)),
    (4096, 6, 26624, 2048, jnp.bfloat16, (2048, 512, 512)),
    (4096, 8, 33792, 2048, jnp.bfloat16, (2048, 512, 512)),
    (8192, 8, 66560, 2304, jnp.bfloat16, (2304, 512, 512)),
    # twice the tokens: the widest whole-lane divisor that fits, 9 x 128
    (16384, 4, 66560, 2304, jnp.bfloat16, (1152, 512, 512)),
    (8192, 8, 66560, 2304, jnp.float32, (2304, 512, 512)),
    # a width that is not whole lanes; chunks of 8 rows of bf16; an
    # accumulator too large at 128 columns; Source and weights over SMEM;
    # another dtype
    (64, 4, 288, 200, jnp.float32, None),
    (64, 4, 264, 256, jnp.bfloat16, None),
    (64, 4, 264, 256, jnp.float32, (256, 8, 64)),
    (2 ** 18, 1, 2 ** 15, 128, jnp.float32, None),
    (8192, 16, 133120, 2304, jnp.bfloat16, None),
    (64, 4, 288, 256, jnp.float16, None)])
def test_the_plan_reads_the_shapes(n, k, rows, width, dtype, plan):
    assert moe._token_sum_plan(n, k, rows, width, dtype) == plan


def test_a_shape_outside_the_plan_takes_the_loop(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(moe, "_token_sum_call", None)   # would raise
    rng = np.random.RandomState(5)
    moved, source, sizes = _layout(_even(rng), 200, jnp.float32, rng)
    got = moe._tokens_from_rows(moved, source, K, N, sizes, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(moe._token_sum_loop(moved, source, K, N, sizes)))


def test_the_cpu_backend_keeps_the_loop(monkeypatch):
    """Without the rehearsal switch a CPU backend runs no kernel, whatever
    the shape."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(moe, "_token_sum_call", None)
    rng = np.random.RandomState(6)
    moved, source, sizes = _layout(_even(rng), 256, jnp.float32, rng)
    assert moe._token_sum_plan(N, K, moved.shape[0], 256, jnp.float32)
    got = moe._tokens_from_rows(moved, source, K, N, sizes, jnp.float32)
    assert np.isfinite(np.asarray(got)).all()


def _share_program(index, x, weight, weights):
    """A share's expert layer on a given routing, the sum of its result
    times a fixed tensor as the loss: (the result, the gradients of the
    layer's input and the experts' weights, the `moe_token_sum` call sites
    of the step)."""
    counts = np.bincount(index.reshape(-1), minlength=EXPERTS) \
        .astype(np.int32)
    feed = {"x": x, "weight": weight, "index": index, "counts": counts,
            "probe": np.random.RandomState(99).randn(*x.shape)
            .astype(np.float32)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        d = {name: layers.data(name=name, shape=list(v.shape),
                               dtype=str(v.dtype), append_batch_size=False,
                               stop_gradient=name != "x")
             for name, v in feed.items()}
        out = layers.moe_experts(
            d["x"], {"weight": d["weight"], "index": d["index"],
                     "tokens_per_expert": d["counts"]}, EXPERTS, 16,
            name="e", first_expert=FIRST, experts_held=HELD)
        fluid.append_backward(layers.reduce_sum(
            layers.elementwise_mul(out, d["probe"])))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    for name, value in weights.items():
        scope.set_var(name, jnp.asarray(value))
    wrt = ["x"] + sorted(weights)
    fetched = exe.run(main, feed=feed, scope=scope,
                      fetch_list=[out] + [n + "@GRAD" for n in wrt])
    calls = kernel_calls(step_text(exe, main, scope, feed), "moe_token_sum")
    return fetched[0], dict(zip(wrt, fetched[1:])), calls


def test_the_ops_of_a_share_program_take_the_kernel(monkeypatch):
    """`moe_combine` and `moe_dispatch_grad` of a share Program 128 wide
    are one call each in the step (the registered grads do not trace a
    forward again), and the Program's result and gradients are those of
    the same Program on the loop (the layer's input gradient, which
    `moe_dispatch_grad` sums without weights, bitwise)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(7)
    n, width = 96, 128
    index = np.stack([rng.permutation(EXPERTS)[:K] for _ in range(n)]) \
        .astype(np.int32)
    x = rng.randn(n, width).astype(np.float32)
    weight = rng.rand(n, K).astype(np.float32)
    weights = {f"e.{which}.w": (0.1 * rng.randn(*shape)).astype(np.float32)
               for which, shape in (("gate", (HELD, width, 16)),
                                    ("up", (HELD, width, 16)),
                                    ("down", (HELD, 16, width)))}
    out, grads, calls = _share_program(index, x, weight, weights)
    assert calls == 2
    monkeypatch.setattr(moe, "_token_sum_plan", lambda *a: None)
    out_loop, grads_loop, calls = _share_program(index, x, weight, weights)
    assert calls == 0
    np.testing.assert_allclose(out, out_loop, rtol=0, atol=K * 2.0 ** -22)
    assert sorted(grads) == sorted(grads_loop) and len(grads) == 4
    for name in grads:
        np.testing.assert_array_equal(grads[name], grads_loop[name])
