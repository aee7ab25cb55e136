"""TPU-only: the Pallas kernels keep their names through Mosaic and XLA, so
a device trace can tell them apart (`name=` on each `pallas_call`; an op's
event in the trace is its instruction line in the compiled step). The CPU
suite checks the Fluid op scopes in the lowered text
(tests/test_run_spans.py); what the chip's compiler names the custom calls
only the chip can say."""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import models
from paddle_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic custom-call names need real TPU hardware")


def _custom_calls(text):
    """Result names of the Mosaic custom calls in compiled HLO text."""
    return re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .* custom-call\(.*"
                      r"custom_call_target=\"tpu_custom_call\"", text, re.M)


def test_flash_kernels_keep_their_names_in_the_compiled_step():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = models.transformer.build(
            src_vocab_size=1024, trg_vocab_size=1024, seq_len=128, n_layer=1,
            n_head=2, d_model=128, d_inner=256, dropout_rate=0.1)
        loss = fetches["loss"]
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
    exe.run(startup, scope=scope)
    word = np.ones((8, 128), np.int32)
    feed = {"src_word": word, "trg_word": word, "lbl_word": word}
    out, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert np.isfinite(out).all()
    compiled, = [c for c in exe._cache.values() if c.program is main]
    text = compiled._step.lower(
        feed, {n: scope.find_var(n) for n in compiled.mut_names},
        {n: scope.find_var(n) for n in compiled.const_names},
        np.uint32(0)).compile().as_text()
    names = _custom_calls(text)
    # three attention blocks (encoder self, decoder self, decoder cross),
    # each kernel once a block: the grad op reads the forward op's saved Out
    # and Lse, so no forward kernel is lowered again inside it, and one
    # backward kernel gives dQ, dK and dV. Its name holds both `flash_dq`
    # and `flash_dkv`: the benchmark's metrics of those names each find it.
    # A row of 128 is one K block, so the forward is the one-pass kernel,
    # whose name holds `flash_fwd` for the same reason
    assert all(n.split(".")[0] in ("flash_fwd_onepass", "flash_dq_flash_dkv")
               for n in names), names
    for kernel in ("flash_fwd_onepass", "flash_fwd", "flash_dq_flash_dkv",
                   "flash_dq", "flash_dkv"):
        assert sum(kernel in n for n in names) == 3, (kernel, names)


def test_streaming_forward_keeps_its_name():
    """A row of several K blocks (384 = three 128-tiles) takes the
    streaming kernel, `%flash_fwd.N` as before."""
    from paddle_tpu.ops import pallas_attention
    q = jnp.ones((1, 2, 384, 64), jnp.bfloat16)
    text = jax.jit(lambda q: pallas_attention._flash_forward(
        q, q, q, True, 0.125)).lower(q).compile().as_text()
    names = _custom_calls(text)
    assert [n.split(".")[0] for n in names] == ["flash_fwd"], names


def test_paged_kernel_keeps_its_name():
    S, H, Dh, bs, max_b = 4, 8, 128, 16, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    kc = jnp.asarray(rng.randn(1 + S * max_b, bs, H, Dh), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(S * max_b).reshape(S, max_b),
                     jnp.int32)
    seq = jnp.asarray([bs, 0, 3, 2 * bs], jnp.int32)
    text = jax.jit(pa.paged_attention).lower(q, kc, kc, bt, seq) \
        .compile().as_text()
    names = _custom_calls(text)
    assert names and all("paged_attention" in n for n in names), names


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_gated_norm_kernels_keep_their_names(dtype):
    """`gated_rms_norm` and its registered grad at the cell's value heads
    (32 of 128) are one custom call each, `%gated_norm_fwd` and
    `%gated_norm_bwd`, whose first results (Y; dX) have no shape that
    `gdn_scan_ms.train`'s pattern finds."""
    from paddle_tpu.ops import decoder_block as db
    x = jnp.ones((1, 256, 32, 128), dtype)
    w = jnp.ones((128,), jnp.float32)
    text = jax.jit(lambda x, w: (
        db._gated_norm_call(x, x, w, 1e-6),
        db._gated_norm_call(x, x, w, 1e-6, x))).lower(x, w) \
        .compile().as_text()
    names = _custom_calls(text)
    assert sorted(n.split(".")[0] for n in names) == [
        "gated_norm_bwd", "gated_norm_fwd"], names
    short = {"bfloat16": "bf16", "float32": "f32"}[dtype]
    assert re.search(rf"%gated_norm_fwd[\w.]* = {short}\[1,256,4096\]", text)
    assert re.search(rf"%gated_norm_bwd[\w.]* = \({short}\[1,256,4096\]",
                     text)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("scaled", [True, False],
                         ids=["moe_combine", "moe_dispatch_grad"])
def test_token_sum_keeps_its_name_and_is_the_loop(scaled, dtype):
    """A share's token-side sums (`ops/moe.py::_tokens_from_rows`, 512
    tokens x top 4, 4 of 16 experts held, 256 wide) are one custom call,
    `%moe_token_sum`, which `moe_token_sum_kernel_calls.train`'s pattern
    finds; its result is the loop's bitwise, float32 rows times a weight
    too (the chip has no fused multiply-add to contract them into), with
    NaN in every row no assignment holds."""
    from paddle_tpu.ops import moe
    n, k, experts, first, held, width = 512, 4, 16, 4, 4, 256
    rng = np.random.RandomState(0)
    index = np.stack([rng.permutation(experts)[:k] for _ in range(n)])
    counts = np.bincount(index.reshape(-1), minlength=experts)
    layout = moe._dispatch_share(
        jnp.zeros((n, width), dtype), jnp.asarray(index, jnp.int32),
        jnp.asarray(counts, jnp.int32), moe.ROW_TILE, first, held)
    source, sizes = layout["Source"], layout["GroupSizes"]
    moved = rng.randn(source.shape[0], width).astype(np.float32)
    moved[np.asarray(source) < 0] = np.nan
    moved = jnp.asarray(moved, dtype)
    scale = (jnp.asarray(rng.rand(n * k), jnp.float32),) if scaled else ()
    assert moe._token_sum_plan(n, k, *moved.shape, moved.dtype)
    sums = jax.jit(lambda m, s, z, *scale: moe._tokens_from_rows(
        m, s, k, n, z, m.dtype, *scale))
    text = sums.lower(moved, source, sizes, *scale).compile().as_text()
    assert [name.split(".")[0] for name in _custom_calls(text)] \
        == ["moe_token_sum"]
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "metrics",
            "moe_token_sum_kernel_calls.train.json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    # an op's event in a trace is its instruction line; here the call is
    # the jitted function's ROOT
    lines = [line for line in (re.sub(r"^\s*(ROOT )?", "", raw)
                               for raw in text.splitlines())
             if re.search(pattern, line)]
    assert len(lines) == 1 and "tpu_custom_call" in lines[0]
    got = sums(moved, source, sizes, *scale)
    want = jax.jit(lambda m, s, z, *scale: moe._token_sum_loop(
        m, s, k, n, z, *scale).astype(m.dtype))(moved, source, sizes, *scale)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all() and got.any()
    np.testing.assert_array_equal(got, want)
