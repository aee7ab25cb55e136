"""TPU-only: the Pallas kernels keep their names through Mosaic and XLA, so
a device trace can tell them apart (`name=` on each `pallas_call`; an op's
event in the trace is its instruction line in the compiled step). The CPU
suite checks the Fluid op scopes in the lowered text
(tests/test_run_spans.py); what the chip's compiler names the custom calls
only the chip can say."""

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
