"""Flash-attention kernel + ring-attention tests.

The Pallas kernels run under the Pallas interpreter on CPU
(PADDLE_TPU_PALLAS_INTERPRET=1), so the actual kernel code — online softmax,
causal block skipping, the FlashAttention-2 backward — is exercised by the
CPU suite; the TPU hardware path is identical modulo Mosaic lowering.
(In-kernel dropout uses the hardware PRNG, which has no interpreter
implementation — covered by the jnp fallback-path test instead.)
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers, models
from paddle_tpu.core import registry
from paddle_tpu.ops import pallas_attention
from paddle_tpu.ops.pallas_attention import (flash_attention,
                                             _attention_reference,
                                             ring_attention)

from attention_program import (attention_grads, flash_calls,
                               float32_grad_layer, kernel_calls, qkv_feed,
                               step_text)


# the one-pass forward kernel (a row is one K block, `_fwd_plan`) and the
# streaming one, the fused backward kernel, and the split pair it replaces
# wherever a row's dQ accumulator fits (`_bwd_plan`)
KERNELS = ("flash_fwd_onepass", "flash_fwd", "flash_dq_flash_dkv",
           "flash_dq", "flash_dkv")


@pytest.fixture
def interpret_kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


# query/key width and value width: the plain case, latent attention's
# (192 over 128, no multiple of a vreg's lanes), and values wider than keys
WIDTHS = [(64, 64), (192, 128), (64, 128)]
WIDTH_IDS = [f"D{d}-Dv{dv}" for d, dv in WIDTHS]


def _qkv(rng, B, H, T, D, Dv):
    q, k = (jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
            for _ in range(2))
    return q, k, jnp.asarray(rng.randn(B, H, T, Dv), jnp.float32)


@pytest.mark.parametrize("plan", ["onepass", "stream"])
@pytest.mark.parametrize("D,Dv", WIDTHS, ids=WIDTH_IDS)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_reference(interpret_kernels, monkeypatch,
                                        causal, D, Dv, plan):
    """Both forward kernels; `Out` has the value heads' width."""
    rng = np.random.RandomState(0)
    B, H, T = 1, 2, 256
    if plan == "stream":
        monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    assert pallas_attention._fwd_plan(
        T, pallas_attention._blk(T, causal)[1]) == plan
    q, k, v = _qkv(rng, B, H, T, D, Dv)
    seed = jnp.int32(0)
    out = flash_attention(q, k, v, seed, causal, D ** -0.5, 0.0)
    ref = _attention_reference(q, k, v, causal, D ** -0.5)
    assert out.shape == (B, H, T, Dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kernels", ["fused", "fused_resident_row", "split"])
@pytest.mark.parametrize("D,Dv", WIDTHS, ids=WIDTH_IDS)
@pytest.mark.parametrize("causal", [False, True])
def test_backward_kernels_match_reference_at_every_width(
        interpret_kernels, monkeypatch, causal, D, Dv, kernels):
    """The fused backward (a row one K block; a row of several, its dQ
    resident) and the split pair, each against the einsum reference's
    gradients: dQ and dK at the query/key width, dV at the value width."""
    rng = np.random.RandomState(4)
    B, H, T = 1, 2, 256
    if kernels != "fused":
        monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    if kernels == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    q, k, v = _qkv(rng, B, H, T, D, Dv)
    g = jnp.asarray(rng.randn(B, H, T, Dv), jnp.float32)
    out, lse = pallas_attention._flash_forward(q, k, v, causal, D ** -0.5)
    got = pallas_attention._flash_backward(q, k, v, out, lse, g, causal,
                                           D ** -0.5, 0.0, 0)
    _, vjp = jax.vjp(lambda *a: _attention_reference(*a, causal, D ** -0.5),
                     q, k, v)
    for a, b, name in zip(got, vjp(g), "qkv"):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("D,Dv", WIDTHS, ids=WIDTH_IDS)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(interpret_kernels, causal, D, Dv):
    rng = np.random.RandomState(1)
    B, H, T = 1, 2, 256
    q, k, v = _qkv(rng, B, H, T, D, Dv)
    g = jnp.asarray(rng.randn(B, H, T, Dv), jnp.float32)
    seed = jnp.int32(0)

    def f(q, k, v):
        return (flash_attention(q, k, v, seed, causal, D ** -0.5, 0.0)
                * g).sum()

    def r(q, k, v):
        return (_attention_reference(q, k, v, causal, D ** -0.5) * g).sum()

    g1 = jax.grad(f, (0, 1, 2))(q, k, v)
    g2 = jax.grad(r, (0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def test_flash_dropout_fallback_path():
    """On CPU without interpret mode the jnp fallback handles dropout; the
    output must be unbiased-ish and differentiable."""
    rng = np.random.RandomState(2)
    B, H, T, D = 2, 2, 128, 32
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
               for _ in range(3))
    seed = jnp.int32(5)
    out = flash_attention(q, k, v, seed, False, D ** -0.5, 0.5)
    base = flash_attention(q, k, v, seed, False, D ** -0.5, 0.0)
    assert np.isfinite(np.asarray(out)).all()
    assert not np.allclose(np.asarray(out), np.asarray(base))
    grads = jax.grad(
        lambda q, k, v: flash_attention(q, k, v, seed, True, D ** -0.5,
                                        0.1).sum(), (0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)


def test_ring_attention_matches_reference():
    """Ring attention over an 8-way 'sp' mesh == exact attention."""
    from paddle_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    assert len(devices) >= 8
    mesh = make_mesh([8], ["sp"], devices[:8])
    rng = np.random.RandomState(3)
    B, H, T, D = 2, 2, 64, 16
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        out = ring_attention(q, k, v, mesh, axis="sp", causal=causal)
        ref = _attention_reference(q, k, v, causal, D ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"causal={causal}")


def test_ring_attention_grad():
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh([4], ["sp"], jax.devices()[:4])
    rng = np.random.RandomState(4)
    B, H, T, D = 1, 2, 32, 16
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
               for _ in range(3))

    g1 = jax.grad(lambda q, k, v: ring_attention(
        q, k, v, mesh, axis="sp", causal=True).sum(), (0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: _attention_reference(
        q, k, v, True, D ** -0.5).sum(), (0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def test_transformer_fused_attention_trains():
    """The fused_attention op path through the program executor: loss drops
    and stays finite over a few steps (CPU -> jnp fallback path)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = models.transformer.build(
            src_vocab_size=64, trg_vocab_size=64, seq_len=16, n_layer=1,
            n_head=2, d_model=32, d_inner=64, dropout_rate=0.1,
            fused_attention=True)
        loss = fetches["loss"]
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(10):
        feed = {k: rng.randint(1, 64, (4, 16)).astype(np.int64)
                for k in ("src_word", "trg_word", "lbl_word")}
        out, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(out).reshape(-1)[0]))
    assert all(np.isfinite(l) for l in losses)
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_multiblock_streaming(interpret_kernels, monkeypatch,
                                           causal):
    """T=1024 at block 512 = multiple innermost-grid steps: exercises the
    scratch-carried online softmax across kj iterations (its statistics
    lane-replicated `[blk_q, 128]` arrays), the kj==0 init / kj==nk-1
    finalize split, and the causal live-block skip — none of which the
    one-pass kernel of a one-block row has. (`_blk` alone gives 1024 one
    1024-tile, so the tiles are set here.)"""
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (512, 512))
    rng = np.random.RandomState(1)
    B, H, T, D = 1, 2, 1024, 64
    assert pallas_attention._fwd_plan(
        T, pallas_attention._blk(T, causal)[1]) == "stream"
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D) * 0.2, jnp.float32)
               for _ in range(3))
    seed = jnp.int32(0)

    out = flash_attention(q, k, v, seed, causal, D ** -0.5, 0.0)
    ref = _attention_reference(q, k, v, causal, D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)

    g = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, seed, causal, D ** -0.5, 0.0).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: _attention_reference(
        q, k, v, causal, D ** -0.5).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


# -- fused_attention saves its log-sum-exp; its grad op runs the backward
#    kernels alone (ops/pallas_attention.py::_fused_attention_grad) ----------

@pytest.mark.parametrize("names", [("q", "k", "v"), ("q",)],
                         ids=["distinct_qkv", "one_var_in_three_slots"])
@pytest.mark.parametrize("causal", [False, True])
def test_grad_op_on_saved_lse_is_bitwise_the_generic_path(
        interpret_kernels, monkeypatch, causal, names):
    """The same program lowered twice, with the op's grad rule and with it
    taken off the op's definition (the generic vjp path, which runs the
    forward kernel again): dQ/dK/dV bitwise equal over 2 x 2 blocks a row,
    under AMP (float32 inputs the forward saw as bf16) with a float32
    `Out@GRAD`; one forward kernel against two."""
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    feed, float32_grad_op = qkv_feed(names), float32_grad_layer(monkeypatch)
    out, grads, text = attention_grads(feed, causal, amp=True,
                                        after=float32_grad_op)
    monkeypatch.setattr(registry.get_op_def("fused_attention"), "grad_lower",
                        None)
    out_g, grads_g, text_g = attention_grads(feed, causal, amp=True,
                                              after=float32_grad_op)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(out_g, np.float32))
    for n in grads:
        assert grads[n].dtype == np.float32 and np.abs(grads[n]).max() > 0
        np.testing.assert_array_equal(grads[n], grads_g[n], err_msg=n)
    assert [kernel_calls(text, k) for k in KERNELS] == [0, 1, 1, 0, 0]
    assert [kernel_calls(text_g, k) for k in KERNELS] == [0, 2, 1, 0, 0]


@pytest.mark.parametrize("D,Dv", WIDTHS, ids=WIDTH_IDS)
@pytest.mark.parametrize("causal", [False, True])
def test_grad_op_on_saved_lse_matches_reference(interpret_kernels,
                                                monkeypatch, causal, D, Dv):
    """Float32, no AMP: the grad op's dQ/dK/dV against `jax.grad` of the
    jnp reference, over 2 x 2 blocks a row; the op's `Out` and `V@GRAD` at
    the value heads' width, `Q@GRAD` and `K@GRAD` at the keys'."""
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    feed = qkv_feed(("q", "k"), shape=(1, 2, 256, D))
    feed.update(qkv_feed(("v",), shape=(1, 2, 256, Dv), seed=6))
    out, grads, _ = attention_grads(feed, causal, amp=False)
    assert out.shape == (1, 2, 256, Dv)
    want = jax.grad(lambda q, k, v: (_attention_reference(
        q, k, v, causal, D ** -0.5) * feed["probe"]).sum(), (0, 1, 2))(
            *(jnp.asarray(feed[n]) for n in "qkv"))
    for n, w in zip("qkv", want):
        np.testing.assert_allclose(grads[n], np.asarray(w), atol=1e-4,
                                   rtol=1e-4, err_msg=n)


# -- one backward kernel: dQ, dK and dV from one pass over the score tiles
#    (ops/pallas_attention.py::_flash_bwd_kernel); the split pair is its
#    oracle and what rows over the VMEM budget (`_bwd_plan`) still take ------

@pytest.mark.parametrize("inputs", ["float32", "amp_float32_out_grad"])
@pytest.mark.parametrize("tiles", [None, (128, 128)],
                         ids=["one_block_a_row", "2x2_blocks"])
@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_is_bitwise_the_split_kernels(
        interpret_kernels, monkeypatch, causal, tiles, inputs):
    """The same program lowered with the fused backward kernel and with the
    plan forced to the split pair: dQ/dK/dV bitwise equal, and within
    tolerance of `jax.grad` of the jnp reference. `one_block_a_row`: T 256
    at `_blk`'s (256, 256), dQ written straight from its one grid step;
    `2x2_blocks`: the row's dQ accumulates in scratch over kj. Float32
    without AMP, and float32 inputs the op sees as bf16 with a float32
    `Out@GRAD`."""
    if tiles:
        monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    assert pallas_attention._blk(256, causal) == (tiles or (256, 256))
    amp = inputs != "float32"
    after = float32_grad_layer(monkeypatch) if amp else None
    feed = qkv_feed(("q", "k", "v"))
    out, grads, text = attention_grads(feed, causal, amp=amp, after=after)
    monkeypatch.setattr(pallas_attention, "_bwd_plan", lambda *a: "split")
    out_s, grads_s, text_s = attention_grads(feed, causal, amp=amp,
                                              after=after)
    fwd = [0, 1] if tiles else [1, 0]   # one block a row: no state kept
    assert [kernel_calls(text, k) for k in KERNELS] == fwd + [1, 0, 0]
    assert [kernel_calls(text_s, k) for k in KERNELS] == fwd + [0, 1, 1]
    assert out.dtype == (jnp.bfloat16 if amp else jnp.float32)
    for n in "qkv":
        assert grads[n].dtype == np.float32 and np.abs(grads[n]).max() > 0
        np.testing.assert_array_equal(grads[n], grads_s[n], err_msg=n)
    q, k, v = (jnp.asarray(feed[n]) for n in "qkv")
    if amp:  # what the op saw
        q, k, v = (x.astype(jnp.bfloat16).astype(jnp.float32)
                   for x in (q, k, v))
    want = jax.grad(lambda q, k, v: (_attention_reference(
        q, k, v, causal, 64 ** -0.5) * feed["probe"]).sum(), (0, 1, 2))(
            q, k, v)
    tol = 6e-2 if amp else 1e-4
    for n, w in zip("qkv", want):
        np.testing.assert_allclose(grads[n], np.asarray(w), atol=tol,
                                   rtol=tol, err_msg=n)


@pytest.mark.parametrize("kernel,D,Dv,window", [
    ("flash", 192, 128, None),      # latent attention's widths
    ("swa_flash", 64, 64, 200),     # a band narrower than the row
    ("swa_flash", 192, 128, 130),
], ids=["D192-Dv128", "window200", "D192-Dv128-window130"])
def test_resident_row_backward_is_bitwise_the_split_pair(
        interpret_kernels, monkeypatch, kernel, D, Dv, window):
    """A row of four K blocks, its dQ accumulated in the resident float32
    row over ascending kj: dQ, dK and dV bitwise the split pair's, where the
    query/key heads have a width of their own and under a window."""
    rng = np.random.RandomState(12)
    B, H, T = 1, 2, 512
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    assert pallas_attention._bwd_plan(T, D, Dv, 128, 128, 4) == "fused"
    q, k, v = _qkv(rng, B, H, T, D, Dv)
    g = jnp.asarray(rng.randn(B, H, T, Dv), jnp.float32)
    out, lse = pallas_attention._flash_forward(q, k, v, True, D ** -0.5,
                                               window=window)

    def run():
        """A trace of its own for each plan: jax keeps one a function."""
        def backward(*a):
            return pallas_attention._flash_backward(
                *a, out, lse, g, True, D ** -0.5, 0.0, 0, window)
        return str(jax.make_jaxpr(backward)(q, k, v)), backward(q, k, v)

    text, fused = run()
    monkeypatch.setattr(pallas_attention, "_bwd_plan", lambda *a: "split")
    text_s, split = run()
    names = [f"{kernel}_{n}" for n in ("dq_flash_dkv", "dq", "dkv")]
    assert [kernel_calls(text, n) for n in names] == [1, 0, 0]
    assert [kernel_calls(text_s, n) for n in names] == [0, 1, 1]
    for a, b, name in zip(fused, split, "qkv"):
        assert np.abs(np.asarray(a)).max() > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"d{name}")


@pytest.mark.parametrize("shape,causal,window,plan,asked_mib", [
    ((96, 8, 256, 64), False, None, "fused", None),  # transformer_base.seq256
    ((96, 8, 256, 64), True, None, "fused", None),
    ((12, 8, 2048, 64), False, None, "fused", None),        # .seq2048
    ((12, 8, 2048, 64), True, None, "fused", None),
    ((1, 16, 4096, 128), True, None, "fused", 32),  # olmoe_1b_7b.bs1, ouro
    ((1, 32, 4096, 192), True, None, "fused", 32),  # kanana_2_30b_a3b.bs1
    ((1, 32, 8192, 128), True, None, "fused", 32),  # mellum2_12b_a2_5b.s8192
    ((1, 32, 8192, 128), True, 1024, "fused", 32),  # its windowed layers
    ((1, 16, 4096, 256), True, None, "fused", 32),  # qwen3_next_80b_a3b.bs1
    ((1, 4, 16384, 128), True, None, "fused", 36),
    ((1, 1, 32768, 64), True, None, "fused", 52),  # test_long_context_tpu
    ((1, 1, 32768, 128), False, None, "fused", 54.5),
    ((1, 1, 65536, 128), True, None, "fused", 84),  # the longest run (PR 41)
    ((1, 1, 65536, 256), True, None, "split", 152),
    ((1, 1, 131072, 128), True, None, "split", 148),
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_backward_plan_follows_the_resident_dq_row(shape, causal, window,
                                                   plan, asked_mib):
    """Fused wherever a row is one K block, or its dQ (the float32
    accumulator and the double-buffered output block, lanes padded) and a
    step's tiles are within the budget; the choice reads the shape, the item
    size and the tile alone. The kernel asks for what the row needs, 32 MiB
    at the least: the cells' rows ask what the kernel always asked, the long
    rows what they were run at on the chip (PR 41)."""
    _, _, T, D = shape
    BQ, BK = pallas_attention._blk(T, causal, window)
    assert pallas_attention._bwd_plan(T, D, D, BQ, BK, 2) == plan
    if T != BK:
        asked = pallas_attention._fused_bwd_vmem(T, D, D, BQ, BK, 2)
        assert asked == asked_mib * 2 ** 20
        assert (asked <= pallas_attention._VMEM_BUDGET_BYTES) == (
            plan == "fused")


def test_lse_has_a_shape_at_build_time_without_a_tpu():
    """Shape inference does not trace the rule: on this machine the rule
    takes the reference path and returns no `Lse`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data(name="q", shape=[8, 256, 64], dtype="float32")
        out = layers.fused_attention(q, q, q, causal=True)
    block = main.global_block()
    lse = block.var(block.ops[-1].outputs["Lse"][0])
    assert tuple(out.shape) == (-1, 8, 256, 64)
    assert tuple(lse.shape) == (-1, 1, 256) and str(lse.dtype) == "float32"
    assert lse.stop_gradient


def _tiny_transformer(strip_lse):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = models.transformer.build(
            src_vocab_size=64, trg_vocab_size=64, seq_len=128, n_layer=1,
            n_head=2, d_model=32, d_inner=64, dropout_rate=0.0)
        if strip_lse:
            for op in main.global_block().ops:
                if op.type == "fused_attention":
                    del op.outputs["Lse"]
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(fetches["loss"])
    return main, startup, fetches["loss"]


@pytest.mark.parametrize("strip_lse", [False, True],
                         ids=["with_lse", "program_without_lse"])
def test_tiny_transformer_step_runs_flash_fwd_once_a_block(interpret_kernels,
                                                           strip_lse):
    """The lowered training step of the one-layer model (encoder self,
    decoder self, cross attention) holds one forward `pallas_call` for
    each attention block: `flash_fwd_onepass`, a row of 128 being one K
    block, and no `flash_fwd` besides. A program whose ops have no `Lse`
    output (built before the slot existed) falls back to the forward under
    `jax.vjp`, two a block, and trains all the same."""
    main, startup, loss = _tiny_transformer(strip_lse)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace(), amp=True)
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {k: rng.randint(1, 64, (2, 128)).astype(np.int64)
            for k in ("src_word", "trg_word", "lbl_word")}
    losses = [float(np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                                       scope=scope)[0]).reshape(-1)[0])
              for _ in range(6)]
    assert all(np.isfinite(l) for l in losses) and losses[-1] < losses[0]
    text = step_text(exe, main, scope, feed)
    assert [kernel_calls(text, k) for k in KERNELS] == [
        6 if strip_lse else 3, 0, 3, 0, 0]


@pytest.mark.parametrize("causal", [False, True])
def test_forward_op_and_grad_op_draw_one_dropout_mask(causal):
    """Dropout on (CPU reference path; the grad op falls back to `jax.vjp`
    over the rule): Out and the gradients through the program equal the
    reference and its `jax.grad` at the key the forward op was given —
    fold_in(step key, the op's index in the block) — so the grad op was
    given the same one."""
    rate, shape = 0.3, (2, 2, 128, 32)
    feed = qkv_feed(("q", "k", "v"), shape=shape)
    out, grads, _ = attention_grads(feed, causal, amp=False, rate=rate)
    step_key = jax.random.fold_in(jax.random.key(7), np.uint32(0))
    op_key = jax.random.fold_in(step_key, 1)      # scale is op 0
    seed = jax.random.key_data(op_key).reshape(-1)[0].astype(jnp.int32)

    def f(q, k, v):
        return _attention_reference(q, k, v, causal, shape[-1] ** -0.5, rate,
                                    seed)

    q, k, v = (jnp.asarray(feed[n]) for n in "qkv")
    np.testing.assert_allclose(out, np.asarray(f(q, k, v)), atol=1e-5,
                               rtol=1e-5)
    assert not np.allclose(out, np.asarray(_attention_reference(
        q, k, v, causal, shape[-1] ** -0.5)), atol=1e-2)
    want = jax.grad(lambda *a: (f(*a) * feed["probe"]).sum(), (0, 1, 2))(
        q, k, v)
    for n, w in zip("qkv", want):
        np.testing.assert_allclose(grads[n], np.asarray(w), atol=1e-4,
                                   rtol=1e-4, err_msg=n)


# -- two forward kernels, one algorithm: no softmax state where a row is one
#    K block (`_flash_fwd_onepass_kernel`), the online-softmax state carried
#    over the blocks where it has several (`_flash_fwd_kernel`) -------------

FWD_SHAPES = [(128, 64), (256, 64), (256, 128)]


def _forward_both_ways(monkeypatch, T, D, BQ, causal, seed=3):
    """`_flash_forward` at the tiles (BQ, T) with the plan the shape gives
    (one pass) and with it forced to the streaming kernel: (out, lse) of
    each."""
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (BQ, T))
    assert pallas_attention._blk(T, causal) == (BQ, T)
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.randn(2, 2, T, D), jnp.float32)
               for _ in range(3))
    assert pallas_attention._fwd_plan(T, T) == "onepass"
    one = pallas_attention._flash_forward(q, k, v, causal, D ** -0.5)
    monkeypatch.setattr(pallas_attention, "_fwd_plan", lambda *a: "stream")
    stream = pallas_attention._flash_forward(q, k, v, causal, D ** -0.5)
    return one, stream


@pytest.mark.parametrize("whole_row", [True, False],
                         ids=["BQ=T", "BQ=T/2"])
@pytest.mark.parametrize("T,D", FWD_SHAPES,
                         ids=[f"{t}x{d}" for t, d in FWD_SHAPES])
@pytest.mark.parametrize("causal", [False, True])
def test_onepass_forward_is_bitwise_the_streaming_kernel(
        interpret_kernels, monkeypatch, causal, T, D, whole_row):
    """Same tiles, same products, same float32 `exp`, a true division: the
    streaming kernel's first step scales a zero state by exp(NEG_INF - m)
    = 0, so dropping the state changes no bit of `Out` or `Lse`."""
    one, stream = _forward_both_ways(monkeypatch, T, D,
                                     T if whole_row else T // 2, causal)
    for a, b, name in zip(one, stream, ("Out", "Lse")):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    assert one[1].shape == (4, 1, T) and one[1].dtype == jnp.float32


@pytest.mark.parametrize("plan", ["onepass", "stream"])
@pytest.mark.parametrize("T,D", FWD_SHAPES,
                         ids=[f"{t}x{d}" for t, d in FWD_SHAPES])
@pytest.mark.parametrize("causal", [False, True])
def test_forward_lse_is_the_logsumexp_of_the_masked_scores(
        interpret_kernels, monkeypatch, causal, T, D, plan):
    """`Lse` of both forward kernels against `jax.nn.logsumexp` of the
    scores the reference masks, in the layout the backward reads; the
    streaming kernel over 2 x 2 blocks a row."""
    if plan == "stream":
        monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE",
                            (T // 2, T // 2))
    BQ, BK = pallas_attention._blk(T, causal)
    assert pallas_attention._fwd_plan(T, BK) == plan
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(1, 2, T, D), jnp.float32)
               for _ in range(3))
    out, lse = pallas_attention._flash_forward(q, k, v, causal, D ** -0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
    if causal:
        s = jnp.where(jnp.arange(T)[None, :] > jnp.arange(T)[:, None],
                      pallas_attention.NEG_INF, s)
    want = jax.nn.logsumexp(s, axis=-1).reshape(2, 1, T)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_attention_reference(q, k, v, causal,
                                                         D ** -0.5)),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape,causal,plan", [
    ((96, 8, 256, 64), False, "onepass"),     # transformer_base.seq256
    ((96, 8, 256, 64), True, "onepass"),
    ((12, 8, 2048, 64), False, "onepass"),    # transformer_base.seq2048
    ((12, 8, 2048, 64), True, "onepass"),
    ((1, 16, 4096, 128), True, "stream"),     # olmoe_1b_7b.bs1, ouro_2_6b.bs1
    ((1, 16, 4096, 128), False, "stream"),    # (512, 2048): two K blocks
    ((2, 2, 128, 64), False, "onepass"),      # the tiny transformer step
    ((2, 2, 384, 64), False, "stream"),       # 128-tiles, three a row
    ((1, 2, 1024, 64), True, "onepass"),      # one 1024-tile
    ((1, 1, 32768, 64), True, "stream"),      # tests/test_long_context_tpu
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_forward_plan_follows_the_k_blocks_of_a_row(shape, causal, plan):
    """One pass wherever a row is one K block; the choice reads T and the
    tile alone."""
    _, _, T, _ = shape
    _, BK = pallas_attention._blk(T, causal)
    assert pallas_attention._fwd_plan(T, BK) == plan
    assert (T // BK == 1) == (plan == "onepass")


@pytest.mark.parametrize("D", [32, 64, 128, 192, 256])
def test_streaming_statistics_reach_every_head_width(interpret_kernels,
                                                     monkeypatch, D):
    """The lane-replicated statistics are sliced or tiled to the head
    width (`_lanes`): under, at and over one vreg of lanes, and a width
    that is no multiple of it."""
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    rng = np.random.RandomState(D)
    q, k, v = (jnp.asarray(rng.randn(1, 1, 256, D), jnp.float32)
               for _ in range(3))
    assert pallas_attention._fwd_plan(256, 128) == "stream"
    out, _ = pallas_attention._flash_forward(q, k, v, True, D ** -0.5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_attention_reference(q, k, v, True,
                                                         D ** -0.5)),
        atol=3e-5, rtol=3e-5)


# -- a window: key j visible to query i iff 0 <= i - j < W -----------------------

def _masked_softmax(q, k, v, window, scale):
    """The band written as its two inequalities, independent of the op."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    i = jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[2])[None, :]
    s = jnp.where((j <= i) & (i - j < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


# T 512 in tiles of 128: below a tile, a tile, across three tiles (two whole
# and two part ones), no multiple of 128 nor of 8, one key, all but one
WINDOWS = [1, 37, 128, 300, 384, 511]


@pytest.mark.parametrize("plan", ["onepass", "stream"])
@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_forward_is_the_masked_softmax(interpret_kernels,
                                                monkeypatch, window, plan):
    rng = np.random.RandomState(7)
    B, H, T, D = 1, 2, 512, 32
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE",
                        (128, 512) if plan == "onepass" else (128, 128))
    assert pallas_attention._fwd_plan(
        T, pallas_attention._blk(T, True)[1]) == plan
    q, k, v = _qkv(rng, B, H, T, D, D)
    out = flash_attention(q, k, v, jnp.int32(0), True, D ** -0.5, 0.0, window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_masked_softmax(q, k, v, window,
                                                    D ** -0.5)),
        atol=2e-5, rtol=2e-5)
    # and the CPU path's reference is the same function
    ref = _attention_reference(q, k, v, True, D ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("kernels", ["fused", "fused_resident_row", "split"])
@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_backward_plans_match_the_masked_softmax(
        interpret_kernels, monkeypatch, window, kernels):
    """The fused backward (a row one K block; a row of several, its dQ
    resident) and the split pair under a window, against `jax.vjp` of the
    masked softmax."""
    rng = np.random.RandomState(8)
    B, H, T, D = 1, 2, 512, 32
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE",
                        (128, 512) if kernels == "fused" else (128, 128))
    if kernels == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    q, k, v = _qkv(rng, B, H, T, D, D)
    g = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    out, lse = pallas_attention._flash_forward(q, k, v, True, D ** -0.5,
                                               window=window)
    got = pallas_attention._flash_backward(q, k, v, out, lse, g, True,
                                           D ** -0.5, 0.0, 0, window)
    _, vjp = jax.vjp(lambda *a: _masked_softmax(*a, window, D ** -0.5),
                     q, k, v)
    for a, b, name in zip(got, vjp(g), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("tiles", [(128, 256), (256, 128)],
                         ids=["bq128-bk256", "bq256-bk128"])
@pytest.mark.parametrize("window", [37, 300])
def test_window_over_tiles_that_are_not_square(interpret_kernels, monkeypatch,
                                               window, tiles):
    """The band's arithmetic does not assume BQ == BK."""
    rng = np.random.RandomState(9)
    B, H, T, D = 1, 1, 512, 32
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    monkeypatch.setattr(pallas_attention, "_bwd_plan", lambda *a: "split")
    q, k, v = _qkv(rng, B, H, T, D, D)
    g = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)

    def f(*a):
        return (flash_attention(*a, jnp.int32(0), True, D ** -0.5, 0.0,
                                window) * g).sum()

    def r(*a):
        return (_masked_softmax(*a, window, D ** -0.5) * g).sum()

    for a, b, name in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                          jax.grad(r, (0, 1, 2))(q, k, v), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("kernels", ["fused_resident_row", "split"])
def test_window_with_a_value_width_of_its_own(interpret_kernels, monkeypatch,
                                              kernels):
    """Latent attention's widths (192 over 128) under a window."""
    rng = np.random.RandomState(10)
    B, H, T, D, Dv, window = 1, 2, 384, 192, 128, 200
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    if kernels == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    q, k, v = _qkv(rng, B, H, T, D, Dv)
    g = jnp.asarray(rng.randn(B, H, T, Dv), jnp.float32)
    out, vjp = jax.vjp(lambda *a: flash_attention(
        *a, jnp.int32(0), True, D ** -0.5, 0.0, window), q, k, v)
    want, want_vjp = jax.vjp(
        lambda *a: _masked_softmax(*a, window, D ** -0.5), q, k, v)
    assert out.shape == (B, H, T, Dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    for a, b, name in zip(vjp(g), want_vjp(g), "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("window", [512, 513, 100000])
def test_a_window_over_the_whole_row_is_bitwise_plain_causal(
        interpret_kernels, monkeypatch, window):
    rng = np.random.RandomState(11)
    B, H, T, D = 1, 2, 512, 32
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    q, k, v = _qkv(rng, B, H, T, D, D)
    g = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)

    def run(w):
        return jax.vjp(lambda *a: flash_attention(
            *a, jnp.int32(0), True, D ** -0.5, 0.0, w), q, k, v)

    (out, vjp), (plain, plain_vjp) = run(window), run(None)
    assert np.array_equal(np.asarray(out), np.asarray(plain))
    for a, b in zip(vjp(g), plain_vjp(g)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the step's text names the plain kernels
    text = jax.jit(lambda *a: flash_attention(
        *a, jnp.int32(0), True, D ** -0.5, 0.0, window)).lower(
            q, k, v).as_text()
    assert "swa_" not in text


def test_window_with_dropout_takes_the_causal_paths_fallback():
    """On the CPU a dropout rate sends the call to the jnp reference, with
    or without a window, and the window is applied there: a weight outside
    the band stays 0 whatever the mask drops."""
    rng = np.random.RandomState(12)
    B, H, T, D, window = 1, 2, 128, 16, 20
    q, k, _ = _qkv(rng, B, H, T, D, D)
    v = jnp.eye(T, dtype=jnp.float32)[None, None].repeat(H, 1)  # Out = P
    assert not pallas_attention._pallas_ok(q, 0.5, v, window)
    out = np.asarray(flash_attention(q, k, v, jnp.int32(3), True, D ** -0.5,
                                     0.5, window))
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    outside = ~((j <= i) & (i - j < window))
    assert np.all(out[..., outside] == 0) and np.isfinite(out).all()
    kept = out[..., ~outside]
    assert 0.3 < np.mean(kept == 0) < 0.7       # about half are dropped
    base = flash_attention(q, k, v, jnp.int32(3), True, D ** -0.5, 0.0,
                           window)
    np.testing.assert_allclose(
        np.asarray(base), np.asarray(_masked_softmax(q, k, v, window,
                                                     D ** -0.5)), atol=1e-5)


@pytest.mark.parametrize("T,tiles,window,band,causal", [
    (8192, (512, 512), 1024, 45, 136),  # the cell before PR 72
    (8192, None, 1024, 15, 36),         # the cell: 1024 x 1024 tiles
    (4096, (512, 512), 2048, 30, 36),   # Trinity-Mini's before PR 72
    (4096, None, 2048, 9, 10),          # Trinity-Mini's: 1024 x 1024 tiles
    (512, (128, 128), 128, 7, 10),
    (512, (128, 128), 129, 7, 10),
    (512, (128, 128), 130, 9, 10),
    (512, (128, 128), 1, 4, 10)])
def test_windowed_grids_cover_the_bands_tiles(monkeypatch, T, tiles, window,
                                              band, causal):
    """`window_tiles`: the tiles the forward grid computes a head, of the
    `causal` a call without the window has at the same tiles;
    `_band_steps`: the inner axes are the band's width."""
    if tiles:
        monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    assert pallas_attention.window_tiles(T, window) == band
    bq, bk = pallas_attention._blk(T, True, window)
    nkw, nqw = pallas_attention._band_steps(T, bq, bk, window)
    live = [[bool(pallas_attention._causal_live(qi, kj, bq, bk, window))
             for kj in range(T // bk)] for qi in range(T // bq)]
    assert sum(map(sum, live)) == band
    assert sum(bool(pallas_attention._causal_live(qi, kj, bq, bk))
               for qi in range(T // bq) for kj in range(T // bk)) == causal
    assert nkw == max(map(sum, live)) and nqw == max(map(sum, zip(*live)))
    # every live tile is visited once by each windowed grid, no dead one
    for qi in range(T // bq):
        seen = [pallas_attention._band_kj(qi, s, bq, bk, window)
                for s in range(nkw)]
        assert [kj for kj in seen if 0 <= kj < T // bk and live[qi][kj]] \
            == [kj for kj in range(T // bk) if live[qi][kj]]
    for kj in range(T // bk):
        seen = [pallas_attention._band_qi(kj, s, nqw, bq, bk, window,
                                          T // bq) for s in range(nqw)]
        assert [qi for qi in seen if 0 <= qi < T // bq and live[qi][kj]] \
            == [qi for qi in range(T // bq) if live[qi][kj]]


# -- a causal grid's dead steps fetch nothing -------------------------------------

def walk_the_grid(name, call, every, bq, bk):
    """The input blocks of the causal call `name` over its grid in the
    order the steps run (the last axis fastest), beside the `every`-step
    maps, which give each operand's own block: (live steps, steps, the
    blocks each operand fetches, the blocks the live steps alone would
    fetch). A block is fetched where the index moves. A live step reads its
    own blocks."""
    (grid, specs), (_, own_specs) = call, every
    # operands (seed, q, k, v[, dO, lse, delta or Out][, kept]): which tile
    # axis each one's block follows
    follows = "-qkk" if "fwd" in name else "-qkkqqq"
    follows += "b" * (len(specs) - len(follows))
    live = steps = 0
    fetched, needed = ([[] for _ in specs] for _ in range(2))
    for g in np.ndindex(*grid):
        qi, kj = g[-2:][::-1] if "dkv" in name else g[-2:]
        is_live = bool(pallas_attention._causal_live(qi, kj, bq, bk))
        live, steps = live + is_live, steps + 1
        for n, (spec, own_spec) in enumerate(zip(specs, own_specs)):
            block = tuple(int(i) for i in spec.index_map(*g))
            own = tuple(int(i) for i in own_spec.index_map(*g))
            # `Lse`'s and delta's blocks are (rows, 1, tile)
            tile = own[-1 if spec.block_shape[1] == 1 else 1]
            assert {"-": own == (0, 0), "q": tile == qi, "k": tile == kj,
                    "b": own[1:] == (qi, kj)}[follows[n]], (n, g, own)
            assert not is_live or block == own, (n, g, block, own)
            if fetched[n][-1:] != [block]:
                fetched[n].append(block)
            if is_live and needed[n][-1:] != [own]:
                needed[n].append(own)
    return live, steps, fetched, needed


@pytest.mark.parametrize("layout", ["BHTD", "BTHD"])
@pytest.mark.parametrize("split", [False, True], ids=["fused", "split"])
@pytest.mark.parametrize("T,bq,bk", [
    (8192, 1024, 1024), (4096, 1024, 1024), (512, 128, 256), (512, 256, 128)])
def test_no_operand_is_fetched_for_a_step_above_the_diagonal(
        monkeypatch, T, bq, bk, split, layout):
    """K and V of the forward and of dQ (k blocks innermost), and Q, dOut,
    `Lse` and delta (`Out`'s block of a token-major call) of the fused
    backward and of dK/dV (q blocks innermost): on a causal grid without a
    window a live step reads its own block and a dead step the block of the
    live step beside it (the row's last, the column's first), so every
    operand is fetched as a grid of the live steps alone would fetch it: 36
    of a head's 64 at 8192 tokens. With each operand's own block on every
    step (the maps before PR 70) the inner ones moved on every step."""
    kw = dict(token_major=layout == "BTHD", split=split)
    held = flash_calls(monkeypatch, T, (bq, bk), **kw)
    every = flash_calls(monkeypatch, T, (bq, bk), every_step=True, **kw)
    assert sorted(held) == (
        ["flash_dkv", "flash_dq", "flash_fwd"] if split
        else ["flash_dq_flash_dkv", "flash_fwd"])
    per_head = sum(pallas_attention._last_k(qi, bq, bk) + 1
                   for qi in range(T // bq))
    dead = pallas_attention._dead_steps(T, bq, bk)
    assert per_head + dead == (T // bq) * (T // bk) and dead > 0
    if T == 8192:
        assert (per_head, dead) == (36, 28)
    for name in held:
        heads = int(np.prod(held[name][0][:-2]))
        live, steps, fetched, needed = walk_the_grid(
            name, held[name], every[name], bq, bk)
        assert (live, steps) == (heads * per_head, heads * (per_head + dead))
        assert fetched == needed
        assert max(map(len, fetched)) <= live
        _, _, moved, _ = walk_the_grid(name, every[name], every[name], bq, bk)
        inner = [n for n, blocks in enumerate(moved) if len(blocks) > live]
        assert inner == ([1, 4, 5, 6] if "dkv" in name else [2, 3])
        assert all(len(moved[n]) > len(fetched[n]) for n in inner)


@pytest.mark.parametrize("why,T,tiles,causal", [
    ("not causal", 512, (128, 128), False),
    ("one K block a row", 2048, None, True),
    ("one K block a row, wide q blocks", 512, (256, 512), True)])
@pytest.mark.parametrize("layout", ["BHTD", "BTHD"])
def test_a_grid_without_a_dead_step_keeps_the_maps_it_had(
        monkeypatch, why, T, tiles, causal, layout):
    """A call that is not causal and a causal row of one K block (every
    attention call at 2048 tokens under `_BLOCK_TABLE`'s (256, 2048)) have
    no step above the diagonal: their index maps are each step's own block,
    with no clamp in them, the text they lowered to before the hold."""
    kw = dict(causal=causal, token_major=layout == "BTHD")
    bq, bk = tiles or pallas_attention._blk(T, causal)
    assert not causal or pallas_attention._dead_steps(T, bq, bk) == 0
    for split in (False, True):
        held = flash_calls(monkeypatch, T, tiles, split=split, **kw)
        every = flash_calls(monkeypatch, T, tiles, split=split,
                            every_step=True, **kw)
        assert len(held) == 2 + split
        for name, (grid, specs) in held.items():
            for spec, own in zip(specs, every[name][1]):
                maps = [str(jax.make_jaxpr(s.index_map)(*[0] * len(grid)))
                        for s in (spec, own)]
                assert maps[0] == maps[1]
                assert "min" not in maps[0] and "max" not in maps[0]


# name -> layout, (D, Dv), kept set, backward plan
HELD_CASES = {
    "fused": ("BHTD", (64, 64), False, "fused"),
    "split": ("BHTD", (64, 64), False, "split"),
    "fused_D192_Dv128": ("BHTD", (192, 128), False, "fused"),
    "kept_fused": ("BHTD", (64, 64), True, "fused"),
    "kept_split": ("BHTD", (64, 64), True, "split"),
    "token_major_fused": ("BTHD", (64, 64), False, "fused"),
    "token_major_split": ("BTHD", (128, 128), False, "split"),
}


@pytest.mark.parametrize("tiles", [(128, 128), (128, 256), (256, 128)],
                         ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("case", sorted(HELD_CASES))
def test_held_dead_steps_give_the_bits_of_blocks_fetched_there(
        interpret_kernels, monkeypatch, case, tiles):
    """`Out`, `Lse`, dQ, dK and dV of a causal call whose dead steps stay on
    the live neighbour's blocks, `==` those of the same kernels with every
    operand's own block on every step (the maps before PR 70): a step that
    reads nothing does not care what lies in VMEM. Head-major and
    token-major, fused and split, with and without a kept set, under the
    interpreter (`tests/test_flash_grad_tpu.py` holds it on the chip at the
    cells' shapes, with dropout too)."""
    layout, (D, Dv), kept, plan = HELD_CASES[case]
    rng = np.random.RandomState(23)
    B, H, T, scale = 1, 2, 512, D ** -0.5
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    monkeypatch.setattr(pallas_attention, "_bwd_plan", lambda *a: plan)
    # a jitted call keeps the trace of the first form
    monkeypatch.setattr(pallas_attention, "_jitted_forward",
                        pallas_attention._forward)
    monkeypatch.setattr(pallas_attention, "_jitted_backward",
                        pallas_attention._backward)
    assert pallas_attention._dead_steps(T, *tiles) > 0
    q, k, v = _qkv(rng, B, H, T, D, Dv)
    g = jnp.asarray(rng.randn(B, H, T, Dv), jnp.float32)
    token_major = layout == "BTHD"
    if token_major:
        q, k, v, g = (x.transpose(0, 2, 1, 3) for x in (q, k, v, g))
    the_set = _selected(rng, B, T, 200) if kept else None

    def run():
        out, lse = pallas_attention._flash_forward(
            q, k, v, True, scale, token_major=token_major, kept=the_set)
        return (out, lse) + tuple(pallas_attention._flash_backward(
            q, k, v, out, lse, g, True, scale, 0.0, 0,
            token_major=token_major, kept=the_set))

    got = run()
    monkeypatch.setattr(pallas_attention, "_dead_steps", lambda *a: 0)
    want = run()
    for a, b, name in zip(got, want, ("Out", "Lse", "dQ", "dK", "dV")):
        assert np.abs(np.asarray(a)).max() > 0, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    f32 = [x.transpose(0, 2, 1, 3) if token_major else x for x in (q, k, v)]
    mask = None if the_set is None else the_set * jnp.asarray(
        _brute_visible(T, None), jnp.int8)[None]
    ref = _attention_reference(*f32, True, scale, kept=mask)
    np.testing.assert_allclose(
        np.asarray(got[0].transpose(0, 2, 1, 3) if token_major else got[0]),
        np.asarray(ref), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T,tiles,dead", [
    (8192, None, 28), (4096, None, 6), (2048, None, 0), (256, None, 0),
    (512, (128, 128), 6), (512, (128, 256), 2), (512, (256, 128), 2),
    (512, (512, 128), 0), (512, (128, 512), 0)])
def test_dead_steps_at_the_cells_lengths(monkeypatch, T, tiles, dead):
    """The steps of a causal grid above the diagonal, a head: the grid's
    less the triangle's tiles; none where a row is one K block or one Q
    block reaches over every K block."""
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    bq, bk = pallas_attention._blk(T, True)
    assert pallas_attention._dead_steps(T, bq, bk) == dead \
        == (T // bq) * (T // bk) - pallas_attention.causal_tiles(T)
    assert dead == sum(
        not pallas_attention._causal_live(qi, kj, bq, bk)
        for qi in range(T // bq) for kj in range(T // bk))


@pytest.mark.parametrize("why,kw,tiles,held", [
    ("causal", dict(causal=True), (128, 128), 2 * 2 * 6),
    ("causal, token-major", dict(causal=True, layout="BTHD"), (128, 128),
     2 * 2 * 6),
    ("a window over the whole row", dict(causal=True, window=512),
     (128, 128), 2 * 2 * 6),
    ("one K block a row", dict(causal=True), None, 0),
    ("a window", dict(causal=True, window=300), (128, 128), None),
    ("not causal", dict(causal=False), (128, 128), None)])
def test_the_op_tallies_the_dead_steps_it_holds(monkeypatch, why, kw, tiles,
                                                held):
    """`flash_dead_steps_held` on the compile event: batch x heads x the
    forward grid's steps above the diagonal, for a causal op without a
    window (0 where its rows are one K block: both transformer cells);
    a windowed op and one that is not causal do not count, and the grad
    op's trace adds nothing."""
    from paddle_tpu import observe
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    B, H, T, D = 2, 2, 512, 64
    shape = [B, T, H, D] if kw.get("layout") == "BTHD" else [B, H, T, D]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data(name="q", shape=shape, dtype="float32",
                        append_batch_size=False, stop_gradient=False)
        loss = layers.reduce_sum(layers.fused_attention(q, q, q, **kw))
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(main, feed={"q": np.ones(shape, np.float32)}, fetch_list=[loss],
            scope=fluid.Scope())
    detail = observe.observatory().latest(main._uid).detail
    assert detail.get("flash_dead_steps_held") == held


# -- the second window width: 2048 keys over 4096 tokens (Trinity-Mini) -----------

W2048 = dict(B=1, H=1, T=4096, D=32, window=2048)


def test_the_tile_rule_at_a_window_of_2048():
    """A window of whole tiles of 1024 takes them, its edge tiles in strips
    (PR 72: with every tile whole 1024 x 1024 read a twentieth slower than
    512 x 512, PR 49; in strips a tenth faster): 1024 x 1024 at 4096
    tokens, where a row of keys is four K blocks (the streaming forward; the
    fused backward keeps the row's dQ resident). Any other window of 512 or
    more: square tiles of 512 (PR 73: at a window of 512 they read a quarter
    faster than half the window's 256); a shorter one half the window."""
    T, window = W2048["T"], W2048["window"]
    assert pallas_attention._blk(T, True, window) == (1024, 1024)
    assert pallas_attention._blk(T, True, 1024) == (1024, 1024)
    assert pallas_attention._blk(16384, True, 4096) == (1024, 1024)
    assert pallas_attention._blk(T, True, 1536) == (512, 512)
    assert pallas_attention._blk(T, True, 2000) == (512, 512)
    assert pallas_attention._blk(T, True, 600) == (512, 512)
    assert pallas_attention._blk(T, True, 512) == (512, 512)
    assert pallas_attention._blk(T, True, 500) == (128, 128)
    assert pallas_attention._blk(T, True, 300) == (128, 128)
    assert pallas_attention._blk(T, True) == (1024, 1024)
    assert pallas_attention._fwd_plan(T, 1024) == "stream"
    assert pallas_attention._band_steps(T, 1024, 1024, window) == (3, 3)
    assert pallas_attention._band_steps(T, 512, 512, window) == (5, 5)


@pytest.mark.parametrize("tiles", [None, (512, 512)],
                         ids=["rule", "512x512"])
def test_windowed_forward_at_2048_is_the_masked_softmax(interpret_kernels,
                                                        monkeypatch, tiles):
    rng = np.random.RandomState(17)
    B, H, T, D, window = W2048.values()
    if tiles:
        monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    q, k, v = _qkv(rng, B, H, T, D, D)
    out = flash_attention(q, k, v, jnp.int32(0), True, D ** -0.5, 0.0, window)
    want = _masked_softmax(q, k, v, window, D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    # one key more in the window is another function
    off = _masked_softmax(q, k, v, window + 1, D ** -0.5)
    assert float(jnp.abs(out - off).max()) > 1e-4


@pytest.mark.parametrize("kernels", ["fused", "fused_resident_row", "split"])
def test_windowed_backward_plans_at_2048_match_the_masked_softmax(
        interpret_kernels, monkeypatch, kernels):
    """Every backward plan under the window of 2048: the fused kernel where
    a row is one K block, the fused kernel with the row's dQ resident (what
    the tile rule gives), and the split pair."""
    rng = np.random.RandomState(18)
    B, H, T, D, window = W2048.values()
    if kernels == "fused":
        monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (1024, T))
    if kernels == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    q, k, v = _qkv(rng, B, H, T, D, D)
    g = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    out, lse = pallas_attention._flash_forward(q, k, v, True, D ** -0.5,
                                               window=window)
    got = pallas_attention._flash_backward(q, k, v, out, lse, g, True,
                                           D ** -0.5, 0.0, 0, window)
    _, vjp = jax.vjp(lambda *a: _masked_softmax(*a, window, D ** -0.5),
                     q, k, v)
    for a, b, name in zip(got, vjp(g), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("case", ["not_causal", "zero", "fraction",
                                  "sequence_parallel", "paged"])
def test_a_window_that_cannot_run_is_refused_by_name(case):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data(name="q", shape=[1, 2, 128, 16], dtype="float32",
                        append_batch_size=False)
        if case == "not_causal":
            with pytest.raises(ValueError, match="window.*causal call only"):
                layers.fused_attention(q, q, q, causal=False, window=8)
        elif case == "zero":
            with pytest.raises(ValueError, match="window of at least 1 key"):
                layers.fused_attention(q, q, q, causal=True, window=0)
        elif case == "fraction":
            with pytest.raises(ValueError, match="window of at least 1 key.*whole number"):
                layers.fused_attention(q, q, q, causal=True, window=2.5)
        elif case == "sequence_parallel":
            from paddle_tpu.parallel.mesh import make_mesh
            mesh = make_mesh([2], ["sp"], jax.devices()[:2])
            ctx = registry.LoweringContext(
                {"causal": True, "window": 8}, lowerer=type(
                    "L", (), {"mesh": mesh, "program": main,
                              "tallies": {}})())
            x = jnp.zeros((1, 2, 128, 16))
            with pytest.raises(NotImplementedError,
                               match="window.*sequence parallelism"):
                registry.get_op_def("fused_attention").lower(ctx, x, x, x)
        else:
            from paddle_tpu.ops import paged_attention
            ctx = registry.LoweringContext({"window": 8, "num_heads": 2})
            with pytest.raises(NotImplementedError,
                               match="window.*paged attention"):
                paged_attention._no_window(ctx)
            paged_attention._no_window(registry.LoweringContext({}))


# -- operands as the projections leave them: `layout="BTHD"`, a head a range
#    of lanes picked by the kernels' block specs, several heads a grid step --

def _tile_keep(seed, bh, qi, kj, shape, rate):
    """A keep-mask that is a function of (seed, b * H + h, q tile, k tile,
    row, column) alone, in int32 arithmetic the interpreter has: stands in
    for the TPU's PRNG (`_dropout_mask`), which it has not."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    x = ((seed * 1103515245 + bh) * 1664525 + qi) * 1013904223 + kj
    x = (x * 69069 + row) * 1103515245 + col
    x = x ^ (x >> 15)
    x = x * 739981 + 12345
    x = x ^ (x >> 13)
    return ((x >> 4) & 0xFFFF) >= int(rate * 65536)


def _reference_under_tile_masks(q, k, v, causal, scale, rate, seed, tiles):
    """`_attention_reference`'s softmax on `[B, H, T, D]` with the keep-mask
    `_tile_keep` gives every (b * H + h, q tile, k tile)."""
    B, H, T, _ = q.shape
    bq, bk = tiles
    keep = jnp.stack([
        jnp.block([[_tile_keep(seed, bh, qi, kj, (bq, bk), rate)
                    for kj in range(T // bk)] for qi in range(T // bq)])
        for bh in range(B * H)]).reshape(B, H, T, T)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = jnp.where(jnp.arange(T)[None, :] > jnp.arange(T)[:, None],
                      pallas_attention.NEG_INF, s)
    p = jnp.where(keep, jax.nn.softmax(s, axis=-1) / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("slots", [("q", "k", "v"), ("q",)],
                         ids=["cross", "self"])
@pytest.mark.parametrize("plan", ["onepass", "stream"])
@pytest.mark.parametrize("H,D", [(8, 64), (4, 128)],
                         ids=["8_heads_of_64", "4_heads_of_128"])
@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["no_dropout", "dropout"])
@pytest.mark.parametrize("causal", [False, True])
def test_token_major_op_matches_the_reference_and_the_head_major_op(
        interpret_kernels, monkeypatch, causal, rate, H, D, plan, slots):
    """The `layout="BTHD"` op and its grad op, through a program, under the
    interpreter: `Out` and dQ, dK, dV (their sum where one var feeds all
    three slots) against the reference on the transposed operands and its
    `jax.grad`, and against the head-major op on the same values. With
    dropout both layouts run the kernels under a stand-in for the chip's
    PRNG keyed as `_dropout_mask` is, by (b * H + h, q tile, k tile): the
    token-major call draws the head-major call's masks, and the reference
    is given the same ones. `Out` is bitwise the head-major op's at heads
    of 128 lanes, a head being its own lane group, and to float rounding at
    64, where a product contracts over the pair's 128 lanes with the other
    head's zeroed (bitwise on the chip, tests/test_flash_grad_tpu.py: XLA's
    CPU dot sums 128 terms in another order than 64); the gradients are to
    float rounding either way."""
    B, T = 1, 256
    tiles = (256, 256) if plan == "onepass" else (128, 128)
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    assert pallas_attention._fwd_plan(T, tiles[1]) == plan
    if rate:
        monkeypatch.setattr(
            pallas_attention, "_dropout_mask",
            lambda seed_ref, *a: _tile_keep(seed_ref[0, 0], *a))
        monkeypatch.setattr(pallas_attention, "_pallas_ok",
                            lambda *a, **k: True)
    feed = qkv_feed(slots, shape=(B, T, H, D))

    def head_major(x):
        return np.ascontiguousarray(np.transpose(x, (0, 2, 1, 3)))

    out, grads, text = attention_grads(feed, causal, amp=False, rate=rate,
                                       layout="BTHD")
    out_h, grads_h, text_h = attention_grads(
        {n: head_major(x) for n, x in feed.items()}, causal, amp=False,
        rate=rate)
    want_calls = ([1, 0] if plan == "onepass" else [0, 1]) + [1, 0, 0]
    for t in (text, text_h):
        assert [kernel_calls(t, k) for k in KERNELS] == want_calls
    assert not any(eqn.primitive.name == "transpose"
                   for eqn in text.jaxpr.jaxpr.eqns)

    step_key = jax.random.fold_in(jax.random.key(7), np.uint32(0))
    seed = jax.random.key_data(jax.random.fold_in(step_key, 1)).reshape(
        -1)[0].astype(jnp.int32)              # the op is op 1 of its block
    scale = D ** -0.5

    def reference(*qkv):
        q, k, v = (jnp.transpose(x, (0, 2, 1, 3))
                   for x in (qkv if len(qkv) == 3 else qkv * 3))
        if rate:
            ref = _reference_under_tile_masks(q, k, v, causal, scale, rate,
                                              seed, tiles)
        else:
            ref = _attention_reference(q, k, v, causal, scale)
        return jnp.transpose(ref, (0, 2, 1, 3))

    args = [jnp.asarray(feed[n]) for n in slots]
    np.testing.assert_allclose(out, np.asarray(reference(*args)), atol=2e-5,
                               rtol=2e-5)
    if rate:
        assert not np.allclose(out, np.asarray(_attention_reference(
            *(jnp.transpose(x, (0, 2, 1, 3)) for x in (args * 3)[:3]),
            causal, scale)).transpose(0, 2, 1, 3), atol=1e-2)
    want = jax.grad(lambda *a: (reference(*a) * feed["probe"]).sum(),
                    tuple(range(len(args))))(*args)
    for n, w in zip(slots, want):
        np.testing.assert_allclose(grads[n], np.asarray(w), atol=2e-4,
                                   rtol=2e-4, err_msg=f"d{n}")
        np.testing.assert_allclose(grads[n], grads_h[n].transpose(0, 2, 1, 3),
                                   atol=2e-5, rtol=2e-5, err_msg=f"d{n}")
    if D == 128:
        np.testing.assert_array_equal(out, out_h.transpose(0, 2, 1, 3))
    else:
        np.testing.assert_allclose(out, out_h.transpose(0, 2, 1, 3),
                                   atol=2e-6, rtol=2e-6)


def _transposed_heads_attention(q_in, kv_in, d_model, num_heads,
                                dropout_rate=0.0, causal=False, is_test=False,
                                name="", fused=True):
    """`models.transformer.multi_head_attention` as it was before the op
    took `layout="BTHD"`: a head-split transpose on each operand, the
    head-major op, a transpose back."""
    d_head = d_model // num_heads

    def project(x, tag):
        return layers.fc(input=x, size=d_model, num_flatten_dims=2,
                         bias_attr=False, name=name + tag)

    def split_heads(x):
        r = layers.reshape(x, shape=[0, 0, num_heads, d_head])
        return layers.transpose(r, perm=[0, 2, 1, 3])

    ctx = layers.fused_attention(
        split_heads(project(q_in, "_q")), split_heads(project(kv_in, "_k")),
        split_heads(project(kv_in, "_v")), causal=causal,
        sm_scale=d_head ** -0.5, dropout_rate=dropout_rate, is_test=is_test)
    merged = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                            shape=[0, 0, d_model])
    return project(merged, "_o")


def test_transformer_program_has_no_transpose_around_its_attention_ops(
        interpret_kernels, monkeypatch):
    """The Program `models/transformer.py` builds with `fused_attention=True`
    holds 18 `fused_attention` ops, token-major, none fed by or feeding a
    `transpose` op (the reshapes on either side are free), and no
    `transpose` op at all; its first loss at a tiny size, the kernels
    interpreted, is the loss of the same model built with transposes around
    a head-major op."""
    def build(patched):
        if patched:
            monkeypatch.setattr(models.transformer, "multi_head_attention",
                                _transposed_heads_attention)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            _, fetches = models.transformer.build(
                src_vocab_size=64, trg_vocab_size=64, seq_len=128, n_layer=6,
                n_head=2, d_model=128, d_inner=64, dropout_rate=0.0)
        main.random_seed = startup.random_seed = 7
        return main, startup, fetches["loss"]

    def first_loss(main, startup, loss):
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(0)
        feed = {k: rng.randint(1, 64, (2, 128)).astype(np.int64)
                for k in ("src_word", "trg_word", "lbl_word")}
        value = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)[0]
        assert kernel_calls(step_text(exe, main, scope, feed),
                            "flash_fwd_onepass") == 18
        return float(np.asarray(value).reshape(-1)[0])

    main, startup, loss = build(patched=False)
    ops = main.global_block().ops
    attention = [op for op in ops if op.type == "fused_attention"]
    assert len(attention) == 18
    assert all(op.attrs["layout"] == "BTHD" for op in attention)
    assert not [op for op in ops if op.type.startswith("transpose")]
    by_output = {n: op for op in ops for n in op.output_arg_names}
    for op in attention:
        assert {by_output[n].type for n in op.input_arg_names} == {"reshape"}
        out = op.output("Out")[0]
        assert {o.type for o in ops if out in o.input_arg_names} == {
            "reshape"}
    token_major = first_loss(main, startup, loss)
    main_h, startup_h, loss_h = build(patched=True)
    assert sum(op.type == "transpose"
               for op in main_h.global_block().ops) == 18 * 4
    assert np.isfinite(token_major)
    np.testing.assert_allclose(token_major, first_loss(main_h, startup_h,
                                                       loss_h), rtol=1e-6)


# -- interior tiles run without the causal mask -----------------------------------
# A live tile that lies wholly under the diagonal and inside the window is
# *interior* (`_causal_interior`): the mask's selects would hand back the bits
# they were given, and under a window or a kept set (`_interior_apart`) the
# kernels run such a tile in a body without them. The oracle is the same
# kernels with the predicate answering "edge" for every tile: the mask on
# every live tile, one body, the form they had. A plain causal call is that
# form still: the pass hides behind its products on the chip.

def _brute_visible(T, window):
    row, col = np.arange(T)[:, None], np.arange(T)[None, :]
    visible = col <= row
    return visible if window is None else visible & (row - col < window)


@pytest.mark.parametrize("window", [None, 1, 96, 128, 255, 256, 257, 384,
                                    640])
@pytest.mark.parametrize("tiles", [(bq, bk) for bq in (128, 256, 512)
                                   for bk in (128, 256, 512)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
def test_interior_is_a_tile_whose_mask_hides_nothing(monkeypatch, tiles,
                                                     window):
    """Against the `[T, T]` mask itself: a tile is interior iff every pair
    in it is visible, and live iff any is; an interior tile is live;
    `interior_tiles` counts them."""
    T, (bq, bk) = 1024, tiles
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    visible = _brute_visible(T, window)
    count = 0
    for qi in range(T // bq):
        for kj in range(T // bk):
            tile = visible[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
            interior = pallas_attention._causal_interior(qi, kj, bq, bk,
                                                         window)
            assert bool(interior) == bool(tile.all()), (qi, kj)
            assert bool(pallas_attention._causal_live(
                qi, kj, bq, bk, window)) == bool(tile.any()), (qi, kj)
            # the same answer on traced int32 as on Python ints
            traced = pallas_attention._causal_interior(
                jnp.int32(qi), jnp.int32(kj), bq, bk, window)
            assert bool(traced) == bool(interior)
            count += bool(interior)
    assert pallas_attention.interior_tiles(T, window) == count


@pytest.mark.parametrize("T,window,tiles,interior", [
    (8192, None, (1024, 1024), 28),     # Keye's and Mellum2's full layer: of 36
    (4096, None, (1024, 1024), 6),      # Ouro, Kanana-2, OLMoE, ...: of 10
    (8192, 1024, (1024, 1024), 0),      # Mellum2's windowed layers: of 15
    (4096, 2048, (1024, 1024), 3),      # Trinity-Mini's: of 9
    (8192, 1536, (512, 512), 29),       # a window off the tiles of 1024: of 58
    (2048, None, (256, 2048), 0),       # seq 2048: one K block a row
    (256, None, (256, 256), 0),
    (200, None, None, 0)],              # the reference path's
    ids=["8192", "4096", "8192_w1024", "4096_w2048", "8192_w1536", "2048",
         "256", "200"])
def test_interior_tiles_at_the_cells_lengths(T, window, tiles, interior):
    if tiles:
        assert pallas_attention._blk(T, True, window) == tiles
    assert pallas_attention.interior_tiles(T, window) == interior


def _selected(rng, B, T, topk):
    from paddle_tpu.ops import sparse_attention
    return sparse_attention.select_xla(
        jnp.asarray(rng.randn(B, T, T), jnp.float32), topk)


# name -> T, tiles, (D, Dv), window, kept set, dtype
INTERIOR_CASES = {
    # Ouro's, OLMoE's pattern, four tiles a row, six of ten interior, and
    # Kanana-2's, value heads narrower than the query's, bf16 as under AMP:
    # plain causal calls, one body
    "causal_4x4": (512, (128, 128), (64, 64), None, None, jnp.float32),
    "causal_4x4_D192_Dv128": (512, (128, 128), (192, 128), None, None,
                              jnp.bfloat16),
    "window_D192_Dv128": (512, (128, 128), (192, 128), 300, None,
                          jnp.bfloat16),
    # Mellum2's: a window of two tiles; Trinity-Mini's: of four
    "window_2_tiles": (1024, (128, 128), (64, 64), 256, None, jnp.float32),
    "window_4_tiles": (1024, (128, 128), (64, 64), 512, None, jnp.float32),
    "window_off_the_tiles": (1024, (128, 128), (64, 64), 300, None,
                             jnp.float32),
    # Keye's: a kept set from the selection, the causal mask on the
    # diagonal tiles alone
    "kept_selected": (512, (128, 128), (64, 64), None, "selected",
                      jnp.float32),
    # a set with ones above the diagonal: the diagonal tiles still hide them
    "kept_ones_above": (512, (128, 128), (64, 64), None, "ones",
                        jnp.float32),
    "tiles_256x128": (1024, (256, 128), (64, 64), None, "selected",
                      jnp.float32),
    "tiles_128x256": (1024, (128, 256), (64, 64), 600, None, jnp.float32),
}


@pytest.mark.parametrize("plan", ["fused", "split"])
@pytest.mark.parametrize("case", sorted(INTERIOR_CASES))
def test_unmasked_interior_is_bitwise_the_mask_on_every_tile(
        interpret_kernels, monkeypatch, case, plan):
    """`Out`, `Lse`, dQ, dK and dV of the streaming forward, the fused
    backward and the split pair, `==` the same kernels' with the mask on
    every live tile (a select whose predicate is false in every element
    changes no bit), at tiles among which are interior, diagonal and
    band-edge ones; and close to the einsum reference. The scale is a power
    of two: XLA:CPU, which runs the interpreted bodies, contracts `s * scale
    - m` into one fused multiply-add where no select stands between the two,
    and the product has to be exact for that to round as the pair does (the
    gated `tests/test_flash_grad_tpu.py` holds the chip's own arithmetic at
    the cells' scales)."""
    T, tiles, (D, Dv), window, kept, dtype = INTERIOR_CASES[case]
    rng = np.random.RandomState(21)
    B, H, scale = 1, 2, 2.0 ** round(np.log2(D ** -0.5))
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    if plan == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    bq, bk = tiles
    kinds = {(bool(pallas_attention._causal_interior(qi, kj, bq, bk, window)),
              bool(pallas_attention._causal_live(qi, kj, bq, bk, window)))
             for qi in range(T // bq) for kj in range(T // bk)}
    assert kinds == {(True, True), (False, True), (False, False)}
    q, k, v = (x.astype(dtype) for x in _qkv(rng, B, H, T, D, Dv))
    g = jnp.asarray(rng.randn(B, H, T, Dv), dtype)
    if kept == "selected":
        kept = _selected(rng, B, T, 200)
    elif kept == "ones":
        kept = jnp.ones((B, T, T), jnp.int8)

    def run():
        """A trace of its own for each form: jax keeps one a function."""
        def both(q, k, v):
            out, lse = pallas_attention._flash_forward(
                q, k, v, True, scale, window=window, kept=kept)
            return (out, lse) + tuple(pallas_attention._flash_backward(
                q, k, v, out, lse, g, True, scale, 0.0, 0, window, kept=kept))
        return str(jax.make_jaxpr(both)(q, k, v)).count("cond["), \
            both(q, k, v)

    conds, got = run()
    monkeypatch.setattr(pallas_attention, "_causal_interior",
                        lambda *a, **kw: False)
    conds_masked, want = run()
    # under a window or a kept set a body more in each kernel (the forward,
    # and one or two backward); a plain causal call is the one form
    apart = window is not None or kept is not None
    assert conds - conds_masked == apart * (2 if plan == "fused" else 3)
    for a, b, name in zip(got, want, ("Out", "Lse", "dQ", "dK", "dV")):
        assert np.abs(np.asarray(a, np.float32)).max() > 0, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    mask = None if kept is None else kept * jnp.asarray(
        _brute_visible(T, None), jnp.int8)[None]
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, vjp = jax.vjp(lambda *a: _attention_reference(
        *a, True, scale, window=window, kept=mask), *f32)
    tol = 1e-4 if dtype == jnp.float32 else 6e-2
    for a, b, name in zip((got[0],) + got[2:],
                          (ref,) + vjp(g.astype(jnp.float32)),
                          ("Out", "dQ", "dK", "dV")):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol, rtol=tol, err_msg=name)


# -- an aligned edge tile runs in strips ----------------------------------
# The diagonal tile of a call of square tiles of 1024, and under a window of
# whole tiles the band's lower-edge tile, are crossed by their edge from
# corner to corner (`_strip_side`): the four kernels that stream tiles run
# such a tile strip by strip over the extent the edge leaves live
# (`_edge_strips`), and only the sub-block the edge crosses keeps a select.
# The oracle is the same kernels with `_strip_side` answering "not aligned":
# every edge tile whole under the mask.

def _local_visible(lower, blk):
    """Key c against row r of an aligned edge tile, local to it."""
    r, c = np.arange(blk)[:, None], np.arange(blk)[None, :]
    return c > r if lower else c <= r


@pytest.mark.parametrize("blk,side", [(512, 128), (1024, 256), (256, 128),
                                      (512, 256)])
@pytest.mark.parametrize("by", ["rows", "keys"])
@pytest.mark.parametrize("lower", [False, True], ids=["diagonal", "lower"])
def test_strips_cover_the_pairs_an_edge_leaves_live(lower, by, blk, side):
    """Against the tile's own mask: the pieces' rows x keys are disjoint,
    hold every visible pair and only sub-blocks that hold one; a piece's
    mask, on scores of zeros, is `NEG_INF` exactly on its pairs that are not
    visible; `blk / side` strips, each its own rows (or keys)."""
    visible = _local_visible(lower, blk)
    pieces = pallas_attention._edge_strips(lower, by, blk, side)
    assert len(pieces) == blk // side
    covered = np.zeros((blk, blk), int)
    for i, pc in enumerate(pieces):
        own = pc.rows if by == "rows" else pc.keys
        assert (own.start, own.stop) == (i * side, (i + 1) * side)
        covered[pc.rows, pc.keys] += 1
        part = visible[pc.rows, pc.keys]
        for r0 in range(0, part.shape[0], side):
            for c0 in range(0, part.shape[1], side):
                assert part[r0:r0 + side, c0:c0 + side].any()
        masked = np.asarray(pc.mask(jnp.zeros(part.shape, jnp.float32)))
        np.testing.assert_array_equal(masked == pallas_attention.NEG_INF,
                                      ~part)
        np.testing.assert_array_equal(masked[part], 0.0)
    assert covered.max() == 1 and (covered[visible] == 1).all()
    n = blk // side
    assert covered.sum() == (n * (n + 1) // 2) * side * side


@pytest.mark.parametrize("window", [None, 512, 1000, 1024, 2048, 3072, 4096])
@pytest.mark.parametrize("tiles", [(512, 512), (1024, 1024), (2048, 2048),
                                   (1024, 2048), (1024, 512)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
def test_edge_strips_counts_what_a_brute_force_count_finds(monkeypatch,
                                                            tiles, window):
    """`edge_strips` against the `[T, T]` mask itself on a small grid: where
    the call is aligned (`_strip_side`), the tiles an edge crosses (live and
    not interior) and, of their side x side sub-blocks, those without a
    visible pair; `_on_edge` names exactly those tiles, on Python ints as on
    traced int32, and every other live tile is interior. (0, 0) where the
    tiles are not square, the window no whole number of tiles, a tile
    under 1024."""
    T, (bq, bk) = 8192, tiles
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    w = pallas_attention._window_of(window, T)
    side = pallas_attention._strip_side(bq, bk, w)
    aligned = bq == bk and bq >= 1024 and (w is None or w % bk == 0)
    assert bool(side) == aligned
    if not aligned:
        assert pallas_attention.edge_strips(T, window) == (0, 0)
        return
    assert side == bq // 4
    visible = _brute_visible(T, w)
    edge_tiles = skipped = 0
    for qi in range(T // bq):
        for kj in range(T // bk):
            tile = visible[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
            on_edge = tile.any() and not tile.all()
            diagonal, lower = pallas_attention._on_edge(qi, kj, bq, bk, w)
            assert bool(diagonal) + bool(lower) == on_edge, (qi, kj)
            traced = pallas_attention._on_edge(jnp.int32(qi), jnp.int32(kj),
                                               bq, bk, w)
            assert [bool(x) for x in traced] == [bool(diagonal), bool(lower)]
            if not on_edge:
                continue
            np.testing.assert_array_equal(
                tile, _local_visible(bool(lower), bq), err_msg=f"{qi} {kj}")
            edge_tiles += 1
            skipped += sum(
                not tile[r:r + side, c:c + side].any()
                for r in range(0, bq, side) for c in range(0, bk, side))
    assert pallas_attention.edge_strips(T, window) == (edge_tiles, skipped)


@pytest.mark.parametrize("T,window,strips", [
    (8192, 1024, (15, 90)),     # Mellum2's windowed layers: 8 + 7, all 15
    (8192, None, (8, 48)),      # its full layer, Keye's call: of 36
    (4096, 2048, (6, 36)),      # Trinity-Mini's windowed layers: 4 + 2 of 9
    (4096, None, (4, 24)),      # Ouro, Kanana-2, OLMoE, ...: of 10
    (8192, 1000, (0, 0)),       # a window off the tiles
    (2048, None, (0, 0)),       # seq 2048: one K block a row
    (256, None, (0, 0)),
    (200, None, (0, 0))],       # the reference path's
    ids=["8192_w1024", "8192", "4096_w2048", "4096", "8192_w1000", "2048",
         "256", "200"])
def test_edge_strips_at_the_cells_lengths(T, window, strips):
    assert pallas_attention.edge_strips(T, window) == strips


# name -> T, tiles (None: `_blk`'s), (D, Dv), window, kept set, dtype, whether
# strips run
STRIP_CASES = {
    # four tiles of 1024 a row in strips of 256: plain causal (Ouro's
    # pattern), latent attention's widths in bf16 (Kanana-2's)
    "causal": (4096, None, (64, 64), None, None, jnp.float32, True),
    "causal_D192_Dv128": (4096, None, (192, 128), None, None, jnp.bfloat16,
                          True),
    # a window of one tile (Mellum2's: a lower-edge tile beside the diagonal
    # one, none interior) and of two (Trinity-Mini's: one interior between)
    "window_1_tile": (2048, None, (64, 64), 1024, None, jnp.float32, True),
    "window_2_tiles": (4096, None, (64, 64), 2048, None, jnp.float32, True),
    # Keye's: the kept tile is cut with the scores
    "kept_selected": (2048, (1024, 1024), (64, 64), None, "selected",
                      jnp.float32, True),
    # calls that are not aligned keep the body they had: the same bits
    "window_off_the_tiles": (2048, None, (64, 64), 1000, None, jnp.float32,
                             False),
    "tiles_512x1024": (2048, (512, 1024), (64, 64), None, None, jnp.float32,
                       False),
    "tiles_1024x512_window": (2048, (1024, 512), (64, 64), 1024, None,
                              jnp.float32, False),
    "tiles_512": (2048, (512, 512), (64, 64), 1024, None, jnp.float32, False),
}


@pytest.mark.parametrize("plan", ["fused", "split"])
@pytest.mark.parametrize("case", sorted(STRIP_CASES))
def test_edge_tiles_in_strips_are_the_whole_masked_tiles(
        interpret_kernels, monkeypatch, case, plan):
    """`Out`, `Lse`, dQ, dK and dV of the streaming forward, the fused
    backward and the split pair with aligned edge tiles in strips, against
    the same kernels with every edge tile whole under the mask. `Lse` `==`:
    a strip's row maximum and sum of weights run over the live keys in the
    order the whole tile's do, and what they leave out is zeros at one end.
    `Out`, dQ, dK and dV, each a product whose contraction a strip
    shortens, to float32 rounding here and `==` on the chip
    (tests/test_flash_grad_tpu.py): XLA:CPU's dot, which runs the
    interpreted bodies, blocks a contraction of 256 otherwise than one of
    1024 (the MXU adds a contraction's passes in order). A call that is not
    aligned runs the body it had and every result is `==`. The scale is a
    power of two (see
    `test_unmasked_interior_is_bitwise_the_mask_on_every_tile`)."""
    T, tiles, (D, Dv), window, kept, dtype, strips = STRIP_CASES[case]
    rng = np.random.RandomState(72)
    B, H, scale = 1, 1, 2.0 ** round(np.log2(D ** -0.5))
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    if plan == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    bq, bk = pallas_attention._blk(T, True, window)
    assert bool(pallas_attention._strip_side(bq, bk, window)) == strips
    assert bool(pallas_attention.edge_strips(T, window)[0]) == strips
    q, k, v = (x.astype(dtype) for x in _qkv(rng, B, H, T, D, Dv))
    g = jnp.asarray(rng.randn(B, H, T, Dv), dtype)
    if kept == "selected":
        kept = _selected(rng, B, T, 600)

    def run():
        """A trace of its own for each form: jax keeps one a function."""
        def both(q, k, v):
            out, lse = pallas_attention._flash_forward(
                q, k, v, True, scale, window=window, kept=kept)
            return (out, lse) + tuple(pallas_attention._flash_backward(
                q, k, v, out, lse, g, True, scale, 0.0, 0, window, kept=kept))
        return str(jax.make_jaxpr(both)(q, k, v)).count("cond["), \
            both(q, k, v)

    conds, got = run()
    monkeypatch.setattr(pallas_attention, "_strip_side", lambda *a: None)
    conds_whole, want = run()
    # in each kernel (the forward, and one or two backward) a body for each
    # edge and, unless the window is one tile and the band two edge tiles,
    # one for every other live tile, where the whole form has the
    # masked body and, under a window or a kept set that leaves a tile
    # interior, the one without the mask
    apart = (window is not None or kept is not None) and any(
        pallas_attention._causal_interior(qi, kj, bq, bk, window)
        for qi in range(T // bq) for kj in range(T // bk))
    edges = 1 + (window is not None)
    rest = window is None or window > bk     # a tile that is no edge tile
    assert conds - conds_whole == strips * (edges + rest - (1 + apart)) * (
        2 if plan == "fused" else 3)
    for a, b, name in zip(got, want, ("Out", "Lse", "dQ", "dK", "dV")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a).max() > 0, name
        if strips and name != "Lse":
            tol = 2e-6 if dtype == jnp.float32 else 2 ** -7
            np.testing.assert_allclose(a, b, rtol=tol,
                                       atol=tol * np.abs(b).max(),
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)
    mask = None if kept is None else kept * jnp.asarray(
        _brute_visible(T, None), jnp.int8)[None]
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, vjp = jax.vjp(lambda *a: _attention_reference(
        *a, True, scale, window=window, kept=mask), *f32)
    tol = 1e-4 if dtype == jnp.float32 else 6e-2
    for a, b, name in zip((got[0],) + got[2:],
                          (ref,) + vjp(g.astype(jnp.float32)),
                          ("Out", "dQ", "dK", "dV")):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("plan", ["fused", "split"])
@pytest.mark.parametrize("T,window", [(2048, 1024), (4096, 2048)],
                         ids=["w1024", "w2048"])
def test_strips_hold_the_window_to_the_key(interpret_kernels, monkeypatch,
                                           T, window, plan):
    """No score that was computed and unmasked is skipped, none that was
    masked is let in: on scores of std 6, where one key more or less moves a
    row's softmax, `Out` and the gradients of a call whose edge tiles run in
    strips are the masked softmax's at the window it was given, and are
    refused, by the same limits, by the softmax of a window one key longer
    and of one a key shorter."""
    rng = np.random.RandomState(73)
    B, H, D = 1, 1, 64
    if plan == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    assert pallas_attention._blk(T, True, window) == (1024, 1024)
    assert pallas_attention.edge_strips(T, window)[0] == T // 1024 + (
        T - window) // 1024
    q, k, v = _qkv(rng, B, H, T, D, D)
    q = q * 6.0
    g = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    scale = D ** -0.5
    out, vjp = jax.vjp(lambda *a: flash_attention(
        *a, jnp.int32(0), True, scale, 0.0, window), q, k, v)
    got = (out,) + vjp(g)

    def softmax_of(w):
        ref, ref_vjp = jax.vjp(lambda *a: _masked_softmax(*a, w, scale),
                               q, k, v)
        return (ref,) + ref_vjp(g)

    def close(a, b):
        return np.allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)

    for a, b, name in zip(got, softmax_of(window), ("Out", "dQ", "dK", "dV")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=name)
    for off in (window + 1, window - 1):
        assert not any(close(a, b) for a, b in zip(got, softmax_of(off))), off


@pytest.mark.parametrize("why,kw,tiles,want", [
    ("causal", dict(causal=True), (1024, 1024), (2 * 2 * 2, 2 * 2 * 12)),
    ("a window of one tile", dict(causal=True, window=1024), None,
     (2 * 2 * 3, 2 * 2 * 18)),
    ("a window off the tiles", dict(causal=True, window=1000), None, (0, 0)),
    ("tiles under 1024", dict(causal=True, window=1024), (512, 512), (0, 0)),
    ("tiles that are not square", dict(causal=True), (512, 1024), (0, 0)),
    ("token-major", dict(causal=True, layout="BTHD"), (1024, 1024), (0, 0)),
    ("one K block a row", dict(causal=True), None, (0, 0)),
    ("not causal", dict(causal=False), (1024, 1024), (None, None))])
def test_the_op_tallies_the_edge_tiles_it_runs_in_strips(monkeypatch, why, kw,
                                                         tiles, want):
    """`flash_edge_tiles_stripped` and `flash_subblocks_skipped` on the
    compile event of a built Program: batch x heads x the forward grid's
    aligned edge tiles and the sub-blocks of them that are not computed; 0
    of a causal op that falls back (off the alignment, tiles under 1024,
    token-major operands, a row of one K block: both transformer cells); an
    op that is not causal
    does not count, and the grad op's trace adds nothing."""
    from paddle_tpu import observe
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    B, H, T, D = 2, 2, 2048, 16
    shape = [B, T, H, D] if kw.get("layout") == "BTHD" else [B, H, T, D]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data(name="q", shape=shape, dtype="float32",
                        append_batch_size=False, stop_gradient=False)
        loss = layers.reduce_sum(layers.fused_attention(q, q, q, **kw))
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(main, feed={"q": np.ones(shape, np.float32)}, fetch_list=[loss],
            scope=fluid.Scope())
    detail = observe.observatory().latest(main._uid).detail
    assert (detail.get("flash_edge_tiles_stripped"),
            detail.get("flash_subblocks_skipped")) == want
