"""TPU-only: `fused_attention_grad` on the forward op's saved `Out` and
`Lse` against the generic vjp path (the forward kernel run again inside the
grad op), at the benchmark cells' own shapes and tiles, with the cells'
dropout rate. The CPU suite holds the same equality under the Pallas
interpreter (tests/test_flash_attention.py), where dropout is off and a
row has the blocks a test gives it; what only the chip can say is that the
Mosaic kernels, the hardware PRNG's masks re-seeded per tile, and `Lse`
written and read through 4, 8 or 16 q-blocks give the same bits both ways.
And that the fused backward kernel (dQ, dK and dV from one pass over the
score tiles) gives the bits of the split pair it replaced, masks and all.
And that the kernels on `[batch, seq, heads, head_dim]` operands, a head a
range of lanes and several heads a grid step, give what the head-major
kernels give on the transposed operands: `Out`, `Lse` and dV bit for bit.
And that the kernels which run a causal call's interior tiles without the
mask give the bits of the mask on every tile, at the cells' own scales. And
that a causal call whose index maps hold every operand on the live
neighbour of a step above the diagonal gives the bits of the call that
fetches each one's own block there. And that a call whose aligned edge tiles
(the diagonal one, a window's lower one) run in strips over their live
extent gives the bits of the call that runs them whole under the mask."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.core.executor import resolve_compiler_options
from paddle_tpu.ops import pallas_attention

from attention_program import (attention_grads, float32_grad_layer,
                               kernel_calls, qkv_feed)

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic kernels and in-kernel dropout need real TPU hardware")

# (q shape, causal, the tiles `_blk` gives it): transformer_base.seq256,
# .seq2048 (encoder self and cross, decoder self), olmoe_1b_7b.bs1,
# mellum2_12b_a2_5b.s8192's full layer, qwen3_next_80b_a3b.bs1
CELLS = [((96, 8, 256, 64), False, (256, 256)),
         ((96, 8, 256, 64), True, (256, 256)),
         ((12, 8, 2048, 64), False, (512, 2048)),
         ((12, 8, 2048, 64), True, (256, 2048)),
         ((1, 16, 4096, 128), True, (1024, 1024)),
         ((1, 32, 8192, 128), True, (1024, 1024)),
         ((1, 16, 4096, 256), True, (1024, 1024))]
KERNELS = ("flash_fwd_onepass", "flash_fwd", "flash_dq_flash_dkv",
           "flash_dq", "flash_dkv")


def _fwd(tiles, shape, calls=1):
    """Call counts of (one-pass, streaming) forward kernels: a row that is
    one K block keeps no softmax state (`_fwd_plan`)."""
    return [calls, 0] if tiles[1] == shape[2] else [0, calls]


def _feed_for(inputs, shape, monkeypatch):
    """`bf16_inputs`: bf16 Q/K/V in the scope, a bf16 `Out@GRAD`.
    `float32_out_grad`: float32 Q/K/V that AMP casts for the op, and a
    float32 `Out@GRAD` beside the bf16 `Out`."""
    if inputs == "bf16_inputs":
        return qkv_feed(("q", "k", "v"), shape, dtype=jnp.bfloat16), None
    return qkv_feed(("q", "k", "v"), shape), float32_grad_layer(monkeypatch)


def _assert_same_bits(feed, out, grads, out_w, grads_w):
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(out_w, np.float32))
    for n in "qkv":
        got, want = (np.asarray(g[n], np.float32) for g in (grads, grads_w))
        assert grads[n].dtype == feed[n].dtype
        assert np.isfinite(got).all() and np.abs(got).max() > 0
        np.testing.assert_array_equal(got, want, err_msg=f"d{n}")


@pytest.mark.parametrize("inputs", ["bf16_inputs", "float32_out_grad"])
@pytest.mark.parametrize("shape,causal,tiles", CELLS,
                         ids=[f"{s[2]}x{s[3]}_{'causal' if c else 'full'}"
                              for s, c, _ in CELLS])
def test_saved_lse_grad_is_bitwise_the_generic_path(monkeypatch, shape,
                                                    causal, tiles, inputs):
    assert pallas_attention._blk(shape[2], causal) == tiles
    feed, after = _feed_for(inputs, shape, monkeypatch)
    place = fluid.TPUPlace(0)
    out, grads, text = attention_grads(feed, causal, amp=True, rate=0.1,
                                       after=after, place=place)
    monkeypatch.setattr(registry.get_op_def("fused_attention"), "grad_lower",
                        None)
    out_g, grads_g, text_g = attention_grads(feed, causal, amp=True, rate=0.1,
                                             after=after, place=place)
    assert [kernel_calls(text, k) for k in KERNELS] == (
        _fwd(tiles, shape) + [1, 0, 0])
    assert [kernel_calls(text_g, k) for k in KERNELS] == (
        _fwd(tiles, shape, 2) + [1, 0, 0])
    _assert_same_bits(feed, out, grads, out_g, grads_g)
    # dropout is on: another step (another key) gives another mask
    assert not np.array_equal(np.asarray(out, np.float32), np.asarray(
        attention_grads(feed, causal, amp=True, rate=0.0, after=after,
                        place=place)[0], np.float32))


@pytest.mark.parametrize("inputs", ["bf16_inputs", "float32_out_grad"])
@pytest.mark.parametrize("shape,causal,tiles", CELLS,
                         ids=[f"{s[2]}x{s[3]}_{'causal' if c else 'full'}"
                              for s, c, _ in CELLS])
def test_fused_backward_is_bitwise_the_split_kernels(monkeypatch, shape,
                                                     causal, tiles, inputs):
    """Every cell's shape takes the fused kernel (one K block a row in the
    transformer cells, a resident float32 dQ row of `[4096, 128]` in OLMoE's,
    `[8192, 128]` in Mellum2's, `[4096, 256]` in Qwen3-Next's);
    with the plan forced to the split pair the same program gives the same
    dQ/dK/dV, dropout 0.1."""
    _, _, T, D = shape
    assert pallas_attention._bwd_plan(T, D, D, *tiles, 2) == "fused"
    feed, after = _feed_for(inputs, shape, monkeypatch)
    place = fluid.TPUPlace(0)
    out, grads, text = attention_grads(feed, causal, amp=True, rate=0.1,
                                       after=after, place=place)
    monkeypatch.setattr(pallas_attention, "_bwd_plan", lambda *a: "split")
    out_s, grads_s, text_s = attention_grads(feed, causal, amp=True, rate=0.1,
                                             after=after, place=place)
    assert [kernel_calls(text, k) for k in KERNELS] == (
        _fwd(tiles, shape) + [1, 0, 0])
    assert [kernel_calls(text_s, k) for k in KERNELS] == (
        _fwd(tiles, shape) + [0, 1, 1])
    _assert_same_bits(feed, out, grads, out_s, grads_s)


ONEPASS = [((1, 8, 256, 64), False), ((1, 8, 256, 64), True),
           ((1, 2, 2048, 64), False), ((1, 2, 2048, 64), True)]


@pytest.mark.parametrize("shape,causal", ONEPASS,
                         ids=[f"{s[1]}x{s[2]}x{s[3]}_"
                              f"{'causal' if c else 'full'}"
                              for s, c in ONEPASS])
def test_onepass_forward_is_bitwise_the_streaming_kernel(monkeypatch, shape,
                                                         causal):
    """Dropout 0.1, the cells' tiles: the one-pass forward kernel against
    the streaming kernel the plan is forced to, bitwise in `Out`, in `Lse`
    and, through the fused backward kernel on each one's saved pair, in
    dQ, dK and dV: both draw the mask of tile (bh, qi, 0)."""
    _, _, T, D = shape
    BQ, BK = pallas_attention._blk(T, causal)
    assert pallas_attention._fwd_plan(T, BK) == "onepass"
    rng = np.random.RandomState(T + causal)
    q, k, v, g = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                  for _ in range(4))

    def run(q, k, v, g):
        out, lse = pallas_attention._flash_forward(q, k, v, causal,
                                                   D ** -0.5, 0.1, 77)
        return (out, lse) + pallas_attention._flash_backward(
            q, k, v, out, lse, g, causal, D ** -0.5, 0.1, 77)

    def compiled_run(kernel):
        """`run` traced afresh (a new function object, so the plan in
        force is read) and compiled with the executor's options: the
        backward's (512, 2048) tile needs its scoped-VMEM budget."""
        fn = jax.jit(lambda *a: run(*a))
        text = str(jax.make_jaxpr(fn)(q, k, v, g))
        assert [kernel_calls(text, n) for n in KERNELS[:2]] == [
            int(n == kernel) for n in KERNELS[:2]]
        return fn.lower(q, k, v, g).compile(
            compiler_options=resolve_compiler_options("tpu"))(q, k, v, g)

    one = compiled_run("flash_fwd_onepass")
    monkeypatch.setattr(pallas_attention, "_fwd_plan", lambda *a: "stream")
    stream = compiled_run("flash_fwd")
    no_drop = pallas_attention._flash_forward(q, k, v, causal, D ** -0.5)
    assert not np.array_equal(np.asarray(one[0], np.float32),
                              np.asarray(no_drop[0], np.float32))
    for a, b, name in zip(one, stream, ("Out", "Lse", "dQ", "dK", "dV")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all() and np.abs(a).max() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


# (q shape, value width, window, kept set): mellum2_12b_a2_5b.s8192's
# windowed layers, trinity_mini_26b_a3b.s4096's, a window over latent
# attention's widths, keye_vl_2_30b_a3b.s8192: the calls whose interior tiles
# run without the causal mask (`_interior_apart`)
INTERIOR = {"8192x128_w1024": ((1, 32, 8192, 128), 128, 1024, False),
            "4096x128_w2048": ((1, 32, 4096, 128), 128, 2048, False),
            "4096x192_128_w2048": ((1, 32, 4096, 192), 128, 2048, False),
            "8192x128_kept": ((1, 32, 8192, 128), 128, None, True)}


@pytest.mark.parametrize("plan", ["fused", "split"])
@pytest.mark.parametrize("case", sorted(INTERIOR))
def test_unmasked_interior_is_bitwise_the_mask_on_every_tile(monkeypatch,
                                                             case, plan):
    """At the cells' shapes, tiles and scales (`head_dim ** -0.5`, no power
    of two at 128 and 192), by Mosaic's own arithmetic: `Out`, `Lse`, dQ, dK
    and dV of the kernels that run interior tiles without the causal mask
    are the bits of the same kernels with the mask on every live tile (the
    interior predicate answering "edge" always), under a window and under a
    kept set (whose tile a step above the diagonal does not fetch:
    `_kept_spec`)."""
    shape, Dv, window, kept = INTERIOR[case]
    B, H, T, D = shape
    # no tile large enough for strips: a window's tiles are 512 (what they
    # were until PR 72, and are at a window off the tiles of 1024) and every
    # edge tile is whole, so what is held is the interior tiles alone
    monkeypatch.setattr(pallas_attention, "_STRIP_TILE", 1 << 30)
    assert pallas_attention.interior_tiles(T, window) > 0
    if plan == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    rng = np.random.RandomState(T + D)
    q, k = (jnp.asarray(rng.randn(*shape), jnp.bfloat16) for _ in range(2))
    v, g = (jnp.asarray(rng.randn(B, H, T, Dv), jnp.bfloat16)
            for _ in range(2))
    if kept:        # a third of the keys below the diagonal, and a row's own
        kept = jnp.asarray(np.tril(rng.rand(B, T, T) < 0.3)
                           | np.eye(T, dtype=bool), jnp.int8)
    else:
        kept = None

    def run(q, k, v, g):
        out, lse = pallas_attention._flash_forward(
            q, k, v, True, D ** -0.5, window=window, kept=kept)
        return (out, lse) + pallas_attention._flash_backward(
            q, k, v, out, lse, g, True, D ** -0.5, 0.0, 0, window, kept=kept)

    def compiled_run():
        """`run` traced afresh: the predicate in force is read."""
        return jax.jit(lambda *a: run(*a)).lower(q, k, v, g).compile(
            compiler_options=resolve_compiler_options("tpu"))(q, k, v, g)

    got = compiled_run()
    monkeypatch.setattr(pallas_attention, "_causal_interior",
                        lambda *a, **kw: False)
    want = compiled_run()
    for a, b, name in zip(got, want, ("Out", "Lse", "dQ", "dK", "dV")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all() and np.abs(a).max() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


# name -> (q shape, value width, window, kept set, dropout): the calls whose
# edge tiles run in strips (`_strip_side`: tiles of 1024, strips of 256), at
# the cells' shapes: Mellum2's windowed layers and its full layer,
# Trinity-Mini's windowed layers, Kanana-2's latent heads, Ouro's and
# OLMoE's, Qwen3-Next's, LFM2's, Keye's call under its kept set (the first
# case the `dsa_` kernels have in this file against another form of
# themselves than a held index map), and one with the hardware PRNG's masks
STRIPS = {"8192x128_w1024": ((1, 32, 8192, 128), 128, 1024, False, 0.0),
          "8192x128": ((1, 32, 8192, 128), 128, None, False, 0.0),
          "4096x128_w2048": ((1, 32, 4096, 128), 128, 2048, False, 0.0),
          "4096x192_128": ((1, 32, 4096, 192), 128, None, False, 0.0),
          "4096x128": ((1, 16, 4096, 128), 128, None, False, 0.0),
          "4096x256": ((1, 16, 4096, 256), 256, None, False, 0.0),
          "4096x64": ((1, 32, 4096, 64), 64, None, False, 0.0),
          "8192x128_kept": ((1, 32, 8192, 128), 128, None, True, 0.0),
          "8192x128_dropout": ((1, 32, 8192, 128), 128, None, False, 0.1)}


@pytest.mark.parametrize("plan", ["fused", "split"])
@pytest.mark.parametrize("case", sorted(STRIPS))
def test_edge_tiles_in_strips_give_the_bits_of_whole_masked_tiles(
        monkeypatch, case, plan):
    """By Mosaic's own arithmetic, at the cells' shapes, tiles and scales:
    `Out`, `Lse`, dQ, dK and dV of the kernels that run an aligned edge tile
    strip by strip over its live extent (the streaming forward and dQ by
    rows, dK/dV and the fused backward by keys, its dQ from the key strips'
    shares side by side) are the bits of the same kernels with every edge
    tile whole under the mask (`_strip_side` answering "not aligned": the
    lowered text before PR 72). Every term a strip leaves out of a row's sum
    of weights or of a product's contraction is an exact zero at one end of
    the sum, and the MXU adds a contraction's passes in order."""
    shape, Dv, window, kept, rate = STRIPS[case]
    B, H, T, D = shape
    assert pallas_attention.edge_strips(T, window)[0] > 0
    if plan == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    rng = np.random.RandomState(T + D + 72)
    q, k = (jnp.asarray(rng.randn(*shape), jnp.bfloat16) for _ in range(2))
    v, g = (jnp.asarray(rng.randn(B, H, T, Dv), jnp.bfloat16)
            for _ in range(2))
    if kept:        # a third of the keys below the diagonal, and a row's own
        kept = jnp.asarray(np.tril(rng.rand(B, T, T) < 0.3)
                           | np.eye(T, dtype=bool), jnp.int8)
    else:
        kept = None

    def run(q, k, v, g):
        out, lse = pallas_attention._flash_forward(
            q, k, v, True, D ** -0.5, rate, 77, window, kept=kept)
        return (out, lse) + pallas_attention._flash_backward(
            q, k, v, out, lse, g, True, D ** -0.5, rate, 77, window,
            kept=kept)

    def compiled_run():
        """`run` traced afresh: the strips in force are read."""
        return jax.jit(lambda *a: run(*a)).lower(q, k, v, g).compile(
            compiler_options=resolve_compiler_options("tpu"))(q, k, v, g)

    got = compiled_run()
    monkeypatch.setattr(pallas_attention, "_strip_side", lambda *a: None)
    want = compiled_run()
    for a, b, name in zip(got, want, ("Out", "Lse", "dQ", "dK", "dV")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all() and np.abs(a).max() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("case", ["8192x128_w1024", "4096x128_w2048"])
def test_a_window_at_tiles_of_1024_is_the_call_at_512_to_a_rounding(
        monkeypatch, case):
    """A window of whole tiles of 1024 takes them since PR 72 (`_blk`),
    where it took 512 x 512: another tile is another running maximum under
    the weights at the moment they are rounded to bf16 for `p v`, and
    another order of every sum over a row's tiles, so `Out`, `Lse`, dQ, dK
    and dV of Mellum2's and Trinity-Mini's windowed calls are not the bits
    they were (as they were not when PR 49 took Trinity-Mini's tiles from
    1024 to 512): the same arithmetic at the same precision in another
    order. Held here: float32 `Lse` to 1e-5 of its range, the bf16 results
    to two roundings of their last place on every element (a sixth of
    `Out`'s elements differ by one, chip run, PR 72; the shares are
    printed)."""
    shape, Dv, window, _, _ = STRIPS[case]
    B, H, T, D = shape
    rng = np.random.RandomState(T + D + 1024)
    q, k, v, g = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                  for _ in range(4))

    def run(q, k, v, g):
        out, lse = pallas_attention._flash_forward(
            q, k, v, True, D ** -0.5, window=window)
        return (out, lse) + pallas_attention._flash_backward(
            q, k, v, out, lse, g, True, D ** -0.5, 0.0, 0, window)

    def compiled_run(tiles):
        """`run` traced afresh: the tile rule in force is read."""
        assert pallas_attention._blk(T, True, window) == tiles
        return jax.jit(lambda *a: run(*a)).lower(q, k, v, g).compile(
            compiler_options=resolve_compiler_options("tpu"))(q, k, v, g)

    got = compiled_run((1024, 1024))
    monkeypatch.setattr(pallas_attention, "_STRIP_TILE", 1 << 30)
    want = compiled_run((512, 512))
    for a, b, name in zip(got, want, ("Out", "Lse", "dQ", "dK", "dV")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all() and np.abs(a).max() > 0, name
        if name == "Lse":
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * np.ptp(b),
                                       err_msg=name)
            continue
        np.testing.assert_allclose(a, b, rtol=2 ** -6,
                                   atol=2 ** -8 * np.abs(b).max(),
                                   err_msg=name)
        print(name, "elements off the bit:", float((a != b).mean()),
              "largest difference:", float(np.abs(a - b).max()),
              "of", float(np.abs(b).max()))


def _held_against_every_step(monkeypatch, run, operands):
    """`run(*operands)` compiled with the index maps in force, then with
    every operand's own block on every grid step (`_dead_steps` answering
    "none": the maps before PR 70, and under a kept set its tile fetched on
    every step as before PR 68): the same bits, name by name."""
    def compiled_run():
        """Traced afresh: the maps in force are read."""
        return jax.jit(lambda *a: run(*a)).lower(*operands).compile(
            compiler_options=resolve_compiler_options("tpu"))(*operands)

    got = compiled_run()
    monkeypatch.setattr(pallas_attention, "_dead_steps", lambda *a: 0)
    # a token-major call is jitted and keeps the trace of the first form
    monkeypatch.setattr(pallas_attention, "_jitted_forward",
                        pallas_attention._forward)
    monkeypatch.setattr(pallas_attention, "_jitted_backward",
                        pallas_attention._backward)
    want = compiled_run()
    for a, b, name in zip(got, want, ("Out", "Lse", "dQ", "dK", "dV")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all() and np.abs(a).max() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("plan", ["fused", "split"])
def test_a_kept_set_held_on_dead_steps_gives_the_bits_of_one_fetched_there(
        monkeypatch, plan):
    """`keye_vl_2_30b_a3b.s8192`'s call, `[1, 32, 8192, 128]` bf16 under an
    int8 `[1, 8192, 8192]`, by Mosaic's own arithmetic: `Out`, `Lse`, dQ, dK
    and dV of the kernels whose kept set, K, V, Q, dOut, `Lse` and delta stay
    on the live tile beside a step above the diagonal (28 of a head's 64
    steps) are the bits of the same kernels with each one's own block
    fetched on every step."""
    shape, Dv, _, _ = INTERIOR["8192x128_kept"]
    B, H, T, D = shape
    if plan == "split":
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda *a: "split")
    rng = np.random.RandomState(68)
    q, k, v, g = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                  for _ in range(4))
    kept = jnp.asarray(np.tril(rng.rand(B, T, T) < 0.3)
                       | np.eye(T, dtype=bool), jnp.int8)

    def run(q, k, v, g):
        out, lse = pallas_attention._flash_forward(
            q, k, v, True, D ** -0.5, kept=kept)
        return (out, lse) + pallas_attention._flash_backward(
            q, k, v, out, lse, g, True, D ** -0.5, 0.0, 0, kept=kept)

    _held_against_every_step(monkeypatch, run, (q, k, v, g))


# name -> (q shape, value width, token-major, dropout): the full layer of
# mellum2_12b_a2_5b.s8192 (and Keye's call without its set), kanana_2_30b_a3b
# .bs1's latent heads, ouro_2_6b.bs1's and olmoe_1b_7b.bs1's, a token-major
# call of heads a vreg wide forced to two K blocks a row
HELD = {"8192x128": ((1, 32, 8192, 128), 128, False, 0.0),
        "8192x128_dropout": ((1, 32, 8192, 128), 128, False, 0.1),
        "4096x192_128": ((1, 32, 4096, 192), 128, False, 0.0),
        "4096x128": ((1, 16, 4096, 128), 128, False, 0.0),
        "token_major_2048x4x128": ((2, 2048, 4, 128), 128, True, 0.0),
        "token_major_2048x4x128_dropout": ((2, 2048, 4, 128), 128, True, 0.1)}


@pytest.mark.parametrize("plan", ["fused", "split"])
@pytest.mark.parametrize("case", sorted(HELD))
def test_held_dead_steps_give_the_bits_of_blocks_fetched_there(
        monkeypatch, case, plan):
    """A plain causal call at the cells' shapes and tiles, by Mosaic's own
    arithmetic and with the hardware PRNG's masks: `Out`, `Lse`, dQ, dK and
    dV of the kernels whose K and V (the forward, dQ) and Q, dOut, `Lse`
    and delta (the fused backward, dK/dV) stay on the live neighbour's
    block over the steps above the diagonal (28 of a head's 64 at 8192
    tokens, 6 of 16 at 4096, 1 of 4 at 2048 in forced tiles) are the bits of
    the same kernels with each operand's own block fetched on every step."""
    shape, Dv, token_major, rate = HELD[case]
    B, H, T, D = pallas_attention._shape_of(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16), token_major)
    if token_major:
        monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (1024, 1024))
    assert pallas_attention._dead_steps(
        T, *pallas_attention._blk(T, True)) > 0
    monkeypatch.setattr(pallas_attention, "_bwd_plan", lambda *a: plan)
    rng = np.random.RandomState(T + D)
    q, k = (jnp.asarray(rng.randn(*shape), jnp.bfloat16) for _ in range(2))
    v, g = (jnp.asarray(rng.randn(*shape[:3], Dv), jnp.bfloat16)
            for _ in range(2))

    def run(q, k, v, g):
        out, lse = pallas_attention._flash_forward(
            q, k, v, True, D ** -0.5, rate, 77, token_major=token_major)
        return (out, lse) + pallas_attention._flash_backward(
            q, k, v, out, lse, g, True, D ** -0.5, rate, 77,
            token_major=token_major)

    _held_against_every_step(monkeypatch, run, (q, k, v, g))


TOKEN_MAJOR = [((96, 256, 8, 64), False), ((96, 256, 8, 64), True),
               ((12, 2048, 8, 64), False), ((12, 2048, 8, 64), True),
               ((2, 2048, 4, 128), True)]


@pytest.mark.parametrize("shape,causal", TOKEN_MAJOR,
                         ids=[f"{s[1]}x{s[2]}x{s[3]}_"
                              f"{'causal' if c else 'full'}"
                              for s, c in TOKEN_MAJOR])
def test_token_major_kernels_give_what_the_head_major_ones_give(shape, causal):
    """`[batch, seq, heads, head_dim]` operands at both transformer cells'
    shapes (and a head of a whole vreg's lanes, two K blocks a row), dropout
    0.1: `Out`, `Lse` and dV are the bits the head-major kernels give on the
    transposed operands, dQ and dK theirs to a bf16 rounding or two (the
    row sums of dOut * Out are summed in another place), so both draw the
    mask of tile (b * H + h, qi, kj) and a head's lanes take nothing from
    its neighbour's; and without dropout both agree with the reference."""
    B, T, H, D = shape
    if D == 128:
        pallas_attention._BLOCK_OVERRIDE = (1024, 1024)
    try:
        rng = np.random.RandomState(T + causal)
        q, k, v, g = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                      for _ in range(4))

        def run(token_major, rate):
            def both(q, k, v, g):
                out, lse = pallas_attention._flash_forward(
                    q, k, v, causal, D ** -0.5, rate, 77,
                    token_major=token_major)
                return (out, lse) + pallas_attention._flash_backward(
                    q, k, v, out, lse, g, causal, D ** -0.5, rate, 77,
                    token_major=token_major)
            args = (q, k, v, g) if token_major else tuple(
                x.transpose(0, 2, 1, 3) for x in (q, k, v, g))
            got = jax.jit(both).lower(*args).compile(
                compiler_options=resolve_compiler_options("tpu"))(*args)
            return [np.asarray(x if token_major or x.ndim == 3
                               else x.transpose(0, 2, 1, 3), np.float32)
                    for x in got]

        names = ("Out", "Lse", "dQ", "dK", "dV")
        dropped = run(True, 0.1)
        for a, b, name in zip(dropped, run(False, 0.1), names):
            assert np.isfinite(a).all() and np.abs(a).max() > 0, name
            if name in ("dQ", "dK"):
                # through delta = rowsum(dOut * Out): the token-major
                # kernels sum it themselves over a head's lanes (`_delta`),
                # XLA sums it for the head-major ones over [B*H, T, D]
                np.testing.assert_allclose(a, b, rtol=2 ** -6,
                                           atol=2 ** -9 * np.abs(b).max(),
                                           err_msg=name)
            else:
                np.testing.assert_array_equal(a, b, err_msg=name)
        plain = run(True, 0.0)
        assert not np.array_equal(plain[0], dropped[0])

        def reference(q, k, v):
            return pallas_attention._reference(
                q, k, v, causal, D ** -0.5, 0.0, 0, None, True)

        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        want, vjp = jax.vjp(reference, *f32)
        for a, b, name in zip([plain[0]] + plain[2:],
                              (want,) + vjp(g.astype(jnp.float32)),
                              ("Out", "dQ", "dK", "dV")):
            np.testing.assert_allclose(a, np.asarray(b), atol=0.08,
                                       rtol=0.05, err_msg=name)
    finally:
        pallas_attention._BLOCK_OVERRIDE = None
