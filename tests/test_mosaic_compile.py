"""Kernels of the main paths compiled by the TPU's own compiler for a
described v5e chip, at the cells' widths, with no chip attached: what the
Pallas interpreter cannot refuse (a slice off the tiling, too much VMEM, a
product Mosaic has no lowering for). Nothing runs, so nothing here is a
result or a time. Every such compile lives in this one file: the worker
that is given it loads the TPU's library, and keeps it."""

import os

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import linear_attention as la


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep these out of it
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


def _custom_calls(compiled, name):
    return [line for line in compiled.as_text().splitlines()
            if f"%{name}" in line and " custom-call(" in line
            and "= " in line]


def test_gdn_kernels_compile_at_the_cells_shapes(one_chip, monkeypatch):
    """`gdn_fwd` and `gdn_bwd` as `qwen3_next_80b_a3b.bs1` calls them: bf16
    q, k `[1, 4096, 16, 128]`, v `[1, 4096, 32, 128]`, the chip's one-pass
    products; each is one Mosaic custom call whose first result is the one
    the benchmark's pattern knows."""
    monkeypatch.setattr(la, "_on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = arg((1, 4096, 16, 128), jnp.bfloat16)
    v = arg((1, 4096, 32, 128), jnp.bfloat16)
    g = arg((1, 4096, 32), jnp.float32)
    states = arg((64, 1, 32, 128, 128), jnp.float32)
    fwd = jax.jit(lambda *a: la._gdn_forward(*a, 64)).lower(
        q, q, v, g, g).compile()
    (call,) = _custom_calls(fwd, "gdn_fwd")
    assert "(f32[64,1,32,128,128]{" in call and "tpu_custom_call" in call
    bwd = jax.jit(lambda *a: la._gdn_backward(*a, 64)).lower(
        q, q, v, g, g, states, v).compile()
    (call,) = _custom_calls(bwd, "gdn_bwd")
    assert "(f32[1,32,64,1,64]{" in call and "tpu_custom_call" in call


def test_causal_conv_kernels_compile_at_the_cells_shape(one_chip,
                                                        monkeypatch):
    """`causal_conv_fwd` and `causal_conv_bwd` as `qwen3_next_80b_a3b.bs1`
    calls them: X and dOut bf16 `[1, 4096, 8192]`, W float32 `[8192, 4]`;
    the misaligned sublane slices of the taps and the 16-row block before a
    time block are what the interpreter cannot refuse. One Mosaic custom
    call each, named for the benchmark's pattern; the float32 form too."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert la._conv_plan(4096, 8192, 4) == "kernel"

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = arg((8192, 4), jnp.float32)
    for dtype, short in ((jnp.bfloat16, "bf16"), (jnp.float32, "f32")):
        x = arg((1, 4096, 8192), dtype)
        fwd = jax.jit(lambda x, w: la._conv_forward(x, w, True)).lower(
            x, w).compile()
        (call,) = _custom_calls(fwd, "causal_conv_fwd")
        assert f"= {short}[1,4096,8192]{{" in call
        assert "tpu_custom_call" in call
        bwd = jax.jit(lambda x, w, d: la._conv_backward(x, w, d, True)).lower(
            x, w, x).compile()
        (call,) = _custom_calls(bwd, "causal_conv_bwd")
        assert f"= ({short}[1,4096,8192]{{" in call and ", f32[4,8192]{" in call


def test_share_movements_compile_at_the_cells_shapes(one_chip):
    """A share's layout and a token-side movement as `qwen3_next_80b_a3b.bs1`
    runs them (4096 tokens, top 10 of 512, experts 64..95 held, 2048 wide):
    `lax.while_loop`s over the used rows, the 45056-row buffer an
    `AllocateBuffer` that the loop takes as it is (no fill, no copy: the
    program needs no temporary of the buffer's size beside its result)."""
    from paddle_tpu.ops import moe
    n, k, width, held = 4096, 10, 2048, 32
    rows = n * k + held * moe.ROW_TILE

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layout = jax.jit(lambda x, index, counts: moe._dispatch_share(
        x, index, counts, moe.ROW_TILE, 64, held)).lower(
            arg((n, width), jnp.bfloat16), arg((n, k), jnp.int32),
            arg((512,), jnp.int32)).compile()
    text = layout.as_text()
    assert 'custom_call_target="AllocateBuffer"' in text and " while(" in text
    assert layout.memory_analysis().temp_size_in_bytes < rows * width * 2
    combine = jax.jit(lambda y, source, sizes, weight: moe._tokens_from_rows(
        y, source, k, n, sizes, scale=weight)).lower(
            arg((rows, width), jnp.bfloat16), arg((rows,), jnp.int32),
            arg((held,), jnp.int32), arg((n * k,), jnp.float32)).compile()
    assert " while(" in combine.as_text()
    # no 40960-row gather of the layout is left in either
    assert f"[{n * k},{width}]" not in text + combine.as_text()


def test_flash_kernels_compile_at_latent_attentions_widths(one_chip,
                                                           monkeypatch):
    """The streaming forward and the split backward pair as
    `kanana_2_30b_a3b.bs1` calls them: bf16 q, k `[1, 32, 4096, 192]` over
    v `[1, 32, 4096, 128]`. A 192-wide block is the array's whole last axis
    (one and a half vregs of lanes): Mosaic takes it; `Out` and `dV` leave at
    128, `dQ` and `dK` at 192, and no 192-wide value is anywhere."""
    from paddle_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(pa, "_interpret", lambda: False)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, v = arg((1, 32, 4096, 192)), arg((1, 32, 4096, 128))
    assert pa._fwd_plan(4096, pa._blk(4096, True)[1]) == "stream"
    assert pa._bwd_plan(4096, 192, pa._blk(4096, True)[1]) == "split"
    fwd = jax.jit(lambda q, k, v: pa._flash_forward(
        q, k, v, True, 192 ** -0.5)).lower(q, q, v).compile()
    (call,) = _custom_calls(fwd, "flash_fwd")
    assert "(bf16[32,4096,128]{" in call and "f32[32,1,4096]{" in call
    bwd = jax.jit(lambda q, k, v, o, lse, g: pa._flash_backward(
        q, k, v, o, lse, g, True, 192 ** -0.5, 0.0, 0)).lower(
            q, q, v, v, arg((32, 1, 4096), jnp.float32), v).compile()
    (dq,) = _custom_calls(bwd, "flash_dq")
    (dkv,) = _custom_calls(bwd, "flash_dkv")
    assert "= bf16[32,4096,192]{" in dq
    assert "(bf16[32,4096,192]{" in dkv and ", bf16[32,4096,128]{" in dkv
