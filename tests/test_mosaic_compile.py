"""Kernels of the main paths compiled by the TPU's own compiler for a
described v5e chip, at the cells' widths, with no chip attached: what the
Pallas interpreter cannot refuse (a slice off the tiling, too much VMEM, a
product Mosaic has no lowering for). Nothing runs, so nothing here is a
result or a time. Every such compile lives in this one file: the worker
that is given it loads the TPU's library, and keeps it."""

import os
import re

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import _kernels
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
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = arg((1, 4096, 16, 128), jnp.bfloat16)
    v = arg((1, 4096, 32, 128), jnp.bfloat16)
    g = arg((1, 4096, 32), jnp.float32)
    states = arg((64, 1, 32, 128, 128), jnp.float32)
    assert la._grid(q, v, 64) == ((1, 16, 32), 2)      # two chunks a step
    fwd = jax.jit(lambda *a: la._gdn_forward(*a, 64)).lower(
        q, q, v, g, g).compile()
    (call,) = _custom_calls(fwd, "gdn_fwd")
    assert "(f32[64,1,32,128,128]{" in call and "tpu_custom_call" in call
    bwd = jax.jit(lambda *a: la._gdn_backward(*a, 64)).lower(
        q, q, v, g, g, states, v).compile()
    (call,) = _custom_calls(bwd, "gdn_bwd")
    assert "(f32[1,32,64,1,64]{" in call and "tpu_custom_call" in call


# `gdn_fwd` / `gdn_bwd` as `qwen3_next_80b_a3b.bs1` calls them: digests by
# `_lowered_digest` taken on the commit before the per-channel rule's kernels
# came to share `_Chunks`, `_plan` and `_gdn_call` with them (e20fc17, PR 61)
GDN_CALLS = ("b16ac51de9d9bce6e64e6ba38b4e7a4e",
             "2e9c9902e19d98025ce25bdc340717a5")


def test_gdn_kernels_lower_as_they_did_before_the_kda_pair(one_chip,
                                                           monkeypatch):
    """The scalar rule's two kernels are the instructions they were: what
    the two rules share changed no line of their text."""
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = arg((1, 4096, 16, 128), jnp.bfloat16)
    v = arg((1, 4096, 32, 128), jnp.bfloat16)
    g = arg((1, 4096, 32), jnp.float32)
    states = arg((64, 1, 32, 128, 128), jnp.float32)
    fwd = jax.jit(lambda *a: la._gdn_forward(*a, 64)).lower(q, q, v, g, g)
    bwd = jax.jit(lambda *a: la._gdn_backward(*a, 64)).lower(
        q, q, v, g, g, states, v)
    assert (_lowered_digest(fwd)[:32], _lowered_digest(bwd)[:32]) \
        == GDN_CALLS


def test_gdn_kernels_compile_off_the_lane_tile(one_chip, monkeypatch):
    """`gdn_fwd` and `gdn_bwd` as `olmo_hybrid_7b.s4096` calls them since PR
    64: bf16 q, k `[1, 4096, 15, 96]`, v and dO `[1, 4096, 15, 192]`, filled
    out to 128 / 256 lanes around the calls. Each way one Mosaic custom call
    at the filled widths and no `while` (the XLA form's loops over the
    chunks' states), the states written and read at `[.., 96, 192]`: what
    the interpreter cannot refuse is the state block whose last two dims
    are the array's and no whole tiles, its cut on the way out and its fill
    on the way in."""
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = arg((1, 4096, 15, 96), jnp.bfloat16)
    v = arg((1, 4096, 15, 192), jnp.bfloat16)
    g = arg((1, 4096, 15), jnp.float32)
    states = arg((64, 1, 15, 96, 192), jnp.float32)
    assert la._grid(q, v, 64) == ((1, 15, 32), 2)      # two chunks a step
    fwd = jax.jit(lambda *a: la._gdn_forward(*a, 64)).lower(
        q, q, v, g, g).compile()
    (call,) = _custom_calls(fwd, "gdn_fwd")
    assert "(f32[64,1,15,96,192]{" in call and "tpu_custom_call" in call
    assert ", bf16[1,4096,3840]{" in call               # 15 heads of 256
    bwd = jax.jit(lambda *a: la._gdn_backward(*a, 64)).lower(
        q, q, v, g, g, states, v).compile()
    (call,) = _custom_calls(bwd, "gdn_bwd")
    assert "(f32[1,15,64,1,64]{" in call and "tpu_custom_call" in call
    assert call.split(" custom-call(")[0].count("bf16[1,4096,1920]{") == 2
    for compiled in (fwd, bwd):
        text = compiled.as_text()
        assert " while(" not in text
        assert "InvertDiagBlocksLowerTriangular" not in text
    out, saved = fwd.out_info
    assert out.shape == (1, 4096, 15, 192)
    assert saved.shape == (64, 1, 15, 96, 192)


def test_kda_kernels_compile_at_the_cells_shapes(one_chip, monkeypatch):
    """`kda_fwd` and `kda_bwd` as `ling_3_0_flash_vl.s2048` calls them: bf16
    q, k, v `[1, 2048, 32, 128]`, g float32 `[1, 2048, 32, 128]`, beta
    `[1, 2048, 32]`, the chip's one-pass products; each is one Mosaic custom
    call. What the interpreter cannot refuse here: the 16-row blocks sliced
    out of a chunk's rows, a row spread over a block, a `[1, 128]` row
    turned into a `[128, 1]` column, the room ~100 float32 `[128, 128]`
    tiles take."""
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = arg((1, 2048, 32, 128), jnp.bfloat16)
    g = arg((1, 2048, 32, 128), jnp.float32)
    beta = arg((1, 2048, 32), jnp.float32)
    states = arg((32, 1, 32, 128, 128), jnp.float32)
    assert la._grid(x, x, 64) == ((1, 32, 16), 2)       # two chunks a step
    fwd = jax.jit(lambda *a: la._kda_forward(*a, 64)).lower(
        x, x, x, g, beta).compile()
    (call,) = _custom_calls(fwd, "kda_fwd")
    assert "(f32[32,1,32,128,128]{" in call and "tpu_custom_call" in call
    assert ", bf16[1,2048,4096]{" in call
    bwd = jax.jit(lambda *a: la._kda_backward(*a, 64)).lower(
        x, x, x, g, beta, states, x).compile()
    (call,) = _custom_calls(bwd, "kda_bwd")
    assert "(f32[1,2048,4096]{" in call and "tpu_custom_call" in call
    assert call.split(" custom-call(")[0].count("bf16[1,2048,4096]{") == 3
    assert not _custom_calls(fwd, "gdn_fwd")
    assert not _custom_calls(bwd, "gdn_bwd")


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


@pytest.mark.parametrize("shape,interleaved,plan", [
    ((1, 32, 8192, 128), False, "roll"), ((1, 4, 8192, 128), False, "roll"),
    ((1, 32, 4096, 64), True, "dot")],
    ids=["mellum2_q", "mellum2_k", "kanana2_q"])
def test_rotary_kernels_compile_at_the_cells_shapes(one_chip, monkeypatch,
                                                    shape, interleaved, plan):
    """`rotary_fwd` and `rotary_bwd` as `mellum2_12b_a2_5b.s8192` and
    `kanana_2_30b_a3b.bs1` call them: bf16 q and k against float32 tables;
    the lane rotation of a whole head, the 64-wide blocks and their products
    with a `[64, 64]` matrix, and the room a block of eight heads takes are
    what the interpreter cannot refuse. One Mosaic custom call each, named
    for the benchmark's pattern, X's shape and dtype out."""
    from paddle_tpu.ops import decoder_block as db
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    T, D = shape[-2:]
    assert db._rotary_plan(shape, jnp.dtype(jnp.bfloat16), D,
                           interleaved) == plan
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    table = jax.ShapeDtypeStruct((T, D), jnp.float32, sharding=one_chip)
    for backward, name in ((False, "rotary_fwd"), (True, "rotary_bwd")):
        compiled = jax.jit(lambda x, cos, sin: db._rotary_call(
            x, cos, sin, interleaved, backward)).lower(
                x, table, table).compile()
        (call,) = _custom_calls(compiled, name)
        assert f"= bf16[{shape[1]},{T},{D}]{{" in call
        assert "tpu_custom_call" in call


@pytest.mark.parametrize("dtype,short", [(jnp.bfloat16, "bf16"),
                                         (jnp.float32, "f32")],
                         ids=["bf16", "f32"])
def test_gated_norm_kernels_compile_at_the_cells_shape(one_chip, monkeypatch,
                                                       dtype, short):
    """`gated_norm_fwd` and `gated_norm_bwd` as `qwen3_next_80b_a3b.bs1`
    calls them: X, Gate and dY `[1, 4096, 32, 128]` read as
    `[1, 4096, 4096]`, Scale float32 `[128]`; a head's lanes sliced out of a
    block, the row sums over them and the room both blocks' buffers take
    are what the interpreter cannot refuse. One Mosaic custom call each,
    named for the benchmark's pattern, whose first result (Y; dX) has no
    shape that `gdn_scan_ms.train`'s pattern finds."""
    from paddle_tpu.ops import decoder_block as db
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    shape = (1, 4096, 32, 128)
    assert db._gated_norm_plan(shape, jnp.dtype(dtype)) == "kernel"
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip)
    fwd = jax.jit(lambda x, g, w: db._gated_norm_call(x, g, w, 1e-6)).lower(
        x, x, w).compile()
    (call,) = _custom_calls(fwd, "gated_norm_fwd")
    assert f"= {short}[1,4096,4096]{{" in call and "tpu_custom_call" in call
    bwd = jax.jit(lambda x, g, w, d: db._gated_norm_call(
        x, g, w, 1e-6, d)).lower(x, x, w, x).compile()
    (call,) = _custom_calls(bwd, "gated_norm_bwd")
    assert f"= ({short}[1,4096,4096]{{" in call and "tpu_custom_call" in call
    assert re.search(r", f32\[1,16,\d,8,128\]\{", call.split(" custom-call(")[0])


def test_share_movements_compile_at_the_cells_shapes(one_chip):
    """A share's layout as `qwen3_next_80b_a3b.bs1` runs it (4096 tokens,
    top 10 of 512, experts 64..95 held, 2048 wide): a `lax.while_loop` over
    the used rows, the 45056-row buffer an `AllocateBuffer` that the loop
    takes as it is (no fill, no copy: the program needs no temporary of the
    buffer's size beside its result)."""
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
    # no 40960-row gather of the layout is left in it
    assert f"[{n * k},{width}]" not in text


@pytest.mark.parametrize("scaled", [True, False],
                         ids=["moe_combine", "moe_dispatch_grad"])
@pytest.mark.parametrize("n,k,held,width", [
    (4096, 10, 32, 2048), (4096, 6, 16, 2048), (4096, 8, 8, 2048),
    (8192, 8, 8, 2304)], ids=["qwen3_next", "kanana2", "trinity", "mellum2"])
def test_token_sum_compiles_at_the_cells_shapes(one_chip, monkeypatch, n, k,
                                                held, width, scaled):
    """A share's token-side sums as the four share cells run them (bf16
    layouts of `[45056, 2048]`, `[26624, 2048]`, `[33792, 2048]` and
    `[66560, 2304]` rows; with the router weights as `moe_combine` calls
    it, without as `moe_dispatch_grad` does): `_tokens_from_rows` is one
    Mosaic custom call named for the benchmark's pattern, whose result is
    the tokens' rows in bf16. What the interpreter cannot refuse: `Source`
    and the weights (up to 528 KB) in SMEM, a float32 accumulator of 32 or
    72 MiB in VMEM under the limit the call asks for, the dynamic-row
    update. No `while` is left, and no float32 `[tokens, width]` array:
    nothing in HBM beside the result."""
    from paddle_tpu.ops import moe
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    rows = n * k + held * moe.ROW_TILE
    assert moe._token_sum_plan(n, k, rows, width, jnp.bfloat16) \
        == (width, 512, 512)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [arg((rows, width), jnp.bfloat16), arg((rows,), jnp.int32),
            arg((held,), jnp.int32)] \
        + ([arg((n * k,), jnp.float32)] if scaled else [])
    compiled = jax.jit(lambda y, source, sizes, *scale: moe._tokens_from_rows(
        y, source, k, n, sizes, jnp.bfloat16, *scale)).lower(*args).compile()
    (call,) = _custom_calls(compiled, "moe_token_sum")
    assert f"= bf16[{n},{width}]{{" in call and "tpu_custom_call" in call
    text = compiled.as_text()
    assert " while(" not in text and f"f32[{n},{width}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_share_elementwise_passes_compile_at_the_cells_shapes(one_chip):
    """What stands between a share's dispatch and combine as
    `mellum2_12b_a2_5b.s8192` runs it (66560 rows, experts 896 wide, the
    model 2304): the silu product, its grad and the sum of two input
    gradients are `lax.while_loop`s over the used rows whose results are
    `AllocateBuffer`s or, in place, the operands this op reads last (the
    two gradients over gate and up, the sum over its first operand), with
    no temporary as large as a result and no static pass over the layout."""
    from paddle_tpu.ops import decoder_block, moe
    rows, expert_width, width, held = 8192 * 8 + 8 * moe.ROW_TILE, 896, 2304, 8

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hidden, sizes = arg((rows, expert_width)), arg((held,), jnp.int32)
    product = jax.jit(lambda gate, up, sizes: moe.map_used_rows(
        lambda a, b: (decoder_block._silu_product(a, b),), sizes, gate, up)
    ).lower(hidden, hidden, sizes).compile()
    grad = jax.jit(lambda gate, up, g, sizes: moe.map_used_rows(
        decoder_block._silu_product_grads, sizes, gate, up, g, in_place=2),
        donate_argnums=(0, 1)).lower(
            hidden, hidden, hidden, sizes).compile()
    total = jax.jit(lambda a, b, sizes: moe.map_used_rows(
        lambda a, b: (a + b,), sizes, a, b, in_place=1),
        donate_argnums=0).lower(
            arg((rows, width)), arg((rows, width)), sizes).compile()
    for compiled, allocations in ((product, 1), (grad, 0), (total, 0)):
        text = compiled.as_text()
        assert " while(" in text
        assert text.count('custom_call_target="AllocateBuffer"') \
            == allocations
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
        # whatever has the layout's rows is a carried buffer or a chunk's
        # in-place write into one: no arithmetic has that many rows
        for kind, opcode in re.findall(
                r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(", text, re.M):
            if f"[{rows}," in kind:
                assert opcode in ("parameter", "get-tuple-element", "while",
                                  "tuple", "fusion", "custom-call",
                                  "dynamic-update-slice"), (opcode, kind)
    assert total.memory_analysis().alias_size_in_bytes == rows * width * 2
    assert grad.memory_analysis().alias_size_in_bytes \
        == 2 * rows * expert_width * 2


def test_flash_kernels_compile_at_latent_attentions_widths(one_chip,
                                                           monkeypatch):
    """The streaming forward and the fused backward as
    `kanana_2_30b_a3b.bs1` calls them: bf16 q, k `[1, 32, 4096, 192]` over
    v `[1, 32, 4096, 128]`. A 192-wide block is the array's whole last axis
    (one and a half vregs of lanes): Mosaic takes it, and the row's float32
    dQ (4096 x 192, held as 256 lanes) stays resident within the 32 MiB the
    kernel asks for; `Out` and `dV` leave at 128, `dQ` and `dK` at 192, and
    no 192-wide value is anywhere."""
    from paddle_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(_kernels, "interpret", lambda: False)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, v = arg((1, 32, 4096, 192)), arg((1, 32, 4096, 128))
    assert pa._fwd_plan(4096, pa._blk(4096, True)[1]) == "stream"
    assert pa._bwd_plan(4096, 192, 128, *pa._blk(4096, True), 2) == "fused"
    assert pa._fused_bwd_vmem(4096, 192, 128, 1024, 1024, 2) == 32 * 2 ** 20
    fwd = jax.jit(lambda q, k, v: pa._flash_forward(
        q, k, v, True, 192 ** -0.5)).lower(q, q, v).compile()
    (call,) = _custom_calls(fwd, "flash_fwd")
    assert "(bf16[32,4096,128]{" in call and "f32[32,1,4096]{" in call
    bwd = jax.jit(lambda q, k, v, o, lse, g: pa._flash_backward(
        q, k, v, o, lse, g, True, 192 ** -0.5, 0.0, 0)).lower(
            q, q, v, v, arg((32, 1, 4096), jnp.float32), v).compile()
    (call,) = _custom_calls(bwd, "flash_dq_flash_dkv")
    results = call.split(" custom-call(")[0]
    assert (results.count("bf16[32,4096,192]{") == 2
            and results.count("bf16[32,4096,128]{") == 1)
    assert not _custom_calls(bwd, "flash_dkv")


@pytest.mark.parametrize("window,tile,steps", [(1024, 1024, 2),
                                               (1536, 512, 4),
                                               (1000, 512, 3)])
def test_windowed_flash_kernels_compile_at_the_cells_shape(one_chip,
                                                           monkeypatch,
                                                           window, tile,
                                                           steps):
    """The streaming forward and the fused backward under a window as
    `mellum2_12b_a2_5b.s8192` calls them: bf16 `[1, 32, 8192, 128]`, tiles of
    1024 x 1024 (the window), their edge tiles in strips, the inner grid axis
    two tiles long; at a window of 1536 tiles of 512 (half the window and 512
    at most), every tile whole, four steps; at a
    window of 1000, no multiple of 128, tiles of 512 and three steps (256 and
    five until PR 73: half the window lost to 512 on the chip); index
    maps with a clamp and a division in them. One Mosaic custom call each, under the names the
    benchmark's patterns tell from the full layer's."""
    from paddle_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(_kernels, "interpret", lambda: False)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = arg((1, 32, 8192, 128))
    assert pa._blk(8192, True) == (1024, 1024)
    assert pa._blk(8192, True, window) == (tile, tile)
    assert pa._band_steps(8192, tile, tile, window) == (steps, steps)
    assert pa._bwd_plan(8192, 128, 128, tile, tile, 2) == "fused"
    fwd = jax.jit(lambda q, k, v: pa._flash_forward(
        q, k, v, True, 128 ** -0.5, window=window)).lower(q, q, q).compile()
    (call,) = _custom_calls(fwd, "swa_flash_fwd")
    assert "(bf16[32,8192,128]{" in call and "f32[32,1,8192]{" in call
    assert not _custom_calls(fwd, "flash_fwd")
    bwd = jax.jit(lambda q, k, v, o, lse, g: pa._flash_backward(
        q, k, v, o, lse, g, True, 128 ** -0.5, 0.0, 0, window)).lower(
            q, q, q, q, arg((32, 1, 8192), jnp.float32), q).compile()
    (call,) = _custom_calls(bwd, "swa_flash_dq_flash_dkv")
    assert call.split(" custom-call(")[0].count("bf16[32,8192,128]{") == 3
    assert not _custom_calls(bwd, "swa_flash_dkv")
    assert not _custom_calls(bwd, "flash_dq")


@pytest.mark.parametrize("tiles,steps", [(None, 3), ((512, 512), 5)],
                         ids=["rule_1024", "512"])
def test_windowed_flash_kernels_compile_at_a_window_of_2048(one_chip,
                                                            monkeypatch,
                                                            tiles, steps):
    """The second window width, as `trinity_mini_26b_a3b.s4096` calls the
    kernels: bf16 `[1, 32, 4096, 128]` under a window of 2048, where the tile
    rule (a window of whole tiles of 1024 takes them, PR 72) gives 1024 x
    1024, edge tiles in strips, and an inner grid axis three tiles long; and
    at 512 x 512, five long, what the rule gave from PR 49 to PR 71. The
    streaming forward and the fused
    backward, one Mosaic custom call each, under the windowed names."""
    from paddle_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(_kernels, "interpret", lambda: False)
    assert pa._blk(4096, True, 2048) == (1024, 1024)
    if tiles:
        monkeypatch.setattr(pa, "_BLOCK_OVERRIDE", tiles)
    tile = pa._blk(4096, True, 2048)[0]
    assert pa._band_steps(4096, tile, tile, 2048) == (steps, steps)
    assert pa._bwd_plan(4096, 128, 128, tile, tile, 2) == "fused"

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = arg((1, 32, 4096, 128))
    fwd = jax.jit(lambda q, k, v: pa._flash_forward(
        q, k, v, True, 128 ** -0.5, window=2048)).lower(q, q, q).compile()
    (call,) = _custom_calls(fwd, "swa_flash_fwd")
    assert "(bf16[32,4096,128]{" in call and "f32[32,1,4096]{" in call
    assert not _custom_calls(fwd, "flash_fwd")
    bwd = jax.jit(lambda q, k, v, o, lse, g: pa._flash_backward(
        q, k, v, o, lse, g, True, 128 ** -0.5, 0.0, 0, 2048)).lower(
            q, q, q, q, arg((32, 1, 4096), jnp.float32), q).compile()
    (call,) = _custom_calls(bwd, "swa_flash_dq_flash_dkv")
    assert call.split(" custom-call(")[0].count("bf16[32,4096,128]{") == 3
    assert not _custom_calls(bwd, "swa_flash_dkv")
    assert not _custom_calls(bwd, "flash_dq")


def _lowered_digest(lowered):
    """sha256 of a lowering's StableHLO with every Mosaic kernel in it
    written out as MLIR without debug locations (the serialized body holds
    this file's paths and line numbers)."""
    import base64
    import hashlib
    import json
    import re
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    kernels = []

    def kernel(m):
        # the escapes MLIR prints a string with: a quote, and the newlines
        # of a cost estimate
        config = json.loads(m.group(1).replace("\\22", '"')
                            .replace("\\0A", "\n"))
        body = base64.b64decode(config["custom_call_config"].pop("body"))
        ctx = jmlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            kernels.append(ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False))
        return "backend_config = " + json.dumps(config, sort_keys=True)

    text = re.sub(r'backend_config = "(\{.*?\})"', kernel, lowered.as_text())
    return hashlib.sha256("\n".join([text] + kernels).encode()).hexdigest()


# (q and k shape, v shape, causal) -> digests of the forward and of the
# backward, taken on the commit before `window` (d5581d9) by this function.
# The two shapes whose backward was the split pair then take the fused kernel
# since `_bwd_plan` follows what VMEM holds: their pair's digest stays as the
# oracle's, under the plan forced to it, beside the fused kernel's, taken on
# the commit that changed the plan. The three causal cases whose rows are
# several K blocks (OLMoE's, Kanana-2's, the long one: forward, fused, split)
# were taken again on PR 70's tree, whose parent is b7aee7c: their index maps
# hold K and V, and Q, dOut, `Lse` and delta, on the live neighbour of a step
# above the diagonal, a `min` / `max` in each map and not an instruction of a
# kernel's body; the two cases without such a step (not causal; one K block a
# row) stand as they were taken, and so does every digest of `OTHER_FLASH`.
# Since PR 72 the three causal cases here and the two windowed ones of
# `OTHER_FLASH` run their aligned edge tiles in strips, the windowed ones at
# tiles of 1024: `IN_STRIPS` holds what they lower to as called (taken on that
# PR's tree, whose parent is 6752eec), and the digests here are what they
# lower to with no tile large enough for strips (`_STRIP_TILE` out of reach:
# every edge tile whole, a window's tiles half the window and 512 at most)
PLAIN_FLASH = {
    "olmoe_4096x128_causal": (
        (1, 16, 4096, 128), (1, 16, 4096, 128), True,
        "0442bb35cf2efd81822be03410a31c91",
        {"fused": "f647d52bdab4646bfd666fe37805ed6a"}),
    "kanana_4096x192_128_causal": (
        (1, 32, 4096, 192), (1, 32, 4096, 128), True,
        "cc2bfee2b597335eb2a183b3e2b11c0d",
        {"fused": "be8322c0c27e022192de7a240dfc98d0",
         "split": "d0aa673d76e30efbe91c45ca48ef02bd"}),
    "seq256_noncausal_64": (
        (96, 8, 256, 64), (96, 8, 256, 64), False,
        "4f106a79fd103fdd6da57d71a7591996",
        {"fused": "15e0af9f3b332318675ffebc72a4ad92"}),
    "seq2048_causal_64": (
        (12, 8, 2048, 64), (12, 8, 2048, 64), True,
        "b67dd88f3f453671ff69751b4ff054cf",
        {"fused": "250549d0a6115d9f734b2d64a7e792a0"}),
    "long_8192x128_causal": (
        (1, 32, 8192, 128), (1, 32, 8192, 128), True,
        "60ebe69f8019319ef32e9013255b6432",
        {"fused": "dbe0d6ecbf0da93158e81f89a094e542",
         "split": "d237a074d2798cff846dd5f5bff197e0"}),
}


# case -> (forward, backward by plan) as called, of the calls whose aligned
# edge tiles run in strips
IN_STRIPS = {
    "olmoe_4096x128_causal": (
        "3d572cfefa42af83bcb80829f0cfffec",
        {"fused": "08dd7efbda62c70ab0c3fff061676c1a"}),
    "kanana_4096x192_128_causal": (
        "af4dc034bd9679f77fc7e996e7d590d8",
        {"fused": "0308282d387929d90d6b2dd26e12b985",
         "split": "618db36dd2750860d1b19a6766183bc7"}),
    "long_8192x128_causal": (
        "357ed548abb558959571d14c51bbcc8c",
        {"fused": "c77088b5bebb9938da8322e805678856",
         "split": "9c10e52ec69cc3132cf023632abce7c1"}),
    "mellum2_8192_w1024": (
        "27dad093ff917fbed899bb2284242202",
        {"fused": "4cd4ca01baaf49276eabd1343b84627f"}),
    "trinity_4096_w2048": (
        "15a04f4e38985c0eee9f7cb9ca1df1ff",
        {"fused": "b4e88f89462c527095a6aabd082fa29c"}),
}


def _forms(monkeypatch, pa, case, recorded, head_major=True):
    """(forward digest, backward digests) of the call as it is, where its
    aligned edge tiles run in strips (`IN_STRIPS`), and then of the call as
    it was `recorded`: no tile large enough for strips, and a head-major
    call inline in its caller (since PR 72 it is a call of a jitted function,
    as a token-major one has been since PR 46: the same kernels, traced and
    lowered once a signature)."""
    if case in IN_STRIPS:
        yield IN_STRIPS[case]
        monkeypatch.setattr(pa, "_STRIP_TILE", 1 << 30)
    if head_major:
        monkeypatch.setattr(pa, "_jitted_forward", pa._forward)
        monkeypatch.setattr(pa, "_jitted_backward", pa._backward)
    yield recorded


@pytest.mark.parametrize("case", sorted(PLAIN_FLASH))
def test_flash_kernels_without_a_window_lower_to_the_text_they_did(
        one_chip, monkeypatch, case):
    """`window` absent: the one-pass and streaming forward, the fused
    backward and the split pair are the instructions they were, at the
    shapes the six cells that run them use: the lowered text (kernels'
    MLIR included, debug locations left out, the scoped VMEM a kernel asks
    for among its parameters) has the recorded digest (since PR 70, where a
    causal grid has steps above the diagonal, the digest of that PR)."""
    from paddle_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(_kernels, "interpret", lambda: False)
    qs, vs, causal, fwd_digest, bwd_digests = PLAIN_FLASH[case]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, v, scale = arg(qs), arg(vs), qs[-1] ** -0.5
    assert pa._bwd_plan(qs[2], qs[3], vs[3], *pa._blk(qs[2], causal),
                        2) == "fused"
    assert bool(causal and pa.edge_strips(qs[2])[0]) == (case in IN_STRIPS)
    plan_of = pa._bwd_plan
    for fwd_digest, bwd_digests in _forms(monkeypatch, pa, case,
                                          (fwd_digest, bwd_digests)):
        fwd = jax.jit(lambda q, k, v: pa._flash_forward(
            q, k, v, causal, scale)).lower(q, q, v)
        assert _lowered_digest(fwd)[:32] == fwd_digest
        for plan, digest in bwd_digests.items():
            monkeypatch.setattr(pa, "_bwd_plan", plan_of if plan == "fused"
                                else lambda *a: "split")
            bwd = jax.jit(lambda q, k, v, o, lse, g: pa._flash_backward(
                q, k, v, o, lse, g, causal, scale, 0.0, 0)).lower(
                    q, q, v, v, arg((qs[0] * qs[1], 1, qs[2]), jnp.float32),
                    v)
            assert _lowered_digest(bwd)[:32] == digest, plan


# the calls no case of `PLAIN_FLASH` lowers, digests of the forward and of the
# backward by `_lowered_digest`, taken on the commit before PR 68 (3e18790),
# which changed what a call under a kept set fetches: (shape, token-major,
# causal, dropout, window). Token-major `[batch, seq, heads, head_dim]` with
# the PRNG in the kernel, both transformer cells' four calls; head-major
# under a window, Mellum2's three layers and Trinity-Mini's four
OTHER_FLASH = {
    "seq256_full": ((96, 256, 8, 64), True, False, 0.1, None,
                    "f46f957f51c9475aeb008882eca387de",
                    "def484d5d1c1d70344473516eedef273"),
    "seq256_causal": ((96, 256, 8, 64), True, True, 0.1, None,
                      "595fe101fc5f2e9e7e4f2196d38728e4",
                      "faaf830e1fe541ff6efde44b000ae283"),
    "seq2048_full": ((12, 2048, 8, 64), True, False, 0.1, None,
                     "14876bdca8eae6430afa8cbf94954504",
                     "a31970ca9cc3e9cee1b00bd065c1160f"),
    "seq2048_causal": ((12, 2048, 8, 64), True, True, 0.1, None,
                       "9fb4c623f0c3fe33b4d5391de5af1c7b",
                       "eef4d3b2f61fb24b9f527fa9a4d4eb0c"),
    "mellum2_8192_w1024": ((1, 32, 8192, 128), False, True, 0.0, 1024,
                           "748d4ea7cd6cdbeef0e64b0111842fea",
                           "5c3fccd69cf6335452d419a7b5f659ae"),
    "trinity_4096_w2048": ((1, 32, 4096, 128), False, True, 0.0, 2048,
                           "7d2f4d37cf3224658c1b0c8771bca2ba",
                           "3223e3e31a6ad3dfa18a47a85de6ff09"),
}


@pytest.mark.parametrize("case", sorted(OTHER_FLASH))
def test_token_major_and_windowed_flash_kernels_lower_to_the_text_they_did(
        one_chip, monkeypatch, case):
    """A call without a kept set is the instructions it was where its
    blocks hold several heads in their lanes (`_each_head`'s groups, the
    one-pass forward and the fused backward with dropout, as both
    transformer cells call them) and, at tiles of 512 with every edge tile
    whole, where its grid follows a band (the streaming forward and the
    fused backward with clamped index maps; as called, at tiles of 1024 with
    their edge tiles in strips, they lower to `IN_STRIPS`): no
    case of `PLAIN_FLASH` is token-major or windowed, and the calls under a
    kept set share `_forward`, `_bwd_specs` and the kernels' bodies with
    these."""
    from paddle_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(_kernels, "interpret", lambda: False)
    shape, token_major, causal, rate, window, fwd_digest, bwd_digest = \
        OTHER_FLASH[case]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, H, T, D = pa._shape_of(arg(shape), token_major)
    q, seed = arg(shape), arg((), jnp.int32)
    assert bool(not token_major and pa.edge_strips(T, window)[0]) == (
        case in IN_STRIPS)
    for fwd_digest, bwd_digests in _forms(
            monkeypatch, pa, case, (fwd_digest, {"fused": bwd_digest}),
            head_major=not token_major):
        # without dropout the op hands the kernels the constant 0 for a seed
        fwd = jax.jit(lambda q, k, v, seed: pa._flash_forward(
            q, k, v, causal, D ** -0.5, rate, seed if rate else 0, window,
            token_major)).lower(q, q, q, seed)
        assert _lowered_digest(fwd)[:32] == fwd_digest
        bwd = jax.jit(lambda q, k, v, o, lse, g, seed: pa._flash_backward(
            q, k, v, o, lse, g, causal, D ** -0.5, rate, seed if rate else 0,
            window, token_major)).lower(
                q, q, q, q, arg((B * H, 1, T), jnp.float32), q, seed)
        assert _lowered_digest(bwd)[:32] == bwd_digests["fused"]


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("shape", [(96, 256, 8, 64), (12, 2048, 8, 64)],
                         ids=["seq256", "seq2048"])
def test_token_major_flash_kernels_compile_at_the_cells_shapes(
        one_chip, monkeypatch, shape, causal):
    """The one-pass forward and the fused backward as both transformer
    cells call them since the op takes `[batch, seq, heads, head_dim]`:
    bf16, eight heads of 64 lanes, dropout 0.1 (the PRNG in the kernel),
    several heads a grid step out of a block of `[1, rows, heads * 64]`
    whose lanes the index maps pick. One Mosaic custom call each, under the
    names the benchmark's patterns know, its results in the operands' own
    layout: no transpose and no copy of an operand's size in either
    program."""
    from paddle_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(_kernels, "interpret", lambda: False)
    B, T, H, D = shape

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, lse = arg(shape), arg((B * H, 1, T), jnp.float32)
    seed = arg((), jnp.int32)
    assert pa._fwd_plan(T, pa._blk(T, causal)[1]) == "onepass"
    fwd = jax.jit(lambda q, k, v, seed: pa._flash_forward(
        q, k, v, causal, D ** -0.5, 0.1, seed, token_major=True)).lower(
            q, q, q, seed).compile()
    (call,) = _custom_calls(fwd, "flash_fwd_onepass")
    assert f"(bf16[{B},{T},{H * D}]{{" in call and f"f32[{B * H},1,{T}]{{" in call
    bwd = jax.jit(lambda q, k, v, o, lse, g, seed: pa._flash_backward(
        q, k, v, o, lse, g, causal, D ** -0.5, 0.1, seed,
        token_major=True)).lower(q, q, q, q, lse, q, seed).compile()
    (call,) = _custom_calls(bwd, "flash_dq_flash_dkv")
    assert call.split(" custom-call(")[0].count(
        f"bf16[{B},{T},{H * D}]{{") == 3
    for compiled in (fwd, bwd):
        text = compiled.as_text()
        assert " transpose(" not in text
        assert not re.search(r" copy\(.*bf16\[", text)


@pytest.mark.parametrize("kernel", ["dsa_flash_fwd", "dsa_flash_dq_flash_dkv",
                                    "dsa_index_scores", "dsa_select"])
def test_sparse_attention_kernels_compile_at_the_cells_shapes(
        one_chip, monkeypatch, kernel):
    """The four kernels of a learned key selection as
    `keye_vl_2_30b_a3b.s8192` calls them: the flash forward and the fused
    backward under an int8 kept set `[1, 8192, 8192]` at bf16
    `[1, 32, 8192, 128]` (the widening of a byte tile and the room it takes
    beside the resident dQ row), the index scores of 16 heads of 64 in tiles
    of 512 (a lane slice of the weights, products of depth 64), and the
    selection of 2048 keys a row (loops of a traced length, the ordered bits,
    the int8 result): what the interpreter cannot refuse. One Mosaic custom
    call each, named for the benchmark's patterns."""
    from paddle_tpu.ops import pallas_attention as pa
    from paddle_tpu.ops import sparse_attention as sa
    monkeypatch.setattr(_kernels, "interpret", lambda: False)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    T = 8192
    q, kept = arg((1, 32, T, 128)), arg((1, T, T), jnp.int8)
    if kernel == "dsa_flash_fwd":
        compiled = jax.jit(lambda q, k, v, kept: pa._flash_forward(
            q, k, v, True, 128 ** -0.5, kept=kept)).lower(q, q, q, kept)
    elif kernel == "dsa_flash_dq_flash_dkv":
        assert pa._bwd_plan(T, 128, 128, *pa._blk(T, True), 2) == "fused"
        compiled = jax.jit(lambda q, k, v, o, lse, g, kept: pa._flash_backward(
            q, k, v, o, lse, g, True, 128 ** -0.5, 0.0, 0, kept=kept)).lower(
                q, q, q, q, arg((32, 1, T), jnp.float32), q, kept)
    elif kernel == "dsa_index_scores":
        compiled = jax.jit(lambda q, k, w: sa.index_scores_kernel(
            q, k, w, 2 ** -5, 512)).lower(
                arg((1, 16, T, 64)), arg((1, 1, T, 64)), arg((1, T, 16)))
    else:
        compiled = jax.jit(lambda s: sa.select_kernel(s, 2048)).lower(
            arg((1, T, T), jnp.float32))
    (call,) = _custom_calls(compiled.compile(), kernel)
    assert "tpu_custom_call" in call
    result = {"dsa_flash_fwd": "= (bf16[32,8192,128]{",
              "dsa_flash_dq_flash_dkv": "= (bf16[32,8192,128]{",
              "dsa_index_scores": "= f32[1,8192,8192]{",
              "dsa_select": "= s8[1,8192,8192]{"}[kernel]
    assert result in call


# the parameter-less calls of the two kernel pairs that gained a parameter in
# PR 56 (`causal_conv1d`'s bias, `gated_rms_norm`'s gate-first grouped form),
# as `qwen3_next_80b_a3b.bs1` calls them: digests taken on the commit before
# (22d36e3) by `_lowered_digest`
UNCHANGED_CALLS = {
    "conv_bf16": ("8f78b59f275d2e07fe446c624d942dff",
                  "1b739580d44b6f76c908796c354185ad"),
    "conv_f32": ("c432a3e7a0ddbcce44908db8aafc6d99",
                 "097e1ec833031593702023940ef2734e"),
    "gated_norm_bf16": ("45634ec82f48cdf2f715b63eb259f13e",
                        "e1b30a15127a6881cfc6d0f47aa0d873"),
    "gated_norm_f32": ("d88cf86b40aab135956fce4928a7c372",
                       "7e661029d88f69b9269b2e0058b45db4"),
}


@pytest.mark.parametrize("case", sorted(UNCHANGED_CALLS))
def test_conv_and_gated_norm_without_the_new_parameters_lower_as_they_did(
        one_chip, monkeypatch, case):
    """No bias, the norm before the gate: both kernel pairs are the
    instructions they were (`[1, 4096, 8192]` with 4 taps; `[1, 4096, 32,
    128]`)."""
    from paddle_tpu.ops import decoder_block as db
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    dtype = jnp.bfloat16 if case.endswith("bf16") else jnp.float32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if case.startswith("conv"):
        x, w = arg((1, 4096, 8192), dtype), arg((8192, 4), jnp.float32)
        fwd = jax.jit(lambda x, w: la._conv_forward(x, w, True)).lower(x, w)
        bwd = jax.jit(lambda x, w, d: la._conv_backward(
            x, w, d, True)).lower(x, w, x)
    else:
        x, w = arg((1, 4096, 32, 128), dtype), arg((128,), jnp.float32)
        fwd = jax.jit(lambda x, g, w: db._gated_norm_call(
            x, g, w, 1e-6)).lower(x, x, w)
        bwd = jax.jit(lambda x, g, w, d: db._gated_norm_call(
            x, g, w, 1e-6, d)).lower(x, x, w, x)
    assert (_lowered_digest(fwd)[:32], _lowered_digest(bwd)[:32]) \
        == UNCHANGED_CALLS[case]


def test_nemotron_h_kernels_compile_at_the_cells_shapes(one_chip,
                                                        monkeypatch):
    """What `nemotron_3_nano_30b_a3b.s2048` calls and no other cell does:
    `ssd_fwd` / `ssd_bwd` on bf16 x `[1, 2048, 64, 64]` and B, C `[1, 2048,
    8, 128]` (a `[1, 1]` spread both ways, the 8-lane column blocks and the
    heads' half-tile selects are what the interpreter cannot refuse); the
    convolution WITH a bias at `[1, 2048, 6144]`; the gated norm with the
    gate first over 8 groups of 512. One Mosaic custom call each, the
    scan's first result the saved states."""
    from paddle_tpu.ops import decoder_block as db
    from paddle_tpu.ops import state_space as ss
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert ss._plan(64, 128, 8, 128) == "kernel"

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    x, bc = arg((1, 2048, 64, 64)), arg((1, 2048, 8, 128))
    gate, skip = arg((1, 2048, 64), f32), arg((64,), f32)
    fwd = jax.jit(lambda *a: ss._ssd_forward(*a, 128)).lower(
        x, gate, gate, bc, bc, skip).compile()
    (call,) = _custom_calls(fwd, "ssd_fwd")
    assert "(f32[16,1,8,512,128]{" in call and "tpu_custom_call" in call
    bwd = jax.jit(lambda *a: ss._ssd_backward(*a, 128)).lower(
        x, gate, gate, bc, bc, skip, arg((16, 1, 64, 64, 128), f32),
        x).compile()
    (call,) = _custom_calls(bwd, "ssd_bwd")
    assert "(bf16[1,2048,4096]{" in call and "tpu_custom_call" in call

    u, w, bias = arg((1, 2048, 6144)), arg((6144, 4), f32), arg((6144,), f32)
    conv = jax.jit(lambda x, w, b: la._conv_forward(x, w, True, b)).lower(
        u, w, bias).compile()
    (call,) = _custom_calls(conv, "causal_conv_fwd")
    assert "= bf16[1,2048,6144]{" in call
    conv = jax.jit(lambda x, w, b, d: la._conv_backward(
        x, w, d, True, b)).lower(u, w, bias, u).compile()
    (call,) = _custom_calls(conv, "causal_conv_bwd")
    assert ", f32[5,6144]{" in call

    y, scale = arg((1, 2048, 8, 512)), arg((4096,), f32)
    norm = jax.jit(lambda x, g, w: db._gate_first_norm_call(
        x, g, w, 1e-5)).lower(y, y, scale).compile()
    (call,) = _custom_calls(norm, "gated_norm_fwd")
    assert "= bf16[1,2048,4096]{" in call
    norm = jax.jit(lambda x, g, w, d: db._gate_first_norm_call(
        x, g, w, 1e-5, d)).lower(y, y, scale, y).compile()
    (call,) = _custom_calls(norm, "gated_norm_bwd")
    assert "= (bf16[1,2048,4096]{" in call


def _scan_lowerings(one_chip, groups, chunk):
    """`_ssd_forward` and `_ssd_backward` lowered at bf16 x `[1, 2048, 64,
    64]`, B and C `[1, 2048, groups, 128]` and the op's `chunk`."""
    from paddle_tpu.ops import state_space as ss

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    x, bc = arg((1, 2048, 64, 64)), arg((1, 2048, groups, 128))
    gate, skip = arg((1, 2048, 64), f32), arg((64,), f32)
    return (jax.jit(lambda *a: ss._ssd_forward(*a, chunk)).lower(
                x, gate, gate, bc, bc, skip),
            jax.jit(lambda *a: ss._ssd_backward(*a, chunk)).lower(
                x, gate, gate, bc, bc, skip,
                arg((16, 1, 64, 64, 128), f32), x))


@pytest.mark.parametrize("case", ["conv", "gated_norm", "scan"])
def test_granite4_kernels_compile_at_the_cells_shapes(one_chip, monkeypatch,
                                                      case):
    """What `granite_4_0_h_micro.s2048` calls and no other cell does: the
    convolution WITH a bias at 4352 channels (34 lane tiles) and the gated
    norm with the gate first over ONE group of 4096 lanes, whose float32
    tiles fit the scoped VMEM only at 32 rows a grid step
    (`_gated_norm_blocks`; at the accepted shapes' 256 rows the backward asked
    for 20 MiB of the 16); `ssd_fwd` / `ssd_bwd` on bf16 x `[1, 2048, 64,
    64]` and B, C `[1, 2048, 1, 128]` at chunk 256: ONE group of 64 heads as
    eight blocks of 8 that read the same B and C, sixteen steps of 128
    tokens, each block's part of dB and dC in float32. One Mosaic custom
    call each way."""
    from paddle_tpu.ops import decoder_block as db
    from paddle_tpu.ops import state_space as ss
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    if case == "scan":
        assert ss._kernels_run(64, 128, 64, 256)
        fwd, bwd = _scan_lowerings(one_chip, 1, 256)
        (call,) = _custom_calls(fwd.compile(), "ssd_fwd")
        assert "(f32[16,1,8,512,128]{" in call and "tpu_custom_call" in call
        (call,) = _custom_calls(bwd.compile(), "ssd_bwd")
        assert "(bf16[1,2048,4096]{" in call and "tpu_custom_call" in call
        assert call.count("f32[1,2048,1024]{") == 2     # dB's and dC's parts
        return
    if case == "conv":
        assert la._conv_plan(2048, 4352, 4) == "kernel"
        u, w, bias = arg((1, 2048, 4352)), arg((4352, 4), f32), \
            arg((4352,), f32)
        conv = jax.jit(lambda x, w, b: la._conv_forward(x, w, True, b)).lower(
            u, w, bias).compile()
        (call,) = _custom_calls(conv, "causal_conv_fwd")
        assert "= bf16[1,2048,4352]{" in call
        conv = jax.jit(lambda x, w, b, d: la._conv_backward(
            x, w, d, True, b)).lower(u, w, bias, u).compile()
        (call,) = _custom_calls(conv, "causal_conv_bwd")
        assert ", f32[5,4352]{" in call
        return
    assert db._gated_norm_plan((1, 2048, 1, 4096), jnp.bfloat16) == "kernel"
    y, scale = arg((1, 2048, 1, 4096)), arg((4096,), f32)
    norm = jax.jit(lambda x, g, w: db._gate_first_norm_call(
        x, g, w, 1e-5)).lower(y, y, scale).compile()
    (call,) = _custom_calls(norm, "gated_norm_fwd")
    assert "= bf16[1,2048,4096]{" in call
    norm = jax.jit(lambda x, g, w, d: db._gate_first_norm_call(
        x, g, w, 1e-5, d)).lower(y, y, scale, y).compile()
    (call,) = _custom_calls(norm, "gated_norm_bwd")
    assert "= (bf16[1,2048,4096]{" in call and "f32[1,64,8,4096]" in call


# `ssd_fwd` / `ssd_bwd` as `nemotron_3_nano_30b_a3b.s2048` calls them (8
# groups of 8 heads, chunk 128: one head block a group, the step the
# attribute's chunk): digests taken on the commit before the grid's axis over
# head blocks (aa1920d) by `_lowered_digest`
NEMOTRON_SCAN = {"forward": "a6f2680a3e0e89523dacf81f050d5028",
                 "backward": "24cdb44a6f191450ea26d930757777f3"}


@pytest.mark.parametrize("way", sorted(NEMOTRON_SCAN))
def test_nemotron_h_scan_lowers_as_it_did_before_head_blocks(one_chip,
                                                             monkeypatch,
                                                             way):
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    lowered = dict(zip(("forward", "backward"),
                       _scan_lowerings(one_chip, 8, 128)))[way]
    assert _lowered_digest(lowered)[:32] == NEMOTRON_SCAN[way]


def test_phi4_flash_scan_kernels_compile_at_the_cells_shapes(one_chip,
                                                             monkeypatch):
    """`sscan_fwd` / `sscan_bwd` as `phi_4_mini_flash_reasoning.s4096` calls
    them: float32 x and dt `[1, 4096, 5120]`, A `[5120, 16]`, B and C `[1,
    4096, 16]`, a grid of 32 chunks x 10 channel blocks of 512. One Mosaic
    custom call each way; the forward's first result is the 32 saved states
    (10.5 MB, not the 1.34 GB of `[4096, 5120, 16]`), the backward writes
    dB's and dC's per-lane parts; neither program holds an array of
    `[4096, 5120, 16]` or `[4096, 16, 5120]`."""
    from paddle_tpu.ops import selective_scan as sscan
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    assert sscan._kernels_run(4096, 5120, 16)
    x, A, bc, D = arg((1, 4096, 5120)), arg((5120, 16)), \
        arg((1, 4096, 16)), arg((5120,))
    fwd = jax.jit(sscan._sscan_forward).lower(x, x, A, bc, bc, D).compile()
    (call,) = _custom_calls(fwd, "sscan_fwd")
    assert "(f32[32,1,16,5120]{" in call and "tpu_custom_call" in call
    bwd = jax.jit(sscan._sscan_backward).lower(
        x, x, A, bc, bc, D, arg((32, 1, 16, 5120)), x).compile()
    (call,) = _custom_calls(bwd, "sscan_bwd")
    assert call.count("f32[1,4096,16,128]{") >= 2 and "tpu_custom_call" in call
    for text in (fwd.as_text(), bwd.as_text()):
        assert "[4096,5120,16]" not in text and "[4096,16,5120]" not in text


# the two kernel pairs as `ling_3_0_flash_vl.s2048` calls them and no other
# cell does (the convolution at 12288 channels, the gated norm with the
# gate's sigmoid): digests by `_lowered_digest` as PR 60 recorded them
LING3_CALLS = {"conv": ("0dc82162ea73cf3adf5ee5640a4ecd5b",
                        "dd2695abd0b9d84df555e4bde81ca3dc"),
               "sigmoid_norm": ("f091d355f947fe7ff1d6a5a8aed27bc3",
                                "75a5876f95414738d7c76f3aa4bb7f66")}


@pytest.mark.parametrize("case", sorted(LING3_CALLS))
def test_ling3_kernels_compile_at_the_cells_shapes(one_chip, monkeypatch,
                                                   case):
    """`causal_conv_fwd` / `causal_conv_bwd` over bf16 `[1, 2048, 12288]`
    (KDA's [q | k | v], 4 taps, no bias) and `gated_norm_fwd` /
    `gated_norm_bwd` with `activation="sigmoid"` over `[1, 2048, 32, 128]`:
    one Mosaic custom call each for a described v5e, lowered to the recorded
    text; the sigmoid form is not silu's text. (The rule's own pair:
    `test_kda_kernels_compile_at_the_cells_shapes`.)"""
    from paddle_tpu.ops import decoder_block as db
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    f32 = jnp.float32

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if case == "conv":
        x, w = arg((1, 2048, 12288)), arg((12288, 4), f32)
        fwd = jax.jit(lambda x, w: la._conv_forward(x, w, True)).lower(x, w)
        bwd = jax.jit(lambda x, w, d: la._conv_backward(
            x, w, d, True)).lower(x, w, x)
        names = ("causal_conv_fwd", "causal_conv_bwd")
        results = ("= bf16[1,2048,12288]{", ", f32[4,12288]{")
    else:
        x, w = arg((1, 2048, 32, 128)), arg((128,), f32)
        fwd = jax.jit(lambda x, g, w: db._gated_norm_call(
            x, g, w, 1e-6, sigmoid=True)).lower(x, x, w)
        bwd = jax.jit(lambda x, g, w, d: db._gated_norm_call(
            x, g, w, 1e-6, d, sigmoid=True)).lower(x, x, w, x)
        names = ("gated_norm_fwd", "gated_norm_bwd")
        results = ("= bf16[1,2048,4096]{", "= (bf16[1,2048,4096]{")
        silu = jax.jit(lambda x, g, w: db._gated_norm_call(
            x, g, w, 1e-6)).lower(x, x, w)
        assert _lowered_digest(silu) != _lowered_digest(fwd)
    for lowered, name, result in zip((fwd, bwd), names, results):
        (call,) = _custom_calls(lowered.compile(), name)
        assert "tpu_custom_call" in call and result in call
    got = (_lowered_digest(fwd)[:32], _lowered_digest(bwd)[:32])
    assert got == LING3_CALLS[case], got


def _expert_step(moe, gated, shadowed=False):
    """One expert layer's step on `_grouped_dot` / `_grouped_dot_grads` as
    the Program runs them under AMP: float32 stacks cast to bf16 at their
    use, the products and their gradients, Adam on each stack with its two
    moments. relu² over one first stack, or gated silu over two. `shadowed`:
    as the executor runs a step with AMP's shadows (PR 59), the stacks'
    bf16 forms come in behind the state (one a stack, in the stacks' order)
    and the update writes the next ones, which go out behind the new
    state."""
    bf16, f32 = jnp.bfloat16, jnp.float32

    def adam(p, g, m, v):
        m, v = 0.9 * m + 0.1 * g, 0.999 * v + 0.001 * g * g
        return p - 1e-3 * m / (jnp.sqrt(v) + 1e-8), m, v

    def step(x, sizes, g, *state):
        if shadowed:
            stacks = len(state) // 4
            state, shadows = state[:3 * stacks], state[3 * stacks:]
            firsts, down = list(shadows[:-1]), shadows[-1]
        else:
            firsts = [w.astype(bf16) for w in state[:-3:3]]
            down = state[-3].astype(bf16)
        hs = [moe._grouped_dot(x, w, sizes).astype(f32) for w in firsts]
        if gated:
            act, d_act = jax.vjp(lambda a, b: jax.nn.silu(a) * b, *hs)
        else:
            act, d_act = jax.vjp(lambda a: jnp.square(jax.nn.relu(a)), *hs)
        act = act.astype(bf16)
        y = moe._grouped_dot(act, down, sizes)
        d_a, d_down = moe._grouped_dot_grads(act, down, g, sizes)
        d_x, grads = 0, []
        for w, d_h in zip(firsts, d_act(d_a.astype(f32))):
            part, d_w = moe._grouped_dot_grads(x, w, d_h.astype(bf16), sizes)
            d_x = d_x + part
            grads.append(d_w)
        new = [adam(state[3 * i], d.astype(f32), *state[3 * i + 1:3 * i + 3])
               for i, d in enumerate(grads + [d_down])]
        out = (y, d_x) + tuple(a for triple in new for a in triple)
        if shadowed:
            out += tuple(p.astype(bf16) for p, _, _ in new)
        return out

    return step


def _lowered_expert_step(moe, one_chip, width, expert_size, gated, rows=2048,
                         shadowed=False):
    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    first, down = (8, width, expert_size), (8, expert_size, width)
    state = [arg(first)] * (6 if gated else 3) + [arg(down)] * 3
    if shadowed:
        state += [arg(first, jnp.bfloat16)] * (2 if gated else 1) \
            + [arg(down, jnp.bfloat16)]
    x = arg((rows, width), jnp.bfloat16)
    return jax.jit(_expert_step(moe, gated, shadowed),
                   donate_argnums=tuple(range(3, 3 + len(state)))).lower(
                       x, arg((8,), jnp.int32), x, *state)


@pytest.fixture
def megablox(monkeypatch):
    """`ops/moe.py` with the megablox kernels as its path, as on a chip."""
    from paddle_tpu.ops import moe
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert moe._kernel() is not None
    return moe


def test_two_matrix_experts_step_copies_no_stack(one_chip, megablox):
    """An E layer of `nemotron_3_nano_30b_a3b.s2048` (relu² experts, `up`
    `[8, 2688, 1856]`, `down` `[8, 1856, 2688]`, float32 with their Adam
    moments donated, 2048 rows): the TPU client holds `up` and its moments
    with the 2688 axis minor-most (`{1,2,0}`: 1856 is no whole number of
    lane tiles), and the products take the stack through its transpose, a
    bitcast there, so the compiled step holds no `copy` of a whole stack
    (the kernels handed `up` row-major had six: parameter and two moments,
    in and out). If a later jax lays `up` out otherwise, the first
    assertion says so."""
    moe = megablox
    assert moe._held_lane_major(jnp.zeros((8, 2688, 1856)))
    assert not moe._held_lane_major(jnp.zeros((8, 1856, 2688)))
    compiled = _lowered_expert_step(moe, one_chip, 2688, 1856,
                                    gated=False).compile()
    text = compiled.as_text()
    (layout,) = re.findall(r"entry_computation_layout=\{\((.*?)\)->", text)
    assert layout.count("f32[8,2688,1856]{1,2,0:") == 3
    assert layout.count("f32[8,1856,2688]{2,1,0:") == 3
    assert len(_custom_calls(compiled, "gmm")) == 4
    assert len(_custom_calls(compiled, "tgmm")) == 2
    copies = re.findall(
        r"= f32\[8,(?:2688,1856|1856,2688)\]\{[^}]*\} copy\(", text)
    assert not copies
    # nor a bf16 one: the cast fuses with the bitcast
    assert not re.findall(
        r"= bf16\[8,(?:2688,1856|1856,2688)\]\{[^}]*\} copy\(", text)


def test_two_matrix_experts_step_with_shadows_copies_no_stack(one_chip,
                                                              megablox):
    """The same E layer's step with AMP's shadows (PR 59): the bf16 forms
    of `up` and `down` come in as arguments of their own (donated) and the
    update writes the next ones. `_held_lane_major` reads the shape alone,
    so what it says of `f32[8,2688,1856]` has to hold of the bf16 array
    too: the TPU client holds `bf16[8,2688,1856]`, argument and result,
    with the 2688 axis minor-most as it holds the float32 one, the kernels
    take it through its transpose, and no copy of a stack, float32 or
    bf16, is in the step; nor is a pass that casts a float32 stack that
    came in: the only converts to a stack's bf16 shape are the update's
    own, fused with it. (These 80 MB stacks are under
    `registry.AMP_SHADOW_MIN_BYTES`, so the cell's step carries none; the
    layout question is the shape's, whatever the number of experts.)"""
    moe = megablox
    assert moe._held_lane_major(jnp.zeros((8, 2688, 1856), jnp.bfloat16))
    assert not moe._held_lane_major(jnp.zeros((8, 1856, 2688), jnp.bfloat16))
    compiled = _lowered_expert_step(moe, one_chip, 2688, 1856, gated=False,
                                    shadowed=True).compile()
    text = compiled.as_text()
    (ins, outs), = re.findall(
        r"entry_computation_layout=\{\((.*?)\)->\((.*?)\)\}, allow_spmd", text)
    for layout in (ins, outs):
        assert layout.count("f32[8,2688,1856]{1,2,0:") == 3
        assert layout.count("f32[8,1856,2688]{2,1,0:") == 3
        assert layout.count("bf16[8,2688,1856]{1,2,0:") == 1
        assert layout.count("bf16[8,1856,2688]{2,1,0:") == 1
    assert len(_custom_calls(compiled, "gmm")) == 4
    assert len(_custom_calls(compiled, "tgmm")) == 2
    stack = r"\[8,(?:2688,1856|1856,2688)\]\{[^}]*\}"
    assert not re.findall(rf"= (?:f32|bf16){stack} copy\(", text)
    # the only converts to a stack's bf16 shape are the update's, one a
    # stack, of the parameter it has just computed (never of a parameter
    # of the computation: a float32 stack that came in), in the fusion
    # that writes that parameter and its moments
    casts = re.findall(rf"= bf16{stack} convert\(%?([\w.-]+)\)", text)
    assert len(casts) == 2 and all(c.startswith("sub") for c in casts), casts
    assert len(re.findall(
        rf"ROOT %?[\w.-]+ = \(bf16{stack}(?:, f32{stack}){{3}}\) tuple\(",
        text)) == 2


# the step of a gated-silu layer at Kanana-2's and Keye-VL-2's widths
# (`[8, 2048, 768]` / `[8, 768, 2048]`), lowered by `_lowered_expert_step` on
# the commit before `_held_lane_major` (bb85f6d)
GATED_SILU_STEP = "90b5660139060bf73fced8c3ed92c2f8"


def test_gated_silu_experts_step_lowers_as_it_did(one_chip, megablox):
    """Every gated-silu cell's stacks end on a whole number of lane tiles
    (768, 1024, 896, 512 / 2048): nothing is swapped and the step is the
    instructions it was."""
    moe = megablox
    for shape in ((8, 2048, 768), (8, 768, 2048), (64, 2048, 1024),
                  (8, 2304, 896), (32, 2048, 512), (4, 64, 24)):
        assert not moe._held_lane_major(jnp.zeros(shape)), shape
    lowered = _lowered_expert_step(moe, one_chip, 2048, 768, gated=True)
    assert _lowered_digest(lowered)[:32] == GATED_SILU_STEP


# -- lfm2_8b_a1b.s4096 (PR 69) -------------------------------------------------------

def test_short_conv_kernels_compile_at_the_cells_shape(one_chip, monkeypatch):
    """`causal_conv_fwd` and `causal_conv_bwd` as `lfm2_8b_a1b.s4096` calls
    them: the first published shape with THREE taps, no silu and no bias, X
    and dOut bf16 `[1, 4096, 2048]`, W float32 `[2048, 3]`. One Mosaic custom
    call each under the names the benchmark's pattern reads, eight channel
    blocks of 256 by two time blocks of 2048."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert la._conv_plan(4096, 2048, 3) == "kernel"
    assert la._conv_blocks(4096, 2048) == (2048, 256, 64)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, w = arg((1, 4096, 2048), jnp.bfloat16), arg((2048, 3), jnp.float32)
    fwd = jax.jit(lambda x, w: la._conv_forward(x, w, False)).lower(x, w)
    (call,) = _custom_calls(fwd.compile(), "causal_conv_fwd")
    assert "= bf16[1,4096,2048]{" in call and "tpu_custom_call" in call
    bwd = jax.jit(lambda x, w, d: la._conv_backward(x, w, d, False)).lower(
        x, w, x)
    (call,) = _custom_calls(bwd.compile(), "causal_conv_bwd")
    assert "= (bf16[1,4096,2048]{" in call and ", f32[3,2048]{" in call
    # without silu the kernels are other kernels than the scans' convolution
    silu = jax.jit(lambda x, w: la._conv_forward(x, w, True)).lower(x, w)
    assert _lowered_digest(silu) != _lowered_digest(fwd)


def test_experts_of_1792_compile_at_the_cells_shapes(one_chip, megablox):
    """An expert layer of `lfm2_8b_a1b.s4096`: the first gated-silu experts
    whose `[2048, 1792]` block `_GMM_BLOCK` does not hold. `gate` and `up`
    `[8, 2048, 1792]` take two column blocks of 896, `down` `[8, 1792, 2048]`
    two of 1024; 1792 is 14 lane tiles, so nothing goes in transposed. The
    whole step (six products, their nine gradients' calls, Adam on the three
    stacks with their moments donated) compiles over the layer's 17408-row
    buffer (4096 x 4 + 8 x 128) and holds no copy of a stack."""
    moe = megablox
    rows = 4096 * 4 + 8 * moe.ROW_TILE
    assert moe._gmm_tiles(rows, 2048, 1792)[1:] == (2048, 896)
    assert moe._gmm_tiles(rows, 1792, 2048)[1:] == (1792, 1024)
    assert not moe._held_lane_major(jnp.zeros((8, 2048, 1792)))
    assert not moe._held_lane_major(jnp.zeros((8, 1792, 2048)))
    compiled = _lowered_expert_step(moe, one_chip, 2048, 1792, gated=True,
                                    rows=rows).compile()
    text = compiled.as_text()
    (layout,) = re.findall(r"entry_computation_layout=\{\((.*?)\)->", text)
    assert layout.count("f32[8,2048,1792]{2,1,0:") == 6
    assert layout.count("f32[8,1792,2048]{2,1,0:") == 3
    assert len(_custom_calls(compiled, "gmm")) == 6
    assert len(_custom_calls(compiled, "tgmm")) == 3
    assert not re.findall(
        r"= (?:f32|bf16)\[8,(?:2048,1792|1792,2048)\]\{[^}]*\} copy\(", text)
