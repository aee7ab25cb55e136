"""The pieces of a 2024 decoder block that are not attention or a plain
matmul: RMSNorm (plain, zero-centred, and gated over a head), rotary position
embedding (on a whole head or its first dims, rotate-half or interleaved
pairs, plain frequencies or YaRN's), the silu-gated product, and a looped LM's exit gate.

No reference analog (the reference predates all three); the equations are
those of the public `olmoe` / `llama`-style model code. The plain norms, the
gated product and the exit gate are plain jnp, so XLA fuses them into their
neighbours; statistics and trigonometry run in float32 whatever dtype flows
through (under AMP the residual stream is bf16), and the result returns in
the input's dtype.

The gated norm and rotary are the exceptions: each stands between Pallas
calls, which take row-major operands, and as jnp each has float32
intermediates of its operand's size that XLA lays out for its own
reductions, writes to HBM and copies back.

The gated norm (`gated_rms_norm`, on `gdn_fwd`'s output and the gate `z`,
in front of the output projection) is one Pallas call each way where
`_gated_norm_plan` gives the kernels (a head of whole 128-lane tiles,
tokens in whole 16-row tiles, bf16 or float32):

    gated_norm_fwd   grid (batch, token block, head block); reads a block of
                     X and of Gate `[tokens, heads * D]` in their dtypes (the
                     bytes of `[B, T, H, D]`, as `gdn_fwd` writes them) and
                     Scale `[1, D]` float32; head by head, over the head's
                     own lanes, `r = rsqrt(mean(x^2) + eps)`, `x r` rounded
                     to X's dtype, `w * that * silu(gate)` in float32; Y in
                     X's dtype. Saves nothing.
    gated_norm_bwd   the same blocks of X, Gate and dY; makes r, `n = x r`
                     and silu(gate) again; `dn = dy w silu(g)`, `dx =
                     r (dn - n mean(dn n))`, `dg = dy w normed silu'(g)` in
                     their operands' dtypes, and a grid step's part of
                     dScale `sum dy normed silu(g)` as `[8, D]` float32
                     partial sums, which one small XLA sum finishes. The
                     registered grad `gated_rms_norm_grad` runs it alone.

Nothing float32 of X's size reaches HBM either way, and dX is what
`gdn_bwd` reads as dO. The normed value's rounding is passed straight
through by the backward (`astype`'s vjp would round its cotangent too; the
kernel keeps float32). Elsewhere (the CPU tests' heads of 6 and 8, 24
tokens; a CPU backend unless the Pallas interpreter is asked for) the op is
the jnp form `_gated_norm_xla` and the grad op its `jax.vjp`.

Rotary: as jnp, XLA writes the float32 halves of every head to HBM and
reads them back (the swap of a head's halves is no lane rotation to it),
five to fifteen times the traffic of one pass. Where `_rotary_plan` gives a
kernel the op is one Pallas call each way:

    rotary_fwd   grid (token block, head block), the heads innermost, so a
                 block of the two float32 tables `[T, D]` is fetched once and
                 serves every head; reads a block of X `[heads, tokens, D]`
                 in its own dtype, `(x A) * cos + (x B) * sin` in float32 in
                 VMEM, head by head, `Out` in X's dtype. No array of X's
                 size in float32 reaches HBM.
    rotary_bwd   the same kernel on dOut with the rotation transposed (its
                 inverse): the registered grad `rotary_embedding_grad` reads
                 dOut alone and saves nothing from the forward. Left to the
                 generic vjp grad op, the Mosaic call of the forward would
                 run again in every backward pass.

`x A` is the head as the rotation lays it out and `x B` its partner
`[-x2 | x1]`. "roll" (a whole head of whole lanes, rotate-half: Mellum2,
Ouro, OLMoE at D = 128): A is the identity and `x B` the lanes turned half
way round (`pltpu.roll`) with B's signs folded into the sin table. "dot"
(interleaved pairs at R = D = 64: Kanana-2): A and B are `[64, 64]` matrices
of 0 and +-1, `[evens | odds]` being a permutation; a product of bf16 rows
with one +-1 a column under a float32 accumulator is exact (float32 rows at
HIGHEST). The tables stay `rotary_tables` of `rotary_frequencies`, float32,
YaRN's factor in both; one rounding, to X's dtype, at the end. Elsewhere (a
rotary part inside a wider head: Qwen3-Next's 64 of 256; the CPU tests'
heads of 8 and 16; a CPU backend unless the Pallas interpreter is asked for)
the op is the jnp form `_rotary_xla` and the grad op its `jax.vjp`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import register_grad, register_op
from . import _kernels
from .moe import map_used_rows


@register_op("rms_norm")
def _rms_norm(ctx, X, Scale):
    """`x * rsqrt(mean(x^2) + eps) * w` over the last axis; with
    `zero_centered`, `* (1 + w)`: the weight is stored around 0 (the
    `qwen3_next` norms)."""
    eps = ctx.attr("epsilon", 1e-5)
    x32 = X.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    w = Scale.astype(jnp.float32)
    if ctx.attr("zero_centered", False):
        w = 1.0 + w
    y = x32 * lax.rsqrt(ms + eps) * w
    return {"Y": y.astype(X.dtype)}


def _gated_norm_xla(X, Gate, Scale, eps, sigmoid=False):
    """The op as plain jnp: what runs outside the kernels' envelope, and the
    form the kernels are held to (`jax.vjp` of it is the grad there).
    `sigmoid`: the gate's sigmoid in its silu's place."""
    x32 = X.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = (x32 * lax.rsqrt(ms + eps)).astype(X.dtype)
    y = Scale.astype(jnp.float32) * normed.astype(jnp.float32)
    act = jax.nn.sigmoid if sigmoid else jax.nn.silu
    return (y * act(Gate.astype(jnp.float32))).astype(X.dtype)


_BLOCK_BYTES = 1 << 20      # bytes of X a grid step of these kernels takes


def _heads_a_block(N, head_bytes):
    """The most of N heads, a divisor of N, that `_BLOCK_BYTES` hold."""
    most = max(_BLOCK_BYTES // head_bytes, 1)
    return next(h for h in range(min(N, most), 0, -1) if N % h == 0)


def _gated_norm_plan(shape, dtype):
    """"kernel": `[..., tokens, heads, dim]` whose head is whole lanes (dim a
    multiple of 128: the published 128) and whose tokens fill packed sublane
    tiles of 16, in bf16 or float32: one Pallas call each way. "xla":
    anything else (the CPU tests' widths of 6 and 8, 24 tokens), which keeps
    `_gated_norm_xla` and its vjp. The choice reads the operand's shape and
    dtype alone."""
    if len(shape) < 3 or dtype not in (jnp.bfloat16, jnp.float32):
        return "xla"
    T, D = shape[-3], shape[-1]
    return "kernel" if T % 16 == 0 and D % 128 == 0 else "xla"


def _gated_norm_kernels_run(shape, dtype):
    return _gated_norm_plan(shape, dtype) == "kernel" \
        and _kernels.backend_takes_kernels()


def _gated_norm_blocks(T, H, D, itemsize, backward):
    """(tokens, heads) of a grid step's block of X `[B, T, H * D]`: at most
    128 tokens (256 backward), fewer where one head's float32 tile at that
    height is past half of `_BLOCK_BYTES`, of as many whole heads as
    `_BLOCK_BYTES` hold; a head's tile `[tokens, D]` is worked on whole. A
    chip probe at `bf16[1, 4096, 32, 128]`, twenty chained calls on the host's clock, ms a
    call forward / backward: (128, 32) 0.149 / 0.282, (256, 16) 0.166 /
    0.259, (256, 8) 0.203 / 0.312 (whole rows of X are the longest
    transfers; the backward's longer dependency chains want the taller
    tile); the same blocks in loop steps of 32 rows 0.154 / 0.41, of 64
    0.149 / 0.34; the row means as products with a `[D, D]` matrix of 1 / D
    on the idle MXU 0.18-0.20 / 0.34-0.43."""
    most = 256 if backward else 128
    # the kernels hold about ten float32 values of a head's tile at once:
    # half a MiB each (256 rows of the published 512 lanes) fits the 16 MiB
    # of scoped VMEM beside the blocks, so a wider head (Granite 4.0-H's one
    # group of 4096 lanes) takes fewer rows: 32
    most = min(most, max(_BLOCK_BYTES // 2 // (4 * D), 16))
    Tb = next(b for b in (256, 128, 64, 32, 16) if b <= most and T % b == 0)
    return Tb, _heads_a_block(H, Tb * D * itemsize)


def _gated_norm_tile(x, g, eps):
    """A head's rows `[tokens, D]` as they arrive -> float32: each row's
    factor r, x r, that value as the op rounds it (to X's dtype), the gate
    and its sigmoid."""
    x32 = x.astype(jnp.float32)
    r = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    n = x32 * r
    g32 = g.astype(jnp.float32)
    return r, n, n.astype(x.dtype).astype(jnp.float32), g32, \
        jax.nn.sigmoid(g32)


def _gated_norm_fwd_kernel(x_ref, g_ref, w_ref, y_ref, *, D, eps,
                           sigmoid=False):
    """One (batch, token block, head block) step, head by head: the rule's
    arithmetic in float32 on a `[tokens, D]` tile, its one rounding, Y in
    X's dtype. `sigmoid`: the gate's sigmoid in its silu's place."""
    w = w_ref[...]
    for h in range(x_ref.shape[2] // D):
        lanes = slice(h * D, (h + 1) * D)
        _, _, normed, g, s = _gated_norm_tile(x_ref[0, :, lanes],
                                              g_ref[0, :, lanes], eps)
        y = (w * normed) * (s if sigmoid else g * s)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)


def _gated_norm_bwd_kernel(x_ref, g_ref, dy_ref, w_ref, dx_ref, dg_ref,
                           dw_ref, *, D, eps, sigmoid=False):
    """The same step on dY: r, x r and silu(gate) made again; with
    `dn = dy w silu(g)`: `dx = r (dn - n mean(dn n))`, `dg = dy w normed
    silu'(g)`, and the block's part of dScale, `sum dy normed silu(g)`, as
    eight sublanes of partial sums. `sigmoid`: the gate's sigmoid and its
    derivative `s (1 - s)` in silu's places."""
    w = w_ref[...]
    acc = jnp.zeros((8, D), jnp.float32)
    for h in range(x_ref.shape[2] // D):
        lanes = slice(h * D, (h + 1) * D)
        r, n, normed, g, s = _gated_norm_tile(x_ref[0, :, lanes],
                                              g_ref[0, :, lanes], eps)
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        silu = s if sigmoid else g * s
        dyw = dy * w
        dn = dyw * silu
        dx = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        dg = dyw * normed * (s * (1.0 - s) if sigmoid
                             else s * (1.0 + g * (1.0 - s)))
        dg_ref[0, :, lanes] = dg.astype(dg_ref.dtype)
        p = dy * normed * silu
        acc = acc + sum(p[i:i + 8] for i in range(0, p.shape[0], 8))
    dw_ref[0, 0, 0] = acc


def _gated_norm_layout(X, backward):
    """What both forms of the gated norm's calls share: X `[..., T, H, D]`
    read as `flat` = `[B, T, H * D]`, the heads (or groups) a block holds,
    D, the grid (batch, token block, head block), the block of X, Gate and
    dY, and the call's grid and compiler parameters."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, D = X.shape[-3:]
    Tb, Hb = _gated_norm_blocks(T, H, D, X.dtype.itemsize, backward)
    grid = (math.prod(X.shape[:-3]), T // Tb, H // Hb)
    block = pl.BlockSpec((1, Tb, Hb * D), lambda b, t, h: (b, t, h))
    params = dict(
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=_kernels.interpret())
    return (-1, T, H * D), Hb, D, grid, block, params


def _gated_norm_call(X, Gate, Scale, eps, d_y=None, sigmoid=False):
    """`gated_norm_fwd` (Y), or with `d_y` `gated_norm_bwd` (dX, dGate in
    their dtypes, dScale float32): X, Gate and dY `[..., T, H, D]` as they
    arrive, read as `[B, T, H * D]` (the same bytes), a head's lanes chosen
    inside a block. A grid step's part of dScale is a block `[8, D]` of its
    own (`[*grid, 8, D]` in all), and one small XLA sum over the steps
    finishes it: the same order every run."""
    from jax.experimental import pallas as pl

    flat, Hb, D, grid, block, params = _gated_norm_layout(X, d_y is not None)
    x, gate = X.reshape(flat), Gate.reshape(flat)
    weight = pl.BlockSpec((1, D), lambda b, t, h: (0, 0))
    w = Scale.astype(jnp.float32).reshape(1, D)
    more = {"sigmoid": True} if sigmoid else {}
    if d_y is None:
        return pl.pallas_call(
            functools.partial(_gated_norm_fwd_kernel, D=D, eps=eps, **more),
            name="gated_norm_fwd", in_specs=[block, block, weight],
            out_specs=block, out_shape=jax.ShapeDtypeStruct(x.shape, X.dtype),
            **params)(x, gate, w).reshape(X.shape)
    dX, dGate, dW = pl.pallas_call(
        functools.partial(_gated_norm_bwd_kernel, D=D, eps=eps, **more),
        name="gated_norm_bwd", in_specs=[block, block, block, weight],
        out_specs=[block, block, pl.BlockSpec(
            (1, 1, 1, 8, D), lambda b, t, h: (b, t, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, X.dtype),
                   jax.ShapeDtypeStruct(x.shape, Gate.dtype),
                   jax.ShapeDtypeStruct(grid + (8, D), jnp.float32)],
        **params)(x, gate, d_y.reshape(flat), w)
    return dX.reshape(X.shape), dGate.reshape(Gate.shape), \
        dW.sum(range(4))


def _gate_first_norm_xla(X, Gate, Scale, eps):
    """The op with the gate before the norm, as plain jnp: `u = x
    silu(gate)`, `u rsqrt(mean(u^2) + eps)` over the last axis (a group),
    rounded to X's dtype, times the weight, which is as wide as all the
    groups together (`[..., groups, width]` against `[groups * width]`)."""
    u = X.astype(jnp.float32) * jax.nn.silu(Gate.astype(jnp.float32))
    ms = jnp.mean(u * u, axis=-1, keepdims=True)
    normed = (u * lax.rsqrt(ms + eps)).astype(X.dtype)
    w = Scale.astype(jnp.float32).reshape(X.shape[-2:])
    return (w * normed.astype(jnp.float32)).astype(X.dtype)


def _gate_first_tile(x, g, eps):
    """A group's rows `[tokens, D]` as they arrive -> float32: x, the gate,
    its sigmoid, each row's factor r of `u = x silu(g)`, u r, and that value
    as the op rounds it (to X's dtype)."""
    x32, g32 = x.astype(jnp.float32), g.astype(jnp.float32)
    s = jax.nn.sigmoid(g32)
    u = x32 * (g32 * s)
    r = lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
    n = u * r
    return x32, g32, s, r, n, n.astype(x.dtype).astype(jnp.float32)


def _gate_first_fwd_kernel(x_ref, g_ref, w_ref, y_ref, *, D, eps):
    """One (batch, token block, group block) step, group by group: Y in X's
    dtype."""
    for h in range(x_ref.shape[2] // D):
        lanes = slice(h * D, (h + 1) * D)
        *_, normed = _gate_first_tile(x_ref[0, :, lanes], g_ref[0, :, lanes],
                                      eps)
        y_ref[0, :, lanes] = (w_ref[:, lanes] * normed).astype(y_ref.dtype)


def _gate_first_bwd_kernel(x_ref, g_ref, dy_ref, w_ref, dx_ref, dg_ref,
                           dw_ref, *, D, eps):
    """The same step on dY: with `dn = dy w`, `du = r (dn - n mean(dn n))`,
    `dx = du silu(g)`, `dg = du x silu'(g)`, and the block's part of dScale,
    `sum dy normed` a lane, as eight sublanes of partial sums."""
    for h in range(x_ref.shape[2] // D):
        lanes = slice(h * D, (h + 1) * D)
        x, g, s, r, n, normed = _gate_first_tile(x_ref[0, :, lanes],
                                                 g_ref[0, :, lanes], eps)
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        dn = dy * w_ref[:, lanes]
        du = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        dx_ref[0, :, lanes] = (du * (g * s)).astype(dx_ref.dtype)
        dg_ref[0, :, lanes] = (du * x * (s * (1.0 + g * (1.0 - s)))) \
            .astype(dg_ref.dtype)
        p = dy * normed
        dw_ref[0, 0, :, lanes] = sum(p[i:i + 8]
                                     for i in range(0, p.shape[0], 8))


def _gate_first_norm_call(X, Gate, Scale, eps, d_y=None):
    """`gated_norm_fwd` / `gated_norm_bwd` with the gate before the norm:
    `_gated_norm_call`'s blocks, a group `[tokens, D]` where it has a head,
    and the weight's lanes following the block's groups. A grid step's part
    of dScale is `[8, groups * D]`, summed outside."""
    from jax.experimental import pallas as pl

    flat, Hb, D, grid, block, params = _gated_norm_layout(X, d_y is not None)
    x, gate = X.reshape(flat), Gate.reshape(flat)
    weight = pl.BlockSpec((1, Hb * D), lambda b, t, h: (0, h))
    w = Scale.astype(jnp.float32).reshape(1, flat[2])
    if d_y is None:
        return pl.pallas_call(
            functools.partial(_gate_first_fwd_kernel, D=D, eps=eps),
            name="gated_norm_fwd", in_specs=[block, block, weight],
            out_specs=block, out_shape=jax.ShapeDtypeStruct(x.shape, X.dtype),
            **params)(x, gate, w).reshape(X.shape)
    dX, dGate, dW = pl.pallas_call(
        functools.partial(_gate_first_bwd_kernel, D=D, eps=eps),
        name="gated_norm_bwd", in_specs=[block, block, block, weight],
        out_specs=[block, block, pl.BlockSpec(
            (1, 1, 8, Hb * D), lambda b, t, h: (b, t, 0, h))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, X.dtype),
                   jax.ShapeDtypeStruct(x.shape, Gate.dtype),
                   jax.ShapeDtypeStruct(grid[:2] + (8, flat[2]), jnp.float32)],
        **params)(x, gate, d_y.reshape(flat), w)
    return dX.reshape(X.shape), dGate.reshape(Gate.shape), \
        dW.sum(range(3))


def _gated_norm_forms(ctx):
    """(the jnp form, the kernels' call) of the op as its attributes have
    it: Qwen3-Next's (the norm over a head, then the gate) or, with
    `gate_first`, Mamba-2's (the gate, then the norm over a group)."""
    if ctx.attr("gate_first", False):
        return _gate_first_norm_xla, _gate_first_norm_call
    if ctx.attr("activation", "silu") == "sigmoid":     # a KDA layer's
        return (functools.partial(_gated_norm_xla, sigmoid=True),
                functools.partial(_gated_norm_call, sigmoid=True))
    return _gated_norm_xla, _gated_norm_call


@register_op("gated_rms_norm")
def _gated_rms_norm(ctx, X, Gate, Scale):
    """`x * rsqrt(mean(x^2) + eps) * w * silu(gate)` over the last axis (a
    head): the output norm of a gated-delta-rule layer. The normed value is
    rounded to the input's dtype before the gate multiplies it in float32,
    as the public `qwen3_next` code does. With `gate_first` the gate
    multiplies x before the statistics are taken and the weight is as wide
    as all the groups (`_gate_first_norm_xla`: a Mamba-2 layer's). One pass
    over X and Gate as `gated_norm_fwd` where `_gated_norm_plan` gives the
    kernels."""
    eps = ctx.attr("epsilon", 1e-6)
    xla, call = _gated_norm_forms(ctx)
    if _gated_norm_kernels_run(X.shape, X.dtype):
        return {"Y": call(X, Gate, Scale, eps)}
    return {"Y": xla(X, Gate, Scale, eps)}


@register_grad("gated_rms_norm")
def _gated_rms_norm_grad(ctx, ins, out_grads):
    """dX, dGate and dScale from X, Gate, Scale and dY alone (the forward
    saves nothing): one pass as `gated_norm_bwd`, which makes r, x r and
    silu(gate) again in VMEM; outside the envelope `jax.vjp` of the jnp
    form, as the generic grad lowering would."""
    d_y = out_grads["Y"][0]
    if d_y is None:
        return {}
    X, Gate, Scale = (ins[s][0] for s in ("X", "Gate", "Scale"))
    d_y = d_y.astype(X.dtype)
    eps = ctx.attr("epsilon", 1e-6)
    xla, call = _gated_norm_forms(ctx)
    if _gated_norm_kernels_run(X.shape, X.dtype):
        dX, dGate, dScale = call(X, Gate, Scale, eps, d_y)
    else:
        _, vjp = jax.vjp(functools.partial(xla, eps=eps), X, Gate, Scale)
        dX, dGate, dScale = vjp(d_y)
    return {"X": dX, "Gate": dGate, "Scale": dScale.astype(Scale.dtype)}


YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "attention_factor")


def rotary_frequencies(dim, theta, scaling=None):
    """(the `dim / 2` frequencies of a rotary head, the factor its tables
    are multiplied by). Plain: `theta^(-2i/dim)` and 1. With a YaRN block
    `scaling` (arXiv:2309.00071, as the public `rope_type: yarn` code
    computes it, applied at every length): dimension i turns
    `original_max_position_embeddings * theta^(-2i/dim) / (2 pi)` times over
    the original context; those that turn more than `beta_fast` times keep
    their frequency, those that turn fewer than `beta_slow` times have it
    divided by `factor`, a linear ramp over the dimensions between; cos and
    sin are both scaled by `attention_factor` (`0.1 ln(factor) + 1` where
    the block has none)."""
    pos_freq = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if scaling is None:
        return 1.0 / pos_freq, 1.0
    unknown = sorted(set(scaling) - set(YARN_KEYS))
    if unknown or "factor" not in scaling \
            or "original_max_position_embeddings" not in scaling:
        raise ValueError(
            f"rotary_embedding's scaling is a YaRN block with keys among "
            f"{YARN_KEYS} ('factor' and 'original_max_position_embeddings' "
            f"required), got {dict(scaling)}")
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def turns_at(turns):        # the dimension that turns `turns` times
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turns_at(float(scaling.get("beta_slow", 1)))),
               dim - 1)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = ramp / (factor * pos_freq) + (1.0 - ramp) / pos_freq
    attention_factor = scaling.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq, float(attention_factor)


def rotary_tables(seq_len, inv_freq, factor=1.0):
    """cos and sin `[seq_len, 2 * len(inv_freq)]` of the rotate-half
    convention: the frequencies (`rotary_frequencies`) repeated over both
    halves, both tables times `factor`. Float32."""
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    if factor == 1.0:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def _rotary_xla(X, R, interleaved, cos, sin):
    """The op as plain jnp: what runs outside the kernel's envelope, and the
    form the kernel is held to (`jax.vjp` of it is the grad there)."""
    D = X.shape[-1]
    x32 = X.astype(jnp.float32)
    head = x32 if R == D else x32[..., :R]
    if interleaved:
        head = jnp.concatenate([head[..., 0::2], head[..., 1::2]], axis=-1)
    x1, x2 = head[..., : R // 2], head[..., R // 2:]
    out = head * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    if R < D:
        out = jnp.concatenate([out, x32[..., R:]], axis=-1)
    return out.astype(X.dtype)


def _rotary_plan(shape, dtype, R, interleaved):
    """"roll": a whole head of whole lanes turns by a lane rotation (R = D,
    a multiple of 128, rotate-half). "dot": the pairs are brought together
    by a product with a 0 / +-1 matrix of the head's size (interleaved pairs
    at R = D = 64, whose `[evens | odds]` layout is a permutation). Both are
    one Pallas call each way. "xla": anything else (a rotary part inside a
    wider head, the CPU tests' heads of 8 and 16, tokens that do not fill a
    packed sublane tile of 16), which keeps `_rotary_xla` and its vjp. The
    choice reads the operand's shape and dtype alone."""
    if len(shape) < 3 or dtype not in (jnp.bfloat16, jnp.float32):
        return "xla"
    T, D = shape[-2], shape[-1]
    if T % 16 or R != D:
        return "xla"
    if not interleaved and D % 128 == 0:
        return "roll"
    if interleaved and D == 64:
        return "dot"
    return "xla"


def _rotary_kernel_runs(shape, dtype, R, interleaved):
    return _rotary_plan(shape, dtype, R, interleaved) != "xla" \
        and _kernels.backend_takes_kernels()


def _rotary_blocks(N, T, D, itemsize):
    """(heads, tokens) of a grid step's block of X `[N, T, D]`: at most 512
    tokens of as many heads as `_BLOCK_BYTES` hold, so a step's two
    table blocks serve every head of it (a chip probe at `bf16[32, 8192,
    128]`, ten chained calls on the host's clock: 0.57 ms a call at one head
    a block, 0.41 at two, 0.33 at four, 0.28-0.30 at eight and sixteen; 1024
    and 2048 tokens read like 512; a block taken whole 0.281, in loop steps
    of 64 rows 0.297, of 16 rows 0.411)."""
    Tb = next(b for b in (512, 256, 128, 64, 32, 16) if T % b == 0)
    return _heads_a_block(N, Tb * D * itemsize), Tb


def _pair_matrices(R, backward):
    """(A, B), `[R, R]` of 0 and +-1, for interleaved pairs: `x @ A` is the
    head laid out `[evens | odds]` and `x @ B` its partner `[-x2 | x1]`.
    `backward`: the transposes, which take a cotangent back to x's layout."""
    half = R // 2
    j = np.arange(half)
    swap = np.zeros((R, R), np.float32)
    swap[j + half, j], swap[j, j + half] = -1.0, 1.0
    lay = np.zeros((R, R), np.float32)
    lay[2 * j, j] = lay[2 * j + 1, j + half] = 1.0
    return (lay.T, swap.T @ lay.T) if backward else (lay, lay @ swap)


def _rotary_kernel(x_ref, c_ref, s_ref, *more):
    """One (token block, head block) step: `(x A) * c + (x B) * s` in
    float32, head by head against one block of the tables, written in X's
    dtype. Without matrices ("roll") A is the identity and `x B` the lanes
    turned half way round, B's signs being in `s`."""
    from jax.experimental.pallas import tpu as pltpu

    *matrices, o_ref = more
    full = x_ref.dtype == jnp.float32

    def dot(x, m_ref):      # exact: one +-1 a column, a float32 accumulator
        return jnp.dot(x, m_ref[...], preferred_element_type=jnp.float32,
                       precision=lax.Precision.HIGHEST if full else None)

    c, s = c_ref[...], s_ref[...]
    for h in range(x_ref.shape[0]):
        x = x_ref[h]
        if matrices:
            head, partner = (dot(x, m_ref) for m_ref in matrices)
        else:
            head = x.astype(jnp.float32)
            partner = pltpu.roll(head, x.shape[-1] // 2, 1)
        o_ref[h] = (head * c + partner * s).astype(o_ref.dtype)


def _rotary_call(X, cos, sin, interleaved, backward):
    """`rotary_fwd` / `rotary_bwd`: X (or dOut) `[..., T, D]` as it arrives,
    of a shape `_rotary_plan` gives a kernel, against the float32 tables
    `[T, D]` -> the rotated (or the inversely rotated) array in X's dtype.
    The token blocks lead the grid, so a block of the tables is fetched once
    and serves every head."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, D = X.shape[-2:]
    half = D // 2
    if not interleaved:     # "roll": B's signs; its transpose is -B
        sin = jnp.concatenate([-sin[:, :half], sin[:, half:]], axis=-1)
        sin = -sin if backward else sin
        matrices = ()
    else:                   # "dot"
        if backward:    # the tables in x's own layout
            cos, sin = (jnp.repeat(t[:, :half], 2, axis=-1)
                        for t in (cos, sin))
        matrices = tuple(jnp.asarray(m, X.dtype)
                         for m in _pair_matrices(D, backward))
    x = X.reshape((-1, T, D))
    N = x.shape[0]
    Hb, Tb = _rotary_blocks(N, T, D, X.dtype.itemsize)
    x_spec = pl.BlockSpec((Hb, Tb, D), lambda t, n: (n, t, 0))
    table = pl.BlockSpec((Tb, D), lambda t, n: (t, 0))
    whole = pl.BlockSpec((D, D), lambda t, n: (0, 0))
    out = pl.pallas_call(
        _rotary_kernel, name="rotary_bwd" if backward else "rotary_fwd",
        grid=(T // Tb, N // Hb),
        in_specs=[x_spec, table, table] + [whole] * len(matrices),
        out_specs=x_spec, out_shape=jax.ShapeDtypeStruct(x.shape, X.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_kernels.interpret(),
    )(x, cos, sin, *matrices)
    return out.reshape(X.shape)


def _rotary_attrs(ctx, X):
    """(R, interleaved, cos, sin) of the op on X: the rotary size, checked,
    and the float32 tables `[T, R]`."""
    T, D = X.shape[-2], X.shape[-1]
    R = int(ctx.attr("rotary_dim") or D)
    if R % 2 or R > D:
        raise ValueError(f"rotary_embedding needs an even rotary size within "
                         f"the head, got {R} of {D}")
    cos, sin = rotary_tables(T, *rotary_frequencies(
        R, float(ctx.attr("theta", 10000.0)), ctx.attr("scaling")))
    return R, bool(ctx.attr("interleaved", False)), cos, sin


@register_op("rotary_embedding", propagate_seqlen=False)
def _rotary_embedding(ctx, X):
    """X `[..., T, D]` (heads already split): position t rotates the pair
    `(x[i], x[i + R/2])` by `t * theta^(-2i/R)` over the first R =
    `rotary_dim` dims of a head (all D where the attribute is absent); the
    other D - R pass through. Positions are 0..T-1. With the attribute
    `interleaved` the pair is `(x[2i], x[2i + 1])` (DeepSeek-V3's
    `rope_interleave`): the R dims are first laid out `[evens | odds]`, as
    the public code does, and stay so in the output (queries and keys alike,
    so their products agree). With the attribute `scaling` (a YaRN block)
    the frequencies and the tables' factor are `rotary_frequencies`'. One
    pass over X as `rotary_fwd` where `_rotary_plan` gives a kernel."""
    R, interleaved, cos, sin = _rotary_attrs(ctx, X)
    if _rotary_kernel_runs(X.shape, X.dtype, R, interleaved):
        ctx.tally("rotary_kernel_ops")
        return {"Out": _rotary_call(X, cos, sin, interleaved, False)}
    return {"Out": _rotary_xla(X, R, interleaved, cos, sin)}


@register_grad("rotary_embedding")
def _rotary_embedding_grad(ctx, ins, out_grads):
    """dX from dOut alone: a rotation's transpose is its inverse, so the
    grad is the same pass with sin negated (`g * cos - rot(g) * sin`, the
    `[evens | odds]` layout undone) as `rotary_bwd`; outside the envelope
    `jax.vjp` of the jnp form, as the generic grad lowering would. Of X it
    reads the shape and the dtype."""
    d_out = out_grads["Out"][0]
    if d_out is None:
        return {}
    X = ins["X"][0]
    d_out = d_out.astype(X.dtype)
    R, interleaved, cos, sin = _rotary_attrs(ctx, X)
    if _rotary_kernel_runs(X.shape, X.dtype, R, interleaved):
        ctx.tally("rotary_kernel_ops")
        return {"X": _rotary_call(d_out, cos, sin, interleaved, True)}
    _, vjp = jax.vjp(lambda x: _rotary_xla(x, R, interleaved, cos, sin), X)
    return {"X": vjp(d_out)[0]}


def _silu_product(gate, up):
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _silu_product_grads(gate, up, g):
    """(dGate, dUp) of `_silu_product` under the cotangent g."""
    return jax.vjp(_silu_product, gate, up)[1](g)


@register_op("swiglu")
def _swiglu(ctx, Gate, Up, GroupSizes=None):
    """`silu(gate) * up`, the gated feed-forward's elementwise middle. With
    `GroupSizes` (the hidden rows of an expert layer's share, `ops/moe.py`)
    the same product over the rows the held groups use, a chunk at a time,
    written into an allocation of the layout's shape: the rows behind them
    are not visited."""
    if GroupSizes is None:
        return {"Out": _silu_product(Gate, Up)}
    ctx.tally("moe_share_bounded_ops")
    out, = map_used_rows(lambda gate, up: (_silu_product(gate, up),),
                         GroupSizes, Gate, Up)
    return {"Out": out}


@register_grad("swiglu")
def _swiglu_grad(ctx, ins, out_grads):
    """The product's own vjp: on the whole arrays what the generic grad op
    traces, and with `GroupSizes` the same on each chunk of the used rows
    (a used row's dGate and dUp are bitwise the static form's)."""
    gate, up = ins["Gate"][0], ins["Up"][0]
    g = out_grads["Out"][0]
    if g is None:
        return {}
    g = g.astype(gate.dtype)
    if not ins.get("GroupSizes"):
        d_gate, d_up = _silu_product_grads(gate, up, g)
    else:
        ctx.tally("moe_share_bounded_ops")
        # the experts' hidden rows are read by this op last: their two
        # gradients take their buffers
        d_gate, d_up = map_used_rows(_silu_product_grads,
                                     ins["GroupSizes"][0], gate, up, g,
                                     in_place=2)
    return {"Gate": d_gate, "Up": d_up}


def _relu2(x):
    y = jnp.maximum(x.astype(jnp.float32), 0.0)
    return (y * y).astype(x.dtype)


def _relu2_grad(x, g):
    return jax.vjp(_relu2, x)[1](g)


@register_op("relu2")
def _relu_squared(ctx, X, GroupSizes=None):
    """`relu(x)^2`, the middle of an ungated two-matrix feed-forward
    (`mlp_hidden_act: relu2`); float32 inside, X's dtype out. With
    `GroupSizes` (the hidden rows of an expert layer's share, `ops/moe.py`)
    over the rows the held groups use only, a chunk at a time, as `swiglu`
    does."""
    if GroupSizes is None:
        return {"Out": _relu2(X)}
    ctx.tally("moe_share_bounded_ops")
    out, = map_used_rows(lambda x: (_relu2(x),), GroupSizes, X)
    return {"Out": out}


@register_grad("relu2")
def _relu_squared_grad(ctx, ins, out_grads):
    """`2 relu(x) g`: on the whole array what the generic grad op traces,
    with `GroupSizes` the same on each chunk of the used rows, written over
    the hidden rows, which this op reads last."""
    x, g = ins["X"][0], out_grads["Out"][0]
    if g is None:
        return {}
    g = g.astype(x.dtype)
    if not ins.get("GroupSizes"):
        return {"X": _relu2_grad(x, g)[0]}
    ctx.tally("moe_share_bounded_ops")
    d_x, = map_used_rows(_relu2_grad, ins["GroupSizes"][0], x, g, in_place=1)
    return {"X": d_x}


@register_op("exit_gate")
def _exit_gate(ctx, X, W, Bias):
    """The gate logit of a looped LM (Ouro, arXiv:2510.25741): `X W + b`,
    X `[..., D]`, W `[D, 1]`, Bias `[1]` -> `[..., 1]`, in float32 with the
    product at HIGHEST (AMP_F32_OPS): the exit distribution is a running
    product of its sigmoids over the passes, and its entropy is part of the
    loss."""
    logit = jnp.dot(X.astype(jnp.float32), W.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST)
    return {"Out": logit + Bias.astype(jnp.float32)}
