"""The pieces of a 2024 decoder block that are not attention or a plain
matmul: RMSNorm (plain, zero-centred, and gated over a head), rotary position
embedding (on a whole head or its first dims, rotate-half or interleaved
pairs, plain frequencies or YaRN's), the silu-gated product, and a looped LM's exit gate.

No reference analog (the reference predates all three); the equations are
those of the public `olmoe` / `llama`-style model code. Each is plain jnp,
so XLA fuses it into its neighbours; statistics and trigonometry run in
float32 whatever dtype flows through (under AMP the residual stream is
bf16), and the result returns in the input's dtype.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_grad, register_op
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


@register_op("gated_rms_norm")
def _gated_rms_norm(ctx, X, Gate, Scale):
    """`x * rsqrt(mean(x^2) + eps) * w * silu(gate)` over the last axis (a
    head): the output norm of a gated-delta-rule layer. The normed value is
    rounded to the input's dtype before the gate multiplies it in float32,
    as the public `qwen3_next` code does."""
    eps = ctx.attr("epsilon", 1e-6)
    x32 = X.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = (x32 * lax.rsqrt(ms + eps)).astype(X.dtype)
    y = Scale.astype(jnp.float32) * normed.astype(jnp.float32)
    return {"Y": (y * jax.nn.silu(Gate.astype(jnp.float32))).astype(X.dtype)}


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
    the frequencies and the tables' factor are `rotary_frequencies`'."""
    T, D = X.shape[-2], X.shape[-1]
    R = int(ctx.attr("rotary_dim") or D)
    if R % 2 or R > D:
        raise ValueError(f"rotary_embedding needs an even rotary size within "
                         f"the head, got {R} of {D}")
    cos, sin = rotary_tables(T, *rotary_frequencies(
        R, float(ctx.attr("theta", 10000.0)), ctx.attr("scaling")))
    x32 = X.astype(jnp.float32)
    head = x32 if R == D else x32[..., :R]
    if ctx.attr("interleaved", False):
        head = jnp.concatenate([head[..., 0::2], head[..., 1::2]], axis=-1)
    x1, x2 = head[..., : R // 2], head[..., R // 2:]
    out = head * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    if R < D:
        out = jnp.concatenate([out, x32[..., R:]], axis=-1)
    return {"Out": out.astype(X.dtype)}


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
