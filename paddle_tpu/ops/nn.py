"""NN compute op lowerings: conv / pool / norm / dropout.

Capability parity with the reference's cuDNN-backed kernels (reference:
paddle/fluid/operators/{conv_op.cc,conv_cudnn_op.cu.cc,pool_op.cc,
batch_norm_op.cc,layer_norm_op.cc,dropout_op.cc,lrn_op.cc}).

TPU-native redesign: convolutions map to `lax.conv_general_dilated`, which XLA
tiles onto the MXU directly (no cuDNN algorithm search, no workspace attr);
batch/layer norm are expressed in plain jnp so XLA fuses them into adjacent
convs; dropout uses the executor's functional PRNG keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import register_op
from ..core import types


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


@register_op("conv2d", propagate_seqlen=False)
def _conv2d(ctx, Input, Filter, Bias=None):
    """Conv in NCHW or NHWC (reference conv_op.cc `data_format`). Filter is
    always stored OIHW so parameters are layout-independent; lax accepts the
    mixed dimension_numbers and XLA picks physical layouts for the MXU."""
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    dils = _pair(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1)
    fmt = ctx.attr("data_format", "NCHW")
    out = lax.conv_general_dilated(
        Input, Filter,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dils,
        dimension_numbers=(fmt, "OIHW", fmt),
        feature_group_count=groups,
    )
    if Bias is not None:
        bshape = (1, -1, 1, 1) if fmt == "NCHW" else (1, 1, 1, -1)
        out = out + Bias.reshape(bshape)
    return {"Output": out}


@register_op("depthwise_conv2d", propagate_seqlen=False)
def _depthwise_conv2d(ctx, Input, Filter, Bias=None):
    ctx.attrs = dict(ctx.attrs)
    c_axis = 1 if ctx.attr("data_format", "NCHW") == "NCHW" else 3
    ctx.attrs["groups"] = Input.shape[c_axis]
    return _conv2d(ctx, Input, Filter, Bias)


@register_op("conv2d_transpose", propagate_seqlen=False)
def _conv2d_transpose(ctx, Input, Filter, Bias=None):
    """Gradient-of-conv as a forward op (reference conv_transpose_op.cc).
    Filter layout follows the reference: [in_c, out_c, H, W]."""
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    dils = _pair(ctx.attr("dilations", [1, 1]))
    # Gradient-of-conv expressed directly: stride becomes lhs (input)
    # dilation, padding p becomes (k_eff-1-p) of the spatially-flipped
    # kernel, giving out = (in-1)*s + k_eff - 2p — the reference formula
    # (conv_transpose_op.cc). Filter stays in the reference [in_c, out_c,
    # H, W] layout ("IOHW"). Validated bit-exact (f64) against torch
    # conv_transpose2d over k/p/s/dilation combinations; lax.conv_transpose
    # was NOT used because its padding semantics diverge for k-1 != 2p.
    k_eff = [dils[i] * (Filter.shape[2 + i] - 1) + 1 for i in (0, 1)]
    out = lax.conv_general_dilated(
        Input, jnp.flip(Filter, axis=(2, 3)),
        window_strides=(1, 1),
        padding=[(k_eff[0] - 1 - pads[0], k_eff[0] - 1 - pads[0]),
                 (k_eff[1] - 1 - pads[1], k_eff[1] - 1 - pads[1])],
        lhs_dilation=strides,
        rhs_dilation=dils,
        dimension_numbers=("NCHW", "IOHW", "NCHW"),
    )
    if Bias is not None:
        out = out + Bias.reshape((1, -1, 1, 1))
    return {"Output": out}


@register_op("pool2d", propagate_seqlen=False)
def _pool2d(ctx, X):
    ptype = ctx.attr("pooling_type", "max")
    ksize = _pair(ctx.attr("ksize", [2, 2]))
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    fmt = ctx.attr("data_format", "NCHW")
    spatial = (2, 3) if fmt == "NCHW" else (1, 2)
    if ctx.attr("global_pooling", False) or ctx.attr("adaptive", False):
        oh, ow = ksize if ctx.attr("adaptive", False) else (1, 1)
        h, w = X.shape[spatial[0]], X.shape[spatial[1]]
        if ctx.attr("adaptive", False) and (oh < 1 or ow < 1):
            raise ValueError(
                "adaptive pool2d needs an explicit positive pool_size "
                f"(the output grid); got {(oh, ow)}")
        if (oh, ow) == (1, 1):
            if ptype == "max":
                return {"Out": jnp.max(X, axis=spatial, keepdims=True)}
            return {"Out": jnp.mean(X, axis=spatial, keepdims=True)}
        # adaptive to (oh, ow): exact when the output divides the input —
        # each output cell reduces an equal (h/oh, w/ow) tile (the
        # reference's bin boundaries coincide in that case)
        if h % oh or w % ow:
            raise NotImplementedError(
                f"adaptive pool2d: output {(oh, ow)} must divide input "
                f"{(h, w)} on TPU (unequal bins need ragged windows)")
        if fmt == "NCHW":
            n, c = X.shape[0], X.shape[1]
            tiles = X.reshape(n, c, oh, h // oh, ow, w // ow)
            red_axes = (3, 5)
        else:
            n, c = X.shape[0], X.shape[3]
            tiles = X.reshape(n, oh, h // oh, ow, w // ow, c)
            red_axes = (2, 4)
        red = jnp.max if ptype == "max" else jnp.mean
        return {"Out": red(tiles, axis=red_axes)}
    if fmt == "NCHW":
        window = (1, 1) + ksize
        strides4 = (1, 1) + strides
        padcfg = ((0, 0), (0, 0), (pads[0], pads[0]), (pads[1], pads[1]))
    else:
        window = (1,) + ksize + (1,)
        strides4 = (1,) + strides + (1,)
        padcfg = ((0, 0), (pads[0], pads[0]), (pads[1], pads[1]), (0, 0))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(X.dtype, jnp.floating) else jnp.iinfo(X.dtype).min
        out = lax.reduce_window(X, init, lax.max, window, strides4, padcfg)
        return {"Out": out}
    # avg pool
    ones = jnp.ones_like(X)
    ssum = lax.reduce_window(X, 0.0, lax.add, window, strides4, padcfg)
    if ctx.attr("exclusive", True):
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides4, padcfg)
    else:
        cnt = float(ksize[0] * ksize[1])
    return {"Out": ssum / cnt}


@register_op("batch_norm", propagate_seqlen=False)
def _batch_norm(ctx, X, Scale, Bias, Mean, Variance):
    """Reference batch_norm_op.cc. Outputs Y plus running-stat updates; the
    layer wires MeanOut/VarianceOut back onto the same variables."""
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    is_test = ctx.attr("is_test", False)
    layout = ctx.attr("data_layout", "NCHW")
    if layout == "NCHW":
        axes = tuple(i for i in range(X.ndim) if i != 1)
        shape = (1, -1) + (1,) * (X.ndim - 2)
    else:  # NHWC
        axes = tuple(range(X.ndim - 1))
        shape = (1,) * (X.ndim - 1) + (-1,)
    if is_test:
        mean, var = Mean, Variance
        saved_mean, saved_var = Mean, Variance
        mean_out, var_out = Mean, Variance
    else:
        x32 = X.astype(jnp.float32)
        mean = jnp.mean(x32, axis=axes)
        var = jnp.var(x32, axis=axes)
        saved_mean, saved_var = mean, var
        mean_out = momentum * Mean + (1.0 - momentum) * mean
        var_out = momentum * Variance + (1.0 - momentum) * var
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    y = (X.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
    y = y * Scale.reshape(shape) + Bias.reshape(shape)
    return {"Y": y.astype(X.dtype), "MeanOut": mean_out, "VarianceOut": var_out,
            "SavedMean": saved_mean, "SavedVariance": inv}


@register_op("layer_norm", propagate_seqlen=True)
def _layer_norm(ctx, X, Scale=None, Bias=None):
    eps = ctx.attr("epsilon", 1e-5)
    begin = ctx.attr("begin_norm_axis", 1)
    axes = tuple(range(begin, X.ndim))
    x32 = X.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.var(x32, axis=axes, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    bshape = (1,) * begin + X.shape[begin:]
    if Scale is not None:
        y = y * Scale.reshape(bshape)
    if Bias is not None:
        y = y + Bias.reshape(bshape)
    return {"Y": y.astype(X.dtype), "Mean": mean.reshape(X.shape[:begin]),
            "Variance": var.reshape(X.shape[:begin])}


@register_op("dropout", needs_rng=True)
def _dropout(ctx, X):
    p = ctx.attr("dropout_prob", 0.5)
    is_test = ctx.attr("is_test", False)
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if is_test:
        out = X if impl == "upscale_in_train" else X * (1.0 - p)
        return {"Out": out, "Mask": jnp.ones_like(X)}
    if p >= 1.0:
        # degenerate: drop everything (upscale would divide by zero)
        return {"Out": jnp.zeros_like(X), "Mask": jnp.zeros_like(X)}
    # One random byte per element from a counter hash decides keep at
    # 1/256 resolution (a bernoulli draw would materialize a full f32
    # uniform tensor): ~8 integer ops that XLA fuses into the surrounding
    # elementwise chain, so the op adds no pass over HBM. A custom kernel
    # here would be a fusion barrier, one extra read and write of the
    # tensor forward and backward. The custom_vjp regenerates the bits in
    # the backward, so the mask is never stored as a residual.
    scale = 1.0 if impl != "upscale_in_train" else 1.0 / (1.0 - p)
    out = _bits_dropout(X, ctx.key, float(p), float(scale))
    # true keep mask from the same key; DCE'd when the Mask var is unused
    mask = _keep_bits(ctx.key, X.shape, float(p))
    return {"Out": out, "Mask": mask.astype(X.dtype)}


def _hash_bits8(key, shape):
    """One random byte per element from a counter hash: murmur3's fmix32
    avalanche over the element's linear index, seeded from the op's
    fold_in'd PRNG key. Dropout-grade randomness (the reference draws from
    cuRAND Philox, dropout_op.cu — also a counter hash, more rounds) at
    ~8 fused integer ops per element; jax.random.bits' threefry is a
    ~100-op unfused block chain that dominated the VPU cost of every
    dropout site it fed."""
    kd = jax.random.key_data(key).reshape(-1).astype(jnp.uint32)
    seed = kd[0] ^ (kd[-1] * np.uint32(0x9E3779B9))
    idx = jnp.zeros(shape, jnp.uint32)   # 0-d tensors: index 0
    stride = 1
    for d in range(len(shape) - 1, -1, -1):
        term = jax.lax.broadcasted_iota(jnp.uint32, shape, d)
        if stride != 1:
            term = term * np.uint32(stride)
        idx = idx + term
        stride *= int(shape[d])
    x = idx * np.uint32(2654435761) + seed
    x = (x ^ (x >> 16)) * np.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * np.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x & np.uint32(0xFF)).astype(jnp.uint8)


def _keep_bits(key, shape, p):
    t = round((1.0 - p) * 256) - 1
    if t < 0:                       # p ~ 1: nothing survives
        return jnp.zeros(shape, bool)
    return _hash_bits8(key, shape) <= np.uint8(min(255, t))


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _bits_dropout(x, key, p, scale):
    keep = _keep_bits(key, x.shape, p)
    return jnp.where(keep, x * jnp.asarray(scale, x.dtype), jnp.zeros_like(x))


def _bits_dropout_fwd(x, key, p, scale):
    return _bits_dropout(x, key, p, scale), key


def _bits_dropout_bwd(p, scale, key, dy):
    keep = _keep_bits(key, dy.shape, p)   # regenerated, not stored
    dx = jnp.where(keep, dy * jnp.asarray(scale, dy.dtype),
                   jnp.zeros_like(dy))
    dkey = np.zeros(jnp.shape(key), jax.dtypes.float0)
    return dx, dkey


_bits_dropout.defvjp(_bits_dropout_fwd, _bits_dropout_bwd)


@register_op("lrn", propagate_seqlen=False)
def _lrn(ctx, X):
    n = ctx.attr("n", 5)
    k = ctx.attr("k", 2.0)
    alpha = ctx.attr("alpha", 1e-4)
    beta = ctx.attr("beta", 0.75)
    sq = jnp.square(X)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, n - 1 - half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + X.shape[1]] for i in range(n))
    mid = jnp.power(k + alpha * acc, beta)
    return {"Out": X / mid, "MidOut": mid}


@register_op("im2sequence", propagate_seqlen=False)
def _im2sequence(ctx, X):
    kernels = _pair(ctx.attr("kernels"))
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = ctx.attr("paddings", [0, 0, 0, 0])
    n, c, h, w = X.shape
    xp = jnp.pad(X, ((0, 0), (0, 0), (pads[0], pads[2]), (pads[1], pads[3])))
    patches = lax.conv_general_dilated_patches(
        xp, kernels, strides, "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"))
    # patches: [N, C*kh*kw, OH, OW] -> [N, OH*OW, C*kh*kw]
    nn, ck, oh, ow = patches.shape
    out = patches.reshape(nn, ck, oh * ow).transpose(0, 2, 1)
    return {"Out": out.reshape(nn * oh * ow, ck)}


@register_op("grid_sampler", propagate_seqlen=False)
def _grid_sampler(ctx, X, Grid):
    """Bilinear grid sample (align_corners), NCHW."""
    n, c, h, w = X.shape
    gx = (Grid[..., 0] + 1.0) * (w - 1) / 2.0
    gy = (Grid[..., 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx); y0 = jnp.floor(gy)
    wx = gx - x0; wy = gy - y0

    def sample(xi, yi):
        xi = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        yi = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        batch = jnp.arange(n)[:, None, None]
        return X[batch, :, yi, xi]  # [N, Hg, Wg, C]

    v00 = sample(x0, y0); v01 = sample(x0 + 1, y0)
    v10 = sample(x0, y0 + 1); v11 = sample(x0 + 1, y0 + 1)
    wx = wx[..., None]; wy = wy[..., None]
    out = (v00 * (1 - wx) * (1 - wy) + v01 * wx * (1 - wy)
           + v10 * (1 - wx) * wy + v11 * wx * wy)
    return {"Output": out.transpose(0, 3, 1, 2)}
