"""Tensor creation / manipulation op lowerings.

Capability parity with the reference's fill/reshape/concat/... op family
(reference: paddle/fluid/operators/{fill_constant,uniform_random,
gaussian_random,reshape,transpose,concat,split,slice,gather,expand,one_hot,
lookup_table,...}_op.cc).

Random ops consume the functional PRNG key threaded by the executor
(replacing the reference's per-device cuRAND generators / `random_seed`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op, register_grad
from ..core import types


@register_op("fill_constant")
def _fill_constant(ctx, X=None):
    shape = [int(s) for s in ctx.attr("shape", [1])]
    dtype = types.np_dtype(ctx.attr("dtype", "float32"))
    return {"Out": jnp.full(shape, ctx.attr("value", 0.0), dtype)}


@register_op("fill_constant_batch_size_like")
def _fill_constant_bsl(ctx, Input):
    shape = [int(s) for s in ctx.attr("shape")]
    in_idx = ctx.attr("input_dim_idx", 0)
    out_idx = ctx.attr("output_dim_idx", 0)
    shape[out_idx] = Input.shape[in_idx]
    dtype = types.np_dtype(ctx.attr("dtype", "float32"))
    return {"Out": jnp.full(shape, ctx.attr("value", 0.0), dtype)}


@register_op("uniform_random", needs_rng=True)
def _uniform_random(ctx, X=None):
    shape = tuple(int(s) for s in ctx.attr("shape"))
    dtype = types.np_dtype(ctx.attr("dtype", "float32"))
    lo, hi = ctx.attr("min", -1.0), ctx.attr("max", 1.0)
    return {"Out": jax.random.uniform(ctx.key, shape, dtype, lo, hi)}


@register_op("gaussian_random", needs_rng=True)
def _gaussian_random(ctx, X=None):
    shape = tuple(int(s) for s in ctx.attr("shape"))
    dtype = types.np_dtype(ctx.attr("dtype", "float32"))
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    return {"Out": mean + std * jax.random.normal(ctx.key, shape, dtype)}


@register_op("truncated_gaussian_random", needs_rng=True)
def _truncated_gaussian_random(ctx, X=None):
    shape = tuple(int(s) for s in ctx.attr("shape"))
    dtype = types.np_dtype(ctx.attr("dtype", "float32"))
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    z = jax.random.truncated_normal(ctx.key, -2.0, 2.0, shape, dtype)
    return {"Out": mean + std * z}


@register_op("assign")
def _assign(ctx, X):
    return {"Out": X}


@register_op("assign_value")
def _assign_value(ctx):
    import numpy as np
    dtype = types.np_dtype(ctx.attr("dtype", "float32"))
    shape = ctx.attr("shape")
    vals = ctx.attr("values")
    return {"Out": jnp.asarray(np.array(vals, dtype).reshape(shape))}


@register_op("shape", propagate_seqlen=False)
def _shape(ctx, Input):
    return {"Out": jnp.array(Input.shape, types.index_dtype())}


def _reshape_infer(ctx, structs):
    """Exact static-shape rule. eval_shape can't be used here: the dynamic
    batch dim is substituted with a prime sentinel, and a target like
    [-1, K] would need SENTINEL % K == 0. With a dynamic input dim, the -1
    output dim is simply dynamic — runtime shapes are authoritative.
    `ctx.dim_sentinel` is whichever sentinel THIS trace substituted
    (infer_op_shapes runs two traces to classify dynamic dims)."""
    import math as _m

    sentinel = ctx.dim_sentinel
    X = structs["X"][0]
    target = [int(s) for s in ctx.attr("shape")]
    target = [int(X.shape[i]) if s == 0 else s
              for i, s in enumerate(target)]
    dynamic_in = any(d >= sentinel and d % sentinel == 0
                     for d in X.shape)
    if -1 in target:
        known = _m.prod(d for d in target if d != -1)
        neg = target.index(-1)
        total = _m.prod(int(d) for d in X.shape)
        if known and total % known == 0:
            # exact: stays a sentinel multiple when the -1 absorbs the
            # dynamic batch, yields the true static dim when it doesn't
            # (e.g. reshape([0, -1]) of a [-1, 4, 8] input -> (-1, 32))
            target[neg] = total // known
        elif dynamic_in:
            target[neg] = sentinel
        else:
            raise ValueError(
                f"reshape: cannot infer -1 dim reshaping {tuple(X.shape)} "
                f"to {ctx.attr('shape')}")
    elif dynamic_in:
        # all-target-dims-concrete reshape of a dynamic tensor: the dim
        # that absorbs the batch is unknowable statically; leave the
        # declared target (runtime authoritative)
        pass
    return {"Out": jax.ShapeDtypeStruct(tuple(target), X.dtype)}


@register_op("reshape", infer=_reshape_infer)
def _reshape(ctx, X, Shape=None):
    shape = [int(s) for s in ctx.attr("shape")]
    # reference reshape_op.cc: 0 means "copy this dim from input".
    shape = [X.shape[i] if s == 0 else s for i, s in enumerate(shape)]
    return {"Out": X.reshape(shape)}


@register_op("squeeze")
def _squeeze(ctx, X):
    axes = ctx.attr("axes", [])
    if axes:
        return {"Out": jnp.squeeze(X, axis=tuple(axes))}
    return {"Out": jnp.squeeze(X)}


@register_op("unsqueeze")
def _unsqueeze(ctx, X):
    out = X
    for a in sorted(ctx.attr("axes")):
        out = jnp.expand_dims(out, a)
    return {"Out": out}


@register_op("flatten")
def _flatten(ctx, X):
    axis = ctx.attr("axis", 1)
    lead = math.prod(X.shape[:axis]) if axis > 0 else 1
    return {"Out": X.reshape((lead, -1))}


@register_op("transpose", propagate_seqlen=False)
def _transpose(ctx, X):
    return {"Out": jnp.transpose(X, ctx.attr("axis"))}


@register_op("concat")
def _concat(ctx, X):
    xs = X if isinstance(X, list) else [X]
    return {"Out": jnp.concatenate(xs, axis=ctx.attr("axis", 0))}


@register_op("split")
def _split(ctx, X):
    axis = ctx.attr("axis", 0)
    sections = ctx.attr("sections", [])
    num = ctx.attr("num", 0)
    if sections:
        idx = list(jnp.cumsum(jnp.array(sections))[:-1])
        outs = jnp.split(X, [int(i) for i in idx], axis=axis)
    else:
        outs = jnp.split(X, num, axis=axis)
    return {"Out": outs}


@register_op("stack")
def _stack(ctx, X):
    xs = X if isinstance(X, list) else [X]
    return {"Y": jnp.stack(xs, axis=ctx.attr("axis", 0))}


@register_op("unstack")
def _unstack(ctx, X):
    axis = ctx.attr("axis", 0)
    n = X.shape[axis]
    return {"Y": [jnp.squeeze(s, axis) for s in jnp.split(X, n, axis=axis)]}


@register_op("slice", propagate_seqlen=False)
def _slice(ctx, Input):
    axes = ctx.attr("axes")
    starts = ctx.attr("starts")
    ends = ctx.attr("ends")
    idx = [slice(None)] * Input.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = Input.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    return {"Out": Input[tuple(idx)]}


@register_op("gather", propagate_seqlen=False)
def _gather(ctx, X, Index):
    return {"Out": jnp.take(X, Index.reshape(-1).astype(jnp.int32), axis=0)}


@register_op("gather_nd", propagate_seqlen=False)
def _gather_nd(ctx, X, Index):
    idx = tuple(jnp.moveaxis(Index, -1, 0))
    return {"Out": X[idx]}


@register_op("scatter", propagate_seqlen=False)
def _scatter(ctx, X, Ids, Updates):
    ids = Ids.reshape(-1).astype(jnp.int32)
    if ctx.attr("overwrite", True):
        return {"Out": X.at[ids].set(Updates)}
    return {"Out": X.at[ids].add(Updates)}


@register_op("expand")
def _expand(ctx, X):
    times = ctx.attr("expand_times")
    return {"Out": jnp.tile(X, tuple(times))}


@register_op("expand_dims_tile")
def _expand_dims_tile(ctx, X):
    return {"Out": jnp.tile(X, tuple(ctx.attr("times")))}


@register_op("pad")
def _pad(ctx, X):
    paddings = ctx.attr("paddings")
    val = ctx.attr("pad_value", 0.0)
    cfg = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(X.ndim)]
    return {"Out": jnp.pad(X, cfg, constant_values=val)}


@register_op("pad2d")
def _pad2d(ctx, X):
    p = ctx.attr("paddings", [0, 0, 0, 0])  # t, b, l, r (NCHW)
    mode = ctx.attr("mode", "constant")
    cfg = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if mode == "constant":
        return {"Out": jnp.pad(X, cfg, constant_values=ctx.attr("pad_value", 0.0))}
    jmode = {"reflect": "reflect", "edge": "edge"}[mode]
    return {"Out": jnp.pad(X, cfg, mode=jmode)}


@register_op("one_hot", propagate_seqlen=False)
def _one_hot(ctx, X):
    depth = ctx.attr("depth")
    ids = X.reshape(X.shape[:-1]) if X.shape and X.shape[-1] == 1 else X
    return {"Out": jax.nn.one_hot(ids.astype(jnp.int32), depth, dtype=jnp.float32)}


@register_op("lookup_table", propagate_seqlen=True)
def _lookup_table(ctx, W, Ids):
    """Embedding lookup (reference lookup_table_op.cc). Ids has a trailing
    size-1 dim in the reference convention."""
    ids = Ids
    if ids.shape and ids.shape[-1] == 1:
        ids = ids.reshape(ids.shape[:-1])
    ids = ids.astype(jnp.int32)
    out = jnp.take(W, ids, axis=0)
    pad = ctx.attr("padding_idx", -1)
    if pad is not None and pad >= 0:
        mask = (ids != pad)[..., None].astype(out.dtype)
        out = out * mask
    return {"Out": out}


@register_op("range")
def _range(ctx):
    return {"Out": jnp.arange(ctx.attr("start", 0), ctx.attr("end"),
                              ctx.attr("step", 1),
                              dtype=types.np_dtype(ctx.attr("dtype", "int64")))}


@register_op("increment")
def _increment(ctx, X):
    # keep X's dtype (int counters must stay int inside loop carries)
    return {"Out": X + jnp.asarray(ctx.attr("step", 1.0)).astype(X.dtype)}


@register_op("reverse")
def _reverse(ctx, X):
    return {"Out": jnp.flip(X, axis=tuple(ctx.attr("axis")))}


@register_op("sequence_mask", propagate_seqlen=False)
def _sequence_mask(ctx, X):
    maxlen = ctx.attr("maxlen", -1)
    if maxlen < 0:
        raise ValueError("sequence_mask requires a static maxlen on TPU")
    dtype = types.np_dtype(ctx.attr("out_dtype", "int64"))
    rng = jnp.arange(maxlen)
    return {"Y": (rng[None, :] < X.reshape(-1, 1)).astype(dtype)}


@register_op("batch_gather", propagate_seqlen=False)
def _batch_gather(ctx, X, Index):
    """Per-row gather along axis 1: X [B, K, ...], Index [B, K'] ->
    [B, K', ...] (beam-search parent reordering)."""
    idx = Index.astype(jnp.int32)
    while idx.ndim < X.ndim:
        idx = idx[..., None]
    return {"Out": jnp.take_along_axis(X, idx, axis=1)}


@register_op("causal_mask", propagate_seqlen=False)
def _causal_mask(ctx):
    """Additive upper-triangular attention mask, computed in-graph (constant-
    folded by XLA) instead of shipping a T*T blob through the IR."""
    t = int(ctx.attr("size"))
    neg = ctx.attr("neg", -1e9)
    row = jnp.arange(t)[:, None]
    col = jnp.arange(t)[None, :]
    mask = jnp.where(col > row, jnp.float32(neg), jnp.float32(0.0))
    return {"Out": mask.reshape(1, 1, t, t)}


@register_op("sinusoid_pos_encoding", propagate_seqlen=False)
def _sinusoid_pos_encoding(ctx):
    """Transformer sinusoidal position table [T, D], computed in-graph."""
    t = int(ctx.attr("size"))
    d = int(ctx.attr("d_model"))
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    i = jnp.arange(d, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, (2.0 * jnp.floor(i / 2.0)) / d)
    even = jnp.sin(angle)
    odd = jnp.cos(angle)
    enc = jnp.where(jnp.arange(d)[None, :] % 2 == 0, even, odd)
    return {"Out": enc}


@register_op("uniform_random_batch_size_like", needs_rng=True)
def _uniform_random_bsl(ctx, Input):
    shape = [int(s) for s in ctx.attr("shape")]
    shape[ctx.attr("output_dim_idx", 0)] = Input.shape[ctx.attr("input_dim_idx", 0)]
    dtype = types.np_dtype(ctx.attr("dtype", "float32"))
    return {"Out": jax.random.uniform(ctx.key, tuple(shape), dtype,
                                      ctx.attr("min", -1.0), ctx.attr("max", 1.0))}


@register_op("argsort")
def _argsort(ctx, X):
    """Sorted values + indices (reference argsort_op.cc). XLA lowers sort
    to an efficient TPU sorting network; the old "use top_k" guidance
    predated that and is retired."""
    axis = ctx.attr("axis", -1)
    idx = jnp.argsort(X, axis=axis)
    out = jnp.take_along_axis(X, idx, axis=axis)
    return {"Out": out, "Indices": idx.astype(types.index_dtype())}


@register_op("is_empty")
def _is_empty(ctx, X):
    """True iff the tensor holds zero elements (reference is_empty_op.cc).
    Shapes are static under XLA, so this folds to a constant."""
    import numpy as _np
    return {"Out": jnp.asarray(int(_np.prod(X.shape)) == 0).reshape((1,))}


@register_op("print")
def _print(ctx, X):
    """Runtime tensor printing (reference print_op.cc) via jax.debug.print:
    the callback fires from the compiled program on the host, so it works
    inside the single-XLA-step executor. Out aliases the input so the op
    can be inserted mid-graph without changing the math."""
    message = ctx.attr("message", "") or ""
    summarize = int(ctx.attr("summarize", -1))
    flat = X.reshape(-1)
    shown = flat[:summarize] if summarize > 0 else flat
    # user text goes through brace-escaping: it must never be interpreted
    # as format placeholders by jax.debug.print
    prefix = (message + "shape=" + str(tuple(X.shape))) \
        .replace("{", "{{").replace("}", "}}")
    jax.debug.print(prefix + " {x}", x=shown)
    return {"Out": X}


@register_op("load")
def _load(ctx):
    """Load one np.save'd array (reference load_op.cc). The file is read at
    trace time and baked into the compiled step as a constant — re-run the
    startup/load program to pick up a changed file (same contract as the
    reference: load runs when its program runs)."""
    import numpy as _np
    path = ctx.attr("file_path")
    if not path.endswith(".npy"):
        try:
            arr = _np.load(path)
        except FileNotFoundError:
            arr = _np.load(path + ".npy")
    else:
        arr = _np.load(path)
    if ctx.attr("load_as_fp16"):
        arr = arr.astype(_np.float16)
    return {"Out": jnp.asarray(arr)}
