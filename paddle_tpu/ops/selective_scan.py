"""The selective scan of a Mamba-1 layer (Gu & Dao 2023, arXiv:2312.00752):
the recurrence whose decay is per channel AND per state, which the chunked
dual form of `state_space.py` (one scalar decay a head) cannot write.

    dt = softplus(dt_raw + dt_bias) [T, channels];  A = -exp(A_log)
                                                    [channels, N]
    per channel c and state n, float32, S_0 = 0:
      S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
      y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]

Sixteen different decays a channel, so there is no `[chunk, chunk]` product
to hand a matrix unit: the work is `T x channels x N` multiply-adds and as
many exponentials, on the vector and transcendental units. At 4096 x 5120 x
16 the states of one sequence are 1.34 GB of float32: no form here ever
holds `[T, channels, N]`.

Two forms, one op (`selective_scan`; `_plan` reads the shape alone):

`scan_plain`   (any shape; the CPU tests, a shape off the plan) a `lax.scan`
    over chunks of `chunk` tokens that carries the state `[B, channels, N]`;
    inside a chunk an associative scan over its tokens on `[B, chunk,
    channels, N]`, every decay <= 1 so nothing overflows. The chunk's body
    is under `jax.checkpoint`: its vjp keeps the state each chunk started
    from and computes a chunk again on the way back.

`sscan_fwd` / `sscan_bwd`   two Pallas kernels on a grid of (batch, chunk,
    channel block), the last two axes sequential, where `_plan` says
    "kernel": whole chunks of 128 tokens, channels in whole 128-lane tiles
    (blocks of 512, 256 or 128 lanes), a state of whole sublane tiles. The
    state of ALL channels lies `[blocks, N, block]` float32 in VMEM scratch
    across a sequence's chunks (327 KB at 5120 x 16), N on the sublanes and
    channels on the lanes; a grid step runs its 128 tokens one after the
    other on a `[N, block]` tile in registers. The channel block is the
    INNER axis so that what every block of a chunk reads alike, B and C,
    is fetched once a chunk, and what every block adds to, dB and dC, is
    written once: B and C arrive spread over 128 lanes (`[B, T, N, 128]`
    float32, one XLA broadcast, 33.5 MB each at the cell's sizes) because a
    token's `[N]` has to multiply a tile whose LANES are channels, and
    Mosaic has no cheap move of a lane vector onto sublanes inside the loop;
    dB and dC leave the kernel as per-lane partial sums of the same shape
    and one XLA sum over the lanes finishes them.
    `sscan_fwd` writes y and `States`, the state each chunk found (`[T /
    128, B, N, channels]` float32: 10.5 MB a layer at the cell's sizes).
    `sscan_bwd` takes the chunks last to first: it runs a chunk forward
    again from its saved state, keeping the 128 states in VMEM (4 MB at a
    block of 512), then backward with H, the gradient the later tokens hand
    the state, carried in scratch as the state is; it writes dx, d dt, the
    partials of dB and dC, and dA summed over tokens in scratch.

Float32 throughout, whatever flows in: the op is on AMP_F32_OPS, so a bf16 x,
dt_raw, B or C is widened before the rule sees it, and y leaves float32. The
op notes the form it ran in (`selective_scan_plan`: "kernel" | "plain") and
tallies the grid steps of its kernel calls (`selective_scan_grid_steps`:
batch x chunks x channel blocks, forward and backward summed) on the compile
event.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import (amp_cast, call_rule, get_op_def, register_grad,
                             register_op)
from . import _kernels

_CHUNK = 128        # the tokens a grid step of the kernels takes
_LANES = 128
_BLOCKS = (512, 256, 128)   # channel blocks, the widest that divides first
# the widest channel block both kernels take (alone on the chip, a layer at
# 4096 x 5120 x 16: sscan_fwd 1.78 ms and sscan_bwd 3.83 at 512, 1.91 and 3.97
# at 256, 2.13 and 4.45 at 128: my chip run, PR 73, tools/sscan_probe.py)
_WIDEST = _BLOCKS[0]
_VMEM_LIMIT = 48 * 1024 * 1024


def _block(channels, widest=_BLOCKS[0]):
    for lanes in _BLOCKS:
        if lanes <= widest and channels % lanes == 0:
            return lanes
    return None


def _plan(T, channels, N):
    """"kernel": whole chunks of 128 tokens, channels in whole 128-lane
    tiles, a state of whole sublane tiles (the published 5120 x 16 at 4096
    tokens). "plain": anything else (the small widths of the CPU tests). The
    choice reads the shape alone."""
    if T % _CHUNK == 0 and _block(channels) and N % 8 == 0:
        return "kernel"
    return "plain"


def _kernels_run(T, channels, N):
    return _plan(T, channels, N) == "kernel" \
        and _kernels.backend_takes_kernels()


def gates(DtRaw, DtBias, ALog):
    """dt = softplus(dt_raw + dt_bias) `[B, T, channels]` and A =
    -exp(A_log) `[channels, N]`, float32. No clamp on dt."""
    f32 = jnp.float32
    return (jax.nn.softplus(DtRaw.astype(f32) + DtBias.astype(f32)),
            -jnp.exp(ALog.astype(f32)))


# ---------------------------------------------------------------------------
# the plain chunked form
# ---------------------------------------------------------------------------

def _combine(first, then):
    a1, b1 = first
    a2, b2 = then
    return a1 * a2, a2 * b1 + b2


def scan_plain(x, dt, A, Bm, Cm, D, chunk=_CHUNK):
    """x, dt `[B, T, channels]`, A `[channels, N]`, Bm, Cm `[B, T, N]`, D
    `[channels]`, all float32 -> y `[B, T, channels]` (module docstring).
    `chunk` is the most tokens whose states exist at once; a T that is no
    multiple of it takes the largest divisor of T under it."""
    B, T, channels = x.shape
    N = A.shape[1]
    chunk = max(c for c in range(1, min(chunk, T) + 1) if T % c == 0)
    n = T // chunk

    @jax.checkpoint
    def body(S, inputs):
        x_c, dt_c, b_c, c_c = inputs                    # [B, chunk, ...]
        decay = jnp.exp(dt_c[..., None] * A)            # [B, chunk, ch, N]
        wrote = (dt_c * x_c)[..., None] * b_c[:, :, None, :]
        decayed, grown = lax.associative_scan(_combine, (decay, wrote),
                                              axis=1)
        states = decayed * S[:, None] + grown
        y = jnp.einsum("btcn,btn->btc", states, c_c,
                       precision=lax.Precision.HIGHEST)
        return states[:, -1], y

    def by_chunk(v):        # [B, T, ...] -> [n, B, chunk, ...]
        return jnp.moveaxis(v.reshape((B, n, chunk) + v.shape[2:]), 1, 0)

    _, y = lax.scan(body, jnp.zeros((B, channels, N), jnp.float32),
                    tuple(by_chunk(v) for v in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1).reshape(B, T, channels) + D * x


# ---------------------------------------------------------------------------
# the two Pallas kernels (module docstring: what stays in VMEM)
# ---------------------------------------------------------------------------

def _wide(tile, reps):
    """`[N, 128]` (a token's B or C, the same in every lane) -> `[N, reps *
    128]`."""
    return tile if reps == 1 else jnp.concatenate([tile] * reps, axis=1)


def _fold(tile, reps):
    """`[N, reps * 128]` -> `[N, 128]`: the lane tiles added."""
    out = tile[:, :_LANES]
    for j in range(1, reps):
        out = out + tile[:, j * _LANES:(j + 1) * _LANES]
    return out


def _over_states(tile):
    return jnp.sum(tile, axis=0, keepdims=True)         # [N, cb] -> [1, cb]


_ROWS = 8       # tokens a pass of the loop takes: one sublane tile of rows


def _rows_of(ref, g):
    """Rows `8 g .. 8 g + 7` of a `[1, chunk, lanes]` block, float32."""
    from jax.experimental import pallas as pl
    return ref[0, pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS), :] \
        .astype(jnp.float32)


def _put_row(rows, j, row):
    """`rows [8, lanes]` with row j replaced by `row [1, lanes]`."""
    sub = lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    return jnp.where(sub == j, row, rows)


def _sscan_fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, states_ref,
                      y_ref, s_sc):
    """One (batch, chunk, channel block) step: the block's state written as
    the chunk found it, the chunk's 128 tokens one after the other (eight a
    pass of the loop: a sublane tile of x, dt and y rows read and written
    whole), the state moved on in scratch."""
    from jax.experimental import pallas as pl

    k = pl.program_id(2)
    chunk, lanes = x_ref.shape[1], x_ref.shape[2]
    reps = lanes // _LANES

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_sc[k] = jnp.zeros(s_sc.shape[1:], jnp.float32)

    A = a_ref[...]                                      # [N, cb]
    skip = d_ref[...]                                   # [1, cb]
    S0 = s_sc[k]
    states_ref[0, 0] = S0

    def tokens(g, S):
        dt8, x8 = _rows_of(dt_ref, g), _rows_of(x_ref, g)
        y8 = skip * x8
        for j in range(_ROWS):
            t = g * _ROWS + j
            dt_t, x_t = dt8[j:j + 1], x8[j:j + 1]       # [1, cb]
            S = jnp.exp(dt_t * A) * S \
                + (dt_t * x_t) * _wide(b_ref[0, t], reps)
            y8 = y8 + _put_row(jnp.zeros_like(y8), j,
                               _over_states(S * _wide(c_ref[0, t], reps)))
        y_ref[0, pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS), :] = \
            y8.astype(y_ref.dtype)
        return S

    s_sc[k] = lax.fori_loop(0, chunk // _ROWS, tokens, S0)


def _sscan_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, states_ref,
                      dy_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref, h_sc,
                      da_sc, hist_sc):
    """The same step with the chunks taken last to first. The chunk runs
    forward again from its saved state, each token's state BEFORE its update
    kept in `hist_sc`; then backward, with G_t = dy_t C_t + H the gradient
    of S_t and H = exp(dt_{t+1} A) G_{t+1} what the later tokens hand it
    (carried in scratch across chunks, as dA's sum is):
        dC_t = sum_c dy_t S_t        dB_t = sum_c G_t dt_t x_t   (per lane
                                     here, summed over lanes outside)
        d(dt x)_t = sum_n G_t B_t    d(dt A)_t = G_t S_{t-1} exp(dt_t A)
        d dt_t = d(dt x)_t x_t + sum_n d(dt A)_t A
        dA += d(dt A)_t dt_t         dx_t = d(dt x)_t dt_t + D dy_t"""
    from jax.experimental import pallas as pl

    k = pl.program_id(2)
    chunk, lanes = x_ref.shape[1], x_ref.shape[2]
    reps = lanes // _LANES
    groups = chunk // _ROWS
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_sc[k] = jnp.zeros(h_sc.shape[1:], f32)
        da_sc[k] = jnp.zeros(da_sc.shape[1:], f32)

    @pl.when(k == 0)
    def _first_block():
        db_ref[...] = jnp.zeros(db_ref.shape, f32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, f32)

    A = a_ref[...]
    skip = d_ref[...]

    def again(g, S):
        dt8, x8 = _rows_of(dt_ref, g), _rows_of(x_ref, g)
        for j in range(_ROWS):
            t = g * _ROWS + j
            hist_sc[t] = S
            dt_t, x_t = dt8[j:j + 1], x8[j:j + 1]
            S = jnp.exp(dt_t * A) * S \
                + (dt_t * x_t) * _wide(b_ref[0, t], reps)
        return S

    lax.fori_loop(0, groups, again, states_ref[0, 0])

    def tokens(i, carry):
        H, dA = carry
        g = groups - 1 - i
        dt8, x8 = _rows_of(dt_ref, g), _rows_of(x_ref, g)
        dy8 = _rows_of(dy_ref, g)
        d_wrote8 = jnp.zeros_like(x8)
        ddt8 = jnp.zeros_like(x8)
        for j in reversed(range(_ROWS)):
            t = g * _ROWS + j
            dt_t, x_t, dy_t = dt8[j:j + 1], x8[j:j + 1], dy8[j:j + 1]
            b_t = _wide(b_ref[0, t], reps)
            before = hist_sc[t]
            decay = jnp.exp(dt_t * A)
            wrote = dt_t * x_t
            G = dy_t * _wide(c_ref[0, t], reps) + H
            dc_ref[0, t] = dc_ref[0, t] + _fold(
                dy_t * (decay * before + wrote * b_t), reps)
            db_ref[0, t] = db_ref[0, t] + _fold(G * wrote, reps)
            through = G * before * decay
            d_wrote8 = _put_row(d_wrote8, j, _over_states(G * b_t))
            ddt8 = _put_row(ddt8, j, _over_states(through * A))
            H = decay * G
            dA = dA + through * dt_t
        at = pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS)
        ddt_ref[0, at, :] = ddt8 + d_wrote8 * x8
        dx_ref[0, at, :] = (d_wrote8 * dt8 + skip * dy8).astype(dx_ref.dtype)
        return H, dA

    H, dA = lax.fori_loop(0, groups, tokens, (h_sc[k], da_sc[k]))
    h_sc[k] = H
    da_sc[k] = dA
    da_ref[0] = dA


def _grid(x, widest=_BLOCKS[0]):
    """(chunks, channel blocks, lanes a block) of the kernels' grid."""
    lanes = _block(x.shape[2], widest)
    return x.shape[1] // _CHUNK, x.shape[2] // lanes, lanes


def _spread(m):
    """B or C `[B, T, N]` -> `[B, T, N, 128]` float32, the same in every
    lane."""
    return jnp.broadcast_to(m.astype(jnp.float32)[..., None],
                            m.shape + (_LANES,))


def _sscan_call(kernel, name, x, dt, A, Bm, Cm, D, more, out_shape,
                out_blocks, scratch, reverse, widest):
    """Both kernels' grid and blocks: (batch, chunk, channel block), the
    channel block innermost; the backward's index maps take the chunks last
    to first."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, channels = x.shape
    N = A.shape[1]
    n, K, lanes = _grid(x, widest)

    def at(c):
        return n - 1 - c if reverse else c

    blocks = {
        "x": pl.BlockSpec((1, _CHUNK, lanes), lambda b, c, k: (b, at(c), k)),
        "a": pl.BlockSpec((N, lanes), lambda b, c, k: (0, k)),
        "bc": pl.BlockSpec((1, _CHUNK, N, _LANES),
                           lambda b, c, k: (b, at(c), 0, 0)),
        "d": pl.BlockSpec((1, lanes), lambda b, c, k: (0, k)),
        "states": pl.BlockSpec((1, 1, N, lanes),
                               lambda b, c, k: (at(c), b, 0, k)),
        "da": pl.BlockSpec((1, N, lanes), lambda b, c, k: (b, 0, k))}
    ins = ["x", "x", "a", "bc", "bc", "d"] + [kind for kind, _ in more]
    f32 = jnp.float32
    return pl.pallas_call(
        kernel, name=name, grid=(B, n, K),
        in_specs=[blocks[kind] for kind in ins],
        out_specs=[blocks[kind] for kind in out_blocks], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(shape, f32) for shape in scratch(K, N,
                                                                    lanes)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_kernels.interpret(),
    )(x, dt, A.T, _spread(Bm), _spread(Cm),
      D.astype(f32).reshape(1, channels), *[v for _, v in more])


def _sscan_forward(x, dt, A, Bm, Cm, D, widest=_WIDEST):
    """x, dt `[B, T, channels]`, A `[channels, N]`, Bm, Cm `[B, T, N]`, D
    `[channels]` -> y in x's shape, float32, and the states `[T / 128, B, N,
    channels]` float32, each as its chunk found it."""
    B, T, channels = x.shape
    N = A.shape[1]
    f32 = jnp.float32
    states, y = _sscan_call(
        _sscan_fwd_kernel, "sscan_fwd", x, dt, A, Bm, Cm, D, [],
        (jax.ShapeDtypeStruct((T // _CHUNK, B, N, channels), f32),
         jax.ShapeDtypeStruct((B, T, channels), f32)),
        ["states", "x"], lambda K, N, lanes: [(K, N, lanes)],
        reverse=False, widest=widest)
    return y, states


def _sscan_backward(x, dt, A, Bm, Cm, D, states, d_out, widest=_WIDEST):
    """(dx, d dt, dA, dB, dC, dD), each in its input's shape, float32."""
    B, T, channels = x.shape
    N = A.shape[1]
    f32 = jnp.float32
    per_token = jax.ShapeDtypeStruct((B, T, channels), f32)
    per_lane = jax.ShapeDtypeStruct((B, T, N, _LANES), f32)
    d_out = d_out.astype(f32)
    dx, ddt, db, dc, da = _sscan_call(
        _sscan_bwd_kernel, "sscan_bwd", x, dt, A, Bm, Cm, D,
        [("states", states), ("x", d_out)],
        (per_token, per_token, per_lane, per_lane,
         jax.ShapeDtypeStruct((B, N, channels), f32)),
        ["x", "x", "bc", "bc", "da"],
        lambda K, N, lanes: [(K, N, lanes), (K, N, lanes),
                             (_CHUNK, N, lanes)],
        reverse=True, widest=widest)
    return (dx, ddt, da.sum(0).T, db.sum(-1), dc.sum(-1),
            jnp.sum(d_out * x.astype(f32), axis=(0, 1)))


# ---------------------------------------------------------------------------
# the op and its grad
# ---------------------------------------------------------------------------

_SLOTS = ("X", "DtRaw", "DtBias", "ALog", "B", "C", "D")


def _check(X, DtRaw, ALog, B, C):
    if X.ndim != 3 or DtRaw.shape != X.shape \
            or ALog.shape[0] != X.shape[2] \
            or B.shape != X.shape[:2] + ALog.shape[1:] or C.shape != B.shape:
        raise ValueError(
            f"selective_scan takes X and DtRaw [B, T, channels], ALog "
            f"[channels, N] and B, C [B, T, N], got X {X.shape}, DtRaw "
            f"{DtRaw.shape}, ALog {ALog.shape}, B {B.shape}, C {C.shape}")


def _tally_grid(ctx, X):
    n, K, _ = _grid(X, _WIDEST)
    ctx.tally("selective_scan_grid_steps", X.shape[0] * n * K)


def _selective_scan_infer(ctx, structs):
    """Build-time shapes without a trace of the scan. `States` is declared
    as the chip's kernels write it where the plan takes the shape: a machine
    with no TPU runs the plain form, which saves none, and the program it
    builds may run on one that has."""
    X, ALog = structs["X"][0], structs["ALog"][0]
    B, T, channels = X.shape
    steps = T // _CHUNK if _plan(T, channels, ALog.shape[1]) == "kernel" \
        else 1
    return {"Out": jax.ShapeDtypeStruct(X.shape, jnp.float32),
            "States": jax.ShapeDtypeStruct(
                (steps, B, ALog.shape[1], channels), jnp.float32)}


@register_op("selective_scan", infer=_selective_scan_infer,
             propagate_seqlen=False)
def _selective_scan(ctx, X, DtRaw, DtBias, ALog, B, C, D):
    """X, DtRaw [B, T, channels], DtBias, D [channels], ALog [channels, N],
    B, C [B, T, N] -> Out [B, T, channels] float32 (AMP_F32_OPS). On the
    kernel path (`_plan`) also `States` [T / 128, B, N, channels] float32,
    the state each chunk of 128 tokens started from, which the grad op reads
    back."""
    _check(X, DtRaw, ALog, B, C)
    f32 = jnp.float32
    dt, A = gates(DtRaw, DtBias, ALog)
    kernels = _kernels_run(X.shape[1], X.shape[2], ALog.shape[1])
    ctx.note(selective_scan_plan="kernel" if kernels else "plain")
    if kernels:
        _tally_grid(ctx, X)
        out, states = _sscan_forward(X.astype(f32), dt, A, B, C, D)
        return {"Out": out, "States": states}
    return {"Out": scan_plain(X.astype(f32), dt, A, B.astype(f32),
                              C.astype(f32), D.astype(f32),
                              int(ctx.attr("chunk", _CHUNK)))}


@register_grad("selective_scan")
def _selective_scan_grad(ctx, ins, out_grads):
    """The seven input gradients. Where the forward op saved its `States`
    the backward kernel runs alone on them, and dt's and A's gradients go
    through the vjp of `gates`; where it saved none (the plain form) the op
    is traced again under `jax.vjp`, whose checkpointed chunks keep a state
    a chunk."""
    d_out = out_grads["Out"][0]
    if d_out is None:
        return {}
    opdef = get_op_def("selective_scan")
    raw = [ins[s][0] for s in _SLOTS]
    states = ctx.fwd_outs.get("States", [None])[0]
    if states is None:
        out, vjp = jax.vjp(
            lambda *xs: call_rule(opdef, ctx, {s: [x] for s, x
                                               in zip(_SLOTS, xs)})["Out"][0],
            *raw)
        grads = vjp(d_out.astype(out.dtype))
    else:
        X, DtRaw, DtBias, ALog, B, C, D = [
            v[0] for v in (amp_cast(opdef, ctx, {s: [x] for s, x
                                                 in zip(_SLOTS, raw)})[s]
                           for s in _SLOTS)]
        _tally_grid(ctx, X)
        (dt, A), gates_vjp = jax.vjp(gates, DtRaw, DtBias, ALog)
        dx, ddt, dA, dB, dC, dD = _sscan_backward(X, dt, A, B, C, D, states,
                                                  d_out)
        d_raw, d_bias, d_alog = gates_vjp((ddt, dA))
        grads = (dx, d_raw, d_bias, d_alog, dB, dC, dD)
    return {s: d.astype(x.dtype) for s, d, x in zip(_SLOTS, grads, raw)}
