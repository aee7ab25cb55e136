"""The selective scan of a Mamba-2 layer in its chunked (state-space-dual)
form (Dao & Gu 2024, arXiv:2405.21060; the mixer of `nemotron_h`), and the
small op in front of it.

    ssd_gates:  dt = softplus(dt_raw + dt_bias) > 0 (a head's step size),
                a = -exp(A_log) * dt <= 0 (the log of its decay), float32
    ssd_scan:   per head a state S [head_dim, state] float32, S_0 = 0:
                  S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T
                  y_t = S_t C_t + D x_t
                head h reads B and C of group h // (heads / groups)

`ssd_scan` computes the recurrence in chunks of `chunk` tokens. Inside a
chunk, with L the running sum of a (L_t <= 0, falling) and S_in the state
the chunk starts from:

    y_t   = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s x_s
            + exp(L_t) S_in C_t + D x_t
    S_out = exp(L_last) S_in + sum_s exp(L_last - L_s) dt_s x_s B_s^T

Every exponent is <= 0, so nothing overflows however negative a is. `C B^T`
is one `[chunk, chunk]` tile a group, shared by the group's heads.

Where a chunk's tiles fill vregs (`_plan`: a chunk attribute of whole
128-token steps, a state of whole 128-lane tiles, head dims that pair up to
whole tiles, a group's heads an even number of at most 8 or whole blocks of
8: the published heads of 64 over a state of 128) the scan is two Pallas
kernels on `linear_attention._gdn_call`'s plan, a grid of (batch, head block,
chunk), the last axis sequential (`_grid`). A head block is a group's heads,
or 8 of them where the group has more: block k reads B's and C's tiles of
group k // (blocks a group), so a group's `[chunk, state]` tiles are read by
each of its blocks. The kernels' chunk is 128 tokens whatever whole number of
them the op's attribute is: the recurrence does not depend on the chunk, and
L, the decay tiles and the saved states are the kernels' own. A grid step
takes the block's heads together: x, y and their gradients as `[chunk, heads
* head_dim]` tiles (512 lanes), the state of all its heads stacked `[heads *
head_dim, state]`, so the products that read or write the state (`C S^T`, `(x
w)^T B` and their transposes) are one MXU product a step for the whole
block, and only the `[chunk, chunk]` decay tile and its two products are
made head by head (two heads side by side in a 128-lane tile).

    ssd_fwd   reads the step's x, B, C (as they arrive: bf16 under AMP), L
              and dt (float32, as rows `[heads, chunk]` and as columns
              `[chunk, heads]`: both small) and D spread over its head's
              lanes; keeps S [heads * head_dim, state] float32 in VMEM
              scratch across a sequence's chunks; makes C B^T, the decay
              tiles and every product in VMEM and writes none of them: only
              y and `States`, S as each chunk found it (float32 [T / 128,
              B, H, head_dim, state]).
    ssd_bwd   the chunks last to first, dS (the gradient of the state a
              chunk hands on) float32 in scratch; computes C B^T, the decay
              tiles and `C S^T` again from the chunk's inputs and its saved
              state; writes dx, dB, dC (summed over the block's heads), the
              gradient of dt and of L per token, and a step's part of dD;
              L's reverse running sum inside a chunk (one small XLA op, like
              the running sum itself) is a's gradient. dB and dC are sums
              over ALL of a group's heads: a group of one block writes them
              in B's dtype; a group of several writes each block's part in
              float32 (`[B, T, blocks * state]`) and one XLA op adds a
              group's parts before the cast, so every sum stays float32
              until its last add.

Between forward and backward nothing of size `[T, T]` or `[chunks, heads,
chunk, chunk]` is kept: the saved states are all. The op and its grad op
tally the grid steps of their calls on the compile event (`ssd_grid_steps`:
batch x head blocks x chunks of 128, summed).

Float32 whatever dtype flows through: dt, a, L, the decays, the state and
dS, every accumulator and every product's result. The products take the
backend's DEFAULT for float32 operands (`linear_attention._dot`: on the chip
the operands rounded to bf16, one pass into a float32 accumulator, as XLA's
default does there; float32 under the interpreter on a CPU).

Which published shapes the plan takes, both at x `[1, 2048, 64, 64]` over a
state of 128 in their cells: Nemotron-3-Nano's (`nemotron_h`: 8 groups of 8
heads, chunk 128) is a head block a group at the attribute's chunk, the grid
(1, 8, 16), B's and C's gradients written by the kernel in bf16.
Granite 4.0-H's (`granite_hybrid`: ONE group of 64 heads, chunk 256) is eight
blocks of 8 heads that all read group 0's B and C, in steps of 128 tokens
under the attribute of 256: the same grid (1, 8, 16), `States` `[16, 1, 64,
64, 128]` (33.5 MB a layer), dB's and dC's parts `[1, 2048, 1024]` float32 (8
MB each a layer) summed over the eight blocks outside. A grid step that held
all 64 heads at chunk 256 would hold `[256, 4096]` blocks of x, y and their
gradients beside 2 MB each of state and dS; the XLA form at this shape
makes the `[chunks, heads, chunk, chunk]` float32 decay tiles in HBM (134 MB
a copy a layer). What the plan leaves to the XLA form: a group of more than
8 heads that is no whole blocks of 8 (12, 20), a chunk attribute that is no
whole steps of 128 (64, 192), head dims other than 64.

Outside the envelope (those, the small head dims of the CPU tests), and
on a CPU backend unless the Pallas interpreter is asked for
(`PADDLE_TPU_PALLAS_INTERPRET=1`), the op keeps the XLA form `chunked_ssd`:
the decay tiles and both in-chunk products for all chunks at once, a
`lax.scan` over the chunks' states, then the states' part of y for all
chunks at once. The grad op is registered (`ssd_scan_grad`): on the saved
`States` it runs `ssd_bwd` alone; where the forward saved none it is
`jax.vjp` of the XLA form. x, B, C arrive in bf16 under AMP; neither op is
on an AMP list but `ssd_gates`, which is on AMP_F32_OPS so that `dt_raw` is
widened before the softplus.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import call_rule, get_op_def, register_grad, register_op
from . import _kernels
from ._kernels import _NN, _NT, _TN, _cols, _dot, _rows, _running_sum


@register_op("ssd_gates")
def _ssd_gates(ctx, DtRaw, DtBias, ALog):
    """DtRaw [..., H] (a projection of the layer's input), DtBias, ALog [H]
    -> Dt = softplus(DtRaw + DtBias) and A = -exp(ALog) * Dt, both float32
    (AMP_F32_OPS). No clamp (`time_step_limit` (0, inf))."""
    dt = jax.nn.softplus(DtRaw.astype(jnp.float32)
                         + DtBias.astype(jnp.float32))
    return {"Dt": dt, "A": -jnp.exp(ALog.astype(jnp.float32)) * dt}


def chunked_ssd(x, dt, a, Bm, Cm, D, chunk):
    """x [B, T, H, P], dt, a [B, T, H], Bm, Cm [B, T, G, N], D [H], all
    float32, T a multiple of `chunk`, H of G -> y [B, T, H, P] float32
    (module docstring)."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    n, r = T // chunk, H // G
    x = x.reshape(B, n, chunk, G, r, P)
    Bm, Cm = (m.reshape(B, n, chunk, G, N) for m in (Bm, Cm))

    def heads(v):       # [B, T, H] -> [B, n, G, r, chunk]
        return jnp.moveaxis(v.reshape(B, n, chunk, G, r), 2, 4)

    dt, L = heads(dt), jnp.cumsum(heads(a), axis=-1)
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = row >= col
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, L[..., :, None] - L[..., None, :], 0.0)), 0.0)
    cb = jnp.einsum("bnigk,bnjgk->bngij", Cm, Bm)
    m = decay * cb[:, :, :, None] * dt[..., None, :]    # [B, n, G, r, C, C]
    y = jnp.einsum("bngrij,bnjgrp->bnigrp", m, x)
    last = L[..., -1]                                   # [B, n, G, r]
    tail = jnp.exp(last[..., None] - L) * dt
    grown = jnp.einsum("bngrs,bnsgrp,bnsgk->bngrpk", tail, x, Bm)

    def step(S, xs):
        grown_i, last_i = xs
        return S * jnp.exp(last_i)[..., None, None] + grown_i, S

    _, S_in = lax.scan(step, jnp.zeros((B, G, r, P, N), jnp.float32),
                       (jnp.moveaxis(grown, 1, 0), jnp.moveaxis(last, 1, 0)))
    S_in = jnp.moveaxis(S_in, 0, 1)                     # [B, n, G, r, P, N]
    y = y + jnp.einsum("bnigk,bngrpk->bnigrp", Cm, S_in) \
        * jnp.moveaxis(jnp.exp(L), 4, 2)[..., None]
    y = y + x * D.reshape(G, r, 1)
    return y.reshape(B, T, H, P)


# ---------------------------------------------------------------------------
# the two Pallas kernels (module docstring: what stays in VMEM, precisions)
# ---------------------------------------------------------------------------

_CHUNK = 128    # the tokens a grid step of the kernels takes
_HEADS = 8      # the most heads of a group it takes together (512 lanes)


def _plan(P, N, r, chunk):
    """"kernel": a chunk attribute of whole 128-token steps, a state of
    whole 128-lane tiles and a group whose heads pair up into whole tiles
    (two heads of 64 side by side) and come as one block of at most 8 or as
    whole blocks of 8 (Nemotron-3-Nano's 8 heads a group at chunk 128,
    Granite 4.0-H's 64 at chunk 256). "xla": anything else (the small head
    dims of the CPU tests, a chunk of 64, 12 heads a group), which keeps
    `chunked_ssd` and its vjp. The choice reads the shape alone."""
    if chunk % _CHUNK == 0 and N % 128 == 0 and 2 * P == 128 \
            and r % 2 == 0 and (r <= _HEADS or r % _HEADS == 0):
        return "kernel"
    return "xla"


def _kernels_run(P, N, r, chunk):
    return _plan(P, N, r, chunk) == "kernel" \
        and _kernels.backend_takes_kernels()


def _grid(X, Bm, chunk):
    """(head blocks, heads a block, tokens a step) of the kernels' grid at
    the op's `chunk` attribute: a group's heads in blocks of at most
    `_HEADS`, steps of `_CHUNK` tokens whatever whole number of them the
    attribute is (the recurrence does not depend on the chunk). A state is
    saved every step. Off the plan, where the XLA form runs and saves none,
    the attribute's chunk."""
    H, r = X.shape[2], X.shape[2] // Bm.shape[2]
    if _plan(X.shape[3], Bm.shape[3], r, chunk) != "kernel":
        return Bm.shape[2], r, chunk
    r = min(r, _HEADS)
    return H // r, r, _CHUNK


class _Step:
    """What both kernels compute of one (batch, head block, chunk) grid step
    before they part. The block's `r` heads lie side by side in the lanes
    of x (`[C, r P]`), two heads a 128-lane tile; a head's per-token values
    come as a column `[C, 1]` (of the `[C, r]` blocks) and as a row `[1, C]`
    (of the `[r, C]` blocks)."""

    def __init__(self, b_ref, c_ref, l_rows, l_cols, dt_rows, dt_cols, r, P):
        C = b_ref.shape[1]
        self.r, self.P, self.C = r, P, C
        self.B = b_ref[0].astype(jnp.float32)           # [C, N]
        self.Cm = c_ref[0].astype(jnp.float32)
        self.cb = _dot(self.Cm, self.B, _NT)            # C B^T [C, C]
        row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
        self.lower = row >= col
        self.left = lax.broadcasted_iota(jnp.int32, (C, 2 * P), 1) < P
        self.L_row = [l_rows[0, j:j + 1, :] for j in range(r)]      # [1, C]
        self.L_col = [l_cols[0, 0, :, j:j + 1] for j in range(r)]   # [C, 1]
        self.dt_row = [dt_rows[0, j:j + 1, :] for j in range(r)]
        self.dt_col = [dt_cols[0, 0, :, j:j + 1] for j in range(r)]
        self.last = [v[C - 1:C, :] for v in self.L_col]             # [1, 1]
        self.e_last = [jnp.exp(v) for v in self.last]
        # the same as rows over the state's lanes, [r, N]: Mosaic spreads
        # no [1, 1] both ways at once, so the lanes first, then the
        # exponential (which keeps the two apart), then a head's rows
        self.e_last_wide = jnp.exp(jnp.broadcast_to(
            l_rows[0][:, C - 1:C], (r, self.B.shape[1])))

    def decay(self, j):
        """Head j's `exp(L_t - L_s)` for s <= t, 0 above the diagonal (an
        exponent there may overflow; the select drops it)."""
        return jnp.where(self.lower,
                         jnp.exp(self.L_col[j] - self.L_row[j]), 0.0)

    def spread(self, columns):
        """A column `[C, 1]` a head -> `[C, r P]`, each over its head's
        lanes."""
        return jnp.concatenate(
            [jnp.where(self.left, columns[j], columns[j + 1])
             for j in range(0, self.r, 2)], axis=1)

    def pair(self, x, j):
        """The 128-lane tile of `x [C, r P]` that holds head j."""
        first = j // 2 * 2 * self.P
        return x[:, first:first + 2 * self.P]

    def mine(self, j):
        """Head j's lanes of its pair's tile."""
        return self.left if j % 2 == 0 else ~self.left

    def of_heads(self, x):
        """`[C, r P]` -> each head's row sums over its own lanes, `[C, 1]`
        a head."""
        sums = []
        for j in range(0, self.r, 2):
            tile = self.pair(x, j)
            first = _rows(jnp.where(self.left, tile, 0.0))
            sums += [first, _rows(tile) - first]
        return sums

    def last_decay(self):
        """`exp(L_last)` `[r P, N]`: each head's over its rows of the
        stacked state."""
        wide = self.e_last_wide
        return jnp.concatenate(
            [jnp.broadcast_to(wide[j:j + 1], (self.P, wide.shape[1]))
             for j in range(self.r)], axis=0)


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, l_rows, l_cols, dt_rows, dt_cols,
                    d_ref, states_ref, y_ref, s_sc, *, r, P):
    """One (batch, head block, chunk) step for the block's `r` heads: the state
    written as the chunk found it, the chunk's outputs, the state moved on
    in scratch."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_sc[...] = jnp.zeros_like(s_sc)

    st = _Step(b_ref, c_ref, l_rows, l_cols, dt_rows, dt_cols, r, P)
    x = x_ref[0].astype(jnp.float32)                    # [C, r P]
    S = s_sc[...]                                       # [r P, N]
    states_ref[0, 0, 0] = S
    inside = []
    for j in range(0, r, 2):
        tile = st.pair(x, j)
        inside.append(jnp.where(
            st.left,
            _dot(st.decay(j) * st.cb * st.dt_row[j], tile, _NN),
            _dot(st.decay(j + 1) * st.cb * st.dt_row[j + 1], tile, _NN)))
    y = jnp.concatenate(inside, axis=1) \
        + _dot(st.Cm, S, _NT) * st.spread([jnp.exp(v) for v in st.L_col]) \
        + x * d_ref[...]
    y_ref[0] = y.astype(y_ref.dtype)
    tail = st.spread([jnp.exp(st.last[j] - st.L_col[j]) * st.dt_col[j]
                      for j in range(r)])
    s_sc[...] = S * st.last_decay() + _dot(x * tail, st.B, _TN)


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, l_rows, l_cols, dt_rows, dt_cols,
                    d_ref, states_ref, dy_ref, dx_ref, db_ref, dc_ref,
                    dl_rows, dl_cols, ddt_rows, ddt_cols, dd_ref, ds_sc, *,
                    r, P):
    """The same step with the chunks taken last to first. dS, the gradient
    of the state the chunk hands on, is carried in scratch; the chunk's
    tiles are made again from its inputs and its saved state. With M = W o
    (C B^T) o dt (W the decay tile), y = M x + e_in (C S^T) + D x and S' =
    e_last S + (x tail)^T B. dL is the gradient of the running sum at each
    token (a's is its reverse running sum inside a chunk, taken outside);
    dL and ddt come out in two parts, what a tile's row sums give as
    columns `[C, r]` and what its column sums give as rows `[r, C]`, added
    outside."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_sc[...] = jnp.zeros_like(ds_sc)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    st = _Step(b_ref, c_ref, l_rows, l_cols, dt_rows, dt_cols, r, P)
    C = st.C
    x = x_ref[0].astype(jnp.float32)                    # [C, r P]
    dy = dy_ref[0].astype(jnp.float32)
    S = states_ref[0, 0, 0]                             # [r P, N]: S_in
    dS = ds_sc[...]                                     # of S_out
    e_in = st.spread([jnp.exp(v) for v in st.L_col])
    e_tail = [jnp.exp(st.last[j] - st.L_col[j]) for j in range(r)]
    tail = st.spread([e_tail[j] * st.dt_col[j] for j in range(r)])
    from_state = _dot(st.Cm, S, _NT) * e_in             # its part of y
    to_state = _dot(st.B, dS, _NT)                      # d(x tail) [C, r P]
    dz = dy * e_in
    through_in = st.of_heads(dy * from_state)           # dL through e_in
    through_tail = st.of_heads(to_state * x)            # d tail
    last_row = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    kept = S * dS                                       # dL_last through e_last
    at_end = [_rows(_cols(kept[j * P:(j + 1) * P])) for j in range(r)]
    lane = lax.broadcasted_iota(jnp.int32, (C, r), 1)
    sub = lax.broadcasted_iota(jnp.int32, (r, C), 0)
    dl_c = jnp.zeros((C, r), jnp.float32)
    ddt_c = jnp.zeros((C, r), jnp.float32)
    dl_r = jnp.zeros((r, C), jnp.float32)
    ddt_r = jnp.zeros((r, C), jnp.float32)
    inside, dcb = [], 0.0
    for j in range(r):
        w = st.decay(j)
        wcb = w * st.cb
        m = wcb * st.dt_row[j]
        dy_j = jnp.where(st.mine(j), st.pair(dy, j), 0.0)
        dm = _dot(dy_j, st.pair(x, j), _NT)             # dy_j x_j^T [C, C]
        part = _dot(m, dy_j, _TN)                       # M^T dy_j: j's lanes
        if j % 2 == 0:
            inside.append(part)
        else:
            inside[-1] = inside[-1] + part
        dcb = dcb + dm * w * st.dt_row[j]
        h = dm * wcb
        g = h * st.dt_row[j]
        moved = through_tail[j] * e_tail[j]             # [C, 1]
        ends = (at_end[j] * st.e_last[j]
                + _cols(moved * st.dt_col[j]))          # [1, 1]: dL_last
        dl_c = jnp.where(lane == j, _rows(g) + through_in[j]
                         - moved * st.dt_col[j]
                         + jnp.where(last_row, ends, 0.0), dl_c)
        ddt_c = jnp.where(lane == j, moved, ddt_c)
        dl_r = jnp.where(sub == j, -_cols(g), dl_r)
        ddt_r = jnp.where(sub == j, _cols(h), ddt_r)
    dx = jnp.concatenate(inside, axis=1) + dy * d_ref[...] + to_state * tail
    dx_ref[0] = dx.astype(dx_ref.dtype)
    dc_ref[0] = (_dot(dcb, st.B, _NN) + _dot(dz, S, _NN)).astype(dc_ref.dtype)
    db_ref[0] = (_dot(dcb, st.Cm, _TN)
                 + _dot(x * tail, dS, _NN)).astype(db_ref.dtype)
    dl_cols[0, 0] = dl_c
    ddt_cols[0, 0] = ddt_c
    dl_rows[0] = dl_r
    ddt_rows[0] = ddt_r
    dd_ref[0] += _cols(dy * x)
    ds_sc[...] = dS * st.last_decay() + _dot(dz, st.Cm, _TN)


def _ssd_call(kernel, name, X, Dt, A, Bm, Cm, D, more, out_shape, out_blocks,
              chunk, reverse):
    """Both kernels' grid and blocks (`_grid`): (batch, head block, chunk),
    the last axis sequential. A head block is a group's heads, or `_HEADS`
    of them where it has more: block k reads B and C of group k // (blocks a
    group), so a group's tiles are read by each of its blocks. x and its
    like are read where they lie, as `[B, T, H * P]` with a block's heads'
    lanes chosen by the block index; B and C as `[B, T, G * N]`; L (a's
    running sum inside a chunk) and dt twice, as `[B, H, T]` (a head's
    tokens a row) and as `[B, blocks, T, heads a block]` (a column): small
    float32 arrays that XLA lays out; D spread over its head's lanes. "part"
    is a block's share of dB or dC, `[B, T, blocks * N]`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, P = X.shape
    G, N = Bm.shape[2], Bm.shape[3]
    K, r, chunk = _grid(X, Bm, chunk)
    n, per_group = T // chunk, K // G

    def at(c):                          # the chunk a grid step works on
        return n - 1 - c if reverse else c

    def group(k):                       # of head block k
        return k if per_group == 1 else k // per_group

    blocks = {
        "x": pl.BlockSpec((1, chunk, r * P), lambda b, k, c: (b, at(c), k)),
        "bc": pl.BlockSpec((1, chunk, N),
                           lambda b, k, c: (b, at(c), group(k))),
        "part": pl.BlockSpec((1, chunk, N), lambda b, k, c: (b, at(c), k)),
        "rows": pl.BlockSpec((1, r, chunk), lambda b, k, c: (b, k, at(c))),
        "cols": pl.BlockSpec((1, 1, chunk, r),
                             lambda b, k, c: (b, k, at(c), 0)),
        "skip": pl.BlockSpec((1, r * P), lambda b, k, c: (0, k)),
        "states": pl.BlockSpec((1, 1, 1, r * P, N),
                               lambda b, k, c: (at(c), b, k, 0, 0)),
        "skip_sum": pl.BlockSpec((1, 1, r * P), lambda b, k, c: (b, 0, k))}
    L = _running_sum(A, chunk)
    dt = Dt.astype(jnp.float32)

    def rows(v):        # [B, T, H] -> [B, H, T]
        return jnp.swapaxes(v, 1, 2)

    def cols(v):        # [B, T, H] -> [B, K, T, r]
        return jnp.swapaxes(v.reshape(B, T, K, r), 1, 2)

    skip = jnp.repeat(D.astype(jnp.float32), P).reshape(1, H * P)
    ins = ["x", "bc", "bc", "rows", "cols", "rows", "cols", "skip"] \
        + [k for k, _ in more]
    return pl.pallas_call(
        functools.partial(kernel, r=r, P=P), name=name, grid=(B, K, n),
        in_specs=[blocks[k] for k in ins],
        out_specs=[blocks[k] for k in out_blocks], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r * P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_kernels.interpret(),
    )(X.reshape(B, T, H * P), Bm.reshape(B, T, G * N),
      Cm.reshape(B, T, G * N), rows(L), cols(L), rows(dt), cols(dt), skip,
      *[v for _, v in more])


def _ssd_forward(X, Dt, A, Bm, Cm, D, chunk):
    """X [B, T, H, P] as it arrives, Dt, A [B, T, H], Bm, Cm [B, T, G, N],
    D [H], the op's `chunk` attribute -> out in X's shape and dtype and the
    states [steps, B, H, P, N] float32, each as its step of the grid
    (`_grid`) found it."""
    B, T, H, P = X.shape
    N = Bm.shape[3]
    K, r, step = _grid(X, Bm, chunk)
    states, out = _ssd_call(
        _ssd_fwd_kernel, "ssd_fwd", X, Dt, A, Bm, Cm, D, [],
        (jax.ShapeDtypeStruct((T // step, B, K, r * P, N), jnp.float32),
         jax.ShapeDtypeStruct((B, T, H * P), X.dtype)),
        ["states", "x"], chunk, reverse=False)
    return out.reshape(X.shape), states.reshape(T // step, B, H, P, N)


def _ssd_backward(X, Dt, A, Bm, Cm, D, states, d_out, chunk):
    """The six input gradients from the saved states and `d_out` [B, T, H,
    P], each in its input's shape (X's, B's and C's in their dtypes, the
    others float32). dB and dC are sums over all of a group's heads: where
    a group is several head blocks each writes its part in float32, and
    they are added here before the cast."""
    B, T, H, P = X.shape
    G, N = Bm.shape[2], Bm.shape[3]
    K, r, step = _grid(X, Bm, chunk)
    f32 = jnp.float32
    per_row = jax.ShapeDtypeStruct((B, H, T), f32)
    per_col = jax.ShapeDtypeStruct((B, K, T, r), f32)
    part = jax.ShapeDtypeStruct((B, T, K * N), Bm.dtype if K == G else f32)
    dx, db, dc, dl_r, dl_c, ddt_r, ddt_c, dd = _ssd_call(
        _ssd_bwd_kernel, "ssd_bwd", X, Dt, A, Bm, Cm, D,
        [("states", states.reshape(T // step, B, K, r * P, N)),
         ("x", d_out.astype(X.dtype).reshape(B, T, H * P))],
        (jax.ShapeDtypeStruct((B, T, H * P), X.dtype), part, part,
         per_row, per_col, per_row, per_col,
         jax.ShapeDtypeStruct((B, 1, H * P), f32)),
        ["x", "part", "part", "rows", "cols", "rows", "cols", "skip_sum"],
        chunk, reverse=True)

    def per_token(rows, cols):  # both parts -> [B, T, H]
        return jnp.swapaxes(rows, 1, 2) \
            + jnp.swapaxes(cols, 1, 2).reshape(B, T, H)

    def of_group(parts):        # [B, T, K * N] -> [B, T, G, N]
        if K > G:
            parts = parts.reshape(B, T, G, K // G, N).sum(3)
        return parts.reshape(B, T, G, N).astype(Bm.dtype)

    dL = per_token(dl_r, dl_c).reshape(B, T // step, step, H)
    dA = lax.cumsum(dL, axis=2, reverse=True).reshape(B, T, H)
    return (dx.reshape(X.shape), per_token(ddt_r, ddt_c), dA,
            of_group(db), of_group(dc), dd.reshape(B, H, P).sum((0, 2)))


# ---------------------------------------------------------------------------
# the op and its grad
# ---------------------------------------------------------------------------

def _check(X, Bm, chunk):
    T, H, G = X.shape[1], X.shape[2], Bm.shape[2]
    if T % chunk or H % G:
        raise ValueError(f"ssd_scan needs a length that is a multiple of the "
                         f"chunk ({chunk}) and heads that are a multiple of "
                         f"the groups, got T {T}, heads {H} and {G} groups")


def _states_shape(X, Bm, chunk):
    B, T, H, P = X.shape
    return jax.ShapeDtypeStruct((T // _grid(X, Bm, chunk)[2], B, H, P,
                                 Bm.shape[3]), jnp.float32)


def _tally_grid(ctx, X, Bm, chunk):
    """The grid steps this op's kernel call runs, onto the compile event
    (`ssd_grid_steps`, summed over the program's ops and grad ops)."""
    blocks, _, step = _grid(X, Bm, chunk)
    ctx.tally("ssd_grid_steps", X.shape[0] * blocks * (X.shape[1] // step))


def _ssd_scan_infer(ctx, structs):
    """Build-time shapes without a trace of the scan: a machine with no TPU
    takes the XLA form, which saves no `States`, and the program it builds
    may run on one that has. `States` is declared as the chip's kernels
    write it: a state every step of their grid (`_grid`) where the plan
    takes the shape, whatever the chunk attribute."""
    X, Bm = structs["X"][0], structs["B"][0]
    return {"Out": jax.ShapeDtypeStruct(X.shape, X.dtype),
            "States": _states_shape(X, Bm, int(ctx.attr("chunk", 128)))}


_SLOTS = ("X", "Dt", "A", "B", "C", "D")


@register_op("ssd_scan", infer=_ssd_scan_infer, propagate_seqlen=False)
def _ssd_scan(ctx, X, Dt, A, B, C, D):
    """X [B, T, H, P], Dt, A [B, T, H] (`ssd_gates`), B, C [B, T, G, N], D
    [H] -> Out [B, T, H, P] in X's dtype. H is a multiple of G: group g
    serves heads g * H/G .. (g + 1) * H/G - 1. T must be a multiple of
    `chunk`. On the kernel path (`_plan`) the scan also returns `States`
    [T / 128, B, H, P, N] float32, the state each of the kernels' chunks
    started from, which the grad op reads back."""
    chunk = int(ctx.attr("chunk", 128))
    _check(X, B, chunk)
    kernels = _kernels_run(X.shape[3], B.shape[3], X.shape[2] // B.shape[2],
                           chunk)
    ctx.note(ssd_plan="kernel" if kernels else "xla")
    if kernels:
        _tally_grid(ctx, X, B, chunk)
        out, states = _ssd_forward(X, Dt, A, B, C, D, chunk)
        return {"Out": out, "States": states}
    f32 = jnp.float32
    out = chunked_ssd(X.astype(f32), Dt.astype(f32), A.astype(f32),
                      B.astype(f32), C.astype(f32), D.astype(f32), chunk)
    return {"Out": out.astype(X.dtype)}


@register_grad("ssd_scan")
def _ssd_scan_grad(ctx, ins, out_grads):
    """The six input gradients. Where the forward op saved its `States` the
    backward kernel runs alone on them; where it saved none (the XLA form,
    a program built without the slot) the scan is traced again under
    `jax.vjp`, as the generic grad lowering would."""
    d_out = out_grads["Out"][0]
    if d_out is None:
        return {}
    raw = [ins[s][0] for s in _SLOTS]
    states = ctx.fwd_outs.get("States", [None])[0]
    if states is None:
        opdef = get_op_def("ssd_scan")
        out, vjp = jax.vjp(
            lambda *xs: call_rule(opdef, ctx, {s: [x] for s, x
                                               in zip(_SLOTS, xs)})["Out"][0],
            *raw)
        grads = vjp(d_out.astype(out.dtype))
    else:
        chunk = int(ctx.attr("chunk", 128))
        _tally_grid(ctx, raw[0], raw[3], chunk)
        grads = _ssd_backward(*raw, states, d_out, chunk)
    return {s: d.astype(x.dtype) for s, d, x in zip(_SLOTS, grads, raw)}
