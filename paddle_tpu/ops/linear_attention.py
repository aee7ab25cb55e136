"""Linear attention by the gated delta rule (Gated DeltaNet; the mixer of
three layers in four of `qwen3_next`), and the two small ops around it.

    causal_conv1d:      a depthwise convolution over time, then silu (two
                        Pallas kernels too: the end of this docstring)
    delta_rule_gates:   g = -exp(A_log) * softplus(a + dt_bias) <= 0 (the log
                        of a head's decay), beta = sigmoid(b)
    gated_delta_rule:   per value head a state S [key, value], S_0 = 0:
                          S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);
                          S <- S + k_t d^T;  o_t = S^T q_t

`gated_delta_rule` computes the recurrence in chunks of `chunk` tokens (the
WY / UT-transform form of the public `qwen3_next` code and of
flash-linear-attention). Inside a chunk, with G the running sum of g and
D[i, j] = exp(G_i - G_j) for i >= j:

    A = strict_lower((beta k) k^T * D);   T = (I + A)^-1  (unit lower
    triangular);   [u | w] = T [beta v | beta k exp(G)]
    v' = u - w S;   o = (q exp(G)) S + lower(q k^T * D) v'
    S <- S exp(G_last) + (k exp(G_last - G))^T v'

Every exponent is <= 0, so nothing overflows however negative g is.

Where a chunk's tiles fill vregs (`_plan`: chunk 64, both head dims
multiples of 128: the published widths) the rule is two Pallas kernels on a
grid of (batch, key head, chunk), the chunk axis sequential; a grid step
takes the key head's value heads in turn, so q, k and their Gram tiles
`k k^T`, `q k^T` are made once for them and no head is repeated in HBM.

    gdn_fwd   reads a chunk's q, k, v (as they arrive: bf16 under AMP), G and
              beta; keeps S [Dk, Dv] float32 in VMEM scratch across the
              chunks; makes the l2-norms, D, A, T, u, w, v' and the scores in
              VMEM and writes none of them: only o and `States`, S as each
              chunk found it (float32 [chunks, B, Hv, Dk, Dv]). o
              [B, T, Hv * Dv] is read where it lies by `gated_norm_fwd`
              (`ops/decoder_block.py`: the layer's output norm), whose
              backward `gated_norm_bwd` writes `gdn_bwd`'s dO the same way:
              no XLA op and no layout copy stands between the two pairs.
    gdn_bwd   the chunks last to first, dS [Dk, Dv] float32 in scratch;
              computes the chunk's factors again from its inputs and its
              saved state; with X = [u | w] = T R: dR = T^T dX,
              dA = -strict_lower(dR X^T); writes dv, dq and dk (summed over
              a key head's value heads, through the l2-norm), dbeta, and dG
              per token, whose reverse running sum inside a chunk (one
              small XLA op, like the running sum G itself) is g's gradient.

T comes from blocked forward substitution (`_unit_lower_inverse`): the
32-wide diagonal blocks row by row in float32 on the VPU, then merged pair
by pair; no series in A, which would lose digits where |A| is near 1.
Float32 whatever dtype flows through: g, beta, G, D, the l2-norms, A, T, the
state and dS, every accumulator and every product's result
(`preferred_element_type=float32` on every `dot`). `precision=HIGHEST`
(float32 operands, the MXU's float32 passes): `k k^T`, the merges of T,
`T [beta v | beta k exp(G)]`, and in the backward `T^T dX`, `dR X^T` and
dk's part through `k k^T`, as the XLA form has them. The others (`w S`,
`k^T v'`, `q S`, `q k^T`, the scores times v', and their transposes in the
backward) take the backend's DEFAULT for float32 operands (`_dot`: on the
chip the operands rounded to bf16, one pass, as XLA's default does there;
float32 under the interpreter on a CPU).

Outside the envelope (the tiny head dims of the CPU tests), and on a CPU
backend unless the Pallas interpreter is asked for
(`PADDLE_TPU_PALLAS_INTERPRET=1`), the op keeps the XLA form
`chunked_gated_delta_rule`: A, the solve, u and w for all chunks at once, a
`lax.scan` over the chunks' states, then the outputs for all chunks at once;
A and the solve at HIGHEST, the rest at the default precision. The grad op
is registered (`gated_delta_rule_grad`): on the saved `States` it runs
`gdn_bwd` alone; where the forward saved none it is `jax.vjp` of the XLA
form. q, k, v arrive in bf16 under AMP; none of the three ops is on an AMP
list but `delta_rule_gates`, which is on AMP_F32_OPS so that a and b are
widened before the softplus.

`causal_conv1d` makes one pass over its arrays each way where channels
fill lanes and tokens sublane tiles (`_conv_plan`: C a multiple of 128, T
of 16, at most 9 taps: the published 8192 channels, 4096 tokens, 4 taps),
as two more Pallas kernels; the widened tile, the shifted taps, the
pre-activation, silu and its derivative stay in VMEM:

    causal_conv_fwd   grid (batch, channel block, time block); reads a
                      block of X as it arrives and the 16 rows before it (a
                      second block of the same array; zeros at t = 0), W as
                      [K, Cb] float32; the K taps summed in float32, silu,
                      Out in X's dtype. Saves nothing.
    causal_conv_bwd   grid (channel block, batch, time block), the time
                      blocks last to first; reads X (with the rows before),
                      dOut and W; makes the pre-activation again, dpre =
                      dOut silu'(pre) in float32, dX[t] = sum_j W[j]
                      dpre[t + K-1-j] with dpre's first rows of the block
                      after carried in scratch (zeros after the end), and
                      dW [K, Cb] float32, resident while the batch and the
                      time blocks add to it.

Inside a time block both work on 64 rows at a time, so a loop step's tiles
stay in vregs. Elsewhere the op is the jnp form `_conv_xla` and the grad op
(`causal_conv1d_grad`, registered) its `jax.vjp`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import call_rule, get_op_def, register_grad, register_op
from .pallas_attention import _interpret


def _conv_xla(X, W, silu):
    """The convolution as plain jnp, and the form `jax.vjp` differentiates
    outside the kernels' envelope."""
    K = W.shape[1]
    T = X.shape[1]
    x32 = jnp.pad(X.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    w32 = W.astype(jnp.float32)
    y = sum(x32[:, j:j + T] * w32[:, j] for j in range(K))
    if silu:
        y = jax.nn.silu(y)
    return y.astype(X.dtype)


@register_op("causal_conv1d")
def _causal_conv1d(ctx, X, W):
    """X [B, T, C], W [C, K]: `y[t, c] = sum_j W[c, j] x[t - (K-1) + j, c]`
    (zeros before t = 0, so output t reads inputs <= t only), then silu
    unless `activation` is empty. Float32 sums, the input's dtype out. One
    pass over X as `causal_conv_fwd` where `_conv_plan` gives the kernels."""
    silu = ctx.attr("activation", "silu") == "silu"
    if _conv_kernels_run(X.shape[1], X.shape[2], W.shape[1]):
        return {"Out": _conv_forward(X, W, silu)}
    return {"Out": _conv_xla(X, W, silu)}


@register_grad("causal_conv1d")
def _causal_conv1d_grad(ctx, ins, out_grads):
    """dX and dW from X, W and dOut alone (the forward saves nothing): one
    pass as `causal_conv_bwd`, which makes the pre-activation again in VMEM;
    outside the envelope `jax.vjp` of the jnp form, as the generic grad
    lowering would."""
    d_out = out_grads["Out"][0]
    if d_out is None:
        return {}
    X, W = ins["X"][0], ins["W"][0]
    d_out = d_out.astype(X.dtype)
    silu = ctx.attr("activation", "silu") == "silu"
    if _conv_kernels_run(X.shape[1], X.shape[2], W.shape[1]):
        dX, dW = _conv_backward(X, W, d_out, silu)
    else:
        _, vjp = jax.vjp(lambda x, w: _conv_xla(x, w, silu), X, W)
        dX, dW = vjp(d_out)
    return {"X": dX, "W": dW.astype(W.dtype)}


@register_op("delta_rule_gates")
def _delta_rule_gates(ctx, A, B, ALog, DtBias):
    """A, B [..., H] (two projections of the layer's input), ALog, DtBias
    [H] -> G = -exp(ALog) * softplus(A + DtBias) and Beta = sigmoid(B), both
    float32 (AMP_F32_OPS)."""
    a32, b32 = A.astype(jnp.float32), B.astype(jnp.float32)
    g = -jnp.exp(ALog.astype(jnp.float32)) \
        * jax.nn.softplus(a32 + DtBias.astype(jnp.float32))
    return {"G": g, "Beta": jax.nn.sigmoid(b32)}


def l2_normalize(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def chunked_gated_delta_rule(q, k, v, g, beta, chunk):
    """q, k [B, T, H, Dk] (already normalised and scaled), v [B, T, H, Dv],
    g, beta [B, T, H], all float32, T a multiple of `chunk` -> o
    [B, T, H, Dv] float32 (module docstring)."""
    B, T, H, Dk = q.shape
    n = T // chunk

    def chunks(x):      # [B, T, H, ...] -> [B, H, n, chunk, ...]
        x = x.reshape((B, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                            # [B, H, n, C]
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = row >= col
    diff = jnp.where(lower, G[..., :, None] - G[..., None, :], 0.0)
    decay = jnp.where(lower, jnp.exp(diff), 0.0)          # D[i, j], i >= j
    k_beta = k * beta[..., None]
    a = jnp.einsum("bhnid,bhnjd->bhnij", k_beta, k,
                   precision=lax.Precision.HIGHEST) * decay
    a = jnp.where(row > col, a, 0.0)
    rhs = jnp.concatenate([v * beta[..., None],
                           k_beta * jnp.exp(G)[..., None]], axis=-1)
    with jax.default_matmul_precision("highest"):
        solved = lax.linalg.triangular_solve(
            a, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = solved[..., : v.shape[-1]], solved[..., v.shape[-1]:]
    g_last = G[..., -1]                                   # [B, H, n]
    k_tail = k * jnp.exp(g_last[..., None] - G)[..., None]

    def step(S, xs):
        u_i, w_i, k_i, last_i = xs
        v_new = u_i - jnp.einsum("bhck,bhkv->bhcv", w_i, S)
        S_next = S * jnp.exp(last_i)[..., None, None] \
            + jnp.einsum("bhck,bhcv->bhkv", k_i, v_new)
        return S_next, (S, v_new)

    def by_chunk(x):    # [B, H, n, ...] -> [n, B, H, ...]
        return jnp.moveaxis(x, 2, 0)

    S0 = jnp.zeros((B, H, Dk, v.shape[-1]), jnp.float32)
    _, (S_in, v_new) = lax.scan(
        step, S0, (by_chunk(u), by_chunk(w), by_chunk(k_tail),
                   by_chunk(g_last)))
    S_in, v_new = jnp.moveaxis(S_in, 0, 2), jnp.moveaxis(v_new, 0, 2)
    scores = jnp.einsum("bhnid,bhnjd->bhnij", q, k) * decay
    o = jnp.einsum("bhnck,bhnkv->bhncv", q * jnp.exp(G)[..., None], S_in) \
        + jnp.einsum("bhnij,bhnjv->bhniv", scores, v_new)
    return jnp.moveaxis(o, 1, 3).reshape(B, T, H, v.shape[-1])


# ---------------------------------------------------------------------------
# the two Pallas kernels (module docstring: what stays in VMEM, precisions)
# ---------------------------------------------------------------------------

_HI = lax.Precision.HIGHEST
_NN = ((1,), (0,))      # a b
_NT = ((1,), (1,))      # a b^T
_TN = ((0,), (0,))      # a^T b
_SUB = 32               # the diagonal blocks the substitution inverts by rows


def _plan(Dk, Dv, chunk):
    """"kernel": a chunk's tiles fill vregs (head dims whole lanes of 128,
    the chunk the 64 tokens the blocked substitution is laid out for).
    "xla": anything else (the tiny head dims of the CPU tests), which keeps
    `chunked_gated_delta_rule` and its vjp. One algorithm either way; the
    choice reads the shape alone."""
    if chunk == 64 and Dk % 128 == 0 and Dv % 128 == 0:
        return "kernel"
    return "xla"


def _on_chip():
    return jax.default_backend() != "cpu"


def _backend_takes_kernels():
    """Whether this backend takes the kernels for a shape a plan gives
    them: always on a TPU; on a CPU backend only under the interpreter's
    rehearsal switch (`pallas_attention._interpret`, refused on the chip),
    since a model interpreted at the cell's widths never ends."""
    return _on_chip() or _interpret()


def _kernels_run(Dk, Dv, chunk):
    return _plan(Dk, Dv, chunk) == "kernel" and _backend_takes_kernels()


def _dot(a, b, dims, full=False):
    """The float32 product of two float32 tiles. `full`: HIGHEST, the MXU's
    float32 passes. Otherwise the backend's DEFAULT for float32 operands,
    spelled out: on the chip XLA rounds them to bf16 and makes one pass
    into a float32 accumulator, so the kernel does; under the interpreter
    on a CPU they stay float32, as that backend's dots do."""
    if full:
        return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)
    if _on_chip():
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


def _rows(x):
    return jnp.sum(x, axis=1, keepdims=True)            # [C, n] -> [C, 1]


def _cols(x):
    return jnp.sum(x, axis=0, keepdims=True)            # [C, n] -> [1, n]


def _l2(x_ref):
    """A tile's rows l2-normalised in float32, and each row's factor."""
    x = x_ref[0].astype(jnp.float32)
    r = lax.rsqrt(_rows(x * x) + 1e-6)
    return x * r, r


def _l2_grad(y, r, dy):
    """The gradient of x given that of y = x * r, r = rsqrt(sum x^2 + eps)."""
    return r * (dy - y * _rows(y * dy))


def _unit_lower_inverse(a, a_t, row, col):
    """(I + a)^-1 of a strictly lower [C, C] tile (`a_t` its transpose), by
    blocked forward substitution. The `_SUB`-wide diagonal blocks row by
    row on the VPU, all blocks at once: row i of a block is `-a_i -
    sum_{j<i} a_ij row_j`. Then pairs of blocks merged level by level,
    `[[T1, 0], [-T2 a21 T1, T2]]`, with HIGHEST products."""
    C = a.shape[0]
    shift = _SUB.bit_length() - 1

    def at(x):                                          # place in its block
        return jnp.bitwise_and(x, _SUB - 1)

    same = jnp.right_shift(row, shift) == jnp.right_shift(col, shift)
    y = jnp.where(same, -a, 0.0)
    n_t = jnp.where(same, -a_t, 0.0)
    for i in range(1, _SUB):
        # row i's multipliers, of every block, down the sublanes
        m = _rows(jnp.where(at(col) == i, n_t, 0.0))
        y = y + jnp.where(same & (at(row) == i), _cols(m * y), 0.0)
    t = y + jnp.where(row == col, 1.0, 0.0)
    while (1 << shift) < C:
        below = (jnp.right_shift(row, shift)
                 == jnp.right_shift(col, shift) + 1) \
            & (jnp.right_shift(row, shift + 1)
               == jnp.right_shift(col, shift + 1))
        t = t - _dot(_dot(t, jnp.where(below, a, 0.0), _NN, full=True), t,
                     _NN, full=True)
        shift += 1
    return t


class _Chunk:
    """What both kernels compute of one (batch, key head, chunk) grid step
    before they part: q (normalised, scaled) and k (normalised), their two
    Gram tiles, the index masks; `head(j, ...)` then gives the factors of
    the key head's value head j."""

    def __init__(self, q_ref, k_ref, g_ref, beta_ref, hk, r):
        C, Dk = q_ref.shape[1], q_ref.shape[2]
        self.first_head = hk * r
        self.qn, self.rq = _l2(q_ref)
        self.k, self.rk = _l2(k_ref)
        self.scale = Dk ** -0.5
        self.q = self.qn * self.scale
        self.row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
        self.col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
        self.kk = _dot(self.k, self.k, _NT, full=True)      # k k^T
        self.qk = _dot(self.q, self.k, _NT)                 # q k^T
        self.G_tile, self.beta_tile = g_ref[0, 0], beta_ref[0, 0]  # [C, Hv]

    def _column(self, tile, j):
        lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
        return _rows(jnp.where(lane == self.first_head + j, tile, 0.0))

    def as_row(self, column):                           # [C, 1] -> [1, C]
        return _cols(jnp.where(self.row == self.col, column, 0.0))

    def head(self, j, v_ref, S):
        """The chunk's factors for value head j, from the state S [Dk, Dv]
        it starts from (module docstring's names)."""
        row, col = self.row, self.col
        C, Dv = row.shape[0], S.shape[1]
        G = self._column(self.G_tile, j)                # running sum, <= 0
        beta = self._column(self.beta_tile, j)
        G_row = self.as_row(G)
        # exponents <= 0 where they are kept; an overflow above (below) the
        # diagonal is dropped by the select, nothing is differentiated here
        D = jnp.where(row >= col, jnp.exp(G - G_row), 0.0)
        kkD = self.kk * D
        a = jnp.where(row > col, kkD * beta, 0.0)
        a_t = jnp.where(row < col, self.kk * jnp.exp(G_row - G)
                        * self.as_row(beta), 0.0)
        t = _unit_lower_inverse(a, a_t, row, col)
        eg = jnp.exp(G)
        v = v_ref[0, :, j * Dv:(j + 1) * Dv].astype(jnp.float32)
        k_beg = self.k * (beta * eg)
        u = _dot(t, v * beta, _NN, full=True)
        w = _dot(t, k_beg, _NN, full=True)
        last = G[C - 1:C, :]                            # [1, 1]
        tail = jnp.exp(last - G)
        return dict(beta=beta, D=D, kkD=kkD, a=a, t=t, eg=eg, v=v, tail=tail,
                    k_beg=k_beg, u=u, w=w, v_new=u - _dot(w, S, _NN),
                    P=self.qk * D, qg=self.q * eg, k_tail=self.k * tail,
                    e_last=jnp.exp(last))


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, o_ref,
                    s_sc, *, r):
    """One (batch, key head, chunk) step, the key head's `r` value heads in
    turn: writes the state as the chunk found it and the chunk's outputs,
    and carries the state in scratch to the next chunk."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_sc[...] = jnp.zeros_like(s_sc)

    ch = _Chunk(q_ref, k_ref, g_ref, beta_ref, pl.program_id(1), r)
    Dv = s_sc.shape[2]
    for j in range(r):
        S = s_sc[j]
        states_ref[0, 0, j] = S
        f = ch.head(j, v_ref, S)
        o = _dot(f["qg"], S, _NN) + _dot(f["P"], f["v_new"], _NN)
        o_ref[0, :, j * Dv:(j + 1) * Dv] = o.astype(o_ref.dtype)
        s_sc[j] = S * f["e_last"] + _dot(f["k_tail"], f["v_new"], _TN)


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                    dG_ref, dbeta_ref, dv_ref, dq_ref, dk_ref, ds_sc, *, r):
    """The same step with the chunks taken last to first. dS, the gradient
    of the state a chunk hands on, is carried in scratch; the chunk's
    factors are computed again from its inputs and its saved state. dG is
    the gradient of the running sum at each token (g's is its reverse
    running sum inside a chunk, taken outside)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_sc[...] = jnp.zeros_like(ds_sc)

    ch = _Chunk(q_ref, k_ref, g_ref, beta_ref, pl.program_id(1), r)
    C, Dv = q_ref.shape[1], ds_sc.shape[2]
    strict = ch.row > ch.col
    at_last = lax.broadcasted_iota(jnp.int32, (1, C), 1) == C - 1
    dkk = dqk = dq = dk = 0.0
    for j in range(r):
        S = states_ref[0, 0, j]
        f = ch.head(j, v_ref, S)
        dO = do_ref[0, :, j * Dv:(j + 1) * Dv].astype(jnp.float32)
        dS = ds_sc[j]
        beta, eg, D = f["beta"], f["eg"], f["D"]
        # o = qg S + P v';  S' = e_last S + k_tail^T v';  v' = u - w S
        dv_new = _dot(f["P"], dO, _TN) + _dot(f["k_tail"], dS, _NN)
        dP = _dot(dO, f["v_new"], _NT)      # read only times P or D: lower
        dqg = _dot(dO, S, _NT)
        dk_tail = _dot(f["v_new"], dS, _NT)
        dw = -_dot(dv_new, S, _NT)
        ds_sc[j] = _dot(f["qg"], dO, _TN) + dS * f["e_last"] \
            - _dot(f["w"], dv_new, _TN)
        d_last = _cols(_rows(S * dS)) * f["e_last"]      # [1, 1]
        # [u | w] = T [beta v | beta exp(G) k]:  dR = T^T dX,
        # dA = -strict_lower(dR X^T)
        dRu = _dot(f["t"], dv_new, _TN, full=True)
        dRw = _dot(f["t"], dw, _TN, full=True)
        dA = -jnp.where(strict, _dot(dRu, f["u"], _NT, full=True)
                        + _dot(dRw, f["w"], _NT, full=True), 0.0)
        dv_ref[0, :, j * Dv:(j + 1) * Dv] = (dRu * beta).astype(dv_ref.dtype)
        dbeta = _rows(dRu * f["v"]) + _rows(dRw * ch.k) * eg \
            + _rows(dA * f["kkD"])
        dbeta_ref[0, j, 0] = ch.as_row(dbeta)
        # G enters through D (dD * D = dA * A + dP * P), exp(G) and the
        # tail's exp(G_last - G); G_last also through e_last
        M = dA * f["a"] + dP * f["P"]
        d_tail = _rows(dk_tail * f["k_tail"])
        dG = _rows(M) + _rows(dRw * f["k_beg"]) + _rows(dqg * f["qg"]) \
            - d_tail
        dG_ref[0, j, 0] = ch.as_row(dG) - _cols(M) \
            + jnp.where(at_last, _cols(d_tail) + d_last, 0.0)
        dkk = dkk + dA * (D * beta)
        dqk = dqk + dP * D
        dq = dq + dqg * eg
        dk = dk + dRw * (beta * eg) + dk_tail * f["tail"]
    dk = dk + _dot(dkk, ch.k, _NN, full=True) \
        + _dot(dkk, ch.k, _TN, full=True) + _dot(dqk, ch.q, _TN)
    dq = (dq + _dot(dqk, ch.k, _NN)) * ch.scale
    dq_ref[0] = _l2_grad(ch.qn, ch.rq, dq).astype(dq_ref.dtype)
    dk_ref[0] = _l2_grad(ch.k, ch.rk, dk).astype(dk_ref.dtype)


def _flat(x):           # [B, T, H, D] -> [B, T, H * D]: the same bytes
    return x.reshape(x.shape[0], x.shape[1], -1)


def _by_chunk(x, chunk):    # [B, T, Hv] -> [B, chunks, chunk, Hv]
    return x.reshape(x.shape[0], -1, chunk, x.shape[2])


def _fwd_shapes(Q, V, chunk):
    """(states, out) as `gdn_fwd` lays them out. The states come first: the
    benchmark finds the rule's instructions by their first result's shape
    (`benchmark/metrics/gdn_scan_ms.train.json`)."""
    B, T, Hk, Dk = Q.shape
    Hv, Dv = V.shape[2], V.shape[3]
    return (jax.ShapeDtypeStruct((T // chunk, B, Hv, Dk, Dv), jnp.float32),
            jax.ShapeDtypeStruct((B, T, Hv * Dv), V.dtype))


def _bwd_shapes(Q, V, chunk):
    """(dG, dbeta, dv, dq, dk) of `gdn_bwd`: a value-head array first, for
    the same reason."""
    B, T, Hk, Dk = Q.shape
    Hv, Dv = V.shape[2], V.shape[3]
    gate = jax.ShapeDtypeStruct((B, Hv, T // chunk, 1, chunk), jnp.float32)
    return (gate, gate, jax.ShapeDtypeStruct((B, T, Hv * Dv), V.dtype),
            jax.ShapeDtypeStruct((B, T, Hk * Dk), Q.dtype),
            jax.ShapeDtypeStruct((B, T, Hk * Dk), Q.dtype))


def _gdn_call(kernel, name, Q, K, V, G, beta, more, out_shape, out_blocks,
              reverse):
    """Both kernels' grid and blocks: (batch, key head, chunk), the chunk
    axis sequential. q, k, v and their like are read where they lie, as
    [B, T, heads * dim] with a head's lanes chosen by the block index (a
    key head's `r` value heads are `r * Dv` adjacent lanes, so nothing is
    repeated); G and beta as [B, chunks, C, Hv], every head of a chunk in
    one block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, Hk, Dk = Q.shape
    Hv, Dv = V.shape[2], V.shape[3]
    r, C = Hv // Hk, T // G.shape[1]
    n = G.shape[1]

    def at(c):                          # the chunk a grid step works on
        return n - 1 - c if reverse else c

    blocks = {
        "key": pl.BlockSpec((1, C, Dk), lambda b, h, c: (b, at(c), h)),
        "value": pl.BlockSpec((1, C, r * Dv), lambda b, h, c: (b, at(c), h)),
        "gates": pl.BlockSpec((1, 1, C, Hv), lambda b, h, c: (b, at(c), 0, 0)),
        "states": pl.BlockSpec((1, 1, r, Dk, Dv),
                               lambda b, h, c: (at(c), b, h, 0, 0)),
        "gate_rows": pl.BlockSpec((1, r, 1, 1, C),
                                  lambda b, h, c: (b, h, at(c), 0, 0))}
    ins = ["key", "key", "value", "gates", "gates"] + [x for x, _ in more]
    return pl.pallas_call(
        functools.partial(kernel, r=r), name=name, grid=(B, Hk, n),
        in_specs=[blocks[x] for x in ins],
        out_specs=[blocks[x] for x in out_blocks], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r, Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(_flat(Q), _flat(K), _flat(V), G, beta, *[x for _, x in more])


def _running_sum(g, chunk):
    return jnp.cumsum(_by_chunk(g.astype(jnp.float32), chunk), axis=2)


def _gdn_forward(Q, K, V, g, beta, chunk):
    """Q, K [B, T, Hk, Dk] and V [B, T, Hv, Dv] as they arrive (not
    normalised), g, beta [B, T, Hv] -> out [B, T, Hv, Dv] in V's dtype and
    the states [chunks, B, Hv, Dk, Dv] float32, each as its chunk found
    it."""
    states, out = _gdn_call(
        _gdn_fwd_kernel, "gdn_fwd", Q, K, V, _running_sum(g, chunk),
        _by_chunk(beta.astype(jnp.float32), chunk), [],
        _fwd_shapes(Q, V, chunk), ["states", "value"], reverse=False)
    return out.reshape(V.shape), states


def _gdn_backward(Q, K, V, g, beta, states, d_out, chunk):
    """The five input gradients from the saved states and `d_out`
    [B, T, Hv, Dv], each in its input's shape and dtype."""
    dG, dbeta, dv, dq, dk = _gdn_call(
        _gdn_bwd_kernel, "gdn_bwd", Q, K, V, _running_sum(g, chunk),
        _by_chunk(beta.astype(jnp.float32), chunk),
        [("states", states), ("value", _flat(d_out.astype(V.dtype)))],
        _bwd_shapes(Q, V, chunk),
        ["gate_rows", "gate_rows", "value", "key", "key"], reverse=True)

    def per_token(x):   # [B, Hv, chunks, 1, C] -> [B, chunks, C, Hv]
        return jnp.transpose(x[:, :, :, 0, :], (0, 2, 3, 1))

    dg = lax.cumsum(per_token(dG), axis=2, reverse=True)
    return (dq.reshape(Q.shape), dk.reshape(K.shape), dv.reshape(V.shape),
            dg.reshape(g.shape).astype(g.dtype),
            per_token(dbeta).reshape(beta.shape).astype(beta.dtype))


# ---------------------------------------------------------------------------
# the causal convolution's two kernels: one pass over X each way
# ---------------------------------------------------------------------------

_HALO = 16      # rows a step reads before its time block: a packed bf16 tile
_PAD = 8        # float32 sublanes of them that the taps can reach: K - 1 <= 8
_CONV_ROWS = 64     # rows a loop step inside a time block works on


def _conv_plan(T, C, K):
    """"kernel": channels in whole lanes of 128, tokens in whole sublane
    tiles (16 rows: bf16 packs two to a sublane) and the K - 1 rows a tap
    reaches back inside one float32 tile. "xla": anything else (the CPU
    tests' `X (2, 6, 3)`), which keeps `_conv_xla` and its vjp. The choice
    reads the shape alone."""
    if C % 128 == 0 and T % _HALO == 0 and 1 <= K <= _PAD + 1:
        return "kernel"
    return "xla"


def _conv_kernels_run(T, C, K):
    return _conv_plan(T, C, K) == "kernel" and _backend_takes_kernels()


def _conv_blocks(T, C):
    """(time block, channel block, rows a loop step works on): long blocks
    of 256 lanes (a chip probe over sixteen choices at `[1, 4096, 8192]`:
    (2048, 256) forward 0.27 ms, backward 0.48; (512, 512) 0.30 and 0.55;
    (512, 128) 0.44 and 0.63), a loop step small enough that its tiles stay
    in vregs (64 rows; 16 and 128 read 10-15% slower)."""
    Tb = next(b for b in (2048, 1024, 512, 256, 128, 64, 32, _HALO)
              if T % b == 0)
    Cb = 256 if C % 256 == 0 else 128
    return Tb, Cb, min(Tb, _CONV_ROWS)


def _taps(xx, K):
    """xx [_PAD + R, Cb] float32, R rows with the `_PAD` rows before them
    -> the K shifted views `x[t - (K-1) + j]`, each [R, Cb]."""
    R = xx.shape[0] - _PAD
    first = _PAD - (K - 1)
    return [xx[first + j:first + j + R] for j in range(K)]


def _weighted(views, w):
    return sum(x * wj for x, wj in zip(views, w))


def _conv_chunks(x_ref, halo_ref, at_start, rows, chunk, carry, reverse):
    """`carry = chunk(xx, r0, carry)` over a time block's row chunks (last
    to first under `reverse`), `xx` the chunk's rows of X widened with the
    `_PAD` rows before them: from the block itself, and for the block's
    first chunk from the `_HALO` rows before the block (zeros where the
    block starts the sequence)."""
    from jax.experimental import pallas as pl

    n = x_ref.shape[1] // rows

    def first(carry):
        before = halo_ref[0].astype(jnp.float32)[_HALO - _PAD:]
        before = jnp.where(at_start, 0.0, before)
        cur = x_ref[0, 0:rows, :].astype(jnp.float32)
        return chunk(jnp.concatenate([before, cur], axis=0), 0, carry)

    def later(i, carry):
        c = n - i if reverse else i                     # 1 .. n - 1
        r0 = pl.multiple_of(c * rows, rows)
        xx = x_ref[0, pl.ds(r0 - _HALO, _HALO + rows), :]
        return chunk(xx.astype(jnp.float32)[_HALO - _PAD:], r0, carry)

    if n == 1:
        return first(carry)
    if reverse:
        return first(lax.fori_loop(1, n, later, carry))
    return lax.fori_loop(1, n, later, first(carry))


def _conv_fwd_kernel(x_ref, halo_ref, w_ref, o_ref, *, K, rows, silu):
    """One (batch, channel block, time block) step: the K taps summed in
    float32, silu, the block written in X's dtype."""
    from jax.experimental import pallas as pl

    w = [w_ref[j:j + 1, :] for j in range(K)]

    def chunk(xx, r0, carry):
        y = _weighted(_taps(xx, K), w)
        if silu:
            y = y * jax.nn.sigmoid(y)
        o_ref[0, pl.ds(r0, rows), :] = y.astype(o_ref.dtype)
        return carry

    _conv_chunks(x_ref, halo_ref, pl.program_id(2) == 0, rows, chunk, 0,
                 reverse=False)


def _conv_bwd_kernel(x_ref, halo_ref, do_ref, w_ref, dx_ref, dw_ref, head_sc,
                     acc_sc, *, K, rows, silu):
    """One (channel block, batch, time block) step, the time blocks and the
    chunks inside one taken last to first: `dpre = dOut * silu'(pre)` with
    the pre-activation made again, `dX[t] = sum_j W[j] dpre[t + K-1-j]`
    with dpre's first `_PAD` rows of the chunk after (carried; across time
    blocks in `head_sc`; zeros after the end), and `dW[j] += sum_t dpre[t]
    x[t - (K-1) + j]`, kept as 8 sublanes of partial sums in `acc_sc` and
    added to the resident `[K, Cb]` block once a step."""
    from jax.experimental import pallas as pl

    t = pl.program_id(2)
    last = pl.num_programs(2) - 1

    @pl.when((pl.program_id(1) == 0) & (t == 0))
    def _init_dw():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(t == 0)                    # the sequence's last block
    def _init_head():
        head_sc[...] = jnp.zeros_like(head_sc)

    acc_sc[...] = jnp.zeros_like(acc_sc)
    w = [w_ref[j:j + 1, :] for j in range(K)]

    def chunk(xx, r0, after):
        taps = _taps(xx, K)
        dpre = do_ref[0, pl.ds(r0, rows), :].astype(jnp.float32)
        if silu:
            pre = _weighted(taps, w)
            s = jax.nn.sigmoid(pre)
            dpre = dpre * (s * (1.0 + pre * (1.0 - s)))
        dd = jnp.concatenate([dpre, after], axis=0)
        dx = _weighted([dd[K - 1 - j:K - 1 - j + rows] for j in range(K)], w)
        dx_ref[0, pl.ds(r0, rows), :] = dx.astype(dx_ref.dtype)
        for j in range(K):
            p = dpre * taps[j]
            acc_sc[j] += sum(p[i:i + 8] for i in range(0, rows, 8))
        return dpre[:_PAD]

    head_sc[...] = _conv_chunks(x_ref, halo_ref, t == last, rows, chunk,
                                head_sc[...], reverse=True)
    for j in range(K):
        dw_ref[j:j + 1, :] += jnp.sum(acc_sc[j], axis=0, keepdims=True)


def _conv_specs(Tb, Cb, K, at):
    """The blocks both kernels read: X's time block, the `_HALO` rows
    before it (the first block reads its own first rows and zeroes them)
    and the weight's channel block as `[K, Cb]`; `at(*grid)` gives (batch,
    time block, channel block)."""
    from jax.experimental import pallas as pl

    def halo(*g):
        b, t, c = at(*g)
        return b, jnp.maximum(t * (Tb // _HALO) - 1, 0), c

    return (pl.BlockSpec((1, Tb, Cb), at), pl.BlockSpec((1, _HALO, Cb), halo),
            pl.BlockSpec((K, Cb), lambda *g: (0, at(*g)[2])))


def _conv_forward(X, W, silu):
    """`causal_conv_fwd`: X [B, T, C] as it arrives, W [C, K] -> Out in X's
    dtype. Every intermediate stays in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (B, T, C), K = X.shape, W.shape[1]
    Tb, Cb, rows = _conv_blocks(T, C)
    x_spec, halo_spec, w_spec = _conv_specs(Tb, Cb, K,
                                            lambda b, c, t: (b, t, c))
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, K=K, rows=rows, silu=silu),
        name="causal_conv_fwd", grid=(B, C // Cb, T // Tb),
        in_specs=[x_spec, halo_spec, w_spec], out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(X.shape, X.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=_interpret(),
    )(X, X, W.astype(jnp.float32).T)


def _conv_backward(X, W, d_out, silu):
    """`causal_conv_bwd`: (dX in X's dtype, dW [C, K] float32) from X, W and
    dOut. The channel blocks lead the grid, so a block of dW stays resident
    while the batch and the time blocks (last to first) add to it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (B, T, C), K = X.shape, W.shape[1]
    Tb, Cb, rows = _conv_blocks(T, C)
    n = T // Tb
    x_spec, halo_spec, w_spec = _conv_specs(
        Tb, Cb, K, lambda c, b, t: (b, n - 1 - t, c))
    dX, dW = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, K=K, rows=rows, silu=silu),
        name="causal_conv_bwd", grid=(C // Cb, B, n),
        in_specs=[x_spec, halo_spec, x_spec, w_spec],
        out_specs=[x_spec, w_spec],
        out_shape=[jax.ShapeDtypeStruct(X.shape, X.dtype),
                   jax.ShapeDtypeStruct((K, C), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_PAD, Cb), jnp.float32),
                        pltpu.VMEM((K, 8, Cb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(X, X, d_out, W.astype(jnp.float32).T)
    return dX, dW.T


# ---------------------------------------------------------------------------
# the op and its grad
# ---------------------------------------------------------------------------

def _check(Q, V, chunk):
    T, Hk, Hv = Q.shape[1], Q.shape[2], V.shape[2]
    if T % chunk or Hv % Hk:
        raise ValueError(f"gated_delta_rule needs a length that is a multiple "
                         f"of the chunk ({chunk}) and value heads that are a "
                         f"multiple of the key heads, got T {T}, heads {Hk} "
                         f"and {Hv}")


def _gated_delta_rule_infer(ctx, structs):
    """Build-time shapes without a trace of the rule: a machine with no TPU
    takes the XLA form, which saves no `States`, and the program it builds
    may run on one that has."""
    Q, V = structs["Q"][0], structs["V"][0]
    states, _ = _fwd_shapes(Q, V, int(ctx.attr("chunk", 64)))
    return {"Out": jax.ShapeDtypeStruct(V.shape, V.dtype), "States": states}


@register_op("gated_delta_rule", infer=_gated_delta_rule_infer,
             propagate_seqlen=False)
def _gated_delta_rule(ctx, Q, K, V, G, Beta):
    """Q, K [B, T, Hk, Dk], V [B, T, Hv, Dv], G, Beta [B, T, Hv] -> Out
    [B, T, Hv, Dv] in V's dtype. Hv is a multiple of Hk: key head j serves
    value heads j * Hv/Hk .. (j + 1) * Hv/Hk - 1. q and k are l2-normalised
    over a head (`x * rsqrt(sum x^2 + 1e-6)`), q then scaled by
    `Dk^-0.5`. T must be a multiple of `chunk`. On the kernel path
    (`_plan`) the rule also returns `States` [T / chunk, B, Hv, Dk, Dv]
    float32, the state each chunk started from, which the grad op reads
    back."""
    chunk = int(ctx.attr("chunk", 64))
    _check(Q, V, chunk)
    Hk, Dk = Q.shape[2], Q.shape[3]
    Hv = V.shape[2]
    kernels = _kernels_run(Dk, V.shape[3], chunk)
    ctx.note(gdn_plan="kernel" if kernels else "xla")
    if kernels:
        out, states = _gdn_forward(Q, K, V, G, Beta, chunk)
        return {"Out": out, "States": states}
    q = l2_normalize(Q.astype(jnp.float32)) * Dk ** -0.5
    k = l2_normalize(K.astype(jnp.float32))
    if Hv != Hk:
        q = jnp.repeat(q, Hv // Hk, axis=2)
        k = jnp.repeat(k, Hv // Hk, axis=2)
    out = chunked_gated_delta_rule(q, k, V.astype(jnp.float32),
                                   G.astype(jnp.float32),
                                   Beta.astype(jnp.float32), chunk)
    return {"Out": out.astype(V.dtype)}


@register_grad("gated_delta_rule")
def _gated_delta_rule_grad(ctx, ins, out_grads):
    """The five input gradients. Where the forward op saved its `States`
    the backward kernel runs alone on them; where it saved none (the XLA
    form, a program built without the slot) the rule is traced again under
    `jax.vjp`, as the generic grad lowering would."""
    d_out = out_grads["Out"][0]
    if d_out is None:
        return {}
    slots = ("Q", "K", "V", "G", "Beta")
    raw = [ins[s][0] for s in slots]
    states = ctx.fwd_outs.get("States", [None])[0]
    if states is None:
        opdef = get_op_def("gated_delta_rule")
        out, vjp = jax.vjp(
            lambda *xs: call_rule(opdef, ctx, {s: [x] for s, x
                                               in zip(slots, xs)})["Out"][0],
            *raw)
        grads = vjp(d_out.astype(out.dtype))
    else:
        grads = _gdn_backward(*raw, states, d_out,
                              int(ctx.attr("chunk", 64)))
    return {s: d.astype(x.dtype) for s, d, x in zip(slots, grads, raw)}
