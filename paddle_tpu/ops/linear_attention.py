"""Linear attention by the gated delta rule (Gated DeltaNet; the mixer of
three layers in four of `qwen3_next`), the two small ops around it, and the
same rule under a decay per key channel (Kimi Delta Attention: the end of
this docstring).

    causal_conv1d:      a depthwise convolution over time, then silu (two
                        Pallas kernels too: the end of this docstring)
    delta_rule_gates:   g = -exp(A_log) * softplus(a + dt_bias) <= 0 (the log
                        of a head's decay), beta = sigmoid(b), times the op's
                        `beta_scale` where it has one (2 in `olmo_hybrid`: with
                        beta > 1 a token's transition exp(g) (I - beta k k^T)
                        has the eigenvalue exp(g) (1 - beta) < 0; the rule
                        takes Beta as it comes, so nothing below changes)
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

Where a chunk's tiles fill vregs (`_plan`: chunk 64, both head dims at
least 64; a head dim that is not a whole number of 128-lane tiles, as
`olmo_hybrid`'s 96 / 192, is filled out with zero channels to the next one
around the calls, `_filled_out`: zero key channels add nothing to `k k^T`,
`q k^T` or the l2-norms and a zero value column stays zero through the solve
and the state, so the kernels are told the rule's `Dk^-0.5` and nothing
else changes; `States` keeps the given `[Dk, Dv]`) the rule is two Pallas
kernels on a grid of (batch, key head, step of `p` chunks), the last axis
sequential; `_plan` takes `p` = 2 consecutive chunks a step where the chunk count is
even (and the pair's blocks fit the VMEM a call has unasked), 1 otherwise,
through one kernel body each way. A grid step takes the key head's value
heads together, so q, k and their Gram tiles `k k^T`, `q k^T` are made once
for them and no head is repeated in HBM. Of a chunk's chain everything up
to u and w reads no state, so a step first makes those factors for its `p`
chunks and `r` heads at once (`_Chunks`): the `[64, 64]` tiles of the two
chunks side by side in one `[64, 128]` tile, a vreg's 128 lanes full, for
all that is elementwise (D, A, P and their gradients); the substitution's
diagonal blocks of every head and chunk side by side in one tile, so its
row updates run once a step; and one MXU product for the pair wherever a
product is due, its operands the chunks' rows stacked `[128, n]` and, for
T, P and the Gram tiles' gradients, the pair's tiles as the diagonal blocks
of a `[128, 128]` matrix (a `[64, 64]` operand took the MXU as long). Then
the state passes through the step's chunks in order: `v' = u - w S`,
`S <- S exp(G_last) + k_tail^T v'`, four dependent one-pass products a
pair, all that is left of the chain; a single chunk is `p = 1` of the same
body.

    gdn_fwd   reads the step's q, k, v (as they arrive: bf16 under AMP), G
              and beta; keeps S [Dk, Dv] float32 in VMEM scratch across the
              steps; makes the l2-norms, D, A, T, u, w, v' and the scores in
              VMEM and writes none of them: only o and `States`, S as each
              chunk found it, the second of a pair inside its step (float32
              [chunks, B, Hv, Dk, Dv]). o
              [B, T, Hv * Dv] is read where it lies by `gated_norm_fwd`
              (`ops/decoder_block.py`: the layer's output norm), whose
              backward `gated_norm_bwd` writes `gdn_bwd`'s dO the same way:
              no XLA op and no layout copy stands between the two pairs.
    gdn_bwd   the steps, and the chunks inside one, last to first, dS
              [Dk, Dv] float32 in scratch; computes the chunks' factors
              again from their inputs and their saved states (every chunk's
              state is saved, so only dS passes from chunk to chunk: two
              dependent products a chunk); with X = [u | w] = T R: dR =
              T^T dX, dA = -strict_lower(dR X^T); writes dv, dq and dk
              (summed over a key head's value heads, through the l2-norm),
              dbeta, and dG per token, whose reverse running sum inside a
              chunk (one small XLA op, like the running sum G itself) is
              g's gradient.

The op and its grad op tally the grid steps of their calls on the compile
event (`gdn_grid_steps`: batch x key heads x chunks / `p`, summed), beside
`gdn_plan` (`"kernel"` or `"xla"`) and, where channels were filled in,
`gdn_lanes_filled` (`[32, 64]`: a key and a value head's).

T comes from blocked forward substitution (`_Chunks.inverses`): the
16-wide diagonal blocks column by column in float32 on the VPU (15 rank-one
updates of one tile that holds every block of the step), then merged pair
by pair, twice; no series in A, which would lose digits where |A| is near 1.
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

Outside the envelope (the tiny head dims of the CPU tests, another chunk),
and on a CPU backend unless the Pallas interpreter is asked for
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

The delta rule under a decay PER KEY CHANNEL (Kimi Delta Attention, the mixer
of five layers in six of `ling3`):

    kda_gates:        g = lower_bound * sigmoid(exp(A_log_h) (f + dt_bias)),
                      [.., H, Dk] in (lower_bound, 0): a head AND key channel;
                      beta = sigmoid(b)
    kda_delta_rule:   per head a state S [key, value], S_0 = 0:
                        S <- Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);
                        S <- S + k_t d^T;  o_t = S^T q_t

In chunks of `chunk` tokens (`chunked_kda_rule`), with G the running sum of g
inside a chunk, `[chunk, Dk]` a head, and S the state the chunk starts from:

    A = strict_lower(beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c]))
    P = lower(sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c]));   T = (I + A)^-1
    u = T (beta v);  w = T (beta k * exp(G));  v' = u - w S
    o = (q * exp(G)) S + P v'
    S <- Diag(exp(G_last)) S + (k * exp(G_last - G))^T v'

The decay sits inside the contraction over the key channels, so the scalar
rule's `k k^T * D` is gone: each side of a Gram tile carries its half of the
decay relative to a reference row r, `(x_i * exp(G_i - G_r)) . (k_j * exp(G_r
- G_j))`, and one of the halves has a positive exponent wherever r does not lie
between j and i. That is what the bound on g is for (`kda_safe_gate`: g >= -5
a token). A chunk's tiles are made in blocks of 16 rows (`_KDA_BLOCK`). Against
the keys of the blocks before it a block takes r = its own first row: both
exponents <= 0, whatever g. Against its own 16 keys it takes r = its middle
row: both within +-8 x 5 = 40, `exp(40) = 2.4e17`, far from float32's ends on
both sides. (r = the block's first row would do for the forward pass, at
exponents up to 75 < 88; but then the other half reaches `exp(-75)`, and the
backward's products of it with a small cotangent fall under float32's
smallest normal number and are flushed: g's gradient read 1% off at g = -5
everywhere, 1e-4 with the middle row. A 64-token chunk relative to one row,
320, would overflow outright.) No gradient is passed through r: the tiles do
not depend on it. The other exponents, `exp(G)`, `exp(G_last - G)` and
`exp(G_last)`, are <= 0 as in the scalar rule. No `[chunk, chunk, Dk]` array
is formed: a block's rows times the keys it reaches is one product of two
`[.., Dk]` operands.

Where a chunk's tiles fill vregs (`_plan`'s envelope, as for the scalar rule:
chunk 64, head dims multiples of 128) the rule is two more Pallas kernels on
`gdn_fwd` / `gdn_bwd`'s grid (`_gdn_call`: batch, head, step of `p` = 2
chunks; q, k, v, o and here G `[B, T, H * Dk]` read where they lie), one head
a step, `_KdaChunks` in `_Chunks`' two layouts:

    kda_fwd   S [Dk, Dv] float32 in scratch across a head's steps; per step
              the exponentials of G relative to each 16-row block's first
              and middle row ([p C, Dk] tiles), the Gram tiles by row block
              (a block's rows of both chunks against the zero-padded keys
              before it: one product of [p 16, Dk] by [p C, Dk]; the diagonal
              blocks of all rows: one product of the stacked pair), A at
              HIGHEST and P at the default precision; T by
              `_Chunks.inverses`; u, w; then the state through the chunks,
              its rows scaled by the column `exp(G_last)` [Dk, 1]. Writes o
              and `States`, as `gdn_fwd` does.
    kda_bwd   the steps and chunks last to first, dS in scratch, the factors
              made again from the inputs and the saved states; dA and dP go
              back through `tiles_grad`, each half of a tile giving G its
              part (`x dx` to the rows, `-k dk` to the keys, none through a
              reference row); dG leaves per token and channel [B, T, H * Dk]
              float32 (through `exp(G)`, the tail's `exp(G_last - G)`,
              `exp(G_last)` at a chunk's last token, and the tiles), and g's
              gradient is its reverse running sum inside a chunk: that sum
              and the running sum G itself stay XLA ops (`_running_sum`).

Precisions are the XLA form's: float32 for g, G, beta, every `exp`, the
l2-norms, A, T, the state, dS and every accumulator; HIGHEST for A's products
(and their transposes in the backward), the merges of T and `T [beta v |
beta k exp(G)]`; the backend's default for `w S`, `k_tail^T v'`, `q S`, P's
products and `P v'`. Outside the envelope, and on a CPU backend unless the
interpreter is asked for, the op keeps `chunked_kda_rule`: A, the solve, u
and w for all chunks at once, a `lax.scan` over the chunks' states, the
outputs for all chunks at once. The grad op is registered
(`kda_delta_rule_grad`): on the saved `States` it runs `kda_bwd` alone; where
the forward saved none it is `jax.vjp` of the XLA form. On the compile event:
`kda_plan` (`"kernel"` or `"xla"`), the rule's chunk steps whatever runs them
(`kda_grid_steps`: batch x heads x chunks, the op and its grad op) and the
grid steps the two kernel calls ran (`kda_kernel_grid_steps`: batch x heads x
chunks / `p`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import call_rule, get_op_def, register_grad, register_op
from . import _kernels
from ._kernels import _NN, _NT, _TN, _cols, _dot, _rows, _running_sum


def _conv_xla(X, W, silu, bias=None):
    """The convolution as plain jnp, and the form `jax.vjp` differentiates
    outside the kernels' envelope."""
    K = W.shape[1]
    T = X.shape[1]
    x32 = jnp.pad(X.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    w32 = W.astype(jnp.float32)
    y = sum(x32[:, j:j + T] * w32[:, j] for j in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if silu:
        y = jax.nn.silu(y)
    return y.astype(X.dtype)


@register_op("causal_conv1d")
def _causal_conv1d(ctx, X, W, Bias=None):
    """X [B, T, C], W [C, K]: `y[t, c] = sum_j W[c, j] x[t - (K-1) + j, c]`
    (zeros before t = 0, so output t reads inputs <= t only), plus `Bias`
    [C] where given, then silu unless `activation` is empty. Float32 sums,
    the input's dtype out. One pass over X as `causal_conv_fwd` where
    `_conv_plan` gives the kernels; which form ran goes on the compile event
    as `causal_conv_plan` ("kernel" or "xla")."""
    silu = ctx.attr("activation", "silu") == "silu"
    kernels = _conv_kernels_run(X.shape[1], X.shape[2], W.shape[1])
    ctx.note(causal_conv_plan="kernel" if kernels else "xla")
    if kernels:
        return {"Out": _conv_forward(X, W, silu, Bias)}
    return {"Out": _conv_xla(X, W, silu, Bias)}


@register_grad("causal_conv1d")
def _causal_conv1d_grad(ctx, ins, out_grads):
    """dX and dW (and dBias, the pre-activation's gradient summed over
    batch and time, where the op has a bias) from X, W and dOut alone (the
    forward saves nothing): one pass as `causal_conv_bwd`, which makes the
    pre-activation again in VMEM; outside the envelope `jax.vjp` of the jnp
    form, as the generic grad lowering would."""
    d_out = out_grads["Out"][0]
    if d_out is None:
        return {}
    X, W = ins["X"][0], ins["W"][0]
    bias = ins["Bias"][0] if ins.get("Bias") else None
    d_out = d_out.astype(X.dtype)
    silu = ctx.attr("activation", "silu") == "silu"
    more = () if bias is None else (bias,)
    if _conv_kernels_run(X.shape[1], X.shape[2], W.shape[1]):
        dX, dW, *d_bias = _conv_backward(X, W, d_out, silu, *more)
    else:
        _, vjp = jax.vjp(lambda x, w, *b: _conv_xla(x, w, silu, *b), X, W,
                         *more)
        dX, dW, *d_bias = vjp(d_out)
    grads = {"X": dX, "W": dW.astype(W.dtype)}
    if d_bias:
        grads["Bias"] = d_bias[0].astype(bias.dtype)
    return grads


@register_op("delta_rule_gates")
def _delta_rule_gates(ctx, A, B, ALog, DtBias):
    """A, B [..., H] (two projections of the layer's input), ALog, DtBias
    [H] -> G = -exp(ALog) * softplus(A + DtBias) and Beta = sigmoid(B), both
    float32 (AMP_F32_OPS). `beta_scale` (absent: 1) multiplies Beta: at 2 it
    lies in (0, 2), and above 1 the rule's transition has a negative
    eigenvalue (module docstring)."""
    a32, b32 = A.astype(jnp.float32), B.astype(jnp.float32)
    g = -jnp.exp(ALog.astype(jnp.float32)) \
        * jax.nn.softplus(a32 + DtBias.astype(jnp.float32))
    beta = jax.nn.sigmoid(b32)
    scale = ctx.attr("beta_scale", 1.0)
    return {"G": g, "Beta": beta if scale == 1 else beta * scale}


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
# the delta rule under a decay per key channel (Kimi Delta Attention)
# ---------------------------------------------------------------------------

_KDA_BLOCK = 16     # rows of a Gram tile that share one reference row


@register_op("kda_gates")
def _kda_gates(ctx, F, B, ALog, DtBias):
    """F [..., H * Dk] or [..., H, Dk] and B [..., H] (two projections of the
    layer's input), ALog [H], DtBias [H * Dk] -> G [..., H, Dk] =
    `lower_bound * sigmoid(exp(ALog_h) * (F + DtBias))`, the log of a head's
    decay per KEY CHANNEL, in (`lower_bound`, 0) (the attribute, -5 as
    published: `kda_safe_gate`), and Beta = sigmoid(B); both float32
    (AMP_F32_OPS)."""
    heads = ALog.shape[0]
    f32 = F.astype(jnp.float32).reshape(B.shape[:-1] + (heads, -1))
    rate = jnp.exp(ALog.astype(jnp.float32))[:, None]
    bias = DtBias.astype(jnp.float32).reshape(heads, -1)
    g = float(ctx.attr("lower_bound", -5.0)) \
        * jax.nn.sigmoid(rate * (f32 + bias))
    return {"G": g, "Beta": jax.nn.sigmoid(B.astype(jnp.float32))}


def chunked_kda_rule(q, k, v, g, beta, chunk):
    """q, k [B, T, H, Dk] (already normalised and scaled), v [B, T, H, Dv],
    g [B, T, H, Dk] (a channel's log-decay, bounded below), beta [B, T, H],
    all float32, T a multiple of `chunk` -> o [B, T, H, Dv] float32 (module
    docstring: the per-channel chunk algebra). No exponent above `-8 min(g)`
    is formed (40 at the published bound), and none above 0 outside the
    diagonal 16 x 16 blocks of a chunk's tiles."""
    B, T, H, Dk = q.shape
    n = T // chunk
    # a chunk that 16-row blocks do not divide (the tests' 4) is one block
    block = chunk if chunk % _KDA_BLOCK else _KDA_BLOCK
    nb = chunk // block

    def chunks(x):      # [B, T, H, ...] -> [B, H, n, chunk, ...]
        x = x.reshape((B, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=3)                             # [B, H, n, C, Dk]

    def blocks(x):      # [.., C, Dk] -> [.., nb, block, Dk]
        return x.reshape(x.shape[:-2] + (nb, block, Dk))

    # A block of `block` rows reads its Gram tiles relative to a reference
    # row r: (x_i exp(G_i - G_r)) . (k_j exp(G_r - G_j)). Against the keys of
    # the blocks before it r is the block's first row: both exponents <= 0.
    # Against its own keys r is its middle row: both within +-(block / 2)
    # |min g|, 40 as published, so that neither half nears float32's ends (at
    # r = the first row a half reaches exp(-75) and its gradient's products
    # are flushed). The tiles do not depend on r, so no gradient passes
    # through it: the two halves' parts, equal and opposite, would only
    # leave their rounding behind.
    Gb = blocks(G)
    first = lax.stop_gradient(Gb[..., :1, :])             # [.., nb, 1, Dk]
    middle = lax.stop_gradient(Gb[..., block // 2:block // 2 + 1, :])
    before = lax.broadcasted_iota(jnp.int32, (nb, chunk, 1), 1) \
        < block * lax.broadcasted_iota(jnp.int32, (nb, chunk, 1), 0)
    diff = first - G[..., None, :, :]                     # [.., nb, C, Dk]
    k_before = jnp.where(before, jnp.exp(jnp.where(before, diff, 0.0)), 0.0) \
        * k[..., None, :, :]
    k_own = blocks(k) * jnp.exp(middle - Gb)              # [.., nb, block, Dk]
    to_first, to_middle = jnp.exp(Gb - first), jnp.exp(Gb - middle)
    own = jnp.eye(nb, dtype=jnp.float32)[:, None, :, None]

    def tiles(x, precision=None):   # rows x [.., C, Dk] -> [.., C, C]
        x = blocks(x)
        t = jnp.einsum("bhnrad,bhnrjd->bhnraj", x * to_first, k_before,
                       precision=precision)
        d = jnp.einsum("bhnrad,bhnrjd->bhnraj", x * to_middle, k_own,
                       precision=precision)
        d = (d[..., None, :] * own).reshape(t.shape)  # on the block diagonal
        return (t + d).reshape(t.shape[:-3] + (chunk, chunk))

    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    k_beta = k * beta[..., None]
    a = jnp.where(row > col, tiles(k_beta, lax.Precision.HIGHEST), 0.0)
    eg = jnp.exp(G)
    rhs = jnp.concatenate([v * beta[..., None], k_beta * eg], axis=-1)
    with jax.default_matmul_precision("highest"):
        solved = lax.linalg.triangular_solve(
            a, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = solved[..., : v.shape[-1]], solved[..., v.shape[-1]:]
    g_last = G[..., -1, :]                                # [B, H, n, Dk]
    k_tail = k * jnp.exp(g_last[..., None, :] - G)

    def step(S, xs):
        u_i, w_i, k_i, last_i = xs
        v_new = u_i - jnp.einsum("bhck,bhkv->bhcv", w_i, S)
        S_next = S * jnp.exp(last_i)[..., None] \
            + jnp.einsum("bhck,bhcv->bhkv", k_i, v_new)
        return S_next, (S, v_new)

    def by_chunk(x):    # [B, H, n, ...] -> [n, B, H, ...]
        return jnp.moveaxis(x, 2, 0)

    S0 = jnp.zeros((B, H, Dk, v.shape[-1]), jnp.float32)
    _, (S_in, v_new) = lax.scan(
        step, S0, (by_chunk(u), by_chunk(w), by_chunk(k_tail),
                   by_chunk(g_last)))
    S_in, v_new = jnp.moveaxis(S_in, 0, 2), jnp.moveaxis(v_new, 0, 2)
    scores = jnp.where(row >= col, tiles(q), 0.0)
    o = jnp.einsum("bhnck,bhnkv->bhncv", q * eg, S_in) \
        + jnp.einsum("bhnij,bhnjv->bhniv", scores, v_new)
    return jnp.moveaxis(o, 1, 3).reshape(B, T, H, v.shape[-1])


# ---------------------------------------------------------------------------
# the two Pallas kernels (module docstring: what stays in VMEM, precisions)
# ---------------------------------------------------------------------------

_SUB = 16               # the diagonal blocks the substitution inverts on the VPU
_LANES = 128            # a vreg's lanes: what a head dim is filled out to
_VMEM = 12 << 20        # of the 16 MiB a call has unasked, what blocks may take


def _filled(d):
    """`d` channels filled out with zeros to whole tiles of 128 lanes."""
    return -(-d // _LANES) * _LANES


def _plan(Dk, Dv, chunk, chunks=1, r=1):
    """("kernel", p): the chunk is the 64 tokens the blocked substitution is
    laid out for and both head dims are at least half a lane tile (64). A
    head dim that is not a whole number of 128-lane tiles (Olmo-Hybrid's 96
    / 192) is filled out with zero channels to the next one (128 / 256:
    `_filled`; `_gdn_forward` / `_gdn_backward` fill q, k, v and dO and cut
    Out, dq, dk, dv back), because the kernels' time is mostly the
    `[64, 64]` tiles of a chunk (D, A, T, P and their gradients), which do
    not depend on the head dims, while the XLA form spends its time in a
    `while` over the chunks' states and a batched triangular solve whatever
    the heads are: one layer at `[1, 4096, 15, 96 / 192]` took 8.9 ms
    forward and backward as XLA ops and 5.1 through the filled-out kernels
    (`tools/gdn_offtile_probe.py`). Under 64 more than half of every
    operand tile would be zeros and nothing was measured. A grid step takes
    `p` consecutive chunks of a key head: 2 where the chunks pair up and the
    pair's blocks AT THE FILLED WIDTHS (twice, the pipeline holds two of
    each) and the state fit the VMEM a call has without asking for more, 1
    otherwise. ("xla", 0): anything else (another chunk, the tiny head dims
    of the CPU tests), which keeps `chunked_gated_delta_rule` and its vjp.
    One algorithm either way; the choice reads the shape alone."""
    if chunk != 64 or min(Dk, Dv) < _LANES // 2:
        return "xla", 0
    Dk, Dv = _filled(Dk), _filled(Dv)

    def vmem(p):    # the backward's blocks (the larger), float32 operands
        tokens = 4 * p * chunk * (4 * Dk + 3 * r * Dv)  # q k v dO dv dq dk
        return 2 * (tokens + 4 * p * r * Dk * Dv) + 4 * r * Dk * Dv

    return "kernel", 2 if chunks % 2 == 0 and vmem(2) <= _VMEM else 1


def _kernels_run(Dk, Dv, chunk):
    return _plan(Dk, Dv, chunk)[0] == "kernel" \
        and _kernels.backend_takes_kernels()


def _l2(x_ref):
    """A tile's rows l2-normalised in float32, and each row's factor."""
    x = x_ref[0].astype(jnp.float32)
    r = lax.rsqrt(_rows(x * x) + 1e-6)
    return x * r, r


def _l2_grad(y, r, dy):
    """The gradient of x given that of y = x * r, r = rsqrt(sum x^2 + eps)."""
    return r * (dy - y * _rows(y * dy))


class _Chunks:
    """What both kernels compute of one (batch, key head, `p` chunks) grid
    step before they part. Two layouts of the `p` chunks' tokens:

      stacked   [p C, n]: chunk i in rows i C .. (i + 1) C - 1. q (normalised,
                scaled), k (normalised), v, u, w, every per-token column
                [p C, 1]: what a product takes or gives by rows.
      beside    [C, p C]: a [C, C] tile of each chunk side by side, chunk i in
                lanes i C .. (i + 1) C - 1, so the pair fills a vreg's 128
                lanes: the Gram tiles, D, A, P and all that is elementwise
                on them.

    `beside` takes a product of stacked operands [p C, p C] to its diagonal
    blocks side by side, `apart` a beside tile to the block-diagonal [p C,
    p C] matrix a product reads, so one MXU product serves the `p` chunks;
    what the blocks off the diagonal hold is dropped or zero. `heads(...)`
    gives the factors of the key head's value heads that read no state."""

    def __init__(self, q_ref, k_ref, g_ref, beta_ref, hk, r, p, scale):
        self._operands(q_ref, k_ref, hk, r, p, scale)
        self.kk = self.beside(_dot(self.k, self.k, _NT, full=True))  # k k^T
        self.qk = self.beside(_dot(self.q, self.k, _NT))             # q k^T
        self.G_tile, self.beta_tile = g_ref[0], beta_ref[0]      # [p C, Hv]

    def _operands(self, q_ref, k_ref, hk, r, p, scale):
        """q and k of the step, normalised, q then scaled by `scale` (a
        Python float: the rule's `Dk^-0.5` at the head dim the op was given,
        which the block's width is not where zero channels fill it out),
        and the two layouts' indices: what the per-channel rule's step
        (`_KdaChunks`) starts from too."""
        n = q_ref.shape[1]
        self.r, self.p, self.C = r, p, n // p
        C = self.C
        self.first_head = hk * r
        self.qn, self.rq = _l2(q_ref)
        self.k, self.rk = _l2(k_ref)
        self.scale = scale
        self.q = self.qn * self.scale
        lane = lax.broadcasted_iota(jnp.int32, (C, n), 1)
        self.row = lax.broadcasted_iota(jnp.int32, (C, n), 0)
        self.col = jnp.bitwise_and(lane, C - 1)         # place in its chunk
        self.part = jnp.right_shift(lane, C.bit_length() - 1)   # its chunk

    def of(self, i, x):                                 # chunk i's rows
        return x[i * self.C:(i + 1) * self.C]

    def each(self, fn):
        """`fn(i)` [C, n] of every chunk, stacked."""
        return jnp.concatenate([fn(i) for i in range(self.p)], axis=0)

    def beside(self, x):
        """Stacked [p C, m] -> [C, p C] or [C, 1]: chunk i's rows in chunk
        i's lanes (a product's diagonal blocks; a column, ready to be
        broadcast along its chunk's lanes)."""
        out = self.of(0, x)
        for i in range(1, self.p):
            out = jnp.where(self.part == i, self.of(i, x), out)
        return out

    def apart(self, tile):
        """Beside [C, p C] -> block diagonal [p C, p C]."""
        return self.each(lambda i: jnp.where(self.part == i, tile, 0.0))

    def rows(self, tile):
        """Each chunk's row sums of a beside tile, stacked [p C, 1]."""
        return self.each(lambda i: _rows(jnp.where(self.part == i, tile,
                                                   0.0)))

    def as_row(self, column):
        """A stacked column [p C, 1] as a beside row [1, p C]."""
        return _cols(jnp.where(self.row == self.col, self.beside(column),
                               0.0))

    def _column(self, tile, j):
        lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
        return _rows(jnp.where(lane == self.first_head + j, tile, 0.0))

    def inverses(self, a):
        """(I + a)^-1 of each chunk's strictly lower [C, C] tile, for every
        value head at once (`a`: the heads' tiles, each beside), by blocked
        forward substitution; block diagonal [p C, p C] a head. The
        `_SUB`-wide diagonal blocks of all heads and chunks side by side
        in one [_SUB, r p C] tile, identity to start with, column by
        column on the VPU: `y[m] -= a[m, i] y[i]` for the rows under row i,
        the multipliers `a[:, i]` spread along their block's lanes by a
        gather. Then pairs of blocks merged level by level, `[[T1, 0],
        [-T2 a21 T1, T2]]`, with HIGHEST products."""
        row, col = self.row, self.col
        n = self.p * self.C
        shift = _SUB.bit_length() - 1
        same = jnp.right_shift(row, shift) == jnp.right_shift(col, shift)
        blocks = range(self.C // _SUB)

        def folded(x):      # its diagonal blocks, each in its own lanes
            x = jnp.where(same, x, 0.0)
            return sum(x[b * _SUB:(b + 1) * _SUB] for b in blocks)

        minus = [-folded(x) for x in a]
        fill = -(len(a) * n) % 128                      # whole vregs of lanes
        if fill:
            minus.append(jnp.zeros((_SUB, fill), jnp.float32))
        minus = jnp.concatenate(minus, axis=1)
        lane = lax.broadcasted_iota(jnp.int32, (_SUB, 128), 1)
        first = jnp.bitwise_and(lane, -_SUB)            # its block's lane 0
        eye = jnp.where(lax.broadcasted_iota(jnp.int32, (_SUB, 128), 0)
                        == lane - first, 1.0, 0.0)
        minus = [minus[:, x:x + 128] for x in range(0, minus.shape[1], 128)]
        y = [eye] * len(minus)              # a vreg of lanes each, in step
        for i in range(_SUB - 1):
            y = [y_at + jnp.take_along_axis(m, first + i, axis=1)
                 * y_at[i:i + 1] for m, y_at in zip(minus, y)]
        y = jnp.concatenate(y, axis=1)
        t = [self.apart(jnp.where(same, jnp.concatenate(
            [y[:, j * n:(j + 1) * n]] * len(blocks), axis=0), 0.0))
            for j in range(len(a))]
        row = lax.broadcasted_iota(jnp.int32, (n, n), 0)
        col = lax.broadcasted_iota(jnp.int32, (n, n), 1)
        a = [self.apart(x) for x in a]
        while (1 << shift) < self.C:
            below = (jnp.right_shift(row, shift)
                     == jnp.right_shift(col, shift) + 1) \
                & (jnp.right_shift(row, shift + 1)
                   == jnp.right_shift(col, shift + 1))
            x = [_dot(tj, jnp.where(below, aj, 0.0), _NN, full=True)
                 for tj, aj in zip(t, a)]
            t = [tj - _dot(xj, tj, _NN, full=True) for tj, xj in zip(t, x)]
            shift += 1
        return t

    def heads(self, v_ref, Dv):
        """The chunks' factors that read no state, of the key head's `r`
        value heads stage by stage (module docstring's names): the
        per-token columns, v, u, w, qg, k_tail stacked; D, kkD, a, P beside;
        t block diagonal; a chunk's last decay `e_last[i]` [1, 1]."""
        row, col, C = self.row, self.col, self.C
        heads = []
        for j in range(self.r):
            G = self._column(self.G_tile, j)            # running sum, <= 0
            beta = self._column(self.beta_tile, j)
            beta_b = self.beside(beta)
            # exponents <= 0 where they are kept; an overflow above the
            # diagonal is dropped by the select, nothing is differentiated
            D = jnp.where(row >= col,
                          jnp.exp(self.beside(G) - self.as_row(G)), 0.0)
            kkD = self.kk * D
            heads.append(dict(G=G, beta=beta, beta_b=beta_b, D=D, kkD=kkD,
                              a=jnp.where(row > col, kkD * beta_b, 0.0)))
        for j, (f, t) in enumerate(zip(heads,
                                       self.inverses([f["a"] for f in heads]))):
            G, beta = f["G"], f["beta"]
            eg = jnp.exp(G)
            v = v_ref[0, :, j * Dv:(j + 1) * Dv].astype(jnp.float32)
            k_beg = self.k * (beta * eg)
            last = [G[(i + 1) * C - 1:(i + 1) * C, :] for i in range(self.p)]
            tail = jnp.exp(self.each(lambda i: jnp.broadcast_to(last[i],
                                                                (C, 1))) - G)
            f.update(t=t, eg=eg, v=v, tail=tail, k_beg=k_beg,
                     u=_dot(t, v * beta, _NN, full=True),
                     w=_dot(t, k_beg, _NN, full=True), P=self.qk * f["D"],
                     qg=self.q * eg, k_tail=self.k * tail,
                     e_last=[jnp.exp(x) for x in last])
        return heads


def _state_cut(S, states_ref):
    """A state [Dk, Dv] at the kernel's widths as `States` holds it: without
    the zero rows and columns of the channels that were filled in (the
    block's last two dims are the head dims the op was given)."""
    dk, dv = states_ref.shape[3:]
    return S if S.shape == (dk, dv) else S[:dk, :dv]


def _state_filled(S, scratch):
    """A saved state at the widths the kernel works at (`scratch`'s): the
    filled-in channels' rows and columns are zeros, as the forward had
    them."""
    Dk, Dv = scratch.shape[1:]
    if S.shape == (Dk, Dv):
        return S
    return jnp.pad(S, ((0, Dk - S.shape[0]), (0, Dv - S.shape[1])))


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, o_ref,
                    s_sc, *, r, p, scale):
    """One (batch, key head, `p` chunks) step for the key head's `r` value
    heads: the factors that read no state for the `p` chunks at once, then
    the state through the chunks in order (written as each found it,
    carried in scratch to the next step), then the chunks' outputs."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_sc[...] = jnp.zeros_like(s_sc)

    ch = _Chunks(q_ref, k_ref, g_ref, beta_ref, pl.program_id(1), r, p, scale)
    Dv = s_sc.shape[2]
    heads = ch.heads(v_ref, Dv)
    S = [s_sc[j] for j in range(r)]
    v_new, from_state = [[] for _ in heads], [[] for _ in heads]
    for i in range(p):
        for j, f in enumerate(heads):
            states_ref[i, 0, j] = _state_cut(S[j], states_ref)
            v_new[j].append(ch.of(i, f["u"])
                            - _dot(ch.of(i, f["w"]), S[j], _NN))
            from_state[j].append(_dot(ch.of(i, f["qg"]), S[j], _NN))
            S[j] = S[j] * f["e_last"][i] \
                + _dot(ch.of(i, f["k_tail"]), v_new[j][i], _TN)
    for j, f in enumerate(heads):
        s_sc[j] = S[j]
        o = jnp.concatenate(from_state[j], axis=0) \
            + _dot(ch.apart(f["P"]), jnp.concatenate(v_new[j], axis=0), _NN)
        o_ref[0, :, j * Dv:(j + 1) * Dv] = o.astype(o_ref.dtype)


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                    dG_ref, dbeta_ref, dv_ref, dq_ref, dk_ref, ds_sc, *, r,
                    p, scale):
    """The same step with the steps, and the chunks inside one, taken last
    to first. dS, the gradient of the state a chunk hands on, is carried in
    scratch and passes through the step's chunks; the chunks' factors are
    computed again from their inputs and their saved states, for the `p`
    at once. dG is the gradient of the running sum at each token (g's is
    its reverse running sum inside a chunk, taken outside)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_sc[...] = jnp.zeros_like(ds_sc)

    ch = _Chunks(q_ref, k_ref, g_ref, beta_ref, pl.program_id(1), r, p, scale)
    C, Dv = ch.C, ds_sc.shape[2]
    strict = ch.row > ch.col
    at_last = (ch.col == C - 1)[:1]                     # [1, p C]
    heads = ch.heads(v_ref, Dv)
    # o = qg S + P v';  S' = e_last S + k_tail^T v';  v' = u - w S
    for j, f in enumerate(heads):
        S = [_state_filled(states_ref[i, 0, j], ds_sc) for i in range(p)]
        dO = do_ref[0, :, j * Dv:(j + 1) * Dv].astype(jnp.float32)
        f.update(S=S, dO=dO, dS=[None] * p + [ds_sc[j]],   # dS[i + 1]: of
                 dv_new=[None] * p,                     # what chunk i gives
                 v_new=f["u"] - ch.each(
                     lambda i: _dot(ch.of(i, f["w"]), S[i], _NN)),
                 from_out=_dot(ch.apart(f["P"]), dO, _TN))
    for i in reversed(range(p)):
        for f in heads:
            dS = f["dS"]
            f["dv_new"][i] = ch.of(i, f["from_out"]) \
                + _dot(ch.of(i, f["k_tail"]), dS[i + 1], _NN)
            dS[i] = _dot(ch.of(i, f["qg"]), ch.of(i, f["dO"]), _TN) \
                + dS[i + 1] * f["e_last"][i] \
                - _dot(ch.of(i, f["w"]), f["dv_new"][i], _TN)
    dkk = dqk = dq = dk = 0.0
    for j, f in enumerate(heads):
        S, dS, dO, v_new = f["S"], f["dS"], f["dO"], f["v_new"]
        beta, eg, D, P = f["beta"], f["eg"], f["D"], f["P"]
        ds_sc[j] = dS[0]
        dv_new = jnp.concatenate(f["dv_new"], axis=0)
        dP = ch.beside(_dot(dO, v_new, _NT))  # read only times P or D: lower
        dqg = ch.each(lambda i: _dot(ch.of(i, dO), S[i], _NT))
        dk_tail = ch.each(lambda i: _dot(ch.of(i, v_new), dS[i + 1], _NT))
        dw = -ch.each(lambda i: _dot(ch.of(i, dv_new), S[i], _NT))
        d_last = [_rows(_cols(S[i] * dS[i + 1])) * f["e_last"][i]
                  for i in range(p)]                    # [1, 1] each
        # [u | w] = T [beta v | beta exp(G) k]:  dR = T^T dX,
        # dA = -strict_lower(dR X^T)
        dRu = _dot(f["t"], dv_new, _TN, full=True)
        dRw = _dot(f["t"], dw, _TN, full=True)
        dA = -jnp.where(strict, ch.beside(
            _dot(dRu, f["u"], _NT, full=True)
            + _dot(dRw, f["w"], _NT, full=True)), 0.0)
        dv_ref[0, :, j * Dv:(j + 1) * Dv] = (dRu * beta).astype(dv_ref.dtype)
        through_w = _rows(dRw * ch.k) * eg              # w's rows: beta eg k
        dbeta = _rows(dRu * f["v"]) + through_w + ch.rows(dA * f["kkD"])
        # G enters through D (dD * D = dA * A + dP * P), exp(G) and the
        # tail's exp(G_last - G); G_last also through e_last
        M = dA * f["a"] + dP * P
        d_tail = dk_tail * f["k_tail"]
        dG = ch.rows(M) + through_w * beta + _rows(dqg * f["qg"] - d_tail)
        ends = ch.beside(ch.each(lambda i: jnp.broadcast_to(
            _rows(_cols(ch.of(i, d_tail))) + d_last[i], (C, 1))))[:1]
        dG_row = ch.as_row(dG) - _cols(M) + jnp.where(at_last, ends, 0.0)
        dbeta_row = ch.as_row(dbeta)
        for i in range(p):
            dG_ref[0, j, i] = dG_row[:, i * C:(i + 1) * C]
            dbeta_ref[0, j, i] = dbeta_row[:, i * C:(i + 1) * C]
        dkk = dkk + dA * (D * f["beta_b"])
        dqk = dqk + dP * D
        dq = dq + dqg * eg
        dk = dk + dRw * (beta * eg) + dk_tail * f["tail"]
    dkk, dqk = ch.apart(dkk), ch.apart(dqk)
    dk = dk + _dot(dkk + dkk.T, ch.k, _NN, full=True) + _dot(dqk, ch.q, _TN)
    dq = (dq + _dot(dqk, ch.k, _NN)) * ch.scale
    dq_ref[0] = _l2_grad(ch.qn, ch.rq, dq).astype(dq_ref.dtype)
    dk_ref[0] = _l2_grad(ch.k, ch.rk, dk).astype(dk_ref.dtype)


def _flat(x):           # [B, T, H, D] -> [B, T, H * D]: the same bytes
    return x.reshape(x.shape[0], x.shape[1], -1)


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


def _grid(Q, V, chunk):
    """(batch, key heads, steps of `p` chunks) and `p` (`_plan`)."""
    B, T, Hk, Dk = Q.shape
    chunks = T // chunk
    p = _plan(Dk, V.shape[3], chunk, chunks, V.shape[2] // Hk)[1]
    return (B, Hk, chunks // p), p


def _gdn_call(kernel, name, Q, K, V, G, beta, more, out_shape, out_blocks,
              chunk, reverse, decay="gates", dims=None):
    """Both kernels' grid and blocks, of both rules: (batch, key head, step
    of `p` chunks), the last axis sequential. q, k, v and their like are read
    where they lie, as [B, T, heads * dim] with a head's lanes chosen by the
    block index (a key head's `r` value heads are `r * Dv` adjacent lanes, so
    nothing is repeated); G and beta as [B, T, Hv], every head of the step's
    tokens in one block; the per-channel rule's G [B, T, H * Dk] is read like
    a key (`decay="key"`). `dims` are the head dims `(Dk, Dv)` of the rule
    where they are not the operands' (`_filled_out`): q's scale is their
    `Dk^-0.5` and a saved state is `[Dk, Dv]` in HBM, a block whose last two
    dims are the whole array's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Dk, Dv, r = Q.shape[3], V.shape[3], V.shape[2] // Q.shape[2]
    dims = dims or (Dk, Dv)
    grid, p = _grid(Q, V, chunk)
    rows = p * chunk

    def at(c):                          # the chunks a grid step works on
        return grid[2] - 1 - c if reverse else c

    blocks = {
        "key": pl.BlockSpec((1, rows, Dk), lambda b, h, c: (b, at(c), h)),
        "value": pl.BlockSpec((1, rows, r * Dv),
                              lambda b, h, c: (b, at(c), h)),
        "gates": pl.BlockSpec((1, rows, V.shape[2]),
                              lambda b, h, c: (b, at(c), 0)),
        "states": pl.BlockSpec((p, 1, r) + dims,
                               lambda b, h, c: (at(c), b, h, 0, 0)),
        "gate_rows": pl.BlockSpec((1, r, p, 1, chunk),
                                  lambda b, h, c: (b, h, at(c), 0, 0))}
    ins = ["key", "key", "value", decay, "gates"] + [x for x, _ in more]
    return pl.pallas_call(
        functools.partial(kernel, r=r, p=p, scale=dims[0] ** -0.5),
        name=name, grid=grid,
        in_specs=[blocks[x] for x in ins],
        out_specs=[blocks[x] for x in out_blocks], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r, Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_kernels.interpret(),
    )(_flat(Q), _flat(K), _flat(V), G, beta, *[x for _, x in more])


def _fill(x, width):
    """`x` [..., d] with zero channels after its own up to `width`; `x`
    itself where it has them all."""
    d = x.shape[-1]
    if d == width:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - d),))


def _cut(x, like):
    """A kernel's [B, T, heads * width] result in `like`'s shape [B, T,
    heads, d]: the filled-in channels of every head dropped."""
    x = x.reshape(like.shape[:3] + (-1,))
    return x if x.shape == like.shape else x[..., :like.shape[3]]


def _filled_out(Q, K, V, d_out=None):
    """(q, k, v, dO) as the kernels take them, their channels filled out
    with zeros to whole lanes (`_plan`: 96 / 192 -> 128 / 256; nothing
    where a head dim is whole tiles already), and the head dims the op was
    given, which `_gdn_call` takes q's scale and a saved state's shape
    from. Zero key channels add nothing to `k k^T`, `q k^T` or the
    l2-norms, and a zero value column stays zero through the solve and the
    state, so nothing else of the rule depends on the fill."""
    Dk, Dv = Q.shape[3], V.shape[3]
    wide = [_fill(Q, _filled(Dk)), _fill(K, _filled(Dk)),
            _fill(V, _filled(Dv)),
            None if d_out is None
            else _fill(d_out.astype(V.dtype), _filled(Dv))]
    return wide, (Dk, Dv)


def _gdn_forward(Q, K, V, g, beta, chunk):
    """Q, K [B, T, Hk, Dk] and V [B, T, Hv, Dv] as they arrive (not
    normalised), g, beta [B, T, Hv] -> out [B, T, Hv, Dv] in V's dtype and
    the states [chunks, B, Hv, Dk, Dv] float32, each as its chunk found
    it."""
    (q, k, v, _), dims = _filled_out(Q, K, V)
    states, out = _gdn_call(
        _gdn_fwd_kernel, "gdn_fwd", q, k, v, _running_sum(g, chunk),
        beta.astype(jnp.float32), [],
        (_fwd_shapes(Q, V, chunk)[0], _fwd_shapes(q, v, chunk)[1]),
        ["states", "value"], chunk, reverse=False, dims=dims)
    return _cut(out, V), states


def _per_token(x):      # gate rows [B, Hv, chunks, 1, C] -> [B, chunks, C, Hv]
    return jnp.transpose(x[:, :, :, 0, :], (0, 2, 3, 1))


def _gdn_backward(Q, K, V, g, beta, states, d_out, chunk):
    """The five input gradients from the saved states and `d_out`
    [B, T, Hv, Dv], each in its input's shape and dtype."""
    (q, k, v, d_out), dims = _filled_out(Q, K, V, d_out)
    dG, dbeta, dv, dq, dk = _gdn_call(
        _gdn_bwd_kernel, "gdn_bwd", q, k, v, _running_sum(g, chunk),
        beta.astype(jnp.float32),
        [("states", states), ("value", _flat(d_out))],
        _bwd_shapes(q, v, chunk),
        ["gate_rows", "gate_rows", "value", "key", "key"], chunk,
        reverse=True, dims=dims)
    dg = lax.cumsum(_per_token(dG), axis=2, reverse=True)
    return (_cut(dq, Q), _cut(dk, K), _cut(dv, V),
            dg.reshape(g.shape).astype(g.dtype),
            _per_token(dbeta).reshape(beta.shape).astype(beta.dtype))


# ---------------------------------------------------------------------------
# the per-channel rule's two kernels, on the same grid (module docstring)
# ---------------------------------------------------------------------------

class _KdaChunks(_Chunks):
    """One (batch, head, `p` chunks) grid step of the rule under a decay per
    key channel, in `_Chunks`' two layouts (`r` = 1: as many value heads as
    key heads). G is [p C, Dk], so the decay cannot leave the Gram tiles'
    contraction: a tile's two operands each carry their half of it,
    relative to a reference row of the 16-row block (`_KDA_BLOCK`) the
    tile's rows lie in, as `chunked_kda_rule` has it: against the keys of
    the blocks before it the block's first row (`to_first`, `before[b]`:
    every exponent <= 0), against the block's own keys its middle row
    (`to_mid`, `from_mid`: within +-8 |min g|). No gradient passes through a
    reference."""

    def __init__(self, q_ref, k_ref, g_ref, beta_ref, h, p, scale):
        self._operands(q_ref, k_ref, h, 1, p, scale)
        C, B, Dk = self.C, _KDA_BLOCK, q_ref.shape[2]
        n = p * C
        self.nb = C // B
        G = self.G = g_ref[0]                   # running sum, <= 0
        self.beta = self._column(beta_ref[0], 0)
        self.beta_b = self.beside(self.beta)
        shift = B.bit_length() - 1
        self.same = jnp.right_shift(self.row, shift) \
            == jnp.right_shift(self.col, shift)     # a tile's diagonal blocks
        lane = lax.broadcasted_iota(jnp.int32, (B, n), 1)   # of a block's rows
        self.block_col = jnp.bitwise_and(lane, C - 1)
        self.block_part = jnp.right_shift(lane, C.bit_length() - 1)

        def spread(at):     # row `at` of every block, over the block's rows
            return jnp.concatenate(
                [jnp.broadcast_to(G[r0 + at:r0 + at + 1], (B, Dk))
                 for r0 in range(0, n, B)], axis=0)

        middle = spread(B // 2)
        self.to_first = jnp.exp(G - spread(0))
        self.to_mid, self.from_mid = jnp.exp(G - middle), jnp.exp(middle - G)
        self.k_own = self.k * self.from_mid

        def before(b):      # exp(first_b - G_j) of the keys before block b
            parts = []
            for c0 in range(0, n, C):
                first = G[c0 + b * B:c0 + b * B + 1]
                parts += [jnp.exp(first - G[c0:c0 + b * B]),
                          jnp.zeros((C - b * B, Dk), jnp.float32)]
            return jnp.concatenate(parts, axis=0)

        self.before = [None] + [before(b) for b in range(1, self.nb)]
        self.k_before = [None] + [self.k * x for x in self.before[1:]]

    def block(self, x, b):
        """Stacked [p C, m] -> block b of every chunk, stacked [p 16, m]."""
        B = _KDA_BLOCK
        return jnp.concatenate(
            [self.of(i, x)[b * B:(b + 1) * B] for i in range(self.p)], axis=0)

    def unblock(self, blocks):
        """`block`'s inverse: `blocks[b]` [p 16, m] -> stacked [p C, m]."""
        B = _KDA_BLOCK
        return jnp.concatenate([x[i * B:(i + 1) * B] for i in range(self.p)
                                for x in blocks], axis=0)

    def tiles(self, x, full):
        """Rows x [p C, Dk] -> beside [C, p C]: `sum_c x_i[c] k_j[c]
        exp(G_i[c] - G_j[c])` for the keys j up to the end of row i's block
        (zeros after it). A block's rows of both chunks against the padded
        keys before it are one product; the diagonal blocks of all rows
        another."""
        B, part = _KDA_BLOCK, self.block_part
        xf = x * self.to_first
        rows = [jnp.zeros((B, self.p * self.C), jnp.float32)]
        for b in range(1, self.nb):
            t = _dot(self.block(xf, b), self.k_before[b], _NT, full=full)
            out = t[:B]                         # chunk i's rows, its lanes
            for i in range(1, self.p):
                out = jnp.where(part == i, t[i * B:(i + 1) * B], out)
            rows.append(out)
        own = self.beside(_dot(x * self.to_mid, self.k_own, _NT, full=full))
        return jnp.concatenate(rows, axis=0) + jnp.where(self.same, own, 0.0)

    def tiles_grad(self, x, dz, full):
        """The gradient dz (beside, zero above the diagonal) of `tiles(x)`
        to its operands: (dx, dk, dG), each [p C, Dk]. Each half of a tile
        gives G its part, `x dx` to the rows and `-k dk` to the keys."""
        B, part, col = _KDA_BLOCK, self.block_part, self.block_col
        xf = x * self.to_first
        dxf = [jnp.zeros((self.p * B, x.shape[1]), jnp.float32)]
        dk = dG = 0.0
        for b in range(1, self.nb):
            rows = dz[b * B:(b + 1) * B]
            d = jnp.concatenate(
                [jnp.where((part == i) & (col < b * B), rows, 0.0)
                 for i in range(self.p)], axis=0)           # [p 16, p C]
            dxf.append(_dot(d, self.k_before[b], _NN, full=full))
            dkr = _dot(d, self.block(xf, b), _TN, full=full)
            dk = dk + dkr * self.before[b]
            dG = dG - dkr * self.k_before[b]
        dxf = self.unblock(dxf)
        d = self.apart(jnp.where(self.same, dz, 0.0))
        xm = x * self.to_mid
        dxm = _dot(d, self.k_own, _NN, full=full)
        dkr = _dot(d, xm, _TN, full=full)
        return (dxf * self.to_first + dxm * self.to_mid,
                dk + dkr * self.from_mid,
                dG + xf * dxf + xm * dxm - dkr * self.k_own)

    def transposed(self, x):
        """A row [1, Dk] as a column [Dk, 1], or back."""
        n = max(x.shape)
        eye = lax.broadcasted_iota(jnp.int32, (n, n), 0) \
            == lax.broadcasted_iota(jnp.int32, (n, n), 1)
        spread = jnp.where(eye, jnp.broadcast_to(x, (n, n)), 0.0)
        return _rows(spread) if x.shape[0] == 1 else _cols(spread)

    def factors(self, v_ref):
        """The chunks' factors that read no state (module docstring's
        names): v, u, w, qg, k_tail, eg, tail stacked; kkD, P beside; t
        block diagonal; a chunk's last decay as a row `e_last[i]` [1, Dk]
        and as the column `e_col[i]` [Dk, 1] that scales the state's
        rows."""
        C, G, beta = self.C, self.G, self.beta
        kkD = self.tiles(self.k, True)
        a = jnp.where(self.row > self.col, kkD * self.beta_b, 0.0)
        P = jnp.where(self.row >= self.col, self.tiles(self.q, False), 0.0)
        (t,) = self.inverses([a])
        eg = jnp.exp(G)
        v = v_ref[0].astype(jnp.float32)
        k_beg = self.k * (beta * eg)
        last = [G[(i + 1) * C - 1:(i + 1) * C] for i in range(self.p)]
        tail = jnp.exp(self.each(
            lambda i: jnp.broadcast_to(last[i], (C, G.shape[1]))) - G)
        e_last = [jnp.exp(x) for x in last]
        return dict(kkD=kkD, P=P, t=t, eg=eg, v=v, tail=tail,
                    u=_dot(t, v * beta, _NN, full=True),
                    w=_dot(t, k_beg, _NN, full=True), qg=self.q * eg,
                    k_tail=self.k * tail, e_last=e_last,
                    e_col=[self.transposed(x) for x in e_last])


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, o_ref,
                    s_sc, *, r, p, scale):
    """`_gdn_fwd_kernel`'s step for one head (`r` is 1) under the per-channel
    decay: the state's rows decay each by their own channel's
    `exp(G_last)`."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_sc[...] = jnp.zeros_like(s_sc)

    ch = _KdaChunks(q_ref, k_ref, g_ref, beta_ref, pl.program_id(1), p,
                    scale)
    f = ch.factors(v_ref)
    S = s_sc[0]
    v_new, from_state = [], []
    for i in range(p):
        states_ref[i, 0, 0] = S
        v_new.append(ch.of(i, f["u"]) - _dot(ch.of(i, f["w"]), S, _NN))
        from_state.append(_dot(ch.of(i, f["qg"]), S, _NN))
        S = S * f["e_col"][i] + _dot(ch.of(i, f["k_tail"]), v_new[i], _TN)
    s_sc[0] = S
    o = jnp.concatenate(from_state, axis=0) \
        + _dot(ch.apart(f["P"]), jnp.concatenate(v_new, axis=0), _NN)
    o_ref[0] = o.astype(o_ref.dtype)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                    dG_ref, dbeta_ref, dv_ref, dq_ref, dk_ref, ds_sc, *, r,
                    p, scale):
    """`_gdn_bwd_kernel`'s step for one head under the per-channel decay. dG
    is [p C, Dk]: the gradient of the running sum at each token and channel,
    through `exp(G)`, the tail's `exp(G_last - G)`, `exp(G_last)` (at a
    chunk's last token) and both halves of every Gram tile (g's is its
    reverse running sum inside a chunk, taken outside)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_sc[...] = jnp.zeros_like(ds_sc)

    ch = _KdaChunks(q_ref, k_ref, g_ref, beta_ref, pl.program_id(1), p,
                    scale)
    C, beta = ch.C, ch.beta
    f = ch.factors(v_ref)
    eg, tail, u, w = f["eg"], f["tail"], f["u"], f["w"]
    # o = qg S + P v';  S' = Diag(e_last) S + k_tail^T v';  v' = u - w S
    S = [states_ref[i, 0, 0] for i in range(p)]
    dO = do_ref[0].astype(jnp.float32)
    v_new = u - ch.each(lambda i: _dot(ch.of(i, w), S[i], _NN))
    from_out = _dot(ch.apart(f["P"]), dO, _TN)
    dS = [None] * p + [ds_sc[0]]            # dS[i + 1]: of what chunk i gives
    dv_new = [None] * p
    for i in reversed(range(p)):
        dv_new[i] = ch.of(i, from_out) \
            + _dot(ch.of(i, f["k_tail"]), dS[i + 1], _NN)
        dS[i] = _dot(ch.of(i, f["qg"]), ch.of(i, dO), _TN) \
            + dS[i + 1] * f["e_col"][i] - _dot(ch.of(i, w), dv_new[i], _TN)
    ds_sc[0] = dS[0]
    dv_new = jnp.concatenate(dv_new, axis=0)
    dP = jnp.where(ch.row >= ch.col, ch.beside(_dot(dO, v_new, _NT)), 0.0)
    dqg = ch.each(lambda i: _dot(ch.of(i, dO), S[i], _NT))
    dk_tail = ch.each(lambda i: _dot(ch.of(i, v_new), dS[i + 1], _NT))
    dw = -ch.each(lambda i: _dot(ch.of(i, dv_new), S[i], _NT))
    # [u | w] = T [beta v | beta exp(G) k]:  dR = T^T dX,
    # dA = -strict_lower(dR X^T)
    dRu = _dot(f["t"], dv_new, _TN, full=True)
    dRw = _dot(f["t"], dw, _TN, full=True)
    dA = -jnp.where(ch.row > ch.col, ch.beside(
        _dot(dRu, u, _NT, full=True) + _dot(dRw, w, _NT, full=True)), 0.0)
    dv_ref[0] = (dRu * beta).astype(dv_ref.dtype)
    through_w = dRw * ch.k * eg                     # w's rows: beta eg k
    dbeta_row = ch.as_row(_rows(dRu * f["v"] + through_w)
                          + ch.rows(dA * f["kkD"]))
    d_tail = dk_tail * f["k_tail"]
    dkx, dk_a, dG_a = ch.tiles_grad(ch.k, dA * ch.beta_b, True)
    dqx, dk_p, dG_p = ch.tiles_grad(ch.q, dP, False)
    dG = through_w * beta + dqg * f["qg"] - d_tail + dG_a + dG_p
    # G_last, a chunk's last row, also through the tail and e_last
    at = lax.broadcasted_iota(jnp.int32, dG.shape, 0)
    for i in range(p):
        d_last = _cols(ch.of(i, d_tail)) \
            + ch.transposed(_rows(S[i] * dS[i + 1])) * f["e_last"][i]
        dG = dG + jnp.where(at == (i + 1) * C - 1, d_last, 0.0)
        dbeta_ref[0, 0, i] = dbeta_row[:, i * C:(i + 1) * C]
    dG_ref[0] = dG
    dq = (dqg * eg + dqx) * ch.scale
    dk = dRw * (beta * eg) + dk_tail * tail + dkx + dk_a + dk_p
    dq_ref[0] = _l2_grad(ch.qn, ch.rq, dq).astype(dq_ref.dtype)
    dk_ref[0] = _l2_grad(ch.k, ch.rk, dk).astype(dk_ref.dtype)


def _kda_forward(Q, K, V, g, beta, chunk):
    """Q, K, V [B, T, H, D] as they arrive (not normalised), g [B, T, H, Dk],
    beta [B, T, H] -> out in V's shape and dtype and the states [chunks, B,
    H, Dk, Dv] float32, each as its chunk found it."""
    states, out = _gdn_call(
        _kda_fwd_kernel, "kda_fwd", Q, K, V, _running_sum(_flat(g), chunk),
        beta.astype(jnp.float32), [], _fwd_shapes(Q, V, chunk),
        ["states", "value"], chunk, reverse=False, decay="key")
    return out.reshape(V.shape), states


def _kda_backward(Q, K, V, g, beta, states, d_out, chunk):
    """The five input gradients (g's per key channel) from the saved states
    and `d_out`, each in its input's shape and dtype."""
    B, T, H, Dk = Q.shape
    _, dbeta, dv, dq, dk = _bwd_shapes(Q, V, chunk)
    dG = jax.ShapeDtypeStruct((B, T, H * Dk), jnp.float32)
    dG, dbeta, dv, dq, dk = _gdn_call(
        _kda_bwd_kernel, "kda_bwd", Q, K, V, _running_sum(_flat(g), chunk),
        beta.astype(jnp.float32),
        [("states", states), ("value", _flat(d_out.astype(V.dtype)))],
        (dG, dbeta, dv, dq, dk), ["key", "gate_rows", "value", "key", "key"],
        chunk, reverse=True, decay="key")
    dg = lax.cumsum(dG.reshape(B, T // chunk, chunk, H * Dk), axis=2,
                    reverse=True)
    return (dq.reshape(Q.shape), dk.reshape(K.shape), dv.reshape(V.shape),
            dg.reshape(g.shape).astype(g.dtype),
            _per_token(dbeta).reshape(beta.shape).astype(beta.dtype))


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
    return _conv_plan(T, C, K) == "kernel" \
        and _kernels.backend_takes_kernels()


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


def _conv_fwd_kernel(x_ref, halo_ref, w_ref, o_ref, *, K, rows, silu,
                     bias=False):
    """One (batch, channel block, time block) step: the K taps summed in
    float32 (plus the bias, row K of the weight block, where there is one),
    silu, the block written in X's dtype."""
    from jax.experimental import pallas as pl

    w = [w_ref[j:j + 1, :] for j in range(K)]

    def chunk(xx, r0, carry):
        y = _weighted(_taps(xx, K), w)
        if bias:
            y = y + w_ref[K:K + 1, :]
        if silu:
            y = y * jax.nn.sigmoid(y)
        o_ref[0, pl.ds(r0, rows), :] = y.astype(o_ref.dtype)
        return carry

    _conv_chunks(x_ref, halo_ref, pl.program_id(2) == 0, rows, chunk, 0,
                 reverse=False)


def _conv_bwd_kernel(x_ref, halo_ref, do_ref, w_ref, dx_ref, dw_ref, head_sc,
                     acc_sc, *, K, rows, silu, bias=False):
    """One (channel block, batch, time block) step, the time blocks and the
    chunks inside one taken last to first: `dpre = dOut * silu'(pre)` with
    the pre-activation made again, `dX[t] = sum_j W[j] dpre[t + K-1-j]`
    with dpre's first `_PAD` rows of the chunk after (carried; across time
    blocks in `head_sc`; zeros after the end), and `dW[j] += sum_t dpre[t]
    x[t - (K-1) + j]`, kept as 8 sublanes of partial sums in `acc_sc` and
    added to the resident `[K, Cb]` block once a step. With a bias (row K of
    the weight block) the block has a row K too: dBias, dpre's column
    sum."""
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
            if bias:
                pre = pre + w_ref[K:K + 1, :]
            s = jax.nn.sigmoid(pre)
            dpre = dpre * (s * (1.0 + pre * (1.0 - s)))
        dd = jnp.concatenate([dpre, after], axis=0)
        dx = _weighted([dd[K - 1 - j:K - 1 - j + rows] for j in range(K)], w)
        dx_ref[0, pl.ds(r0, rows), :] = dx.astype(dx_ref.dtype)
        for j in range(K):
            p = dpre * taps[j]
            acc_sc[j] += sum(p[i:i + 8] for i in range(0, rows, 8))
        if bias:
            acc_sc[K] += sum(dpre[i:i + 8] for i in range(0, rows, 8))
        return dpre[:_PAD]

    head_sc[...] = _conv_chunks(x_ref, halo_ref, t == last, rows, chunk,
                                head_sc[...], reverse=True)
    for j in range(K + bias):
        dw_ref[j:j + 1, :] += jnp.sum(acc_sc[j], axis=0, keepdims=True)


def _conv_specs(Tb, Cb, K, at):
    """The blocks both kernels read: X's time block, the `_HALO` rows
    before it (the first block reads its own first rows and zeroes them)
    and the weight's channel block as `[K, Cb]` (K the taps, and one row
    more where the op has a bias); `at(*grid)` gives (batch, time block,
    channel block)."""
    from jax.experimental import pallas as pl

    def halo(*g):
        b, t, c = at(*g)
        return b, jnp.maximum(t * (Tb // _HALO) - 1, 0), c

    return (pl.BlockSpec((1, Tb, Cb), at), pl.BlockSpec((1, _HALO, Cb), halo),
            pl.BlockSpec((K, Cb), lambda *g: (0, at(*g)[2])))


def _weight_rows(W, bias):
    """W [C, K] as the kernels read it, `[K, C]` float32, the bias where
    there is one as row K."""
    w = W.astype(jnp.float32).T
    if bias is None:
        return w
    return jnp.concatenate([w, bias.astype(jnp.float32)[None]], axis=0)


def _conv_forward(X, W, silu, bias=None):
    """`causal_conv_fwd`: X [B, T, C] as it arrives, W [C, K] (and a bias
    [C]) -> Out in X's dtype. Every intermediate stays in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (B, T, C), K = X.shape, W.shape[1]
    Tb, Cb, rows = _conv_blocks(T, C)
    more = {} if bias is None else {"bias": True}
    x_spec, halo_spec, w_spec = _conv_specs(Tb, Cb, K + len(more),
                                            lambda b, c, t: (b, t, c))
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, K=K, rows=rows, silu=silu,
                          **more),
        name="causal_conv_fwd", grid=(B, C // Cb, T // Tb),
        in_specs=[x_spec, halo_spec, w_spec], out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(X.shape, X.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=_kernels.interpret(),
    )(X, X, _weight_rows(W, bias))


def _conv_backward(X, W, d_out, silu, bias=None):
    """`causal_conv_bwd`: (dX in X's dtype, dW [C, K] float32, and with a
    bias dBias [C] float32) from X, W (and the bias) and dOut. The channel blocks
    lead the grid, so a block of dW stays resident while the batch and the
    time blocks (last to first) add to it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (B, T, C), K = X.shape, W.shape[1]
    Tb, Cb, rows = _conv_blocks(T, C)
    n = T // Tb
    more = {} if bias is None else {"bias": True}
    Kb = K + len(more)
    x_spec, halo_spec, w_spec = _conv_specs(
        Tb, Cb, Kb, lambda c, b, t: (b, n - 1 - t, c))
    dX, dW = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, K=K, rows=rows, silu=silu,
                          **more),
        name="causal_conv_bwd", grid=(C // Cb, B, n),
        in_specs=[x_spec, halo_spec, x_spec, w_spec],
        out_specs=[x_spec, w_spec],
        out_shape=[jax.ShapeDtypeStruct(X.shape, X.dtype),
                   jax.ShapeDtypeStruct((Kb, C), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_PAD, Cb), jnp.float32),
                        pltpu.VMEM((Kb, 8, Cb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_kernels.interpret(),
    )(X, X, d_out, _weight_rows(W, bias))
    if bias is None:
        return dX, dW.T
    return dX, dW[:K].T, dW[K]


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


def _tally_grid(ctx, Q, V, chunk):
    """The grid steps this op's kernel call runs, onto the compile event
    (`gdn_grid_steps`, summed over the program's ops and grad ops): what
    says how many chunks a step took."""
    (B, Hk, steps), _ = _grid(Q, V, chunk)
    ctx.tally("gdn_grid_steps", B * Hk * steps)


def _gated_delta_rule_infer(ctx, structs):
    """Build-time shapes without a trace of the rule, of both rules' ops: a
    machine with no TPU takes the XLA form, which saves no `States`, and the
    program it builds may run on one that has."""
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
        _tally_grid(ctx, Q, V, chunk)
        filled = [_filled(d) - d for d in (Dk, V.shape[3])]
        if any(filled):     # zero channels a key and a value head gained
            ctx.note(gdn_lanes_filled=filled)
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
        chunk = int(ctx.attr("chunk", 64))
        _tally_grid(ctx, raw[0], raw[2], chunk)
        grads = _gdn_backward(*raw, states, d_out, chunk)
    return {s: d.astype(x.dtype) for s, d, x in zip(slots, grads, raw)}


_KDA_SLOTS = ("Q", "K", "V", "G", "Beta")


def _kda_rule(Q, K, V, G, Beta, chunk):
    """The op on its operands as they arrive: q and k l2-normalised over a
    head in float32 (q then scaled by `Dk^-0.5`), the chunked rule, Out in
    V's dtype."""
    q = l2_normalize(Q.astype(jnp.float32)) * Q.shape[3] ** -0.5
    k = l2_normalize(K.astype(jnp.float32))
    out = chunked_kda_rule(q, k, V.astype(jnp.float32),
                           G.astype(jnp.float32), Beta.astype(jnp.float32),
                           chunk)
    return out.astype(V.dtype)


def _kda_check(ctx, Q, V, G, kernels=None):
    """(chunk, whether a kernel runs: by the shape and the backend, unless
    the caller knows), after the shapes are checked and the call is counted
    on the compile event: `kda_plan`, the rule's chunk steps
    (`kda_grid_steps`: batch x heads x chunks, summed over the program's ops
    and grad ops, whatever runs them) and, where a kernel runs, the grid
    steps its call takes (`kda_kernel_grid_steps`: batch x heads x chunks /
    `p`)."""
    chunk = int(ctx.attr("chunk", 64))
    B, T, H, Dk = Q.shape
    if T % chunk or V.shape[2] != H or G.shape != Q.shape:
        raise ValueError(f"kda_delta_rule needs a length that is a multiple "
                         f"of the chunk ({chunk}), as many value heads as "
                         f"key heads and a decay of q's shape, got q "
                         f"{Q.shape}, v {V.shape}, g {G.shape}")
    if kernels is None:     # whole lanes only: nothing fills this rule's out
        kernels = Dk % _LANES == 0 and V.shape[3] % _LANES == 0 \
            and _kernels_run(Dk, V.shape[3], chunk)
    ctx.note(kda_plan="kernel" if kernels else "xla")
    ctx.tally("kda_grid_steps", B * H * (T // chunk))
    if kernels:
        ctx.tally("kda_kernel_grid_steps", B * H * _grid(Q, V, chunk)[0][2])
    return chunk, kernels


@register_op("kda_delta_rule", infer=_gated_delta_rule_infer,
             propagate_seqlen=False)
def _kda_delta_rule(ctx, Q, K, V, G, Beta):
    """Q, K [B, T, H, Dk], V [B, T, H, Dv], G [B, T, H, Dk] (the log-decay of
    every key channel, float32, bounded below: `kda_gates`), Beta [B, T, H]
    -> Out [B, T, H, Dv] in V's dtype: per head a state S [Dk, Dv] from 0,
    `S <- Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
    o_t = S^T q_t`, in chunks of `chunk` tokens. q and k are l2-normalised
    over a head (`x * rsqrt(sum x^2 + 1e-6)`), q then scaled by `Dk^-0.5`.
    On the kernel path (`_plan`: `kda_fwd`) the rule also returns `States`
    [T / chunk, B, H, Dk, Dv] float32, the state each chunk started from,
    which the grad op reads back; elsewhere it is `chunked_kda_rule`."""
    chunk, kernels = _kda_check(ctx, Q, V, G)
    if kernels:
        out, states = _kda_forward(Q, K, V, G, Beta, chunk)
        return {"Out": out, "States": states}
    return {"Out": _kda_rule(Q, K, V, G, Beta, chunk)}


@register_grad("kda_delta_rule")
def _kda_delta_rule_grad(ctx, ins, out_grads):
    """The five input gradients (G's per key channel). Where the forward op
    saved its `States`, `kda_bwd` alone on them; where it saved none (the
    XLA form, a program built without the slot) `jax.vjp` of the chunked
    form, which makes a chunk's factors again from the op's inputs."""
    d_out = out_grads["Out"][0]
    if d_out is None:
        return {}
    raw = [ins[s][0] for s in _KDA_SLOTS]
    states = ctx.fwd_outs.get("States", [None])[0]
    chunk, kernels = _kda_check(ctx, raw[0], raw[2], raw[3],
                                kernels=states is not None)
    if kernels:
        grads = _kda_backward(*raw, states, d_out, chunk)
    else:
        out, vjp = jax.vjp(functools.partial(_kda_rule, chunk=chunk), *raw)
        grads = vjp(d_out.astype(out.dtype))
    return {s: d.astype(x.dtype) for s, d, x in zip(_KDA_SLOTS, grads, raw)}
