"""Linear attention by the gated delta rule (Gated DeltaNet; the mixer of
three layers in four of `qwen3_next`), and the two small ops around it.

    causal_conv1d:      a depthwise convolution over time, then silu
    delta_rule_gates:   g = -exp(A_log) * softplus(a + dt_bias) <= 0 (the log
                        of a head's decay), beta = sigmoid(b)
    gated_delta_rule:   per value head a state S [key, value], S_0 = 0:
                          S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);
                          S <- S + k_t d^T;  o_t = S^T q_t

`gated_delta_rule` computes the recurrence in chunks of `chunk` tokens (the
WY / UT-transform form of the public `qwen3_next` code and of
flash-linear-attention). Inside a chunk, with G the running sum of g and
D[i, j] = exp(G_i - G_j) for i >= j:

    A = strict_lower((beta k) k^T * D);   T = (I + A)^-1  (a unit-lower-
    triangular solve);   u = T (beta v);   w = T (beta k exp(G))

all chunks at once. Across chunks a `lax.scan` carries S:

    v' = u - w S;   S <- S exp(G_last) + (k exp(G_last - G))^T v'

and writes out S as each chunk found it and v'; the outputs follow for all
chunks at once, o = (q exp(G)) S + lower(q k^T * D) v'. Every exponent is
<= 0, so nothing overflows however negative g is. g, beta, the running sums,
the l2-norms of q and k, the solve and the state are float32 whatever dtype
flows through (q, k, v arrive in bf16 under AMP; none of the three ops is on
an AMP list but `delta_rule_gates`, which is on AMP_F32_OPS so that a and b
are widened before the softplus); A and the solve take their products at
HIGHEST, since T multiplies everything after it; the other products run at
the backend's default precision on float32 operands. The gradient is the
generic one (`jax.vjp` of the rule): no custom call here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op


@register_op("causal_conv1d")
def _causal_conv1d(ctx, X, W):
    """X [B, T, C], W [C, K]: `y[t, c] = sum_j W[c, j] x[t - (K-1) + j, c]`
    (zeros before t = 0, so output t reads inputs <= t only), then silu
    unless `activation` is empty. Float32 sums, the input's dtype out."""
    K = W.shape[1]
    T = X.shape[1]
    x32 = jnp.pad(X.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    w32 = W.astype(jnp.float32)
    y = sum(x32[:, j:j + T] * w32[:, j] for j in range(K))
    if ctx.attr("activation", "silu") == "silu":
        y = jax.nn.silu(y)
    return {"Out": y.astype(X.dtype)}


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


@register_op("gated_delta_rule", propagate_seqlen=False)
def _gated_delta_rule(ctx, Q, K, V, G, Beta):
    """Q, K [B, T, Hk, Dk], V [B, T, Hv, Dv], G, Beta [B, T, Hv] -> Out
    [B, T, Hv, Dv] in V's dtype. Hv is a multiple of Hk: key head j serves
    value heads j * Hv/Hk .. (j + 1) * Hv/Hk - 1. q and k are l2-normalised
    over a head (`x * rsqrt(sum x^2 + 1e-6)`), q then scaled by
    `Dk^-0.5`. T must be a multiple of `chunk`."""
    chunk = int(ctx.attr("chunk", 64))
    T, Hk, Dk = Q.shape[1], Q.shape[2], Q.shape[3]
    Hv = V.shape[2]
    if T % chunk or Hv % Hk:
        raise ValueError(f"gated_delta_rule needs a length that is a multiple "
                         f"of the chunk ({chunk}) and value heads that are a "
                         f"multiple of the key heads, got T {T}, heads {Hk} "
                         f"and {Hv}")
    q = l2_normalize(Q.astype(jnp.float32)) * Dk ** -0.5
    k = l2_normalize(K.astype(jnp.float32))
    if Hv != Hk:
        q = jnp.repeat(q, Hv // Hk, axis=2)
        k = jnp.repeat(k, Hv // Hk, axis=2)
    out = chunked_gated_delta_rule(q, k, V.astype(jnp.float32),
                                   G.astype(jnp.float32),
                                   Beta.astype(jnp.float32), chunk)
    return {"Out": out.astype(V.dtype)}
