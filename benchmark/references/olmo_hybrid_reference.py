"""Olmo-Hybrid (a dense hybrid of gated-delta-rule linear attention whose
write strength reaches 2, three layers in four, and OLMo's softmax attention
with q and k normed over the whole projection, every sublayer normed on the
way out) in plain `jax.numpy`: the forward pass, the loss and its gradients,
for ONE CHIP'S SHARE of each layer's heads. What the program
(`paddle_tpu/models/olmo_hybrid.py`) is compared with; written from the
equations below, not from that file.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel, no
chunks: the delta rule is its recurrence, token by token; the convolution is
`kernel` shifted products; attention is a masked softmax. Weights come as a
dict under the program's parameter names, matrices stored `[in, out]` (D
hidden, V the vocabulary rows held, H the heads HELD here: every per-head
width below is read off the weights, so the same functions compute a share
and the whole layer):

    embed.w [V, D]   head.w [D, V]   final_norm.w [D]
    l<i>.mixer_norm.w, l<i>.mlp_norm.w [D]
    l<i>.gdn.q.w, l<i>.gdn.k.w [D, H * key_dim]
    l<i>.gdn.v.w, l<i>.gdn.g.w [D, H * value_dim]
    l<i>.gdn.a.w, l<i>.gdn.b.w [D, H]     l<i>.gdn.A_log, l<i>.gdn.dt_bias [H]
    l<i>.gdn.conv.w [H * (2 key_dim + value_dim), kernel]    over [q | k | v]
    l<i>.gdn.norm.w [value_dim]           l<i>.gdn.o.w [H * value_dim, D]
    l<i>.attn.q.w, .k.w, .v.w [D, H * head_dim]   l<i>.attn.o.w [H * head_dim, D]
    l<i>.attn.q_norm.w, l<i>.attn.k_norm.w [H * head_dim]
    l<i>.mlp.gate.w, l<i>.mlp.up.w [D, F]   l<i>.mlp.down.w [F, D]

The equations (flash-linear-attention's `GatedDeltaNet` with
`allow_neg_eigval`; the block and the attention layer OLMo 2 / OLMo 3's):

    N(x) = x * rsqrt(mean(x^2) + eps) * w
    layer i:  h = x + N(Mixer_i(x));  y = h + N(MLP(h));  after the last layer
              N, then the head.  MLP(h) = W_down(silu(W_gate h) * W_up h)
    GDN:  q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
          (causal, depthwise, no bias);  beta = 2 sigmoid(x W_b);
          g = -exp(A_log) softplus(x W_a + dt_bias);
          q = q / sqrt(sum q^2 + 1e-6) * key_dim^-0.5, k likewise unscaled;
          per head, S_0 = 0 [key_dim, value_dim], for every token
              S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
              o_t = S^T q_t
          out = (o * rsqrt(mean(o^2) + eps) * w * silu(x W_g)) W_o  (the norm
          over a head)
    Attn: q = N(x W_q), k = N(x W_k) over the whole projection HELD (under a
          share the mean is over the held channels; `qk` hands the layer q and
          k already normed, which is how a test gives a share the whole
          layer's statistic), v = x W_v; heads of `head_dim`; rotary
          (rotate-half) at `rope_theta`, none where it is None; causal
          softmax at head_dim^-0.5; W_o
    loss = mean cross-entropy

The share: what the absent heads would add to a mixer's output is left out,
here as in the program, and that partial result is what the out-norm and the
residual take on; the vocabulary is the slice the weights have.

`dtype` other than float32 computes everything, the decay, the state and the
loss included, in that precision: the comparison's tolerance has to refuse
it. `q_block` computes the attention a block of queries at a time and the
head's cross-entropy a block of positions at a time; `token_block` runs the
recurrence as an outer scan over blocks of that many tokens under
`jax.checkpoint` around the scan over a block's tokens; `remat` wraps each
layer in `jax.checkpoint`: all three are this reference's memory at published
widths, not its mathematics (a test holds that they change nothing).
`fault` plants one of `FAULTS`: a wrong function that a comparison has to
refuse.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools

import jax
import jax.numpy as jnp

FAULTS = {
    "beta_unscaled": "beta = sigmoid(b), without the 2: no negative "
                     "eigenvalue",
    "k_not_normed": "the rule's keys as the convolution leaves them, not "
                    "l2-normalised",
    "q_unscaled": "the rule's queries l2-normalised and not scaled by "
                  "key_dim^-0.5",
    "decay_after_write": "S <- exp(g) (S + k d^T): the token's own write "
                         "decays with the rest",
    "sigmoid_out_gate": "the output gate sigmoid(x W_g), not silu",
    "gate_before_norm": "o * silu(z) first, then the norm over a head",
    "conv_sees_future": "the convolution's taps one place late: output t "
                        "reads input t + 1",
    "conv_no_silu": "no silu after the convolution",
    "norm_on_the_way_in": "h = x + Mixer(N(x)), y = h + MLP(N(h)): the "
                          "block's norms on the way in",
    "qk_norm_per_head": "q and k normed over each head's channels, not over "
                        "the whole projection",
    "no_qk_norm": "q and k as the projections leave them",
    "rotary_on": "the attention layer's q and k turned by rotary at theta "
                 "500000 (OLMo 3's), where the config's null turns nothing",
}


def rms_norm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def rotary(x, theta):
    """x [B, H, T, Dh]; rotate-half over the whole head."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(w, x, *, head_dim, theta, eps, q_block=None, fault=None,
              qk=None):
    """Causal softmax attention of one layer (its weights `w` by their names
    after `l<i>.attn.`) on x [B, T, D], `q_block` queries at a time. `qk`:
    (q, k) [B, T, H * head_dim] already normed, in place of this function's
    own norm over what it holds."""
    b, t, _ = x.shape
    heads = w["v.w"].shape[1] // head_dim

    def split(a):                                   # -> [B, H, T, Dh]
        return a.reshape(b, t, heads, head_dim).transpose(0, 2, 1, 3)

    if qk is not None:
        q, k = qk
    elif fault == "no_qk_norm":
        q, k = x @ w["q.w"], x @ w["k.w"]
    elif fault == "qk_norm_per_head":
        q, k = ((rms_norm((x @ w[n + ".w"]).reshape(b, t, heads, head_dim),
                          w[n + "_norm.w"].reshape(heads, head_dim), eps)
                 ).reshape(b, t, heads * head_dim) for n in "qk")
    else:
        q = rms_norm(x @ w["q.w"], w["q_norm.w"], eps)
        k = rms_norm(x @ w["k.w"], w["k_norm.w"], eps)
    q, k, v = split(q), split(k), split(x @ w["v.w"])
    if fault == "rotary_on":
        theta = 500000.0
    if theta is not None:
        q, k = rotary(q, theta), rotary(k, theta)
    step = q_block or t
    blocks = []
    for first in range(0, t, step):
        end = min(first + step, t)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, first:end],
                            k[:, :, :end]) * head_dim ** -0.5
        row = jnp.arange(first, end)[:, None]
        col = jnp.arange(end)[None, :]
        scores = jnp.where(col > row, -jnp.inf, scores)
        weights = jax.nn.softmax(scores, axis=-1)
        blocks.append(jnp.einsum("bhqk,bhkd->bhqd", weights, v[:, :, :end]))
    ctx = jnp.concatenate(blocks, axis=2).transpose(0, 2, 1, 3)
    return ctx.reshape(b, t, heads * head_dim) @ w["o.w"]


def delta_rule(q, k, v, g, beta, token_block=None, fault=None):
    """The gated delta rule as its recurrence. q, k [B, T, H, Dk] (normalised,
    q scaled), v [B, T, H, Dv], g, beta [B, T, H] -> o [B, T, H, Dv]."""
    b, t, h, dk = q.shape

    def token(S, x):
        q_t, k_t, v_t, g_t, beta_t = x              # [B, H, ...]
        decay = jnp.exp(g_t)[..., None, None]
        if fault != "decay_after_write":
            S = S * decay
        read = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        delta = (v_t - read) * beta_t[..., None]
        S = S + k_t[..., :, None] * delta[..., None, :]
        if fault == "decay_after_write":
            S = S * decay
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    step = token_block or t
    xs = [jnp.moveaxis(a, 1, 0).reshape((t // step, step) + a.shape[:1]
                                        + a.shape[2:])
          for a in (q, k, v, g, beta)]
    S0 = jnp.zeros((b, h, dk, v.shape[-1]), q.dtype)
    _, o = jax.lax.scan(block, S0, xs)              # [T/step, step, B, H, Dv]
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def causal_conv(x, w, fault=None):
    """x [B, T, C], w [C, K]: output t is `sum_j w[:, j] x[t - (K-1) + j]`
    with zeros before the start, then silu."""
    t, kernel = x.shape[1], w.shape[1]
    before, after = (kernel - 2, 1) if fault == "conv_sees_future" \
        else (kernel - 1, 0)
    padded = jnp.pad(x, ((0, 0), (before, after), (0, 0)))
    y = sum(padded[:, j:j + t] * w[:, j] for j in range(kernel))
    return y if fault == "conv_no_silu" else jax.nn.silu(y)


def gated_delta_net(w, x, *, key_dim, value_dim, eps, allow_neg_eigval=True,
                    token_block=None, fault=None):
    """One linear-attention mixer (weights by their names after `l<i>.gdn.`)
    on x [B, T, D], for the heads the weights hold."""
    b, t, _ = x.shape
    heads = w["a.w"].shape[1]
    wide_k = heads * key_dim
    conv = causal_conv(
        jnp.concatenate([x @ w["q.w"], x @ w["k.w"], x @ w["v.w"]], axis=-1),
        w["conv.w"], fault)
    q = conv[..., :wide_k].reshape(b, t, heads, key_dim)
    k = conv[..., wide_k:2 * wide_k].reshape(b, t, heads, key_dim)
    v = conv[..., 2 * wide_k:].reshape(b, t, heads, value_dim)
    z = (x @ w["g.w"]).reshape(b, t, heads, value_dim)
    beta = jax.nn.sigmoid(x @ w["b.w"])
    if allow_neg_eigval and fault != "beta_unscaled":
        beta = 2 * beta
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(x @ w["a.w"] + w["dt_bias"])
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    if fault != "q_unscaled":
        q = q * key_dim ** -0.5
    if fault != "k_not_normed":
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    o = delta_rule(q, k, v, g.astype(q.dtype), beta, token_block, fault)
    gate = jax.nn.sigmoid(z) if fault == "sigmoid_out_gate" \
        else jax.nn.silu(z)
    if fault == "gate_before_norm":
        o = rms_norm(o * gate, w["norm.w"], eps)
    else:
        o = rms_norm(o, w["norm.w"], eps) * gate
    return o.reshape(b, t, heads * value_dim) @ w["o.w"]


def mlp(w, x):
    return (jax.nn.silu(x @ w["gate.w"]) * (x @ w["up.w"])) @ w["down.w"]


def mixer(w, x, kind, s):
    """The layer's mixer alone (weights by their names after `l<i>.`), before
    the out-norm: what the shares of a layer add up in."""
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    if kind == "full_attention":
        return attention(sub("attn."), x, head_dim=s["head_dim"],
                         theta=s["rope_theta"], eps=s["rms_eps"],
                         q_block=s["q_block"], fault=s["fault"])
    return gated_delta_net(
        sub("gdn."), x, key_dim=s["key_dim"], value_dim=s["value_dim"],
        eps=s["rms_eps"], allow_neg_eigval=s["allow_neg_eigval"],
        token_block=s["token_block"], fault=s["fault"])


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer(w, x, kind, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D];
    `sizes` is a tuple of (name, value) pairs."""
    s = dict(sizes)
    eps = s["rms_eps"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    if s["fault"] == "norm_on_the_way_in":
        x = x + mixer(w, rms_norm(x, w["mixer_norm.w"], eps), kind, s)
        return x + mlp(sub("mlp."), rms_norm(x, w["mlp_norm.w"], eps))
    x = x + rms_norm(mixer(w, x, kind, s), w["mixer_norm.w"], eps)
    return x + rms_norm(mlp(sub("mlp."), x), w["mlp_norm.w"], eps)


@functools.partial(jax.jit, static_argnums=(3,))
def head_ce(x, w_head, labels, block=None):
    """Cross-entropy per token [B, T] of `x W_head` against `labels`,
    `block` positions at a time (all at once by default)."""
    t = x.shape[1]
    step = block or t
    out = []
    for first in range(0, t, step):
        logits = x[:, first:first + step] @ w_head
        picked = jnp.take_along_axis(
            logits, labels[:, first:first + step, None], axis=-1)[..., 0]
        out.append(jax.nn.logsumexp(logits, axis=-1) - picked)
    return jnp.concatenate(out, axis=1)


PERIOD = ("linear_attention",) * 3 + ("full_attention",)


def loss_parts(params, tokens, labels, *, n_layer, layer_types=PERIOD,
               head_dim=128, key_dim=96, value_dim=192,
               allow_neg_eigval=True, rope_theta=None, rms_eps=1e-6,
               dtype=jnp.float32, q_block=None, token_block=None,
               remat=False, last=None, fault=None):
    """`loss` and `ce` (the mean cross-entropy, twice: the loss has no other
    part). With `last`, also `logits` on the final `last` positions,
    [B, last, V]."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault is one of {sorted(FAULTS)}, got {fault!r}")
    sizes = tuple(sorted(dict(
        head_dim=head_dim, key_dim=key_dim, value_dim=value_dim,
        allow_neg_eigval=allow_neg_eigval, rope_theta=rope_theta,
        rms_eps=rms_eps, q_block=q_block, token_block=token_block,
        fault=fault).items(), key=lambda kv: kv[0]))
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(2, 3)) if remat \
            else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        for i in range(n_layer):
            prefix = f"l{i}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            x = apply(w, x, layer_types[i % len(layer_types)], sizes)
        x = rms_norm(x, p["final_norm.w"], rms_eps)
        ce_of = jax.checkpoint(head_ce, static_argnums=(3,)) if remat \
            else head_ce
        ce = jnp.mean(ce_of(x, p["head.w"], labels, q_block))
        out = {"loss": ce, "ce": ce}
        if last is not None:
            out["logits"] = x[:, -last:] @ p["head.w"]
        return out


def loss_and_grads(params, tokens, labels, wrt=None, **kw):
    """(parts, {name: gradient of `loss`}) for the parameters named in `wrt`
    (all of them by default)."""
    names = sorted(params) if wrt is None else list(wrt)

    def f(sub):
        parts = loss_parts({**params, **sub}, tokens, labels, **kw)
        return parts["loss"], parts

    (_, parts), grads = jax.value_and_grad(f, has_aux=True)(
        {n: jnp.asarray(params[n], jnp.float32) for n in names})
    return parts, grads
