"""Qwen3-Next (a hybrid of gated-delta-rule linear attention and gated softmax
attention over a sparse-expert feed-forward with a shared expert) in plain
`jax.numpy`: the forward pass, the loss and its gradients, for ONE CHIP'S SHARE
of the expert layers. What the program (`paddle_tpu/models/qwen3_next.py`) is
compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel, no
sort, no grouped matmul, no chunks: the delta rule is its recurrence, token by
token; attention is a masked softmax; the held experts are a loop (a `lax.scan`
over their stacked weights, so that one expert's program is compiled once: 32
experts a layer unrolled in float32 "highest" took 16 minutes and 36 GB of
host memory to compile for the chip), each applied to every token and kept
through a dense mask of the router's weights.
Weights come as a dict under the program's parameter names, matrices stored
`[in, out]` (D hidden, V the vocabulary rows held, E experts routed over, H of
them held here, F an expert's width):

    embed.w [V, D]   head.w [D, V]   final_norm.w [D]
    l<i>.in_norm.w, l<i>.post_norm.w [D]                    (zero-centred)
    l<i>.attn.q.w [D, heads * 2 * head_dim]   a head's query and its output
                                              gate side by side
    l<i>.attn.k.w, l<i>.attn.v.w [D, kv_heads * head_dim]
    l<i>.attn.q_norm.w, l<i>.attn.k_norm.w [head_dim]       (zero-centred)
    l<i>.attn.o.w [heads * head_dim, D]
    l<i>.gdn.qkvz.w [D, key_heads * (2 key_dim + 2 r value_dim)]   per key
        head: q, k, then r = value_heads / key_heads values, then r gates z
    l<i>.gdn.ba.w [D, key_heads * 2 r]    per key head: r of b, then r of a
    l<i>.gdn.conv.w [key_heads * 2 key_dim + value_heads * value_dim, kernel]
    l<i>.gdn.A_log, l<i>.gdn.dt_bias [value_heads]
    l<i>.gdn.norm.w [value_dim]   l<i>.gdn.out.w [value_heads * value_dim, D]
    l<i>.router.w [D, E]
    l<i>.experts.gate.w, l<i>.experts.up.w [H, D, F]  l<i>.experts.down.w [H, F, D]
    l<i>.shared.gate.w, l<i>.shared.up.w [D, Fs]      l<i>.shared.down.w [Fs, D]
    l<i>.shared_gate.w [D, 1]

The equations (the public `qwen3_next` model code):

    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)
    layer i:  h = x + Mixer_i(N(x));  y = h + MoE(N(h));  after the last layer
              N, then the head.  Mixer_i is Attn where (i + 1) % interval == 0
    Attn: [q | gate] = x W_q per head; k, v = x W_k, x W_v; q, k = N(q), N(k)
          over a head; rotary (rotate-half) on the first `rotary_dim` dims;
          causal softmax attention at head_dim^-0.5, a key-value head serving
          heads / kv_heads query heads; out = (ctx * sigmoid(gate)) W_o
    GDN:  [q, k, v, z], [b, a] = x W_qkvz, x W_ba; [q | k | v] <- silu(causal
          depthwise conv); beta = sigmoid(b); g = -exp(A_log) softplus(a +
          dt_bias); q = q / sqrt(sum q^2 + 1e-6) * key_dim^-0.5, k likewise
          without the scale; per value head, S_0 = 0, for every token
              S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
              o_t = S^T q_t
          out = (o * rsqrt(mean(o^2) + eps) * w * silu(z)) W_out
    MoE:  p = softmax(x W_r) over all E; the top-k of p divided by their sum
          (`norm_topk_prob`); routed = sum over the chosen experts THAT ARE
          HELD HERE of p_k * down_e(silu(gate_e x) * up_e x); shared =
          sigmoid(x w_s) * down_s(silu(gate_s x) * up_s x); routed + shared
    loss = mean cross-entropy + aux_coef * E * sum_e f_e P_e over all layers'
           router rows (f_e the assignments to e per row, P_e the mean
           probability; all E experts, wherever they live)

Departures from the public code: no multi-token-prediction module (the
config has no key for it); the load-balancing loss is the form the `olmoe`
code has, with an assumed coefficient. The share: what the absent experts
would add is left out, here as in the program, and that partial result goes
on to the next layer; the vocabulary is the slice the weights have.

`dtype` other than float32 computes everything, the router, the decay, the
state and the losses included, in that precision: the comparison's tolerance
has to refuse it. `q_block` computes the attention a block of queries at a
time and the head's cross-entropy a block of positions at a time;
`token_block` runs the recurrence as an outer scan over blocks of that many
tokens under `jax.checkpoint` around the scan over a block's tokens, so that
its gradient keeps a state a block and not one a token; `remat` wraps each
layer in `jax.checkpoint`: all three are this reference's memory at published
widths, not its mathematics (a test holds that they change nothing). A layer
is one jitted function of its own weights, so the layers of a kind share one
compiled program.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    """Zero-centred weight: `(1 + w)`."""
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1 + w)


def rotary(x, theta, rotary_dim):
    """x [B, H, T, Dh]; rotate-half on the first `rotary_dim` dims."""
    t, r = x.shape[-2], rotary_dim
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    head, rest = x[..., :r], x[..., r:]
    x1, x2 = head[..., : r // 2], head[..., r // 2:]
    turned = head * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return jnp.concatenate([turned, rest], axis=-1)


def attention(w, x, *, n_head, n_kv_head, head_dim, rotary_dim, theta, eps,
              q_block=None):
    """Gated causal softmax attention of one layer (its weights `w` by their
    names after `l<i>.attn.`) on x [B, T, D], `q_block` queries at a time."""
    b, t, _ = x.shape
    qg = (x @ w["q.w"]).reshape(b, t, n_head, 2 * head_dim)
    q, gate = qg[..., :head_dim], qg[..., head_dim:]
    k = (x @ w["k.w"]).reshape(b, t, n_kv_head, head_dim)
    v = (x @ w["v.w"]).reshape(b, t, n_kv_head, head_dim)
    q = rotary(rms_norm(q, w["q_norm.w"], eps).transpose(0, 2, 1, 3), theta,
               rotary_dim)
    k = rotary(rms_norm(k, w["k_norm.w"], eps).transpose(0, 2, 1, 3), theta,
               rotary_dim)
    group = n_head // n_kv_head         # query head h reads kv head h // group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1)
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
    ctx = ctx * jax.nn.sigmoid(gate)
    return ctx.reshape(b, t, n_head * head_dim) @ w["o.w"]


def delta_rule(q, k, v, g, beta, token_block=None):
    """The gated delta rule as its recurrence. q, k [B, T, H, Dk] (normalised,
    q scaled), v [B, T, H, Dv], g, beta [B, T, H] -> o [B, T, H, Dv]."""
    b, t, h, dk = q.shape

    def token(S, x):
        q_t, k_t, v_t, g_t, beta_t = x              # [B, H, ...]
        S = S * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        delta = (v_t - read) * beta_t[..., None]
        S = S + k_t[..., :, None] * delta[..., None, :]
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


def causal_conv_silu(x, w):
    """x [B, T, C], w [C, K]: output t is `sum_j w[:, j] x[t - (K-1) + j]`
    with zeros before the start, then silu."""
    t, kernel = x.shape[1], w.shape[1]
    padded = jnp.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + t] * w[:, j] for j in range(kernel))
    return jax.nn.silu(y)


def gated_delta_net(w, x, *, n_key_head, n_value_head, key_dim, value_dim,
                    eps, token_block=None):
    """One linear-attention mixer (weights by their names after `l<i>.gdn.`)
    on x [B, T, D]."""
    b, t, _ = x.shape
    r = n_value_head // n_key_head
    mixed = (x @ w["qkvz.w"]).reshape(b, t, n_key_head,
                                      2 * key_dim + 2 * r * value_dim)
    q, k = mixed[..., :key_dim], mixed[..., key_dim:2 * key_dim]
    v = mixed[..., 2 * key_dim:2 * key_dim + r * value_dim]
    z = mixed[..., 2 * key_dim + r * value_dim:]
    ba = (x @ w["ba.w"]).reshape(b, t, n_key_head, 2 * r)
    b_in = ba[..., :r].reshape(b, t, n_value_head)
    a_in = ba[..., r:].reshape(b, t, n_value_head)
    wide_k, wide_v = n_key_head * key_dim, n_value_head * value_dim
    conv = causal_conv_silu(
        jnp.concatenate([q.reshape(b, t, wide_k), k.reshape(b, t, wide_k),
                         v.reshape(b, t, wide_v)], axis=-1), w["conv.w"])
    q = conv[..., :wide_k].reshape(b, t, n_key_head, key_dim)
    k = conv[..., wide_k:2 * wide_k].reshape(b, t, n_key_head, key_dim)
    v = conv[..., 2 * wide_k:].reshape(b, t, n_value_head, value_dim)
    z = z.reshape(b, t, n_value_head, value_dim)
    beta = jax.nn.sigmoid(b_in)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a_in + w["dt_bias"])
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * key_dim ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    o = delta_rule(jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v,
                   g.astype(q.dtype), beta, token_block)
    ms = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(ms + eps) * w["norm.w"] * jax.nn.silu(z)
    return o.reshape(b, t, wide_v) @ w["out.w"]


def sparse_experts(w, x, *, top_k, first_expert, norm_topk_prob=True):
    """x [N, D] -> (the held experts' part of the routed result plus the
    shared expert, router probabilities [N, E], chosen indices [N, k])."""
    probs = jax.nn.softmax(x @ w["router.w"], axis=-1)
    weight, index = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    def expert(out, held):                  # one expert held here
        e, w_gate, w_up, w_down = held
        mask = jnp.sum(jnp.where(index == first_expert + e, weight, 0),
                       axis=-1, keepdims=True)
        hidden = jax.nn.silu(x @ w_gate) * (x @ w_up)
        return out + mask.astype(x.dtype) * (hidden @ w_down), None

    stacks = (w["experts.gate.w"], w["experts.up.w"], w["experts.down.w"])
    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.arange(stacks[0].shape[0]),) + stacks)
    shared = (jax.nn.silu(x @ w["shared.gate.w"]) * (x @ w["shared.up.w"])) \
        @ w["shared.down.w"]
    return out + jax.nn.sigmoid(x @ w["shared_gate.w"]) * shared, probs, index


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer(w, x, kind, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D];
    `sizes` is a tuple of (name, value) pairs. Returns the new x and the
    router's probabilities and indices."""
    s = dict(sizes)
    eps = s["rms_eps"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    normed = rms_norm(x, w["in_norm.w"], eps)
    if kind == "full_attention":
        mixed = attention(
            sub("attn."), normed, n_head=s["n_head"],
            n_kv_head=s["n_kv_head"], head_dim=s["head_dim"],
            rotary_dim=s["rotary_dim"], theta=s["rope_theta"], eps=eps,
            q_block=s["q_block"])
    else:
        mixed = gated_delta_net(
            sub("gdn."), normed, n_key_head=s["n_key_head"],
            n_value_head=s["n_value_head"], key_dim=s["key_dim"],
            value_dim=s["value_dim"], eps=eps, token_block=s["token_block"])
    x = x + mixed
    b, t, d = x.shape
    flat = rms_norm(x, w["post_norm.w"], eps).reshape(b * t, d)
    moe, probs, index = sparse_experts(
        w, flat, top_k=s["top_k"], first_expert=s["first_expert"],
        norm_topk_prob=s["norm_topk_prob"])
    return x + moe.reshape(b, t, d), probs, index


def layer_kind(i, full_attention_interval):
    return "full_attention" if (i + 1) % full_attention_interval == 0 \
        else "linear_attention"


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


def loss_parts(params, tokens, labels, *, n_layer, n_head=16, n_kv_head=2,
               head_dim=256, rotary_dim=64, rope_theta=1e7,
               full_attention_interval=4, n_key_head=16, n_value_head=32,
               key_dim=128, value_dim=128, top_k=10, first_expert=0,
               norm_topk_prob=True, rms_eps=1e-6, aux_coef=0.001,
               dtype=jnp.float32, q_block=None, token_block=None,
               remat=False, last=None):
    """The loss that is minimised and its parts: `loss`, `ce` (mean
    cross-entropy), `load_balance` (E * sum_e f_e P_e over all layers'
    router rows), and `tokens_per_expert` [n_layer, E]. With `last`, also
    `logits` on the final `last` positions, [B, last, V]."""
    sizes = tuple(sorted(dict(
        n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
        rotary_dim=rotary_dim, rope_theta=rope_theta, n_key_head=n_key_head,
        n_value_head=n_value_head, key_dim=key_dim, value_dim=value_dim,
        top_k=top_k, first_expert=first_expert,
        norm_topk_prob=norm_topk_prob, rms_eps=rms_eps, q_block=q_block,
        token_block=token_block).items()))
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(2, 3)) if remat \
            else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        routers = []
        for i in range(n_layer):
            prefix = f"l{i}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            x, probs, index = apply(
                w, x, layer_kind(i, full_attention_interval), sizes)
            routers.append((probs, index))
        x = rms_norm(x, p["final_norm.w"], rms_eps)
        ce_of = jax.checkpoint(head_ce, static_argnums=(3,)) if remat \
            else head_ce
        ce = jnp.mean(ce_of(x, p["head.w"], labels, q_block))
        probs = jnp.concatenate([r[0] for r in routers], axis=0)
        index = jnp.concatenate([r[1] for r in routers], axis=0)
        n_expert = probs.shape[-1]
        chosen = jnp.sum(index[:, :, None] == jnp.arange(n_expert), axis=1)
        share = jnp.mean(chosen.astype(probs.dtype), axis=0)    # f_e
        load_balance = n_expert * jnp.sum(share * jnp.mean(probs, axis=0))
        out = {"loss": ce + aux_coef * load_balance, "ce": ce,
               "load_balance": load_balance,
               "tokens_per_expert": jnp.stack(
                   [jnp.sum(r[1][:, :, None] == jnp.arange(n_expert),
                            axis=(0, 1)) for r in routers])}
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
