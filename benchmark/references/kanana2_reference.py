"""Kanana-2-30B-A3B (the `deepseek_v3` family: latent attention, a leading
dense layer, then sparse-expert layers under a sigmoid router with a selection
bias, two shared experts) in plain `jax.numpy`: the forward pass, the loss, its
gradients and the step's update of the router biases, for ONE CHIP'S SHARE of
the expert layers. What the program (`paddle_tpu/models/kanana2.py`) is
compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel, no
sort, no grouped matmul: attention is a masked softmax over the assembled
192-wide queries and keys and the 128-wide values; the held experts are a loop
(a `lax.scan` over their stacked weights, so that one expert's program is
compiled once), each applied to every token and kept through a dense mask of
the router's weights. Weights come as a dict under the program's parameter
names, matrices stored `[in, out]` (D hidden, V the vocabulary rows held, E
experts routed over, H of them held here, F an expert's width, heads h, n =
qk_nope_dim, r = qk_rope_dim, v = v_head_dim, c = kv_rank):

    embed.w [V, D]   head.w [D, V]   final_norm.w [D]
    l<i>.in_norm.w, l<i>.post_norm.w [D]
    l<i>.mla.q.w [D, h * (n + r)]       per head: q_n, then q_r
    l<i>.mla.kv_a.w [D, c + r]          the compressed row, then the one
                                        rotary key head
    l<i>.mla.kv_norm.w [c]
    l<i>.mla.kv_b.w [c, h * (n + v)]    per head: k_n, then v
    l<i>.mla.o.w [h * v, D]
    l<i>.mlp.gate.w, l<i>.mlp.up.w [D, Fd]   l<i>.mlp.down.w [Fd, D]  (dense)
    l<i>.router.w [D, E]   l<i>.router.bias [E]  (float32; not trained)
    l<i>.experts.gate.w, l<i>.experts.up.w [H, D, F]  l<i>.experts.down.w [H, F, D]
    l<i>.shared.gate.w, l<i>.shared.up.w [D, Fs]      l<i>.shared.down.w [Fs, D]

The equations (the public `deepseek_v3` model code):

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w
    layer i:  h = x + MLA(N(x));  y = h + FFN_i(N(h));  FFN_i the dense gated
              MLP where the layer has `mlp.*` weights, MoE where it has a
              router; after the last layer N, then the head
    MLA:  q = x W_q, per head [q_n | q_r];  [c | k_r] = x W_kva;  c = N_c(c);
          [k_n | v] = c W_kvb per head;  rotary on q_r and k_r as the public
          code writes it for `rope_interleave`: the dims de-interleaved to
          [evens | odds], then the halves rotated, theta^(-2i/r), positions
          0..T-1;  q = [q_n | q_r], k = [k_n | k_r for every head];
          causal softmax(q k^T * (n + r)^-0.5) v;  out = ctx W_o
    MoE:  s = sigmoid(x W_r);  idx = top-k of s + b  (one group: n_group 1);
          w = s[idx];  w = w / (sum_k w + 1e-20);  w = routed_scaling_factor w
          routed = sum over the chosen experts THAT ARE HELD HERE of w_k *
          down_e(silu(gate_e x) * up_e x);  shared = down_s(silu(gate_s x) *
          up_s x);  routed + shared
    loss = mean cross-entropy
    after a step, per MoE layer (`next_bias`):  b <- b + gamma sign(mean(c) - c),
          c the step's assignments per expert (all E)

Departures from the public code: the bias update is the DeepSeek-V3 report's
(arXiv:2412.19437, section 2.1.2), which the public inference code does not
carry, at an assumed gamma; no sequence-wise balance loss and no multi-token-
prediction module (the config has no key for either). The share: what the
absent experts would add is left out, here as in the program, and that partial
result goes on to the next layer; the vocabulary is the slice the weights have.

`dtype` other than float32 computes everything, the router and the loss
included, in that precision: the comparison's tolerance has to refuse it.
`q_block` computes the attention a block of queries at a time and the head's
cross-entropy a block of positions at a time; `remat` wraps each layer in
`jax.checkpoint`: both are this reference's memory at published widths, not
its mathematics (a test holds that they change nothing). A layer is one jitted
function of its own weights, so the layers of a kind share one compiled
program.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary_interleaved(x, theta):
    """x [B, H, T, r], the public code's `apply_rotary_pos_emb_interleave`:
    `x.view(..., r/2, 2).transpose(-1, -2).reshape(..., r)` lays the pairs
    (x[2i], x[2i+1]) out as [evens | odds], then `x cos + rotate_half(x)
    sin` with the frequencies repeated over both halves."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    x = x.reshape(x.shape[:-1] + (r // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (r,))
    return x * cos + rotate_half(x) * sin


def latent_attention(w, x, *, n_head, qk_nope_dim, qk_rope_dim, v_head_dim,
                     theta, eps, q_block=None):
    """MLA of one layer (its weights `w` by their names after `l<i>.mla.`) on
    x [B, T, D], `q_block` queries at a time."""
    b, t, _ = x.shape
    n, r, dv = qk_nope_dim, qk_rope_dim, v_head_dim
    q = (x @ w["q.w"]).reshape(b, t, n_head, n + r).transpose(0, 2, 1, 3)
    kv_a = x @ w["kv_a.w"]
    rank = kv_a.shape[-1] - r
    latent = rms_norm(kv_a[..., :rank], w["kv_norm.w"], eps)
    k_rope = rotary_interleaved(kv_a[..., rank:][:, None], theta)  # [B,1,T,r]
    kv = (latent @ w["kv_b.w"]).reshape(b, t, n_head, n + dv) \
        .transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :n], rotary_interleaved(q[..., n:], theta)],
                        axis=-1)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_rope, (b, n_head, t, r))], axis=-1)
    v = kv[..., n:]
    step = q_block or t
    blocks = []
    for first in range(0, t, step):
        end = min(first + step, t)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, first:end],
                            k[:, :, :end]) * (n + r) ** -0.5
        row = jnp.arange(first, end)[:, None]
        col = jnp.arange(end)[None, :]
        scores = jnp.where(col > row, -jnp.inf, scores)
        weights = jax.nn.softmax(scores, axis=-1)
        blocks.append(jnp.einsum("bhqk,bhkd->bhqd", weights, v[:, :, :end]))
    ctx = jnp.concatenate(blocks, axis=2).transpose(0, 2, 1, 3)
    return ctx.reshape(b, t, n_head * dv) @ w["o.w"]


def gated_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, w_router, bias, top_k, routed_scaling_factor):
    """(weights [N, k], indices [N, k], scores [N, E]): chosen by score +
    bias, weighted by the score alone."""
    scores = jax.nn.sigmoid(x @ w_router)
    _, index = jax.lax.top_k(scores + bias, top_k)
    weight = jnp.take_along_axis(scores, index, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return weight * routed_scaling_factor, index, scores


def sparse_experts(w, x, *, top_k, first_expert, routed_scaling_factor):
    """x [N, D] -> (the held experts' part of the routed result plus the
    shared experts, chosen indices [N, k])."""
    weight, index, _ = route(x, w["router.w"], w["router.bias"], top_k,
                             routed_scaling_factor)

    def expert(out, held):                  # one expert held here
        e, w_gate, w_up, w_down = held
        mask = jnp.sum(jnp.where(index == first_expert + e, weight, 0),
                       axis=-1, keepdims=True)
        return out + mask.astype(x.dtype) * gated_mlp(x, w_gate, w_up,
                                                      w_down), None

    stacks = (w["experts.gate.w"], w["experts.up.w"], w["experts.down.w"])
    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.arange(stacks[0].shape[0]),) + stacks)
    shared = gated_mlp(x, w["shared.gate.w"], w["shared.up.w"],
                       w["shared.down.w"])
    return out + shared, index


@functools.partial(jax.jit, static_argnums=(2,))
def layer(w, x, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D];
    `sizes` is a tuple of (name, value) pairs. Returns the new x and the
    router's indices (None for a dense layer)."""
    s = dict(sizes)
    eps = s["rms_eps"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    x = x + latent_attention(
        sub("mla."), rms_norm(x, w["in_norm.w"], eps), n_head=s["n_head"],
        qk_nope_dim=s["qk_nope_dim"], qk_rope_dim=s["qk_rope_dim"],
        v_head_dim=s["v_head_dim"], theta=s["rope_theta"], eps=eps,
        q_block=s["q_block"])
    normed = rms_norm(x, w["post_norm.w"], eps)
    if "router.w" not in w:
        return x + gated_mlp(normed, w["mlp.gate.w"], w["mlp.up.w"],
                             w["mlp.down.w"]), None
    b, t, d = x.shape
    moe, index = sparse_experts(
        w, normed.reshape(b * t, d), top_k=s["top_k"],
        first_expert=s["first_expert"],
        routed_scaling_factor=s["routed_scaling_factor"])
    return x + moe.reshape(b, t, d), index


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


def next_bias(bias, counts, gamma):
    """The step's update of one layer's selection bias from that step's
    assignments per expert `counts` [E]: an expert over the mean load moves
    down by gamma, one under it up, one at it stays."""
    counts = jnp.asarray(counts, jnp.float32)
    return jnp.asarray(bias, jnp.float32) \
        + gamma * jnp.sign(jnp.mean(counts) - counts)


def loss_parts(params, tokens, labels, *, n_layer, n_head=32, qk_nope_dim=128,
               qk_rope_dim=64, v_head_dim=128, rope_theta=1e6, top_k=6,
               first_expert=0, routed_scaling_factor=2.448, rms_eps=1e-6,
               dtype=jnp.float32, q_block=None, remat=False, last=None):
    """The loss that is minimised and its parts: `loss` and `ce` (the mean
    cross-entropy, twice: nothing else is in the loss), and
    `tokens_per_expert` [expert layers, E]. With `last`, also `logits` on the
    final `last` positions, [B, last, V]. The biases are read from `params`
    (`l<i>.router.bias`) and are not advanced here: `next_bias` is."""
    sizes = tuple(sorted(dict(
        n_head=n_head, qk_nope_dim=qk_nope_dim, qk_rope_dim=qk_rope_dim,
        v_head_dim=v_head_dim, rope_theta=rope_theta, top_k=top_k,
        first_expert=first_expert,
        routed_scaling_factor=routed_scaling_factor, rms_eps=rms_eps,
        q_block=q_block).items()))
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(2,)) if remat else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        chosen = []
        for i in range(n_layer):
            prefix = f"l{i}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            x, index = apply(w, x, sizes)
            if index is not None:
                n_expert = w["router.w"].shape[-1]
                chosen.append(jnp.sum(
                    index[:, :, None] == jnp.arange(n_expert), axis=(0, 1)))
        x = rms_norm(x, p["final_norm.w"], rms_eps)
        ce_of = jax.checkpoint(head_ce, static_argnums=(3,)) if remat \
            else head_ce
        ce = jnp.mean(ce_of(x, p["head.w"], labels, q_block))
        out = {"loss": ce, "ce": ce}
        if chosen:
            out["tokens_per_expert"] = jnp.stack(chosen)
        if last is not None:
            out["logits"] = x[:, -last:] @ p["head.w"]
        return out


def loss_and_grads(params, tokens, labels, wrt=None, **kw):
    """(parts, {name: gradient of `loss`}) for the parameters named in `wrt`
    (all of them but the router biases by default: those are not trained)."""
    names = sorted(n for n in params if not n.endswith(".router.bias")) \
        if wrt is None else list(wrt)

    def f(sub):
        parts = loss_parts({**params, **sub}, tokens, labels, **kw)
        return parts["loss"], parts

    (_, parts), grads = jax.value_and_grad(f, has_aux=True)(
        {n: jnp.asarray(params[n], jnp.float32) for n in names})
    return parts, grads
