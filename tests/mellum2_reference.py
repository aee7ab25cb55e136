"""Mellum2 (sliding-window and full causal attention layers mixed, grouped
heads, two rotary regimes, a sparse-expert feed-forward in every layer) in
plain `jax.numpy`: the forward pass, the loss and its gradients, for ONE CHIP'S
SHARE of the expert layers. What the program (`paddle_tpu/models/mellum2.py`)
is compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel, no
sort, no grouped matmul: attention is a masked softmax whose mask is written
as the two inequalities below; key and value heads are repeated with
`jnp.repeat`; the held experts are a loop (a `lax.scan` over their stacked
weights, so that one expert's program is compiled once), each applied to every
token and kept through a dense mask of the router's weights. Weights come as a
dict under the program's parameter names, matrices stored `[in, out]` (D
hidden, V the vocabulary rows held, E experts routed over, H of them held
here, F an expert's width):

    embed.w [V, D]   head.w [D, V]   final_norm.w [D]
    l<i>.in_norm.w, l<i>.post_norm.w [D]
    l<i>.attn.q.w [D, heads * head_dim]
    l<i>.attn.k.w, l<i>.attn.v.w [D, kv_heads * head_dim]
    l<i>.attn.q_norm.w, l<i>.attn.k_norm.w [head_dim]
    l<i>.attn.o.w [heads * head_dim, D]
    l<i>.router.w [D, E]
    l<i>.experts.gate.w, l<i>.experts.up.w [H, D, F]  l<i>.experts.down.w [H, F, D]

The equations (the public config `model_type: mellum`; its key set is the
Qwen3-MoE family's, whose layer these are):

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w
    layer i:  h = x + Attn_i(N(x));  y = h + MoE(N(h));  after the last layer
              N, then the head
    Attn_i: q, k, v = x W_q, x W_k, x W_v; q, k = N(q), N(k) over a head
            (ASSUMED: the config has no key for it); rotary, rotate-half, on
            the whole head of R = head_dim dims:
              sliding_attention: inv_freq_j = theta^(-2j/R), tables unscaled
              full_attention: YaRN, at every length:
                 pos_j = theta^(2j/R); c(r) = R ln(L / (2 pi r)) / (2 ln theta)
                 with L = original_max_position_embeddings;
                 low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)), R - 1)
                 ramp_j = clip((j - low) / (high - low), 0, 1)
                 inv_freq_j = ramp_j / (factor pos_j) + (1 - ramp_j) / pos_j
                 cos and sin both times attention_factor
            key-value head g serves query heads g * group .. g * group + group - 1;
            scores times head_dim^-0.5; key j is visible to query i iff
            j <= i and, on a sliding layer, i - j < sliding_window; softmax;
            out = ctx W_o
    MoE:  p = softmax(x W_r) over all E; the top-k of p divided by their sum
          (`norm_topk_prob`); sum over the chosen experts THAT ARE HELD HERE
          of p_k * down_e(silu(gate_e x) * up_e x)
    loss = mean cross-entropy + aux_coef * E * sum_e f_e P_e over all layers'
           router rows (f_e the assignments to e per row, P_e the mean
           probability; all E experts, wherever they live)

Departures from the public config: the norm of q and k over a head is assumed
(see above); no multi-token-prediction head (no key for it);
`intermediate_size` is unused (every layer is sparse); the load-balancing loss
is the form the `olmoe` code has, with an assumed coefficient. The share: what
the absent experts would add is left out, here as in the program, and that
partial result goes on to the next layer; the vocabulary is the slice the
weights have.

`dtype` other than float32 computes everything, the router, the softmax and
the losses included, in that precision: the comparison's tolerance has to
refuse it. `q_block` computes the attention a block of queries at a time and
the head's cross-entropy a block of positions at a time; `remat` wraps each
layer in `jax.checkpoint`: both are this reference's memory at published
widths, not its mathematics (a test holds that they change nothing). A layer
is one jitted function of its own weights, so the layers of a kind share one
compiled program.

`fault` plants one named fault (`FAULTS`): what a comparison with this
reference has to refuse. A test and `reference_check_mellum2.py` hold that
each moves at least one compared quantity past its limit.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools
import math

import jax
import jax.numpy as jnp

PERIOD = ("sliding_attention",) * 3 + ("full_attention",)    # as published
YARN = {"factor": 16.0, "original_max_position_embeddings": 8192,
        "beta_fast": 32.0, "beta_slow": 1.0,
        "attention_factor": 1.2772588722239782}

FAULTS = {
    "window_off_by_one": "i - j <= W in place of i - j < W",
    "no_window": "the sliding layers see the whole causal triangle",
    "window_on_full": "the full layers are windowed too",
    "no_yarn": "the full layers turn by the plain frequencies, unscaled",
    "yarn_on_sliding": "the sliding layers take YaRN's tables too",
    "wrong_group": "key-value head g serves query heads g, g + kv, g + 2 kv, "
                   "... (tiled, not repeated)",
}


def rms_norm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def yarn_frequencies(dim, theta, scaling):
    """(inv_freq [dim / 2], the tables' factor), by the formulas above."""
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    pos = theta ** (2.0 * j / dim)
    if scaling is None:
        return 1.0 / pos, 1.0
    s = dict(scaling)
    length = s["original_max_position_embeddings"]

    def c(r):
        return dim * math.log(length / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    low = max(math.floor(c(s.get("beta_fast", 32.0))), 0)
    high = min(math.ceil(c(s.get("beta_slow", 1.0))), dim - 1)
    ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    inv_freq = ramp / (s["factor"] * pos) + (1.0 - ramp) / pos
    factor = s.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(s["factor"]) + 1.0
    return inv_freq, factor


def rotary(x, theta, scaling=None):
    """x [B, H, T, Dh]; rotate-half on the whole head."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq, factor = yarn_frequencies(r, theta, scaling)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos = (jnp.cos(angles) * factor).astype(x.dtype)
    sin = (jnp.sin(angles) * factor).astype(x.dtype)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def masked_attention(q, k, v, *, scale, window=None, q_block=None,
                     fault=None):
    """softmax(q k^T * scale) v on [B, H, T, Dh] under the mask written out:
    key j is visible to query i iff j <= i and, with a `window`, i - j <
    window; `q_block` queries at a time."""
    t = q.shape[2]
    step = q_block or t
    blocks = []
    for first in range(0, t, step):
        end = min(first + step, t)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, first:end],
                            k[:, :, :end]) * scale
        i = jnp.arange(first, end)[:, None]
        j = jnp.arange(end)[None, :]
        visible = j <= i
        if window is not None:
            visible = visible & ((i - j <= window)
                                 if fault == "window_off_by_one"
                                 else (i - j < window))
        scores = jnp.where(visible, scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        blocks.append(jnp.einsum("bhqk,bhkd->bhqd", weights, v[:, :, :end]))
    return jnp.concatenate(blocks, axis=2)


def attention(w, x, *, n_head, n_kv_head, head_dim, theta, scaling, window,
              eps, q_block=None, fault=None):
    """Causal softmax attention of one layer (its weights `w` by their names
    after `l<i>.attn.`) on x [B, T, D], `q_block` queries at a time; `window`
    None on a full layer."""
    b, t, _ = x.shape
    q = (x @ w["q.w"]).reshape(b, t, n_head, head_dim)
    k = (x @ w["k.w"]).reshape(b, t, n_kv_head, head_dim)
    v = (x @ w["v.w"]).reshape(b, t, n_kv_head, head_dim)
    q = rotary(rms_norm(q, w["q_norm.w"], eps).transpose(0, 2, 1, 3), theta,
               scaling)
    k = rotary(rms_norm(k, w["k_norm.w"], eps).transpose(0, 2, 1, 3), theta,
               scaling)
    v = v.transpose(0, 2, 1, 3)
    group = n_head // n_kv_head         # query head h reads kv head h // group
    if fault == "wrong_group":
        k, v = jnp.tile(k, (1, group, 1, 1)), jnp.tile(v, (1, group, 1, 1))
    else:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    ctx = masked_attention(q, k, v, scale=head_dim ** -0.5, window=window,
                           q_block=q_block, fault=fault).transpose(0, 2, 1, 3)
    return ctx.reshape(b, t, n_head * head_dim) @ w["o.w"]


def sparse_experts(w, x, *, top_k, first_expert, norm_topk_prob=True):
    """x [N, D] -> (the held experts' part of the routed result, router
    probabilities [N, E], chosen indices [N, k])."""
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
    return out, probs, index


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer(w, x, kind, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D];
    `sizes` is a tuple of (name, value) pairs. Returns the new x and the
    router's probabilities and indices."""
    s = dict(sizes)
    eps, fault = s["rms_eps"], s["fault"]
    sliding = kind == "sliding_attention"
    windowed = (sliding and fault != "no_window") \
        or (not sliding and fault == "window_on_full")
    scaled = (not sliding and fault != "no_yarn") \
        or (sliding and fault == "yarn_on_sliding")
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    mixed = attention(
        sub("attn."), rms_norm(x, w["in_norm.w"], eps), n_head=s["n_head"],
        n_kv_head=s["n_kv_head"], head_dim=s["head_dim"],
        theta=s["rope_theta"],
        scaling=dict(s["rope_scaling"]) if scaled and s["rope_scaling"]
        else None,
        window=s["sliding_window"] if windowed else None, eps=eps,
        q_block=s["q_block"], fault=fault)
    x = x + mixed
    b, t, d = x.shape
    flat = rms_norm(x, w["post_norm.w"], eps).reshape(b * t, d)
    moe, probs, index = sparse_experts(
        w, flat, top_k=s["top_k"], first_expert=s["first_expert"],
        norm_topk_prob=s["norm_topk_prob"])
    return x + moe.reshape(b, t, d), probs, index


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


def loss_parts(params, tokens, labels, *, n_layer, n_head=32, n_kv_head=4,
               head_dim=128, layer_types=PERIOD,
               sliding_window=1024, rope_theta=5e5, rope_scaling=YARN,
               top_k=8, first_expert=0, norm_topk_prob=True, rms_eps=1e-6,
               aux_coef=0.001, dtype=jnp.float32, q_block=None, remat=False,
               last=None, fault=None):
    """The loss that is minimised and its parts: `loss`, `ce` (mean
    cross-entropy), `load_balance` (E * sum_e f_e P_e over all layers'
    router rows), and `tokens_per_expert` [n_layer, E]. With `last`, also
    `logits` on the final `last` positions, [B, last, V]."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault is one of {sorted(FAULTS)}, got {fault!r}")
    sizes = tuple(sorted(dict(
        n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
        sliding_window=sliding_window, rope_theta=rope_theta,
        rope_scaling=None if rope_scaling is None
        else tuple(sorted(dict(rope_scaling).items())),
        top_k=top_k, first_expert=first_expert,
        norm_topk_prob=norm_topk_prob, rms_eps=rms_eps, q_block=q_block,
        fault=fault).items()))
    kinds = [layer_types[i % len(layer_types)] for i in range(n_layer)]
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(2, 3)) if remat \
            else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        routers = []
        for i, kind in enumerate(kinds):
            prefix = f"l{i}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            x, probs, index = apply(w, x, kind, sizes)
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
