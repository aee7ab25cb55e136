"""Trinity-Mini (`model_type: afmoe`: sliding-window layers that turn by
rotary beside full causal layers with no positions, grouped heads, QK-norm, an
output gate on the head-merged context, four norms a layer, a scaled
embedding, a leading dense layer, then sparse-expert layers under a sigmoid
router with a selection bias, one shared expert) in plain `jax.numpy`: the
forward pass, the loss, its gradients and the step's update of the router
biases, for ONE CHIP'S SHARE of the expert layers. What the program
(`paddle_tpu/models/trinity.py`) is compared with.

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
    l<i>.in_norm.w, l<i>.post_attn_norm.w, l<i>.pre_mlp_norm.w,
    l<i>.post_mlp_norm.w [D]
    l<i>.attn.q.w, l<i>.attn.gate.w [D, heads * head_dim]
    l<i>.attn.k.w, l<i>.attn.v.w [D, kv_heads * head_dim]
    l<i>.attn.q_norm.w, l<i>.attn.k_norm.w [head_dim]
    l<i>.attn.o.w [heads * head_dim, D]
    l<i>.mlp.gate.w, l<i>.mlp.up.w [D, Fd]   l<i>.mlp.down.w [Fd, D]  (dense)
    l<i>.router.w [D, E]   l<i>.router.bias [E]  (float32; not trained)
    l<i>.experts.gate.w, l<i>.experts.up.w [H, D, F]  l<i>.experts.down.w [H, F, D]
    l<i>.shared.gate.w, l<i>.shared.up.w [D, Fs]      l<i>.shared.down.w [Fs, D]

The equations (the public `afmoe` model code; the config's keys give the
router, the window, the layer kinds, the dense layers, the shared expert and
`mup_enabled`):

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w
    x0 = embed(tokens) * sqrt(D)
    layer i:  h = x + N(Attn_i(N(x)));  y = h + N(FFN_i(N(h)));  FFN_i the
              dense gated MLP where the layer has `mlp.*` weights, MoE where
              it has a router; after the last layer N, then the head
    Attn_i: q, k, v, g = x W_q, x W_k, x W_v, x W_g;  q, k = N(q), N(k) over a
            head;  on a sliding layer rotary, rotate-half, on the whole head
            of R = head_dim dims, inv_freq_j = theta^(-2j/R), positions
            0..T-1;  on a full layer NOTHING: no rotary, no positions
            key-value head h // group serves query head h;  scores times
            head_dim^-0.5;  key j is visible to query i iff j <= i and, on a
            sliding layer, i - j < sliding_window;  softmax;
            out = (ctx * sigmoid(g)) W_o on the head-merged context
    MoE:  s = sigmoid(x W_r);  idx = top-k of s + b  (one group: n_group 1);
          w = s[idx];  w = w / (sum_k w + 1e-20);  w = route_scale w
          routed = sum over the chosen experts THAT ARE HELD HERE of w_k *
          down_e(silu(gate_e x) * up_e x);  shared = down_s(silu(gate_s x) *
          up_s x);  routed + shared
    loss = mean cross-entropy
    after a step, per MoE layer (`next_bias`):  b <- b + gamma sign(mean(c) - c),
          c the step's assignments per expert (all E)

Departures from the public code: the bias update is the DeepSeek-V3 report's
(arXiv:2412.19437, section 2.1.2) at the config's `load_balance_coeff` as its
rate, not centred; no balance loss and no z-loss. The share: what the absent
experts would add is left out, here as in the program, and that partial result
goes on to the next layer; the vocabulary is the slice the weights have.

`dtype` other than float32 computes everything, the router, the softmax and
the loss included, in that precision: the comparison's tolerance has to refuse
it. `q_block` computes the attention a block of queries at a time and the
head's cross-entropy a block of positions at a time; `remat` wraps each layer
in `jax.checkpoint`: both are this reference's memory at published widths, not
its mathematics (a test holds that they change nothing). A layer is one jitted
function of its own weights, so the layers of a kind share one compiled
program.

`fault` plants one named fault (`FAULTS`): what a comparison with this
reference has to refuse. A test and `reference_check_trinity.py` hold that
each moves at least one compared quantity past its limit.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools

import jax
import jax.numpy as jnp

PERIOD = ("sliding_attention",) * 3 + ("full_attention",)    # as published

FAULTS = {
    "no_gate": "out = ctx W_o: the output gate left off",
    "gate_before_merge_wrong_head": "head h's context is gated by head "
                                    "h + 1's columns of g",
    "rotary_on_full": "the full layers turn q and k too",
    "no_rotary_on_sliding": "the sliding layers do not turn q and k",
    "window_off_by_one": "i - j <= W in place of i - j < W",
    "no_window": "the sliding layers see the whole causal triangle",
    "no_post_norms": "h = x + Attn(N(x)); y = h + FFN(N(h)): no norm on the "
                     "way out of a sublayer",
    "no_mup_scale": "x0 = embed(tokens), not times sqrt(D)",
    "bias_in_weights": "w = (s + b)[idx]: the weights carry the bias",
    "wrong_group": "key-value head g serves query heads g, g + kv, g + 2 kv, "
                   "... (tiled, not repeated)",
}


def rms_norm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def rotary(x, theta):
    """x [B, H, T, Dh]; rotate-half on the whole head."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
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


def gated_attention(w, x, *, n_head, n_kv_head, head_dim, theta, turned,
                    window, eps, q_block=None, fault=None):
    """Causal softmax attention of one layer (its weights `w` by their names
    after `l<i>.attn.`) on x [B, T, D] with its output gate, `q_block` queries
    at a time; `window` None on a full layer, `turned` whether q and k take
    rotary."""
    b, t, _ = x.shape
    q = (x @ w["q.w"]).reshape(b, t, n_head, head_dim)
    k = (x @ w["k.w"]).reshape(b, t, n_kv_head, head_dim)
    v = (x @ w["v.w"]).reshape(b, t, n_kv_head, head_dim)
    gate = jax.nn.sigmoid(x @ w["gate.w"])
    q = rms_norm(q, w["q_norm.w"], eps).transpose(0, 2, 1, 3)
    k = rms_norm(k, w["k_norm.w"], eps).transpose(0, 2, 1, 3)
    if turned:
        q, k = rotary(q, theta), rotary(k, theta)
    v = v.transpose(0, 2, 1, 3)
    group = n_head // n_kv_head         # query head h reads kv head h // group
    if fault == "wrong_group":
        k, v = jnp.tile(k, (1, group, 1, 1)), jnp.tile(v, (1, group, 1, 1))
    else:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    ctx = masked_attention(q, k, v, scale=head_dim ** -0.5, window=window,
                           q_block=q_block, fault=fault).transpose(0, 2, 1, 3)
    ctx = ctx.reshape(b, t, n_head * head_dim)
    if fault == "gate_before_merge_wrong_head":
        gate = jnp.roll(gate, -head_dim, axis=-1)
    if fault != "no_gate":
        ctx = ctx * gate
    return ctx @ w["o.w"]


def gated_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, w_router, bias, top_k, route_scale, fault=None):
    """(weights [N, k], indices [N, k], scores [N, E]): chosen by score +
    bias, weighted by the score alone."""
    scores = jax.nn.sigmoid(x @ w_router)
    _, index = jax.lax.top_k(scores + bias, top_k)
    weight = jnp.take_along_axis(
        scores + bias if fault == "bias_in_weights" else scores, index,
        axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return weight * route_scale, index, scores


def sparse_experts(w, x, *, top_k, first_expert, route_scale, fault=None):
    """x [N, D] -> (the held experts' part of the routed result plus the
    shared expert, chosen indices [N, k])."""
    weight, index, _ = route(x, w["router.w"], w["router.bias"], top_k,
                             route_scale, fault)

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


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer(w, x, kind, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D];
    `sizes` is a tuple of (name, value) pairs. Returns the new x and the
    router's indices (None for a dense layer)."""
    s = dict(sizes)
    eps, fault = s["rms_eps"], s["fault"]
    sliding = kind == "sliding_attention"
    turned = (sliding and fault != "no_rotary_on_sliding") \
        or (not sliding and fault == "rotary_on_full")
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}

    def out_norm(t, name):
        return t if fault == "no_post_norms" else rms_norm(t, w[name], eps)

    mixed = gated_attention(
        sub("attn."), rms_norm(x, w["in_norm.w"], eps), n_head=s["n_head"],
        n_kv_head=s["n_kv_head"], head_dim=s["head_dim"],
        theta=s["rope_theta"], turned=turned,
        window=s["sliding_window"] if sliding and fault != "no_window"
        else None, eps=eps, q_block=s["q_block"], fault=fault)
    x = x + out_norm(mixed, "post_attn_norm.w")
    normed = rms_norm(x, w["pre_mlp_norm.w"], eps)
    if "router.w" not in w:
        fed = gated_mlp(normed, w["mlp.gate.w"], w["mlp.up.w"],
                        w["mlp.down.w"])
        return x + out_norm(fed, "post_mlp_norm.w"), None
    b, t, d = x.shape
    moe, index = sparse_experts(
        w, normed.reshape(b * t, d), top_k=s["top_k"],
        first_expert=s["first_expert"], route_scale=s["route_scale"],
        fault=fault)
    return x + out_norm(moe.reshape(b, t, d), "post_mlp_norm.w"), index


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


def loss_parts(params, tokens, labels, *, n_layer, n_head=32, n_kv_head=4,
               head_dim=128, layer_types=PERIOD, sliding_window=2048,
               rope_theta=1e4, top_k=8, first_expert=0, route_scale=2.826,
               rms_eps=1e-5, dtype=jnp.float32, q_block=None, remat=False,
               last=None, fault=None):
    """The loss that is minimised and its parts: `loss` and `ce` (the mean
    cross-entropy, twice: nothing else is in the loss), and
    `tokens_per_expert` [expert layers, E]. With `last`, also `logits` on the
    final `last` positions, [B, last, V]. The biases are read from `params`
    (`l<i>.router.bias`) and are not advanced here: `next_bias` is."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault is one of {sorted(FAULTS)}, got {fault!r}")
    sizes = tuple(sorted(dict(
        n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
        sliding_window=sliding_window, rope_theta=rope_theta, top_k=top_k,
        first_expert=first_expert, route_scale=route_scale, rms_eps=rms_eps,
        q_block=q_block, fault=fault).items()))
    kinds = [layer_types[i % len(layer_types)] for i in range(n_layer)]
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(2, 3)) if remat \
            else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        if fault != "no_mup_scale":
            x = x * jnp.asarray(x.shape[-1] ** 0.5, dtype)
        chosen = []
        for i, kind in enumerate(kinds):
            prefix = f"l{i}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            x, index = apply(w, x, kind, sizes)
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
