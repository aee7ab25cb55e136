"""LFM2-MoE (`model_type: lfm2_moe`, LFM2-8B-A1B: gated short convolutions of
three taps with no activation beside QK-normed rotary attention, a leading
dense gated MLP, then routed experts alone under a sigmoid router with a
selection bias, one table as embedding and head) in plain `jax.numpy`: the
forward pass, the loss, its gradients and the step's update of the router
biases, for ONE CHIP'S SHARE of the expert layers and a RUN of the published
layers. What the program (`paddle_tpu/models/lfm2_moe.py`) is compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel, no
sort, no grouped matmul: the convolution is a sum of shifted products, the
gates are written as they stand, attention is a masked softmax with the key
and value heads repeated by `jnp.repeat`, the held experts are a loop (a
`lax.scan` over their stacked weights, so that one expert's program is
compiled once), each applied to every token and kept through a dense mask of
the router's weights; the table is used twice, as rows and transposed.
Weights come as a dict under the program's parameter names, which carry the
PUBLISHED layer index p (`first_layer` is the first one here), matrices
stored `[in, out]` (D hidden, V the vocabulary rows held, E experts routed
over, H of them held here, F an expert's width, K taps):

    embed.w [V, D]   final_norm.w [D]   (head.w [D, V] only when untied)
    l<p>.op_norm.w, l<p>.ffn_norm.w [D]
    l<p>.conv.in.w [D, 3 D]   columns [B | C | x']
    l<p>.conv.conv.w [D, K]   l<p>.conv.out.w [D, D]
    l<p>.attn.q.w, l<p>.attn.o.w [D, heads * head_dim] / transposed
    l<p>.attn.k.w, l<p>.attn.v.w [D, kv_heads * head_dim]
    l<p>.attn.q_norm.w, l<p>.attn.k_norm.w [head_dim]
    l<p>.mlp.gate.w, l<p>.mlp.up.w [D, Fd]   l<p>.mlp.down.w [Fd, D]  (dense)
    l<p>.router.w [D, E]   l<p>.router.bias [E]  (float32; not trained)
    l<p>.experts.gate.w, l<p>.experts.up.w [H, D, F]  l<p>.experts.down.w [H, F, D]

The equations (the public `lfm2_moe` model code):

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w
    h_0 = E[tokens]
    layer p:  h = h + Op_p(N_op(h));  h = h + FFN_p(N_ffn(h));  Op_p by
              `layer_types`;  FFN_p the dense gated MLP where the layer has
              `mlp.*` weights, the routed experts where it has a router
    logits = N(h_L) E^T;   loss = mean cross-entropy
    conv:   [B | C | x'] = x W_in;  u = B * x';
            v[t, c] = sum_j w[c, j] u[t - (K-1) + j, c], zeros before t = 0,
            no bias, NO activation;  y = C * v;  out = y W_out
    full_attention:  q, k, v = x W_q, x W_k, x W_v;  q, k = N(q), N(k) over a
            head;  rotary, rotate-half, on the whole head of R = head_dim
            dims, inv_freq_j = theta^(-2j/R), positions 0..T-1;  key-value
            head h // group serves query head h;  scores times head_dim^-0.5;
            key j is visible to query i iff j <= i;  softmax;  out = ctx W_o
    experts:  s = sigmoid(x W_r);  idx = top-k of s + b;  w = s[idx];
            w = w / (sum_k w + route_norm_eps);  w = route_scale w
            sum over the chosen experts THAT ARE HELD HERE of w_k *
            down_e(silu(gate_e x) * up_e x);  no shared expert
    after a step, per expert layer (`next_bias`):  b <- b + gamma sign(mean(c)
            - c), c the step's assignments per expert (all E)

Departures from the public code: the bias update is the DeepSeek-V3 report's
(arXiv:2412.19437, section 2.1.2), the config saying only that b exists; no
balance loss and no z-loss. The share: what the absent experts would add is
left out, here as in the program, and that partial result goes on to the next
layer; the vocabulary is the slice the table has.

`dtype` other than float32 computes everything, the router, the softmax and
the loss included, in that precision: the comparison's tolerance has to refuse
it. `q_block` computes the attention a block of queries at a time and the
head's cross-entropy a block of positions at a time; `remat` wraps each layer
in `jax.checkpoint`: both are this reference's memory at published widths, not
its mathematics (a test holds that they change nothing). A layer is one jitted
function of its own weights, so the layers of a kind share one compiled
program.

`fault` plants one named fault (`FAULTS`): what a comparison with this
reference has to refuse. A test and `reference_check_lfm2.py` hold that each
moves at least one compared quantity past its limit.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools

import jax
import jax.numpy as jnp

# as published: attention at 2, 6, 10, 14, 18, 21 of 24
LAYER_TYPES = tuple("full_attention" if i in (2, 6, 10, 14, 18, 21)
                    else "conv" for i in range(24))

FAULTS = {
    "silu_on_conv": "v = silu(conv(u)): the activation every scan's "
                    "convolution takes",
    "gates_swapped": "u = C * x', y = B * v: C before the convolution, B "
                     "after it",
    "no_gate_before_conv": "u = x': the convolution reads x' ungated",
    "no_gate_after_conv": "y = v: the convolution's result goes to W_out "
                          "ungated",
    "taps_reversed": "v[t] = sum_j w[:, j] u[t - j]: the newest token under "
                     "the first tap",
    "four_taps": "a fourth tap: u[t - K] under the oldest tap's weight as "
                 "well",
    "conv_bias": "v = conv(u) + 0.1: a bias on every channel",
    "no_qk_norm": "q and k go to rotary as projected",
    "qk_norm_over_all_heads": "q and k are normed over the whole projection "
                              "(every head's dims in one mean), the weight "
                              "repeated a head",
    "rotary_interleaved": "the pairs are (x[2i], x[2i + 1]), not (x[i], "
                          "x[i + R/2])",
    "no_rotary": "q and k carry no positions",
    "kv_head_order": "query head h reads key-value head h % kv_heads, not "
                     "h // group",
    "softmax_router": "s = softmax(x W_r) over the experts",
    "bias_in_weights": "w = (s + b)[idx]: the weights carry the bias",
    "no_topk_renorm": "w = s[idx]: the chosen scores as they are",
    "shared_expert_added": "the first held expert is applied to every token "
                           "at weight 1 on top, as a shared expert",
    "second_dense_layer_sparse": "num_dense_layers read as one less: the "
                                 "last dense layer's feed-forward is the "
                                 "next expert layer's router and experts, "
                                 "its own MLP unused",
    "untied_head": "the head's table is a copy the embedding's gradient does "
                   "not reach: embed.w's gradient is the look-up's alone",
    "no_final_norm": "logits = h_L E^T",
}


def rms_norm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def rotary(x, theta, interleaved=False):
    """x [B, H, T, Dh]; rotate-half on the whole head (`interleaved`: the
    pairs are neighbours, a planted fault's)."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    if interleaved:
        cos, sin = jnp.cos(angles).astype(x.dtype), \
            jnp.sin(angles).astype(x.dtype)
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                         axis=-1).reshape(x.shape)
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_conv(u, w, fault=None):
    """u [B, T, C], w [C, K]: output t is `sum_j w[:, j] u[t - (K-1) + j]`
    with zeros before the start; no bias, no activation."""
    t, taps = u.shape[1], w.shape[1]
    if fault == "taps_reversed":
        w = w[:, ::-1]
    if fault == "four_taps":
        w = jnp.concatenate([w[:, :1], w], axis=1)
        taps += 1
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    v = sum(padded[:, j:j + t] * w[:, j] for j in range(taps))
    if fault == "conv_bias":
        v = v + jnp.asarray(0.1, v.dtype)
    return jax.nn.silu(v) if fault == "silu_on_conv" else v


def short_conv(w, x, fault=None):
    """The gated short convolution (weights by their names after
    `l<p>.conv.`) on x [B, T, D]."""
    d = x.shape[-1]
    mixed = x @ w["in.w"]
    b, c, xs = mixed[..., :d], mixed[..., d:2 * d], mixed[..., 2 * d:]
    if fault == "gates_swapped":
        b, c = c, b
    u = xs if fault == "no_gate_before_conv" else b * xs
    v = causal_conv(u, w["conv.w"], fault)
    y = v if fault == "no_gate_after_conv" else c * v
    return y @ w["out.w"]


def attention(w, x, *, n_head, n_kv_head, head_dim, theta, eps, q_block=None,
              fault=None):
    """Causal softmax attention of one layer (weights by their names after
    `l<p>.attn.`) on x [B, T, D], `q_block` queries at a time."""
    bsz, t, _ = x.shape
    q, k = x @ w["q.w"], x @ w["k.w"]
    if fault == "qk_norm_over_all_heads":
        q = rms_norm(q, jnp.tile(w["q_norm.w"], n_head), eps)
        k = rms_norm(k, jnp.tile(w["k_norm.w"], n_kv_head), eps)
    q = q.reshape(bsz, t, n_head, head_dim)
    k = k.reshape(bsz, t, n_kv_head, head_dim)
    if fault not in ("no_qk_norm", "qk_norm_over_all_heads"):
        q = rms_norm(q, w["q_norm.w"], eps)
        k = rms_norm(k, w["k_norm.w"], eps)
    q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
    v = (x @ w["v.w"]).reshape(bsz, t, n_kv_head, head_dim) \
        .transpose(0, 2, 1, 3)
    if fault != "no_rotary":
        turn = functools.partial(rotary, theta=theta,
                                 interleaved=fault == "rotary_interleaved")
        q, k = turn(q), turn(k)
    group = n_head // n_kv_head
    if fault == "kv_head_order":        # head h reads kv head h % kv_heads
        k, v = jnp.tile(k, (1, group, 1, 1)), jnp.tile(v, (1, group, 1, 1))
    else:                               # head h reads kv head h // group
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    step = q_block or t
    blocks = []
    for first in range(0, t, step):
        end = min(first + step, t)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, first:end],
                            k[:, :, :end]) * head_dim ** -0.5
        visible = jnp.arange(end)[None, :] <= jnp.arange(first, end)[:, None]
        weights = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("bhqk,bhkd->bhqd", weights, v[:, :, :end]))
    ctx = jnp.concatenate(blocks, axis=2).transpose(0, 2, 1, 3)
    return ctx.reshape(bsz, t, n_head * head_dim) @ w["o.w"]


def gated_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, w_router, bias, top_k, route_scale, norm_eps, fault=None):
    """(weights [N, k], indices [N, k], scores [N, E]): chosen by score +
    bias, weighted by the score alone."""
    logits = x @ w_router
    scores = jax.nn.softmax(logits, axis=-1) if fault == "softmax_router" \
        else jax.nn.sigmoid(logits)
    _, index = jax.lax.top_k(scores + bias, top_k)
    weight = jnp.take_along_axis(
        scores + bias if fault == "bias_in_weights" else scores, index,
        axis=-1)
    if fault != "no_topk_renorm":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + norm_eps)
    return weight * route_scale, index, scores


def routed_experts(w, x, *, top_k, first_expert, route_scale, norm_eps,
                   fault=None):
    """x [N, D] -> (the held experts' part of the routed result, chosen
    indices [N, k]). No shared expert."""
    weight, index, _ = route(x, w["router.w"], w["router.bias"], top_k,
                             route_scale, norm_eps, fault)

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
    if fault == "shared_expert_added":
        out = out + gated_mlp(x, *(s[0] for s in stacks))
    return out, index


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer(w, x, kind, sizes):
    """One layer (its weights by their names after `l<p>.`) on x [B, T, D]:
    the operator `kind` ("conv" | "full_attention"), then the feed-forward
    its weights name; `sizes` a tuple of (name, value) pairs. Returns the new
    x and the router's indices (None for a dense layer)."""
    s = dict(sizes)
    eps, fault = s["rms_eps"], s["fault"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    normed = rms_norm(x, w["op_norm.w"], eps)
    if kind == "conv":
        mixed = short_conv(sub("conv."), normed, fault)
    else:
        mixed = attention(
            sub("attn."), normed, n_head=s["n_head"],
            n_kv_head=s["n_kv_head"], head_dim=s["head_dim"],
            theta=s["rope_theta"], eps=eps, q_block=s["q_block"],
            fault=fault)
    x = x + mixed
    normed = rms_norm(x, w["ffn_norm.w"], eps)
    if "router.w" not in w:
        return x + gated_mlp(normed, w["mlp.gate.w"], w["mlp.up.w"],
                             w["mlp.down.w"]), None
    b, t, d = x.shape
    fed, index = routed_experts(
        w, normed.reshape(b * t, d), top_k=s["top_k"],
        first_expert=s["first_expert"], route_scale=s["route_scale"],
        norm_eps=s["route_norm_eps"], fault=fault)
    return x + fed.reshape(b, t, d), index


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


def loss_parts(params, tokens, labels, *, layer_types=LAYER_TYPES,
               first_layer=0, n_head=32, n_kv_head=8, head_dim=64,
               rope_theta=1e6, top_k=4, first_expert=0, route_scale=1.0,
               route_norm_eps=1e-6, tie_embeddings=True, rms_eps=1e-5,
               dtype=jnp.float32, q_block=None, remat=False, last=None,
               fault=None):
    """The loss that is minimised and its parts: `loss` and `ce` (the mean
    cross-entropy, twice: nothing else is in the loss), and
    `tokens_per_expert` [expert layers, E]. With `last`, also `logits` on the
    final `last` positions, [B, last, V]. `layer_types`: the kind of each
    layer here, the published layers from `first_layer` on. The biases are
    read from `params` (`l<p>.router.bias`) and are not advanced here:
    `next_bias` is. Tied, the head is `embed.w` transposed; untied it is
    `head.w`."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault is one of {sorted(FAULTS)}, got {fault!r}")
    sizes = tuple(sorted(dict(
        n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
        rope_theta=float(rope_theta), top_k=top_k, first_expert=first_expert,
        route_scale=float(route_scale), route_norm_eps=float(route_norm_eps),
        rms_eps=rms_eps, q_block=q_block, fault=fault).items()))
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(2, 3)) if remat \
            else layer

        def weights(index):
            prefix = f"l{index}."
            return {k[len(prefix):]: v for k, v in p.items()
                    if k.startswith(prefix)}

        own = [weights(first_layer + i) for i in range(len(layer_types))]
        x = jnp.take(p["embed.w"], tokens, axis=0)
        chosen = []
        for i, kind in enumerate(layer_types):
            w, counted = own[i], True
            if fault == "second_dense_layer_sparse" and "router.w" not in w \
                    and own[i + 1:] \
                    and all("router.w" in later for later in own[i + 1:]):
                borrowed = own[i + 1]
                w = {**{k: v for k, v in w.items()
                        if not k.startswith("mlp.")},
                     **{k: v for k, v in borrowed.items()
                        if k.startswith(("router.", "experts."))}}
                counted = False
            x, index = apply(w, x, kind, sizes)
            if index is not None and counted:
                n_expert = w["router.w"].shape[-1]
                chosen.append(jnp.sum(
                    index[:, :, None] == jnp.arange(n_expert), axis=(0, 1)))
        if fault != "no_final_norm":
            x = rms_norm(x, p["final_norm.w"], rms_eps)
        w_head = p["embed.w"].T if tie_embeddings else p["head.w"]
        if fault == "untied_head":
            w_head = jax.lax.stop_gradient(w_head)
        ce_of = jax.checkpoint(head_ce, static_argnums=(3,)) if remat \
            else head_ce
        ce = jnp.mean(ce_of(x, w_head, labels, q_block))
        out = {"loss": ce, "ce": ce}
        if chosen:
            out["tokens_per_expert"] = jnp.stack(chosen)
        if last is not None:
            out["logits"] = x[:, -last:] @ w_head
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
