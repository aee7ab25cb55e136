"""Ling-3.0-flash-VL's language model (groups of `layer_group_size` layers:
Kimi Delta Attention, a delta rule whose decay is per key channel, in all but
the last of a group and latent attention under a head-wise gate in the last;
a dense gated MLP in the first published layers, a sparse-expert layer under a
group-limited sigmoid router with a selection bias and one shared expert in
the others) in plain `jax.numpy`: the forward pass, the loss, its gradients
and the step's update of the router biases, for ONE CHIP'S SHARE of the expert
layers and a RUN of published layers from `first_layer` on. What the program
(`paddle_tpu/models/ling3.py`) is compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel, no
chunks, no sort, no grouped matmul: the delta rule runs TOKEN BY TOKEN (a
`lax.scan` over t of the state's three lines); the convolution is a sum of
`conv_kernel` shifted products; latent attention is a masked softmax with the
rotary key repeated by `jnp.repeat`; the router's groups are `top_k`s and
masks; the held experts are a loop (a `lax.scan` over their stacked weights),
each applied to every token and kept through a dense mask of the router's
weights. Weights come as a dict under the program's parameter names, matrices
stored `[in, out]` (D hidden, V the vocabulary rows held, H heads of Dh, E
experts routed over, Eh of them held here, F an expert's width, K taps):

    embed.w [V, D]   head.w [D, V]   final_norm.w [D]
    l<i>.in_norm.w, l<i>.post_norm.w [D]
    l<i>.kda.q.w, .k.w, .v.w, .f.w, .g.w, [D, H Dh]   l<i>.kda.b.w [D, H]
    l<i>.kda.conv.w [3 H Dh, K]   l<i>.kda.A_log [H]   l<i>.kda.dt_bias [H Dh]
    l<i>.kda.norm.w [Dh]   l<i>.kda.o.w [H Dh, D]
    l<i>.mla.q.w [D, H (nope + rope)]   l<i>.mla.kv_a.w [D, rank + rope]
    l<i>.mla.kv_norm.w [rank]   l<i>.mla.kv_b.w [rank, H (nope + v)]
    l<i>.mla.gate.w [D, H]   l<i>.mla.o.w [H v, D]
    l<i>.mlp.gate.w, l<i>.mlp.up.w [D, Fd]   l<i>.mlp.down.w [Fd, D]
    l<i>.router.w [D, E]   l<i>.router.bias [E]  (float32; not trained)
    l<i>.experts.gate.w, .up.w [Eh, D, F]   l<i>.experts.down.w [Eh, F, D]
    l<i>.shared.gate.w, .up.w [D, Fs]       l<i>.shared.down.w [Fs, D]

The equations (Kimi Linear, arXiv:2510.26692, and the public
`flash-linear-attention` `KimiDeltaAttention` with its `safe_gate` /
`lower_bound` decay for KDA; DeepSeek-V2 for MLA; DeepSeek-V3,
arXiv:2412.19437, section 2.1.2 and the public `deepseek_v3` code for the
router), i the built layer, p = first_layer + i its published index:

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w
    h = x + Mixer_p(N(x));  y = h + FFN_p(N(h));  after the last layer N, head
    Mixer_p = MLA where (p + 1) % layer_group_size == 0, else KDA
    FFN_p = dense down(silu(gate x) * up x) where p < n_dense_layer, else MoE
    KDA:  q, k, v = x W_q, x W_k, x W_v;  [q | k | v] <- silu(conv(.)),
          depthwise, output t reads inputs t - K + 1 .. t, no bias
          q <- q / sqrt(sum q^2 + 1e-6) * Dh^-0.5;  k likewise, unscaled
          g = lower_bound * sigmoid(exp(A_log_h) * (x W_f + dt_bias)) [T, H, Dh]
          beta = sigmoid(x W_b)  [T, H]
          per head, S [Dh key, Dh value], S_0 = 0, every t:
              S <- Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);
              S <- S + k_t d^T;  o_t = S^T q_t
          out = (o * rsqrt(mean(o^2) + eps) * w_norm * sigmoid(x W_g)) W_o
    MLA:  q = x W_q, per head [q_n | q_r];  [c | k_r] = x W_kva;  c = N(c);
          [k_n | v] = c W_kvb per head;  interleaved rotary on q_r and the
          ONE k_r (repeated over the heads);  ctx = causal softmax(q k^T
          (nope + rope)^-0.5) v;  ctx_h <- ctx_h * sigmoid(x W_gate)_h;
          out = ctx W_o
    MoE:  s = sigmoid(x W_r);  c = s + b;  n_group groups of E / n_group
          consecutive experts, a group's score the sum of its two largest c,
          the topk_group best groups stay;  idx = top-k of c over their
          experts;  w = s[idx] / (sum + 1e-20) * routed_scaling_factor
          routed = sum over the chosen experts THAT ARE HELD HERE of w_e *
          down_e(silu(gate_e x) * up_e x);  out = routed + shared(x)
    loss = mean cross-entropy
    after a step, per MoE layer (`next_bias`): b <- b + gamma sign(mean(c) - c),
        c the step's assignments per expert (all E)

The share: what the absent experts would add is left out, here as in the
program, and that partial result goes on to the next layer; the vocabulary is
the slice the weights have.

`dtype` other than float32 computes everything, the recurrence, the router,
the softmax and the loss included, in that precision: the comparison's
tolerance has to refuse it. `q_block` computes the attention a block of
queries at a time and the head's cross-entropy a block of positions at a
time; `token_block` runs the recurrence as an outer scan over blocks of that
many tokens under `jax.checkpoint` around the scan over a block's tokens, so
that a gradient keeps a state a block, not a token; `remat` wraps each layer
in `jax.checkpoint`: all three are this reference's memory at published
widths, not its mathematics (a test holds that they change nothing). A layer
is one jitted function of its own weights, so the layers of a kind share one
compiled program.

`fault` plants one named fault (`FAULTS`): what a comparison with this
reference has to refuse. A test and `reference_check_ling3.py` hold that each
moves at least one compared quantity past its limit.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools

import jax
import jax.numpy as jnp

FAULTS = {
    "scalar_decay": "g is one number a head, the mean over its key channels: "
                    "the gated delta rule under KDA's name",
    "unbounded_decay": "g = -exp(A_log) softplus(x W_f + dt_bias): no bound",
    "decay_after_write": "S <- Diag(exp(g_t)) (S + k_t d^T), d read from the "
                         "undecayed state",
    "beta_one": "beta = 1: every write at full strength",
    "k_not_normed": "k is not l2-normalised",
    "q_unscaled": "q is l2-normalised and not scaled by Dh^-0.5",
    "state_reset": "the state starts from 0 again every `chunk` tokens (64: "
                   "read by this fault alone)",
    "silu_out_gate": "KDA's output gate is silu, not sigmoid",
    "gate_before_norm": "N(o * sigmoid(gate)) * w: the gate first",
    "conv_sees_future": "output t reads inputs t - K + 2 .. t + 1",
    "mla_off_by_one": "built layers 3 and 4 change places: the latent layer "
                      "at published index 4, a group of six off by one",
    "mla_no_gate": "the latent layer's context is not gated",
    "mla_gate_by_channel": "channel c of the context reads gate c mod H: the "
                           "gate laid over channels, not one a head",
    "no_groups": "the top-k over all experts: no groups",
    "group_by_best": "a group's score is its best c alone, not its two best",
    "choice_without_bias": "idx = top-k of s: the bias moves nothing",
    "no_route_scale": "w is not multiplied by routed_scaling_factor",
    "no_shared_expert": "out = routed: no shared expert",
}


def rms_norm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary_interleaved(x, theta):
    """x [B, H, T, r], the public `deepseek_v3` code's
    `apply_rotary_pos_emb_interleave`: the pairs (x[2i], x[2i+1]) laid out as
    [evens | odds], then `x cos + rotate_half(x) sin`."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    x = x.reshape(x.shape[:-1] + (r // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (r,))
    return x * cos + rotate_half(x) * sin


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, token_block=None, reset=None,
               decay_after_write=False):
    """The recurrence token by token: q, k, g [B, T, H, Dk] (q, k already
    normalised and scaled), v [B, T, H, Dv], beta [B, T, H] -> o
    [B, T, H, Dv]. `reset`: the state starts from 0 again at every multiple
    of it; `decay_after_write`: planted faults'."""
    bsz, t, h, dk = q.shape

    def token(S, x):
        i, q_t, k_t, v_t, g_t, b_t = x
        if reset is not None:
            S = jnp.where(i % reset == 0, jnp.zeros_like(S), S)
        decay = jnp.exp(g_t)[..., None]
        if not decay_after_write:
            S = decay * S
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * d[..., None, :]
        if decay_after_write:
            S = decay * S
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    @jax.checkpoint
    def block(S, x):
        return jax.lax.scan(token, S, x)

    step = token_block or t
    seq = [jnp.arange(t).reshape(t // step, step)] + [
        jnp.moveaxis(x, 1, 0).reshape((t // step, step) + x.shape[:1]
                                      + x.shape[2:])
        for x in (q, k, v, g, beta)]
    S0 = jnp.zeros((bsz, h, dk, v.shape[-1]), q.dtype)
    _, o = jax.lax.scan(block, S0, seq)             # [T/step, step, B, H, Dv]
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def causal_conv_silu(x, w, future=False):
    """x [B, T, C], w [C, K]: output t is `sum_j w[:, j] x[t - (K-1) + j]`
    with zeros before the start, then silu. `future`: one token later (a
    planted fault's)."""
    t, kernel = x.shape[1], w.shape[1]
    before = kernel - 2 if future else kernel - 1
    padded = jnp.pad(x, ((0, 0), (before, kernel - 1 - before), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + t] * w[:, j]
                           for j in range(kernel)))


def kda(w, x, *, n_head, lower_bound, eps, chunk=64, token_block=None,
        fault=None):
    """One KDA mixer (weights by their names after `l<i>.kda.`) on x
    [B, T, D]."""
    bsz, t, _ = x.shape
    wide = w["q.w"].shape[1]
    dh = wide // n_head
    qkv = causal_conv_silu(
        jnp.concatenate([x @ w["q.w"], x @ w["k.w"], x @ w["v.w"]], axis=-1),
        w["conv.w"], future=fault == "conv_sees_future")
    q, k, v = (qkv[..., j * wide:(j + 1) * wide].reshape(bsz, t, n_head, dh)
               for j in range(3))
    q = l2_normalize(q)
    if fault != "q_unscaled":
        q = q * dh ** -0.5
    if fault != "k_not_normed":
        k = l2_normalize(k)
    raw = (x @ w["f.w"] + w["dt_bias"]).reshape(bsz, t, n_head, dh)
    rate = jnp.exp(w["A_log"])[:, None]
    if fault == "unbounded_decay":
        g = -rate * jax.nn.softplus(raw)
    else:
        g = lower_bound * jax.nn.sigmoid(rate * raw)
    if fault == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(x @ w["b.w"])
    if fault == "beta_one":
        beta = jnp.ones_like(beta)
    o = delta_rule(q, k, v, g, beta, token_block,
                   reset=chunk if fault == "state_reset" else None,
                   decay_after_write=fault == "decay_after_write")
    gate = (x @ w["g.w"]).reshape(bsz, t, n_head, dh)
    gate = jax.nn.silu(gate) if fault == "silu_out_gate" \
        else jax.nn.sigmoid(gate)
    if fault == "gate_before_norm":
        o = rms_norm(o * gate, w["norm.w"], eps)
    else:
        o = rms_norm(o, w["norm.w"], eps) * gate
    return o.reshape(bsz, t, wide) @ w["o.w"]


def latent_attention(w, x, *, n_head, qk_nope_dim, qk_rope_dim, v_head_dim,
                     theta, eps, q_block=None, fault=None):
    """MLA of one layer (its weights by their names after `l<i>.mla.`) on x
    [B, T, D], `q_block` queries at a time, its context gated head by
    head."""
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
    k = jnp.concatenate([kv[..., :n], jnp.repeat(k_rope, n_head, axis=1)],
                        axis=-1)
    v = kv[..., n:]
    step = q_block or t
    blocks = []
    for first in range(0, t, step):
        end = min(first + step, t)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, first:end],
                            k[:, :, :end]) * (n + r) ** -0.5
        visible = jnp.arange(end)[None, :] <= jnp.arange(first, end)[:, None]
        weights = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("bhqk,bhkd->bhqd", weights, v[:, :, :end]))
    ctx = jnp.concatenate(blocks, axis=2).transpose(0, 2, 1, 3)  # [B,T,H,dv]
    gate = jax.nn.sigmoid(x @ w["gate.w"])                       # [B, T, H]
    ctx = ctx.reshape(b, t, n_head * dv)
    if fault == "mla_gate_by_channel":
        ctx = ctx * jnp.tile(gate, (1, 1, dv))
    elif fault != "mla_no_gate":
        ctx = ctx * jnp.repeat(gate, dv, axis=-1)
    return ctx @ w["o.w"]


def gated_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, w_router, bias, top_k, n_group, topk_group, scale, fault=None):
    """(weights [N, k], indices [N, k]): the experts of the `topk_group` best
    of `n_group` groups, chosen by score + bias, weighted by the score
    alone."""
    scores = jax.nn.sigmoid(x @ w_router)
    choice = scores if fault == "choice_without_bias" else scores + bias
    if fault != "no_groups":
        n, e = choice.shape
        grouped = choice.reshape(n, n_group, e // n_group)
        best = jax.lax.top_k(grouped, 1 if fault == "group_by_best" else 2)[0]
        _, kept = jax.lax.top_k(jnp.sum(best, axis=-1), topk_group)
        stays = jnp.zeros((n, n_group), bool).at[
            jnp.arange(n)[:, None], kept].set(True)
        choice = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(n, e)
    _, index = jax.lax.top_k(choice, top_k)
    weight = jnp.take_along_axis(scores, index, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_route_scale":
        weight = weight * scale
    return weight, index


def sparse_experts(w, x, *, top_k, n_group, topk_group, first_expert, scale,
                   fault=None):
    """x [N, D] -> (the held experts' part of the routed result plus the
    shared expert, chosen indices [N, k])."""
    weight, index = route(x, w["router.w"], w["router.bias"], top_k, n_group,
                          topk_group, scale, fault)

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
    if fault != "no_shared_expert":
        out = out + gated_mlp(x, w["shared.gate.w"], w["shared.up.w"],
                              w["shared.down.w"])
    return out, index


@functools.partial(jax.jit, static_argnums=(2,))
def layer(w, x, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D];
    `sizes` a tuple of (name, value) pairs. The weights say what the layer
    is: `mla.*` or `kda.*`, `mlp.*` or `router.w`. Returns the new x and the
    router's indices (None for a dense layer)."""
    s = dict(sizes)
    eps, fault = s["rms_eps"], s["fault"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    normed = rms_norm(x, w["in_norm.w"], eps)
    if "mla.q.w" in w:
        x = x + latent_attention(
            sub("mla."), normed, n_head=s["n_head"],
            qk_nope_dim=s["qk_nope_dim"], qk_rope_dim=s["qk_rope_dim"],
            v_head_dim=s["v_head_dim"], theta=s["rope_theta"], eps=eps,
            q_block=s["q_block"], fault=fault)
    else:
        x = x + kda(sub("kda."), normed, n_head=s["n_head"],
                    lower_bound=s["kda_lower_bound"], eps=eps,
                    chunk=s["chunk"], token_block=s["token_block"],
                    fault=fault)
    normed = rms_norm(x, w["post_norm.w"], eps)
    if "router.w" not in w:
        return x + gated_mlp(normed, w["mlp.gate.w"], w["mlp.up.w"],
                             w["mlp.down.w"]), None
    b, t, d = x.shape
    moe, index = sparse_experts(
        w, normed.reshape(b * t, d), top_k=s["top_k"], n_group=s["n_group"],
        topk_group=s["topk_group"], first_expert=s["first_expert"],
        scale=s["routed_scaling_factor"], fault=fault)
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


def layer_is(params, i, first_layer, layer_group_size, n_dense_layer):
    """What the PUBLISHED pattern says built layer i is, checked against the
    weights it was given: (mixer, feed-forward)."""
    p = first_layer + i
    mixer = "mla" if (p + 1) % layer_group_size == 0 else "kda"
    fed = "mlp" if p < n_dense_layer else "moe"
    has = {"mla": f"l{i}.mla.q.w", "kda": f"l{i}.kda.q.w",
           "mlp": f"l{i}.mlp.gate.w", "moe": f"l{i}.router.w"}
    if has[mixer] not in params or has[fed] not in params:
        raise ValueError(f"layer {i} (published {p}) is {mixer} + {fed} by "
                         f"the pattern, and the weights lack "
                         f"{has[mixer]} or {has[fed]}")
    return mixer, fed


def loss_parts(params, tokens, labels, *, n_layer, first_layer=0,
               layer_group_size=6, n_dense_layer=2, n_head=32,
               kda_lower_bound=-5.0, qk_nope_dim=128, qk_rope_dim=64,
               v_head_dim=128, rope_theta=6e6, top_k=8, n_group=8,
               topk_group=4, first_expert=0, routed_scaling_factor=2.5,
               rms_eps=1e-6, chunk=64, dtype=jnp.float32, q_block=None,
               token_block=None, remat=False, last=None, fault=None):
    """The loss that is minimised and its parts: `loss` and `ce` (the mean
    cross-entropy, twice: nothing else is in the loss), and
    `tokens_per_expert` [expert layers, E]. With `last`, also `logits` on the
    final `last` positions, [B, last, V]. The biases are read from `params`
    (`l<i>.router.bias`) and are not advanced here: `next_bias` is."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault is one of {sorted(FAULTS)}, got {fault!r}")
    sizes = tuple(sorted(dict(
        n_head=n_head, kda_lower_bound=kda_lower_bound,
        qk_nope_dim=qk_nope_dim, qk_rope_dim=qk_rope_dim,
        v_head_dim=v_head_dim, rope_theta=rope_theta, top_k=top_k,
        n_group=n_group, topk_group=topk_group, first_expert=first_expert,
        routed_scaling_factor=routed_scaling_factor, rms_eps=rms_eps,
        chunk=chunk, q_block=q_block, token_block=token_block,
        fault=fault).items()))
    for i in range(n_layer):
        layer_is(params, i, first_layer, layer_group_size, n_dense_layer)
    order = list(range(n_layer))
    if fault == "mla_off_by_one":
        order[3], order[4] = order[4], order[3]
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(2,)) if remat else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        chosen = {}
        for i in order:
            prefix = f"l{i}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            x, index = apply(w, x, sizes)
            if index is not None:
                n_expert = w["router.w"].shape[-1]
                chosen[i] = jnp.sum(
                    index[:, :, None] == jnp.arange(n_expert), axis=(0, 1))
        x = rms_norm(x, p["final_norm.w"], rms_eps)
        ce_of = jax.checkpoint(head_ce, static_argnums=(3,)) if remat \
            else head_ce
        ce = jnp.mean(ce_of(x, p["head.w"], labels, q_block))
        out = {"loss": ce, "ce": ce}
        if chosen:
            out["tokens_per_expert"] = jnp.stack(
                [chosen[i] for i in sorted(chosen)])
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
