"""Nemotron-H (`model_type: nemotron_h`: layers that are ONE sublayer each by
a pattern string over `M`, a Mamba-2 state-space mixer, `E`, a sparse-expert
layer of two-matrix relu² experts under a sigmoid router with a selection
bias beside one shared expert, and `*`, grouped softmax attention with no
positions) in plain `jax.numpy`: the forward pass, the loss, its gradients and
the step's update of the router biases, for ONE CHIP'S SHARE of the expert
layers. What the program (`paddle_tpu/models/nemotron_h.py`) is compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel, no
chunks, no sort, no grouped matmul: the state-space recurrence runs TOKEN BY
TOKEN (a `lax.scan` over t of the state's update); the convolution is a sum
of `conv_kernel` shifted products; attention is a masked softmax with the key
and value heads repeated by `jnp.repeat`; the held experts are a loop (a
`lax.scan` over their stacked weights), each applied to every token and kept
through a dense mask of the router's weights. Weights come as a dict under
the program's parameter names, matrices stored `[in, out]` (D hidden, V the
vocabulary rows held, E experts routed over, Eh of them held here, F an
expert's width, H heads of P, G groups, N the state, K taps, I = H P):

    embed.w [V, D]   head.w [D, V]   final_norm.w [D]   l<i>.norm.w [D]
    l<i>.mamba.in.w [D, 2 I + 2 G N + H]   columns [z | xs | B | C | dt_raw]
    l<i>.mamba.conv.w [I + 2 G N, K]   l<i>.mamba.conv.b [I + 2 G N]
    l<i>.mamba.A_log, l<i>.mamba.dt_bias, l<i>.mamba.D [H]
    l<i>.mamba.norm.w [I]   l<i>.mamba.out.w [I, D]
    l<i>.attn.q.w [D, heads * head_dim]   l<i>.attn.k.w, l<i>.attn.v.w
    [D, kv_heads * head_dim]   l<i>.attn.o.w [heads * head_dim, D]
    l<i>.router.w [D, E]   l<i>.router.bias [E]  (float32; not trained)
    l<i>.experts.up.w [Eh, D, F]   l<i>.experts.down.w [Eh, F, D]
    l<i>.shared.up.w [D, Fs]       l<i>.shared.down.w [Fs, D]

The equations (Nemotron-H, arXiv:2504.03624, and the public `nemotron_h`
model code for the block; Mamba-2, arXiv:2405.21060, for the mixer;
DeepSeek-V3, arXiv:2412.19437, for the router):

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w
    layer l:  x = x + F_l(N(x));  F_l ONE of M, E, * by `layer_pattern[l]`;
              after the last layer N, then the head
    M:  [z | u | dt_raw] = x W_in;  u = silu(conv(u) + b_conv), depthwise,
        output t reads inputs t - K + 1 .. t;  [xs | B | C] = u
        dt = softplus(dt_raw + dt_bias);  a = -exp(A_log) dt   (no clamp)
        per head h (its B, C those of group h // (H / G)), S_0 = 0, every t:
            S_t = exp(a_t) S_{t-1} + dt_t xs_t B_t^T;   y_t = S_t C_t + D xs_t
        y = y silu(z);  y = y rsqrt(mean(y^2) + eps) over each group of I / G,
        times w_norm;  out = y W_out
    *:  q, k, v = x W_q, x W_k, x W_v;  NO rotary, no positions, no QK-norm;
        key-value head h // group serves query head h;  scores times
        head_dim^-0.5;  key j is visible to query i iff j <= i;  softmax;
        out = ctx W_o
    E:  s = sigmoid(x W_r);  idx = top-k of s + b  (one group: n_group 1);
        w = s[idx];  w = w / (sum_k w + 1e-20);  w = routed_scaling_factor w
        routed = sum over the chosen experts THAT ARE HELD HERE of w_k *
        down_e(relu(up_e x)^2);  shared = down_s(relu(up_s x)^2);  routed +
        shared
    loss = mean cross-entropy
    after a step, per E layer (`next_bias`):  b <- b + gamma sign(mean(c) - c),
        c the step's assignments per expert (all E)

Departures from the public code: none in the forward pass; the bias update is
the DeepSeek-V3 report's at rate gamma (the config has no key for it). The
share: what the absent experts would add is left out, here as in the program,
and that partial result goes on to the next layer; the vocabulary is the slice
the weights have.

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
reference has to refuse. A test and `reference_check_nemotron_h.py` hold that
each moves at least one compared quantity past its limit.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools

import jax
import jax.numpy as jnp

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"  # as published

FAULTS = {
    "no_decay": "a = 0: the state never fades",
    "dt_without_softplus": "dt = dt_raw + dt_bias",
    "dt_without_bias": "dt = softplus(dt_raw)",
    "b_c_swapped": "the state is written by C and read by B",
    "group_zero": "every head reads group 0's B and C",
    "state_reset": "the state starts from 0 again at every chunk's first "
                   "token (`chunk`, 128 as published: read by this fault "
                   "alone)",
    "no_skip": "y_t = S_t C_t: no D term",
    "norm_before_gate": "y = N(y) silu(z): the norm first, then the gate",
    "norm_over_all": "one mean over all I lanes, not one a group",
    "no_conv_bias": "u = silu(conv(u))",
    "conv_sees_future": "output t reads inputs t - K + 2 .. t + 1",
    "relu_not_squared": "the experts' middle is relu(x), not its square",
    "gated_experts": "down(silu(up x) * relu(up x)^2): a gate the experts "
                     "do not have",
    "no_route_scale": "w is not multiplied by routed_scaling_factor",
    "no_renormalise": "w = s[idx], not divided by their sum",
    "rotary_in_attention": "the attention layers turn q and k (rotate-half, "
                           "theta 10000)",
    "layer_order": "layers 5 and 6 change places: MEMEME*ME for MEMEM*EME",
}


def rms_norm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def rotary(x, theta):
    """x [B, H, T, Dh]; rotate-half on the whole head (a planted fault's)."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def selective_scan(xs, dt, a, b, c, skip, token_block=None, reset=None):
    """The recurrence token by token: xs [B, T, H, P], dt, a [B, T, H], b, c
    [B, T, H, N] (already per head), skip [H] or None -> y [B, T, H, P].
    `reset`: the state starts from 0 again at every multiple of it (a
    planted fault's)."""
    bsz, t, h, p = xs.shape

    def token(S, x):
        i, xs_t, dt_t, a_t, b_t, c_t = x
        if reset is not None:
            S = jnp.where(i % reset == 0, jnp.zeros_like(S), S)
        S = jnp.exp(a_t)[..., None, None] * S \
            + (dt_t[..., None] * xs_t)[..., :, None] * b_t[..., None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    @jax.checkpoint
    def block(S, x):
        return jax.lax.scan(token, S, x)

    step = token_block or t
    seq = [jnp.arange(t).reshape(t // step, step)] + [
        jnp.moveaxis(v, 1, 0).reshape((t // step, step) + v.shape[:1]
                                      + v.shape[2:])
        for v in (xs, dt, a, b, c)]
    S0 = jnp.zeros((bsz, h, p, b.shape[-1]), xs.dtype)
    _, y = jax.lax.scan(block, S0, seq)             # [T/step, step, B, H, P]
    y = jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)
    if skip is not None:
        y = y + skip[:, None] * xs
    return y


def causal_conv_silu(x, w, bias, future=False):
    """x [B, T, C], w [C, K], bias [C] or None: output t is `sum_j w[:, j]
    x[t - (K-1) + j]` with zeros before the start, plus the bias, then silu.
    `future`: one token later (a planted fault's)."""
    t, kernel = x.shape[1], w.shape[1]
    before = kernel - 2 if future else kernel - 1
    padded = jnp.pad(x, ((0, 0), (before, kernel - 1 - before), (0, 0)))
    y = sum(padded[:, j:j + t] * w[:, j] for j in range(kernel))
    if bias is not None:
        y = y + bias
    return jax.nn.silu(y)


def mamba(w, x, *, heads, head_dim, groups, state, eps, chunk=128,
          token_block=None, fault=None):
    """One state-space mixer (weights by their names after `l<i>.mamba.`) on
    x [B, T, D]."""
    bsz, t, _ = x.shape
    inner, bc = heads * head_dim, groups * state
    mixed = x @ w["in.w"]
    z, u, dt_raw = mixed[..., :inner], mixed[..., inner:2 * inner + 2 * bc], \
        mixed[..., 2 * inner + 2 * bc:]
    u = causal_conv_silu(u, w["conv.w"],
                         None if fault == "no_conv_bias" else w["conv.b"],
                         future=fault == "conv_sees_future")
    xs = u[..., :inner].reshape(bsz, t, heads, head_dim)
    b = u[..., inner:inner + bc].reshape(bsz, t, groups, state)
    c = u[..., inner + bc:].reshape(bsz, t, groups, state)
    if fault == "b_c_swapped":
        b, c = c, b
    if fault == "group_zero":
        b = jnp.repeat(b[:, :, :1], heads, axis=2)
        c = jnp.repeat(c[:, :, :1], heads, axis=2)
    else:       # head h reads group h // (heads / groups)
        b = jnp.repeat(b, heads // groups, axis=2)
        c = jnp.repeat(c, heads // groups, axis=2)
    dt = dt_raw if fault == "dt_without_bias" else dt_raw + w["dt_bias"]
    if fault != "dt_without_softplus":
        dt = jax.nn.softplus(dt)
    a = jnp.zeros_like(dt) if fault == "no_decay" else -jnp.exp(w["A_log"]) * dt
    y = selective_scan(xs, dt, a, b, c,
                       None if fault == "no_skip" else w["D"], token_block,
                       reset=chunk if fault == "state_reset" else None)
    y = y.reshape(bsz, t, inner)
    gate = jax.nn.silu(z)

    def norm(v):
        if fault == "norm_over_all":
            return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
        g = v.reshape(bsz, t, groups, inner // groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
        return g.reshape(bsz, t, inner)

    if fault == "norm_before_gate":
        y = norm(y) * w["norm.w"] * gate
    else:
        y = norm(y * gate) * w["norm.w"]
    return y @ w["out.w"]


def attention(w, x, *, n_head, n_kv_head, head_dim, q_block=None, fault=None):
    """Causal softmax attention of one layer (weights by their names after
    `l<i>.attn.`) on x [B, T, D], `q_block` queries at a time; no positions
    of any kind."""
    bsz, t, _ = x.shape
    q = (x @ w["q.w"]).reshape(bsz, t, n_head, head_dim).transpose(0, 2, 1, 3)
    k = (x @ w["k.w"]).reshape(bsz, t, n_kv_head, head_dim) \
        .transpose(0, 2, 1, 3)
    v = (x @ w["v.w"]).reshape(bsz, t, n_kv_head, head_dim) \
        .transpose(0, 2, 1, 3)
    if fault == "rotary_in_attention":
        q, k = rotary(q, 1e4), rotary(k, 1e4)
    group = n_head // n_kv_head         # query head h reads kv head h // group
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


def relu2_mlp(x, w_up, w_down, fault=None):
    h = x @ w_up
    mid = jnp.maximum(h, 0)
    if fault != "relu_not_squared":
        mid = mid * mid
    if fault == "gated_experts":
        mid = mid * jax.nn.silu(h)
    return mid @ w_down


def route(x, w_router, bias, top_k, scale, fault=None):
    """(weights [N, k], indices [N, k]): chosen by score + bias, weighted by
    the score alone."""
    scores = jax.nn.sigmoid(x @ w_router)
    _, index = jax.lax.top_k(scores + bias, top_k)
    weight = jnp.take_along_axis(scores, index, axis=-1)
    if fault != "no_renormalise":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_route_scale":
        weight = weight * scale
    return weight, index


def sparse_experts(w, x, *, top_k, first_expert, scale, fault=None):
    """x [N, D] -> (the held experts' part of the routed result plus the
    shared expert, chosen indices [N, k])."""
    weight, index = route(x, w["router.w"], w["router.bias"], top_k, scale,
                          fault)

    def expert(out, held):                  # one expert held here
        e, w_up, w_down = held
        mask = jnp.sum(jnp.where(index == first_expert + e, weight, 0),
                       axis=-1, keepdims=True)
        return out + mask.astype(x.dtype) * relu2_mlp(x, w_up, w_down,
                                                      fault), None

    stacks = (w["experts.up.w"], w["experts.down.w"])
    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.arange(stacks[0].shape[0]),) + stacks)
    # the planted faults are the routed experts': the shared one is plain
    shared = relu2_mlp(x, w["shared.up.w"], w["shared.down.w"],
                       fault if fault == "relu_not_squared" else None)
    return out + shared, index


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer(w, x, kind, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D];
    `kind` one of `M`, `E`, `*`; `sizes` a tuple of (name, value) pairs.
    Returns the new x and the router's indices (None off an E layer)."""
    s = dict(sizes)
    eps, fault = s["rms_eps"], s["fault"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    normed = rms_norm(x, w["norm.w"], eps)
    if kind == "M":
        return x + mamba(
            sub("mamba."), normed, heads=s["mamba_heads"],
            head_dim=s["mamba_head_dim"], groups=s["n_groups"],
            state=s["ssm_state"], eps=eps, chunk=s["chunk"],
            token_block=s["token_block"], fault=fault), None
    if kind == "*":
        return x + attention(
            sub("attn."), normed, n_head=s["n_head"],
            n_kv_head=s["n_kv_head"], head_dim=s["head_dim"],
            q_block=s["q_block"], fault=fault), None
    b, t, d = x.shape
    moe, index = sparse_experts(
        w, normed.reshape(b * t, d), top_k=s["top_k"],
        first_expert=s["first_expert"], scale=s["routed_scaling_factor"],
        fault=fault)
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


def loss_parts(params, tokens, labels, *, layer_pattern=PATTERN,
               mamba_heads=64, mamba_head_dim=64, n_groups=8, ssm_state=128,
               n_head=32, n_kv_head=2, head_dim=128, top_k=6, first_expert=0,
               routed_scaling_factor=2.5, rms_eps=1e-5, chunk=128,
               dtype=jnp.float32, q_block=None, token_block=None, remat=False, last=None,
               fault=None):
    """The loss that is minimised and its parts: `loss` and `ce` (the mean
    cross-entropy, twice: nothing else is in the loss), and
    `tokens_per_expert` [E layers, E]. With `last`, also `logits` on the
    final `last` positions, [B, last, V]. The biases are read from `params`
    (`l<i>.router.bias`) and are not advanced here: `next_bias` is."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault is one of {sorted(FAULTS)}, got {fault!r}")
    sizes = tuple(sorted(dict(
        mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
        n_groups=n_groups, ssm_state=ssm_state, n_head=n_head,
        n_kv_head=n_kv_head, head_dim=head_dim, top_k=top_k,
        first_expert=first_expert,
        routed_scaling_factor=routed_scaling_factor, rms_eps=rms_eps,
        chunk=chunk, q_block=q_block, token_block=token_block, fault=fault).items()))
    order = list(range(len(layer_pattern)))
    if fault == "layer_order":
        order[5], order[6] = order[6], order[5]
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(2, 3)) if remat \
            else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        chosen = {}
        for i in order:
            prefix = f"l{i}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            x, index = apply(w, x, layer_pattern[i], sizes)
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
