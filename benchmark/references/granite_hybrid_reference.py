"""Granite 4.0-H (`model_type: granitemoehybrid` with no experts: every layer
a mixer AND a gated feed-forward, the mixer a Mamba-2 state-space mixer of ONE
group or grouped softmax attention with no positions, four scalar multipliers
and one table that is embedding and head) in plain `jax.numpy`: the forward
pass, the loss and its gradients. What the program
(`paddle_tpu/models/granite_hybrid.py`) is compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel and no
chunks: the state-space recurrence runs TOKEN BY TOKEN (a `lax.scan` over t of
the state's update); the convolution is a sum of `conv_kernel` shifted
products plus its bias; attention is a masked softmax with the key and value
heads repeated by `jnp.repeat`; the table is used twice, as rows and
transposed. Weights come as a dict under the program's parameter names,
matrices stored `[in, out]` (D hidden, V the vocabulary rows held, F the
feed-forward's width, H heads of P, G groups, N the state, K taps, I = H P):

    embed.w [V, D]   final_norm.w [D]   (head.w [D, V] only when untied)
    l<i>.norm.w [D]   l<i>.mlp_norm.w [D]
    l<i>.mamba.in.w [D, 2 I + 2 G N + H]   columns [z | xs | B | C | dt_raw]
    l<i>.mamba.conv.w [I + 2 G N, K]   l<i>.mamba.conv.b [I + 2 G N]
    l<i>.mamba.A_log, l<i>.mamba.dt_bias, l<i>.mamba.D [H]
    l<i>.mamba.norm.w [I]   l<i>.mamba.out.w [I, D]
    l<i>.attn.q.w [D, heads * head_dim]   l<i>.attn.k.w, l<i>.attn.v.w
    [D, kv_heads * head_dim]   l<i>.attn.o.w [heads * head_dim, D]
    l<i>.mlp.gate.w, l<i>.mlp.up.w [D, F]   l<i>.mlp.down.w [F, D]

The equations (the public `granitemoehybrid` model code with
`num_local_experts` 0; Mamba-2, arXiv:2405.21060, for the mixer):

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w
    h_0 = embedding_multiplier * E[tokens]
    layer l:  h = h + residual_multiplier * Mixer_l(N(h)), the mixer by
              `layer_types[l]`;  h = h + residual_multiplier *
              W_down(silu(W_gate n) * W_up n), n = N_mlp(h)
    logits = N(h_L) E^T / logits_scaling;   loss = mean cross-entropy
    mamba:  [z | u | dt_raw] = x W_in;  u = silu(conv(u) + b_conv), depthwise,
        output t reads inputs t - K + 1 .. t;  [xs | B | C] = u
        dt = softplus(dt_raw + dt_bias);  a = -exp(A_log) dt   (no clamp)
        per head h (its B, C those of group h // (H / G): ONE group as
        published, every head the same B and C), S_0 = 0, every t:
            S_t = exp(a_t) S_{t-1} + dt_t xs_t B_t^T;   y_t = S_t C_t + D xs_t
        y = y silu(z);  y = y rsqrt(mean(y^2) + eps) over each group of I / G
        lanes (all I at one group), times w_norm;  out = y W_out
    attention:  q, k, v = x W_q, x W_k, x W_v;  NO rotary, no positions;
        key-value head h // group serves query head h;  scores times
        attention_multiplier (the published number, not head_dim^-0.5);  key j
        is visible to query i iff j <= i;  softmax;  out = ctx W_o

Departures from the public code: none in the forward pass. The vocabulary is
the slice the table has.

`dtype` other than float32 computes everything, the recurrence, the softmax
and the loss included, in that precision: the comparison's tolerance has to
refuse it. `q_block` computes the attention a block of queries at a time and
the head's cross-entropy a block of positions at a time; `token_block` runs
the recurrence as an outer scan over blocks of that many tokens under
`jax.checkpoint` around the scan over a block's tokens, so that a gradient
keeps a state a block, not a token; `remat` wraps each layer in
`jax.checkpoint`: all three are this reference's memory at published widths,
not its mathematics (a test holds that they change nothing). A layer is one
jitted function of its own weights, so the layers of a kind share one
compiled program.

`fault` plants one named fault (`FAULTS`): what a comparison with this
reference has to refuse. A test and `reference_check_granite4.py` hold that
each moves at least one compared quantity past its limit.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools

import jax
import jax.numpy as jnp

# as published: attention at 5, 15, 25, 35 of 40
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))

FAULTS = {
    "no_embedding_multiplier": "h_0 = E[tokens]",
    "residual_multiplier_one": "h = h + Mixer(N(h)), h = h + MLP(N(h))",
    "attention_scale_rsqrt": "scores times head_dim^-0.5 (0.125 for 1/64)",
    "logits_unscaled": "logits = N(h) E^T, not divided by logits_scaling",
    "untied_head": "the head's table is a copy the embedding's gradient does "
                   "not reach: embed.w's gradient is the look-up's alone",
    "rotary_in_attention": "the attention layers turn q and k (rotate-half, "
                           "theta 10000)",
    "norm_before_gate": "y = N(y) silu(z): the norm first, then the gate",
    "norm_over_groups_of_512": "the mixer's norm takes its mean over each "
                               "run of 512 lanes (a sixteenth of the width "
                               "where that is narrower), not over the group",
    "b_c_swapped": "the state is written by C and read by B",
    "no_conv_bias": "u = silu(conv(u))",
    "dt_without_softplus": "dt = dt_raw + dt_bias",
    "no_skip": "y_t = S_t C_t: no D term",
    "state_reset_at_chunk": "the state starts from 0 again at every chunk's "
                            "first token (`chunk`: read by this fault alone)",
    "mlp_not_gated": "W_down(silu(W_gate n)): no product with W_up n",
    "one_norm_a_layer": "the feed-forward reads the mixer's normed input, "
                        "N(h) of before the mixer, not N_mlp of after it",
    "kv_head_order": "query head h reads key-value head h % kv_heads, not "
                     "h // group",
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


def causal_conv_silu(x, w, bias):
    """x [B, T, C], w [C, K], bias [C] or None: output t is `sum_j w[:, j]
    x[t - (K-1) + j]` with zeros before the start, plus the bias, then
    silu."""
    t, kernel = x.shape[1], w.shape[1]
    padded = jnp.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + t] * w[:, j] for j in range(kernel))
    if bias is not None:
        y = y + bias
    return jax.nn.silu(y)


def mamba(w, x, *, heads, head_dim, groups, state, eps, chunk=256,
          token_block=None, fault=None):
    """One state-space mixer (weights by their names after `l<i>.mamba.`) on
    x [B, T, D]."""
    bsz, t, _ = x.shape
    inner, bc = heads * head_dim, groups * state
    mixed = x @ w["in.w"]
    z, u, dt_raw = mixed[..., :inner], mixed[..., inner:2 * inner + 2 * bc], \
        mixed[..., 2 * inner + 2 * bc:]
    u = causal_conv_silu(u, w["conv.w"],
                         None if fault == "no_conv_bias" else w["conv.b"])
    xs = u[..., :inner].reshape(bsz, t, heads, head_dim)
    b = u[..., inner:inner + bc].reshape(bsz, t, groups, state)
    c = u[..., inner + bc:].reshape(bsz, t, groups, state)
    if fault == "b_c_swapped":
        b, c = c, b
    # head h reads group h // (heads / groups)
    b = jnp.repeat(b, heads // groups, axis=2)
    c = jnp.repeat(c, heads // groups, axis=2)
    dt = dt_raw + w["dt_bias"]
    if fault != "dt_without_softplus":
        dt = jax.nn.softplus(dt)
    a = -jnp.exp(w["A_log"]) * dt
    y = selective_scan(xs, dt, a, b, c,
                       None if fault == "no_skip" else w["D"], token_block,
                       reset=chunk if fault == "state_reset_at_chunk" else None)
    y = y.reshape(bsz, t, inner)
    gate = jax.nn.silu(z)
    span = inner // groups
    if fault == "norm_over_groups_of_512":
        span = min(512, inner // 16)

    def norm(v):
        g = v.reshape(bsz, t, inner // span, span)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
        return g.reshape(bsz, t, inner)

    if fault == "norm_before_gate":
        y = norm(y) * w["norm.w"] * gate
    else:
        y = norm(y * gate) * w["norm.w"]
    return y @ w["out.w"]


def attention(w, x, *, n_head, n_kv_head, head_dim, scale, q_block=None,
              fault=None):
    """Causal softmax attention of one layer (weights by their names after
    `l<i>.attn.`) on x [B, T, D], `q_block` queries at a time; no positions
    of any kind; the scores times `scale`."""
    bsz, t, _ = x.shape
    q = (x @ w["q.w"]).reshape(bsz, t, n_head, head_dim).transpose(0, 2, 1, 3)
    k = (x @ w["k.w"]).reshape(bsz, t, n_kv_head, head_dim) \
        .transpose(0, 2, 1, 3)
    v = (x @ w["v.w"]).reshape(bsz, t, n_kv_head, head_dim) \
        .transpose(0, 2, 1, 3)
    if fault == "rotary_in_attention":
        q, k = rotary(q, 1e4), rotary(k, 1e4)
    if fault == "attention_scale_rsqrt":
        scale = head_dim ** -0.5
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
                            k[:, :, :end]) * scale
        visible = jnp.arange(end)[None, :] <= jnp.arange(first, end)[:, None]
        weights = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("bhqk,bhkd->bhqd", weights, v[:, :, :end]))
    ctx = jnp.concatenate(blocks, axis=2).transpose(0, 2, 1, 3)
    return ctx.reshape(bsz, t, n_head * head_dim) @ w["o.w"]


def gated_mlp(w, x, fault=None):
    hidden = jax.nn.silu(x @ w["gate.w"])
    if fault != "mlp_not_gated":
        hidden = hidden * (x @ w["up.w"])
    return hidden @ w["down.w"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer(w, x, kind, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D]:
    the mixer `kind` ("mamba" | "attention"), then the feed-forward; `sizes`
    a tuple of (name, value) pairs."""
    s = dict(sizes)
    eps, fault = s["rms_eps"], s["fault"]
    branch = 1.0 if fault == "residual_multiplier_one" \
        else s["residual_multiplier"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    normed = rms_norm(x, w["norm.w"], eps)
    if kind == "mamba":
        mixed = mamba(
            sub("mamba."), normed, heads=s["mamba_heads"],
            head_dim=s["mamba_head_dim"], groups=s["n_groups"],
            state=s["ssm_state"], eps=eps, chunk=s["chunk"],
            token_block=s["token_block"], fault=fault)
    else:
        mixed = attention(
            sub("attn."), normed, n_head=s["n_head"],
            n_kv_head=s["n_kv_head"], head_dim=s["head_dim"],
            scale=s["attention_multiplier"], q_block=s["q_block"],
            fault=fault)
    x = x + branch * mixed
    if fault != "one_norm_a_layer":
        normed = rms_norm(x, w["mlp_norm.w"], eps)
    return x + branch * gated_mlp(sub("mlp."), normed, fault)


@functools.partial(jax.jit, static_argnums=(3, 4))
def head_ce(x, w_head, labels, block=None, divide_by=1.0):
    """Cross-entropy per token [B, T] of `x W_head / divide_by` against
    `labels`, `block` positions at a time (all at once by default)."""
    t = x.shape[1]
    step = block or t
    out = []
    for first in range(0, t, step):
        logits = (x[:, first:first + step] @ w_head) / divide_by
        picked = jnp.take_along_axis(
            logits, labels[:, first:first + step, None], axis=-1)[..., 0]
        out.append(jax.nn.logsumexp(logits, axis=-1) - picked)
    return jnp.concatenate(out, axis=1)


def loss_parts(params, tokens, labels, *, layer_types=LAYER_TYPES,
               mamba_heads=64, mamba_head_dim=64, n_groups=1, ssm_state=128,
               n_head=32, n_kv_head=8, head_dim=64, embedding_multiplier=12.0,
               residual_multiplier=0.22, attention_multiplier=0.015625,
               logits_scaling=8.0, tie_embeddings=True, rms_eps=1e-5,
               chunk=256, dtype=jnp.float32, q_block=None, token_block=None,
               remat=False, last=None, fault=None):
    """The loss that is minimised and its parts: `loss` and `ce` (the mean
    cross-entropy, twice: nothing else is in the loss). With `last`, also
    `logits` on the final `last` positions, [B, last, V]. Tied, the head is
    `embed.w` transposed; untied it is `head.w`."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault is one of {sorted(FAULTS)}, got {fault!r}")
    sizes = tuple(sorted(dict(
        mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
        n_groups=n_groups, ssm_state=ssm_state, n_head=n_head,
        n_kv_head=n_kv_head, head_dim=head_dim,
        residual_multiplier=float(residual_multiplier),
        attention_multiplier=float(attention_multiplier), rms_eps=rms_eps,
        chunk=chunk, q_block=q_block, token_block=token_block,
        fault=fault).items()))
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(2, 3)) if remat \
            else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        if fault != "no_embedding_multiplier":
            x = x * jnp.asarray(embedding_multiplier, dtype)
        for i, kind in enumerate(layer_types):
            prefix = f"l{i}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            x = apply(w, x, kind, sizes)
        x = rms_norm(x, p["final_norm.w"], rms_eps)
        w_head = p["embed.w"].T if tie_embeddings else p["head.w"]
        if fault == "untied_head":
            w_head = jax.lax.stop_gradient(w_head)
        divide_by = 1.0 if fault == "logits_unscaled" else float(logits_scaling)
        ce_of = jax.checkpoint(head_ce, static_argnums=(3, 4)) if remat \
            else head_ce
        ce = jnp.mean(ce_of(x, w_head, labels, q_block, divide_by))
        out = {"loss": ce, "ce": ce}
        if last is not None:
            out["logits"] = (x[:, -last:] @ w_head) / divide_by
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
