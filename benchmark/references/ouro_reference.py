"""Ouro (a looped LM) in plain `jax.numpy`: the forward pass over the passes,
the exit distribution, the expected-loss objective and its gradients. What
the program (`paddle_tpu/models/ouro.py`) is compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel: a
Python loop over passes and layers, softmax attention written out. The SAME
L layers are applied `n_loop` times, so `jax.grad` gives a weight its
`n_loop` contributions from this function's own structure. Weights come as a
dict under the program's parameter names, matrices stored `[in, out]`:

    embed.w [V, D]   head.w [D, V]   final_norm.w [D]
    exit_gate.w [D, 1]   exit_gate.b [1]
    l<i>.attn_norm.w, l<i>.attn_post_norm.w, l<i>.mlp_norm.w,
    l<i>.mlp_post_norm.w [D]
    l<i>.q.w, l<i>.k.w, l<i>.v.w, l<i>.o.w [D, D]
    l<i>.gate.w, l<i>.up.w [D, F]    l<i>.down.w [F, D]

The equations (Zhu et al. 2025, arXiv:2510.25741, stage I):

    h = Embed(tokens);  for t = 1..R:  h = Norm_f(Layers(h));
        logits_t = h W_head;  lambda_t = sigmoid(h w_gate + b_gate)
    p_t = lambda_t prod_{j<t}(1 - lambda_j) (t < R),  p_R = prod_{j<R}(1 - lambda_j)
    loss = mean over tokens of [ sum_t p_t ce_t - beta H(p) ],  0 log 0 = 0

`dtype` other than float32 computes everything, the gate, the exit
distribution and the losses included, in that precision: the comparison's
tolerance has to refuse it. `q_block` computes the attention a block of
queries at a time and the head's cross-entropy a block of positions at a
time, and `remat` wraps each layer application and each head in
`jax.checkpoint`: both are this reference's memory at published widths, not
its mathematics (a test holds that they change nothing). A layer application
is one jitted function of that layer's own weights, so the `n_layer x n_loop`
applications share one small compiled program (op by op, every run of the
benchmark's check spent a minute compiling the same few hundred ops again:
they compile too fast for the persistent cache to keep).

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


def rotary(x, theta):
    """x [B, H, T, Dh]; rotate-half convention."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


LAYER_WEIGHTS = ("attn_norm", "q", "k", "v", "o", "attn_post_norm",
                 "mlp_norm", "gate", "up", "down", "mlp_post_norm")


def attention(w, x, n_head, theta, q_block=None):
    """Causal self-attention of one layer (its weights `w` by their short
    names) on x [B, T, D], `q_block` queries at a time (all at once by
    default) against the keys up to the block's end."""
    b, t, d = x.shape
    dh = d // n_head

    def heads(a):
        return a.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)

    q = rotary(heads(x @ w["q"]), theta)
    k = rotary(heads(x @ w["k"]), theta)
    v = heads(x @ w["v"])
    step = q_block or t
    blocks = []
    for first in range(0, t, step):
        end = min(first + step, t)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, first:end],
                            k[:, :, :end]) * dh ** -0.5
        row = jnp.arange(first, end)[:, None]
        col = jnp.arange(end)[None, :]
        scores = jnp.where(col > row, -jnp.inf, scores)
        weights = jax.nn.softmax(scores, axis=-1)
        blocks.append(jnp.einsum("bhqk,bhkd->bhqd", weights, v[:, :, :end]))
    ctx = jnp.concatenate(blocks, axis=2)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, d)
    return ctx @ w["o"]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def layer(w, x, n_head, theta, eps, q_block=None):
    """One "sandwich" layer: an RMSNorm before and after each sub-layer."""
    attn = attention(w, rms_norm(x, w["attn_norm"], eps), n_head, theta,
                     q_block)
    x = x + rms_norm(attn, w["attn_post_norm"], eps)
    h = rms_norm(x, w["mlp_norm"], eps)
    mlp = (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]
    return x + rms_norm(mlp, w["mlp_post_norm"], eps)


@functools.partial(jax.jit, static_argnums=(3,))
def head_ce(g, w_head, labels, block=None):
    """Cross-entropy per token [B, T] of `g W_head` against `labels`,
    `block` positions at a time (all at once by default)."""
    t = g.shape[1]
    step = block or t
    out = []
    for first in range(0, t, step):
        logits = g[:, first:first + step] @ w_head
        picked = jnp.take_along_axis(
            logits, labels[:, first:first + step, None], axis=-1)[..., 0]
        out.append(jax.nn.logsumexp(logits, axis=-1) - picked)
    return jnp.concatenate(out, axis=1)


def passes(params, tokens, *, n_layer, n_head, n_loop, rope_theta=1e6,
           rms_eps=1e-6, dtype=jnp.float32, q_block=None, remat=False):
    """The normed state g_t [B, T, D] after each of the `n_loop` passes, and
    the parameters in `dtype`. The next pass starts from g_t."""
    p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
    stack = [{n: p[f"l{i}.{n}.w"] for n in LAYER_WEIGHTS}
             for i in range(n_layer)]
    apply = jax.checkpoint(layer, static_argnums=(2, 3, 4, 5)) if remat \
        else layer
    x = jnp.take(p["embed.w"], tokens, axis=0)
    states = []
    for _ in range(n_loop):
        for w in stack:
            x = apply(w, x, n_head, rope_theta, rms_eps, q_block)
        x = rms_norm(x, p["final_norm.w"], rms_eps)
        states.append(x)
    return states, p


def exit_distribution(gate_logits):
    """p_t [R, ...] from the R - 1 gate logits (the last pass takes what is
    left)."""
    survive = jnp.ones_like(gate_logits[0]) if gate_logits else None
    probs = []
    for z in gate_logits:
        lam = jax.nn.sigmoid(z)
        probs.append(lam * survive)
        survive = survive * (1 - lam)
    probs.append(survive)
    return jnp.stack(probs)


def loss_parts(params, tokens, labels, *, n_layer, n_head, n_loop,
               rope_theta=1e6, rms_eps=1e-6, beta=0.1, dtype=jnp.float32,
               q_block=None, remat=False, last=None):
    """The loss that is minimised and its parts: `loss`, `expected_ce`,
    `entropy`, per pass `ce` [R] (mean cross-entropy of that pass's head) and
    `exit_probs` [R] (mean p_t). With `last`, also `logits`: every pass's on
    the final `last` positions, [R, B, last, V]."""
    with jax.default_matmul_precision("highest"):
        states, p = passes(params, tokens, n_layer=n_layer, n_head=n_head,
                           n_loop=n_loop, rope_theta=rope_theta,
                           rms_eps=rms_eps, dtype=dtype, q_block=q_block,
                           remat=remat)
        ce_of = jax.checkpoint(head_ce, static_argnums=(3,)) if remat \
            else head_ce
        ce = jnp.stack([ce_of(g, p["head.w"], labels, q_block)
                        for g in states])
        if n_loop == 1:
            probs = jnp.ones_like(ce)
        else:
            probs = exit_distribution(
                [(g @ p["exit_gate.w"])[..., 0] + p["exit_gate.b"][0]
                 for g in states[:-1]])
        expected_ce = jnp.mean(jnp.sum(probs * ce, axis=0))
        # 0 log 0 = 0, written so that its gradient is 0 there too (after a
        # hundred steps a gate saturates and a p_t is exactly 0)
        some = probs > 0
        plogp = jnp.where(some, probs * jnp.log(jnp.where(some, probs, 1)), 0)
        entropy = -jnp.mean(jnp.sum(plogp, axis=0))
        out = {"loss": expected_ce - beta * entropy,
               "expected_ce": expected_ce, "entropy": entropy,
               "ce": jnp.mean(ce, axis=(1, 2)),
               "exit_probs": jnp.mean(probs, axis=(1, 2))}
        if last is not None:
            out["logits"] = jnp.stack([g[:, -last:] @ p["head.w"]
                                       for g in states])
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
