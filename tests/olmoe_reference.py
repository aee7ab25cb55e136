"""OLMoE in plain `jax.numpy`: the forward pass, the three-part loss and its
gradients. What the program (`paddle_tpu/models/olmoe.py`) is compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel, no
sort, no grouped matmul: the experts are a Python loop, each applied to every
token and kept through a dense mask of the router's weights. Weights come as
a dict under the program's parameter names, matrices stored `[in, out]`:

    embed.w [V, D]    head.w [D, V]    final_norm.w [D]
    l<i>.attn_norm.w, l<i>.moe_norm.w, l<i>.q_norm.w, l<i>.k_norm.w [D]
    l<i>.q.w, l<i>.k.w, l<i>.v.w, l<i>.o.w [D, D]    l<i>.router.w [D, E]
    l<i>.experts.gate.w, l<i>.experts.up.w [E, D, F]
    l<i>.experts.down.w [E, F, D]

`routing`, where given, is one `[tokens, top_k]` array of expert indices per
layer (all positions; `last` takes the tail), used in place of the
reference's own top-k: "which experts" and "what
the experts compute" can then be compared apart where a lower precision
flips a near-tie. `dtype` other than float32 computes everything, the router
and the losses included, in that precision: the comparison's tolerance has to
refuse it.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

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


def attention(p, name, x, n_head, theta, eps, last=None):
    """Causal self-attention of one layer on x [B, T, D]. `last`: only the
    final `last` positions query (against the whole context)."""
    b, t, d = x.shape
    dh = d // n_head
    q = rms_norm(x @ p[name + ".q.w"], p[name + ".q_norm.w"], eps)
    k = rms_norm(x @ p[name + ".k.w"], p[name + ".k_norm.w"], eps)
    v = x @ p[name + ".v.w"]

    def heads(a):
        return a.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)

    q, k, v = rotary(heads(q), theta), rotary(heads(k), theta), heads(v)
    first = 0 if last is None else t - last
    q = q[:, :, first:]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * dh ** -0.5
    row = jnp.arange(first, t)[:, None]
    col = jnp.arange(t)[None, :]
    scores = jnp.where(col > row, -jnp.inf, scores)
    weights = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t - first, d)
    return ctx @ p[name + ".o.w"]


def router(p, name, x, top_k, index=None):
    """x [N, D] -> logits [N, E], probabilities, the k weights and indices.
    Softmax over all experts; the weights are not renormalised."""
    logits = x @ p[name + ".router.w"]
    probs = jax.nn.softmax(logits, axis=-1)
    if index is None:
        _, index = jax.lax.top_k(probs, top_k)
    weight = jnp.take_along_axis(probs, index, axis=-1)
    return logits, probs, weight, index


def experts(p, name, x, weight, index):
    """Every expert on every token, kept through a dense mask [N, E] of the
    router's weights (zero where the token did not choose the expert)."""
    w_gate, w_up = p[name + ".experts.gate.w"], p[name + ".experts.up.w"]
    w_down = p[name + ".experts.down.w"]
    n_expert = w_gate.shape[0]
    mask = jnp.sum(weight[:, :, None]
                   * (index[:, :, None] == jnp.arange(n_expert)), axis=1)
    out = jnp.zeros_like(x)
    for e in range(n_expert):
        hidden = jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
        out = out + mask[:, e:e + 1].astype(x.dtype) * (hidden @ w_down[e])
    return out


def forward(params, tokens, *, n_layer, n_head, top_k, rope_theta=10000.0,
            rms_eps=1e-5, routing=None, last=None, dtype=jnp.float32):
    """logits [B, T, V] (or [B, last, V]) and, per layer, the router's
    logits, probabilities and chosen indices. With `last` only the final
    `last` positions pass through the last layer's experts and the head; the
    layers below still see the whole context."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        x = jnp.take(p["embed.w"], tokens, axis=0)
        routers = []
        for i in range(n_layer):
            name = f"l{i}"
            keep = last if i == n_layer - 1 else None
            normed = rms_norm(x, p[name + ".attn_norm.w"], rms_eps)
            attn = attention(p, name, normed, n_head, rope_theta, rms_eps,
                             last=keep)
            x = (x if keep is None else x[:, -keep:]) + attn
            b, t, d = x.shape
            flat = rms_norm(x, p[name + ".moe_norm.w"], rms_eps) \
                .reshape(b * t, d)
            given = None if routing is None else \
                routing[i].reshape(b, -1, top_k)[:, -t:].reshape(b * t, top_k)
            logits, probs, weight, index = router(p, name, flat, top_k, given)
            x = x + experts(p, name, flat, weight, index).reshape(b, t, d)
            routers.append({"logits": logits, "probs": probs, "index": index})
        x = rms_norm(x, p["final_norm.w"], rms_eps)
        return x @ p["head.w"], routers


def loss_parts(params, tokens, labels, *, n_layer, n_head, top_k,
               rope_theta=10000.0, rms_eps=1e-5, aux_coef=0.01, z_coef=0.001,
               routing=None, dtype=jnp.float32):
    """The loss that is minimised and its parts.

    ce: mean cross-entropy. load_balance: the `olmoe` code's, all layers'
    router rows taken together: n_expert * sum_e f_e * P_e, f_e the
    assignments to expert e per row (over the k slots), P_e the mean
    probability. z_loss: mean(logsumexp(router logits)^2) over the same rows.
    """
    logits, routers = forward(params, tokens, n_layer=n_layer, n_head=n_head,
                              top_k=top_k, rope_theta=rope_theta,
                              rms_eps=rms_eps, routing=routing, dtype=dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    probs = jnp.concatenate([r["probs"] for r in routers], axis=0)
    index = jnp.concatenate([r["index"] for r in routers], axis=0)
    router_logits = jnp.concatenate([r["logits"] for r in routers], axis=0)
    n_expert = probs.shape[-1]
    chosen = (index[:, :, None] == jnp.arange(n_expert)).astype(probs.dtype)
    share = jnp.mean(chosen, axis=0)                 # [k, E]: per slot
    load_balance = n_expert * jnp.sum(share * jnp.mean(probs, axis=0)[None])
    z_loss = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    tokens_per_expert = jnp.stack(
        [jnp.sum(r["index"][:, :, None] == jnp.arange(n_expert), axis=(0, 1))
         for r in routers])
    return {"loss": ce + aux_coef * load_balance + z_coef * z_loss,
            "ce": ce, "load_balance": load_balance, "z_loss": z_loss,
            "logits": logits, "tokens_per_expert": tokens_per_expert,
            "index": [r["index"] for r in routers]}


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
