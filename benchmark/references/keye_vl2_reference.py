"""Keye-VL-2.0's language model (grouped-head softmax attention over a
LEARNED choice of keys, DeepSeek-Sparse-Attention's indexer, and a
sparse-expert feed-forward in every layer) in plain `jax.numpy`: the forward
pass, the loss and its gradients, for ONE CHIP'S SHARE of the expert layers.
What the program (`paddle_tpu/models/keye_vl2.py`) is compared with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel, no
bisection, no grouped matmul: the index scores of a block of queries against
all keys are an einsum, the kept set is `jax.lax.top_k` (of equal scores the
lower index first) scattered into a mask, attention is a softmax under that
mask, key and value heads are repeated with `jnp.repeat`, the held experts
are a loop. Weights come as a dict under the program's parameter names,
matrices stored `[in, out]` (D hidden, V the vocabulary rows held, E experts
routed over, H of them held here, F an expert's width, Hi index heads of Di):

    embed.w [V, D]   head.w [D, V]   final_norm.w [D]
    l<i>.in_norm.w, l<i>.post_norm.w [D]
    l<i>.attn.q.w [D, heads * head_dim]
    l<i>.attn.k.w, l<i>.attn.v.w [D, kv_heads * head_dim]
    l<i>.attn.q_norm.w, l<i>.attn.k_norm.w [head_dim]
    l<i>.attn.o.w [heads * head_dim, D]
    l<i>.index.q.w [D, Hi * Di]   l<i>.index.k.w [D, Di]   l<i>.index.w.w [D, Hi]
    l<i>.index.k_norm.w, l<i>.index.k_norm.b [Di]
    l<i>.router.w [D, E]
    l<i>.experts.gate.w, l<i>.experts.up.w [H, D, F]  l<i>.experts.down.w [H, F, D]

The equations (the public config `model_type: KeyeVL2`; its key set is the
Qwen3-MoE family's; `sa_config` is DeepSeek-Sparse-Attention's indexer,
DeepSeek-V3.2-Exp report, adapted to grouped heads):

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w
    LN(x)  = (x - mean) * rsqrt(var + 1e-6) * w + b
    layer:  h = x + Attn(N(x));  y = h + MoE(N(h));  after the last layer N,
            then the head
    Attn:   q, k, v = x W_q, x W_k, x W_v; q, k = N(q), N(k) over a head
            (ASSUMED); rotary, rotate-half, the whole head, inv_freq_j =
            theta^(-2j/R); key-value head g serves query heads g * group ..
            g * group + group - 1
    index:  qI = x W_qI, kI = LN(x W_kI), w = x W_w, x DETACHED; rotary on qI
            and kI (the whole Di); I[t, s] = Di^-0.5 * Hi^-0.5 * sum_j w[t, j]
            * ReLU(qI[t, j] . kI[s]) for s <= t; S_t = the topk keys of
            largest I[t, :t + 1], of equal scores the lower index
    ctx[t, h] = softmax over s in S_t of (q[t, h] . k[s, g(h)] * R^-0.5)
            times v[s, g(h)];  out = ctx W_o
    MoE:  p = softmax(x W_r) over all E; the top-k of p divided by their sum;
          sum over the chosen experts THAT ARE HELD HERE of p_k *
          down_e(silu(gate_e x) * up_e x)
    loss = mean cross-entropy + aux_coef * E * sum_e f_e P_e over all layers'
           router rows

Departures from the published description: QK-norm and the load-balancing
term are assumed (the config has no key for either); `mrope_section` is plain
rotary (the three position streams are equal for text); the indexer's
LayerNorm, its rotary over the whole 64 dims, its two scales and its
projections from the hidden state are the published DSA's adapted to grouped
heads, which have no query latent; the Hadamard rotation and the FP8
quantisation of the published index product are left out; DSA's alignment
loss is left out, so no gradient reaches the indexer; the vision tower is
left out. The share: what the absent experts would add is left out, here as
in the program; the vocabulary is the slice the weights have.

`kept`: one `[B, T, T]` array a layer (nonzero = kept) to attend under
INSTEAD of this reference's own selection: a bf16 index score flips keys
that lie at the threshold, so the comparison of logits, loss and gradients
hands the reference the system's kept sets, and the kept sets are compared
apart. `return_kept` adds this reference's own sets to the result.

`dtype` other than float32 computes everything, the index scores, the
router, the softmax and the losses included, in that precision. `q_block`
computes the indexer and the attention a block of queries at a time and the
head's cross-entropy a block of positions at a time, each as a `jax.lax.map`
over blocks under `jax.checkpoint` (one block's scores and weights live at a
time, forward and backward; the loop over the held experts is checkpointed
the same way); `remat` wraps each layer in `jax.checkpoint`: memory, not
mathematics.

`fault` plants one named fault (`FAULTS`): what a comparison with this
reference has to refuse.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`.
"""

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-6

FAULTS = {
    "no_selection": "every key below the diagonal is kept",
    "topk_off_by_one": "topk + 1 keys a row",
    "sees_future": "the selection and the attention run over every key, "
                   "future ones too",
    "no_relu": "the index products enter the head sum without the ReLU",
    "no_index_rotary": "qI and kI are not turned",
    "per_head": "one selection per index head instead of per token: query "
                "head h keeps by index head h % Hi alone",
    "previous_layer": "layer i > 0 attends under layer i - 1's kept set",
    "drop_largest": "the topk SMALLEST scores are kept",
    "indexer_not_detached": "the index scores reach the attention logits as "
                            "I - stop_gradient(I): the same forward pass, a "
                            "gradient into the indexer and through it into "
                            "the layer's input",
}


def rms_norm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def layer_norm(x, w, b, eps=LN_EPS):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def rotary(x, theta):
    """x [B, H, T, R]; rotate-half on the whole head."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def index_scores(qi, ki, w, *, first=0, fault=None):
    """Index scores of the queries `first ..` (qi [B, Hi, Q, Di], w
    [B, Q, Hi]) against all keys (ki [B, T, Di]): [B, Q, T], or of each index
    head apart, [B, Hi, Q, T], under the fault `per_head`; minus infinity
    above the diagonal (not under `sees_future`)."""
    hi, di = qi.shape[1], qi.shape[-1]
    s = jnp.einsum("bhqd,bkd->bhqk", qi, ki)
    if fault != "no_relu":
        s = jnp.maximum(s, 0)
    s = s * jnp.swapaxes(w, 1, 2)[..., None] * (di ** -0.5 * hi ** -0.5)
    if fault != "per_head":
        s = jnp.sum(s, axis=1)
    if fault == "sees_future":
        return s
    rows = first + jnp.arange(qi.shape[2])[:, None]
    return jnp.where(jnp.arange(ki.shape[1])[None, :] <= rows, s, -jnp.inf)


def top_keys(scores, topk):
    """The mask of the `topk` largest of each row of `scores` [..., T] that
    are not minus infinity (`jax.lax.top_k`: of equal scores the lower
    index)."""
    t = scores.shape[-1]
    _, index = jax.lax.top_k(scores, min(topk, t))
    hit = _scatter_rows(index, t)
    return hit & (scores > -jnp.inf)


def _scatter_rows(index, t):
    """index [..., k] -> a mask [..., t] that holds each row's indices."""
    flat = index.reshape(-1, index.shape[-1])
    rows = jnp.arange(flat.shape[0])[:, None]
    mask = jnp.zeros((flat.shape[0], t), bool).at[rows, flat].set(True)
    return mask.reshape(index.shape[:-1] + (t,))


def mixer_block(q, k, v, qi, ki, w, kept, first, topk, scale, fault):
    """Attention of the queries `first ..` (q [B, H, Q, R]; `first` may be
    traced) over all keys
    under their kept set: `kept` [B, Q, T] where it is handed over, else this
    block's own selection. Returns (ctx [B, H, Q, R], the kept set [B, Q, T]
    or, under `per_head`, [B, Hi, Q, T])."""
    t = k.shape[2]
    rows = first + jnp.arange(q.shape[2])[:, None]
    causal = jnp.arange(t)[None, :] <= rows
    index = None
    if kept is None or fault == "indexer_not_detached":
        index = index_scores(qi, ki, w, first=first, fault=fault)
    if kept is not None:
        kept = kept != 0
    elif fault == "no_selection":
        kept = jnp.broadcast_to(causal, (q.shape[0],) + causal.shape)
    elif fault == "drop_largest":
        kept = top_keys(jnp.where(index > -jnp.inf, -index, -jnp.inf), topk)
    else:
        kept = top_keys(index, topk + (fault == "topk_off_by_one"))
    seen = kept[:, None]
    if kept.ndim == 4:              # per index head: query head h by h % Hi
        seen = kept[:, jnp.arange(q.shape[1]) % kept.shape[1]]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if fault == "indexer_not_detached":
        live = jnp.where(kept, index, 0)
        scores = scores + (live - jax.lax.stop_gradient(live))[:, None]
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v), kept


def mixer(w, x, kept, *, n_head, n_kv_head, head_dim, n_index_head,
          index_dim, theta, topk, eps, q_block=None, fault=None):
    """One layer's attention (its weights `w` by their names after `l<i>.`)
    on the normed x [B, T, D]: (out [B, T, D], the kept set it used)."""
    b, t, _ = x.shape
    heads = lambda y, n, d: y.reshape(b, t, n, d).transpose(0, 2, 1, 3)
    q = heads(x @ w["attn.q.w"], n_head, head_dim)
    k = heads(x @ w["attn.k.w"], n_kv_head, head_dim)
    v = heads(x @ w["attn.v.w"], n_kv_head, head_dim)
    q = rotary(rms_norm(q, w["attn.q_norm.w"], eps), theta)
    k = rotary(rms_norm(k, w["attn.k_norm.w"], eps), theta)
    group = n_head // n_kv_head         # query head h reads kv head h // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    xi = x if fault == "indexer_not_detached" else jax.lax.stop_gradient(x)
    qi = heads(xi @ w["index.q.w"], n_index_head, index_dim)
    ki = layer_norm(xi @ w["index.k.w"], w["index.k_norm.w"],
                    w["index.k_norm.b"])
    if fault != "no_index_rotary":
        qi, ki = rotary(qi, theta), rotary(ki[:, None], theta)[:, 0]
    wi = xi @ w["index.w.w"]
    step = q_block or t

    def blocks(y, axis):    # `axis` split into blocks of `step`, blocks first
        y = y.reshape(y.shape[:axis] + (t // step, step) + y.shape[axis + 1:])
        return jnp.moveaxis(y, axis, 0)

    def whole(y, axis):     # the blocks back in their place along `axis`
        y = jnp.moveaxis(y, 0, axis)
        return y.reshape(y.shape[:axis] + (t,) + y.shape[axis + 2:])

    def one(args):          # a block of queries, one at a time
        first, qb, qib, wb = args[:4]
        return mixer_block(qb, k, v, qib, ki, wb, args[4] if kept is not None
                           else None, first, topk, head_dim ** -0.5, fault)

    given = () if kept is None else (blocks(kept, 1),)
    ctx, used = jax.lax.map(jax.checkpoint(one), (
        jnp.arange(0, t, step), blocks(q, 2), blocks(qi, 2), blocks(wi, 1))
        + given)
    ctx = whole(ctx, 2).transpose(0, 2, 1, 3)
    return (ctx.reshape(b, t, n_head * head_dim) @ w["attn.o.w"],
            whole(used, used.ndim - 3))


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
        jax.checkpoint(expert), jnp.zeros_like(x),
        (jnp.arange(stacks[0].shape[0]),) + stacks)
    return out, probs, index


@functools.partial(jax.jit, static_argnums=(3,))
def layer(w, x, kept, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D]
    under `kept` (None: its own selection); `sizes` is a tuple of (name,
    value) pairs. Returns the new x, the router's probabilities and indices,
    and the kept set it attended under."""
    s = dict(sizes)
    eps = s["rms_eps"]
    mixed, used = mixer(
        w, rms_norm(x, w["in_norm.w"], eps), kept, n_head=s["n_head"],
        n_kv_head=s["n_kv_head"], head_dim=s["head_dim"],
        n_index_head=s["n_index_head"], index_dim=s["index_dim"],
        theta=s["rope_theta"], topk=s["topk"], eps=eps, q_block=s["q_block"],
        fault=s["fault"])
    x = x + mixed
    b, t, d = x.shape
    flat = rms_norm(x, w["post_norm.w"], eps).reshape(b * t, d)
    moe, probs, index = sparse_experts(
        w, flat, top_k=s["top_k"], first_expert=s["first_expert"],
        norm_topk_prob=s["norm_topk_prob"])
    return x + moe.reshape(b, t, d), probs, index, used


@functools.partial(jax.jit, static_argnums=(3,))
def head_ce(x, w_head, labels, block=None):
    """Cross-entropy per token [B, T] of `x W_head` against `labels`,
    `block` positions at a time (all at once by default)."""
    b, t, d = x.shape
    step = block or t

    def one(args):          # a block of positions, one at a time
        rows, wanted = args
        logits = rows @ w_head
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)
        return jax.nn.logsumexp(logits, axis=-1) - picked[..., 0]

    ce = jax.lax.map(jax.checkpoint(one), (
        jnp.moveaxis(x.reshape(b, t // step, step, d), 1, 0),
        jnp.moveaxis(labels.reshape(b, t // step, step), 1, 0)))
    return jnp.moveaxis(ce, 0, 1).reshape(b, t)


def loss_parts(params, tokens, labels, *, n_layer, n_head=32, n_kv_head=4,
               head_dim=128, rope_theta=1e7, n_index_head=16, index_dim=64,
               topk=2048, top_k=8, first_expert=0, norm_topk_prob=True,
               rms_eps=1e-6, aux_coef=0.001, dtype=jnp.float32, q_block=None,
               remat=False, last=None, fault=None, kept=None,
               return_kept=False):
    """The loss that is minimised and its parts: `loss`, `ce` (mean
    cross-entropy), `load_balance` (E * sum_e f_e P_e over all layers' router
    rows), and `tokens_per_expert` [n_layer, E]. With `last`, also `logits`
    on the final `last` positions, [B, last, V]; with `return_kept`, `kept`:
    the set each layer attended under, a list of bool arrays."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault is one of {sorted(FAULTS)}, got {fault!r}")
    sizes = tuple(sorted(dict(
        n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
        rope_theta=rope_theta, n_index_head=n_index_head,
        index_dim=index_dim, topk=topk, top_k=top_k,
        first_expert=first_expert, norm_topk_prob=norm_topk_prob,
        rms_eps=rms_eps, q_block=q_block, fault=fault).items()))
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(3,)) if remat else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        routers, used = [], []
        for i in range(n_layer):
            prefix = f"l{i}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            given = None if kept is None else jnp.asarray(kept[i])
            if fault == "previous_layer" and i > 0:
                given = used[-1]
            x, probs, index, mine = apply(w, x, given, sizes)
            routers.append((probs, index))
            used.append(mine)
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
        if return_kept:
            out["kept"] = used
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
