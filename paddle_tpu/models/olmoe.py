"""OLMoE: a decoder-only LM whose feed-forward is a dropless top-k sparse
expert layer (Muennighoff et al. 2024, arXiv:2409.02060; layer equations as
in the public `olmoe` model code, config of OLMoE-1B-7B-0125-Instruct).

    h = x + Attn(RMSNorm(x))        y = h + MoE(RMSNorm(h))
    Attn: q, k, v = x W_q, x W_k, x W_v (no bias); RMSNorm with a learned
          full-width weight on the whole q and the whole k BEFORE the split
          into heads (QK-norm); rotary embedding (rotate-half) on q and k
          per head; causal softmax attention at head_dim^-0.5; W_o
    MoE:  p = softmax(x W_r) in float32 over all experts, the top-k of p
          used as they are (not renormalised);
          sum_k p_k * down_e(silu(gate_e(x)) * up_e(x)), dropless
    final RMSNorm, then an untied head
    loss = mean cross-entropy + aux_coef * load-balancing + z_coef * z-loss

The load-balancing loss is the `olmoe` code's: router outputs of all layers
taken together, `n_expert * sum_e f_e * P_e` with `f_e` the assignments to
expert e per token (summed over the k slots) and `P_e` the mean router
probability; the z-loss is the paper's, `mean(logsumexp(logits)^2)` over
the same rows. Built from `fluid.layers` only; parameter names are fixed
(`l0.q.w`, `l0.experts.gate.w`, ...) so that a reference can be handed the
same weights by name.
"""

from __future__ import annotations

from .. import initializer as init
from .. import layers
from ..param_attr import ParamAttr

INIT_STD = 0.02


def _normal():
    return init.NormalInitializer(0.0, INIT_STD)


def _w(name):
    return ParamAttr(name=name, initializer=_normal())


def _linear(x, size, name):
    return layers.fc(input=x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=_w(name + ".w"))


def _attention(x, d_model, n_head, rope_theta, rms_eps, name):
    d_head = d_model // n_head
    q = layers.rms_norm(_linear(x, d_model, name + ".q"), epsilon=rms_eps,
                        param_attr=ParamAttr(name=name + ".q_norm.w"))
    k = layers.rms_norm(_linear(x, d_model, name + ".k"), epsilon=rms_eps,
                        param_attr=ParamAttr(name=name + ".k_norm.w"))
    v = _linear(x, d_model, name + ".v")

    def split_heads(t):
        t = layers.reshape(t, shape=[0, 0, n_head, d_head])
        return layers.transpose(t, perm=[0, 2, 1, 3])

    qh = layers.rotary_embedding(split_heads(q), theta=rope_theta)
    kh = layers.rotary_embedding(split_heads(k), theta=rope_theta)
    ctx = layers.fused_attention(qh, kh, split_heads(v), causal=True,
                                 sm_scale=d_head ** -0.5)
    ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                         shape=[0, 0, d_model])
    return _linear(ctx, d_model, name + ".o")


def _sparse_experts(x, seq_len, d_model, n_expert, top_k, d_expert, name):
    tokens = layers.reshape(x, shape=[-1, d_model])
    routing = layers.moe_router(tokens, n_expert, top_k,
                                param_attr=_w(name + ".router.w"))
    out = layers.moe_experts(tokens, routing, n_expert, d_expert,
                             param_attr=_normal(), name=name + ".experts")
    return layers.reshape(out, shape=[-1, seq_len, d_model]), routing


def olmoe(vocab_size=50304, seq_len=4096, n_layer=16, d_model=2048,
          n_head=16, n_expert=64, top_k=8, d_expert=1024, rope_theta=10000.0,
          rms_eps=1e-5, aux_coef=0.01, z_coef=0.001):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels."""
    tokens = layers.data(name="tokens", shape=[-1, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data(name="labels", shape=[-1, seq_len], dtype="int64",
                         append_batch_size=False)

    x = layers.embedding(tokens, size=[vocab_size, d_model],
                         param_attr=_w("embed.w"))
    routings = []
    for i in range(n_layer):
        name = f"l{i}"
        normed = layers.rms_norm(x, epsilon=rms_eps,
                                 param_attr=ParamAttr(name=name + ".attn_norm.w"))
        x = layers.elementwise_add(
            x, _attention(normed, d_model, n_head, rope_theta, rms_eps, name))
        normed = layers.rms_norm(x, epsilon=rms_eps,
                                 param_attr=ParamAttr(name=name + ".moe_norm.w"))
        moe, routing = _sparse_experts(normed, seq_len, d_model, n_expert,
                                       top_k, d_expert, name)
        x = layers.elementwise_add(x, moe)
        routings.append(routing)
    x = layers.rms_norm(x, epsilon=rms_eps,
                        param_attr=ParamAttr(name="final_norm.w"))
    logits = _linear(x, vocab_size, "head")

    ce = layers.mean(layers.softmax_with_cross_entropy(logits=logits,
                                                       label=labels))
    # all layers' router rows taken together, as the `olmoe` code does:
    # f_e = assignments to e / rows, P_e = mean probability of e
    counts = layers.sums([layers.cast(r["tokens_per_expert"], "float32")
                          for r in routings])
    rows = layers.scale(layers.reduce_sum(counts), scale=1.0 / top_k)
    share = layers.elementwise_div(counts, rows)
    share.stop_gradient = True      # counts: nothing to differentiate
    mean_prob = layers.scale(
        layers.sums([layers.reduce_mean(r["probs"], dim=0)
                     for r in routings]), scale=1.0 / n_layer)
    load_balance = layers.scale(
        layers.reduce_sum(layers.elementwise_mul(share, mean_prob)),
        scale=float(n_expert))
    z_loss = layers.scale(
        layers.sums([layers.mean(layers.square(r["logsumexp"]))
                     for r in routings]), scale=1.0 / n_layer)
    loss = layers.sums([ce, layers.scale(load_balance, scale=aux_coef),
                        layers.scale(z_loss, scale=z_coef)])
    tokens_per_expert = layers.stack(
        [r["tokens_per_expert"] for r in routings], axis=0)
    return ({"tokens": tokens, "labels": labels},
            {"loss": loss, "ce": ce, "load_balance": load_balance,
             "z_loss": z_loss, "logits": logits,
             "tokens_per_expert": tokens_per_expert})


def build(**kw):
    return olmoe(**kw)
