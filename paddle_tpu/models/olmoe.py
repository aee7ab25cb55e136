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

from .. import layers
from ._decoder import (embed, heads_first, linear, load_balance,
                       mean_cross_entropy, merge_heads, norm,
                       qk_normed_projections, routed_experts, split_heads,
                       token_feeds, tokens_per_expert)


def _attention(x, d_model, n_head, rope_theta, rms_eps, name):
    d_head = d_model // n_head
    q, k, v = qk_normed_projections(x, d_model, rms_eps, name)

    def heads(t):
        return heads_first(split_heads(t, n_head, d_head))

    qh = layers.rotary_embedding(heads(q), theta=rope_theta)
    kh = layers.rotary_embedding(heads(k), theta=rope_theta)
    ctx = layers.fused_attention(qh, kh, heads(v), causal=True,
                                 sm_scale=d_head ** -0.5)
    return linear(merge_heads(ctx, d_model), d_model, name + ".o")


def olmoe(vocab_size=50304, seq_len=4096, n_layer=16, d_model=2048,
          n_head=16, n_expert=64, top_k=8, d_expert=1024, rope_theta=10000.0,
          rms_eps=1e-5, aux_coef=0.01, z_coef=0.001):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels."""
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    routings = []
    for i in range(n_layer):
        name = f"l{i}"
        normed = norm(x, rms_eps, name + ".attn_norm")
        x = layers.elementwise_add(
            x, _attention(normed, d_model, n_head, rope_theta, rms_eps, name))
        normed = norm(x, rms_eps, name + ".moe_norm")
        moe, routing = routed_experts(normed, seq_len, n_expert, top_k,
                                      d_expert, name)
        x = layers.elementwise_add(x, moe)
        routings.append(routing)
    x = norm(x, rms_eps, "final_norm")
    logits = linear(x, vocab_size, "head")

    ce = mean_cross_entropy(logits, labels)
    balance = load_balance(routings, n_expert, top_k)
    z_loss = layers.scale(
        layers.sums([layers.mean(layers.square(r["logsumexp"]))
                     for r in routings]), scale=1.0 / n_layer)
    loss = layers.sums([ce, layers.scale(balance, scale=aux_coef),
                        layers.scale(z_loss, scale=z_coef)])
    return ({"tokens": tokens, "labels": labels},
            {"loss": loss, "ce": ce, "load_balance": balance,
             "z_loss": z_loss, "logits": logits,
             "tokens_per_expert": tokens_per_expert(routings)})


def build(**kw):
    return olmoe(**kw)
