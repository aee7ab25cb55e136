"""Qwen3-Next: a decoder-only LM whose layers alternate a linear-attention
mixer (the gated delta rule, three layers in four) with gated softmax
attention (the fourth), each over a sparse-expert feed-forward with a shared
expert (Qwen3-Next-80B-A3B; layer equations as in the public `qwen3_next`
model code). Built for ONE CHIP'S SHARE of an expert-parallel deployment:
the router chooses among all `n_expert` experts, this chip holds
`experts_held` of them from `first_expert` on and computes their part.

    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)      every RMSNorm over the
           hidden size and over a softmax head: zero-centred, w starts at 0
    layer i:  h = x + Mixer_i(N(x));  y = h + MoE(N(h));  after the last
              layer N, then the untied head
    Mixer_i = GDN where (i + 1) % full_attention_interval != 0, else Attn

    Attn: [q | gate] = x W_q (per head: its query and its output gate side
          by side), k = x W_k, v = x W_v (`n_kv_head` heads), no bias;
          q = N(q), k = N(k) over a head; rotary (rotate-half) on the first
          `rotary_dim` dims of q and k; causal softmax attention at
          head_dim^-0.5, each key-value head serving n_head / n_kv_head
          query heads (the key and value heads are repeated in the Program);
          out = (ctx * sigmoid(gate)) W_o
    GDN:  [q, k, v, z] = x W_qkvz (per key head: q, k, then its value heads'
          v, then their z), [b, a] = x W_ba (per key head: b's, then a's);
          [q | k | v] <- silu(causal depthwise conv, no bias);
          beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias) <= 0;
          q = q / sqrt(sum q^2 + 1e-6) * key_dim^-0.5, k likewise unscaled;
          per value head a state S [key_dim, value_dim], S_0 = 0, every token
              S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
              o_t = S^T q_t
          out = (o * rsqrt(mean(o^2) + eps) * w * silu(z)) W_out   (over a
          value head; a plain weight that starts at 1)
    MoE:  p = softmax(x W_r) in float32 over all experts; the top-k of p
          divided by their sum (`norm_topk_prob`); routed = sum over the
          chosen experts held here of p_k * down_e(silu(gate_e x) * up_e x),
          dropless; shared = sigmoid(x w_s) * down_s(silu(gate_s x) * up_s x);
          MoE(x) = routed + shared
    loss = mean cross-entropy + aux_coef * n_expert * sum_e f_e P_e over all
           layers' router rows (the form `models/olmoe.py` has; no z-loss)

Left out: the multi-token-prediction module (the public config has no key
for it). Float32 under AMP: the router (`moe_router`, AMP_F32_OPS), g and
beta (`delta_rule_gates`, AMP_F32_OPS), and inside their rules the decay's
running sums, the l2-norms, the solve and the state of `gated_delta_rule`,
the convolution's sums and every norm's statistics. Built from
`fluid.layers` only; parameter names are fixed (`l0.gdn.qkvz.w`,
`l3.attn.q.w`, `l0.experts.gate.w`, `l0.shared.gate.w`, ...) so that a
reference can be handed the same weights by name. Each layer's ops carry
`fluid.name_scope("l<i>.gdn" | "l<i>.attn" | "l<i>.moe")`.
"""

from __future__ import annotations

from .. import layers
from ..core.ir import name_scope
from ._decoder import (a_log_init, balanced_loss, conv_heads,
                       delta_rule_normed, embed, expert_rows, gated_mlp,
                       heads_first, last, linear, norm, serve_group,
                       split_heads, token_feeds)


def _norm(x, rms_eps, name):
    return norm(x, rms_eps, name, zero_centered=True)


def layer_kind(i, full_attention_interval):
    return "full_attention" if (i + 1) % full_attention_interval == 0 \
        else "linear_attention"


def _attention(x, n_head, n_kv_head, head_dim, rotary_dim, rope_theta,
               rms_eps, name):
    qg = split_heads(linear(x, n_head * 2 * head_dim, name + ".q"), n_head,
                     2 * head_dim)
    q, gate = last(qg, 0, head_dim), last(qg, head_dim, 2 * head_dim)
    k = split_heads(linear(x, n_kv_head * head_dim, name + ".k"), n_kv_head,
                    head_dim)
    v = split_heads(linear(x, n_kv_head * head_dim, name + ".v"), n_kv_head,
                    head_dim)
    q = layers.rotary_embedding(
        heads_first(_norm(q, rms_eps, name + ".q_norm")), theta=rope_theta,
        rotary_dim=rotary_dim)
    k = layers.rotary_embedding(
        heads_first(_norm(k, rms_eps, name + ".k_norm")), theta=rope_theta,
        rotary_dim=rotary_dim)
    k = serve_group(k, n_head, n_kv_head, head_dim)
    v = serve_group(heads_first(v), n_head, n_kv_head, head_dim)
    ctx = layers.fused_attention(q, k, v, causal=True,
                                 sm_scale=head_dim ** -0.5)
    ctx = layers.elementwise_mul(heads_first(ctx), layers.sigmoid(gate))
    ctx = layers.reshape(ctx, shape=[0, 0, n_head * head_dim])
    return linear(ctx, x.shape[-1], name + ".o")


def _gated_delta_net(x, n_key_head, n_value_head, key_dim, value_dim,
                     conv_kernel, rms_eps, name, seed):
    r = n_value_head // n_key_head
    wide_k, wide_v = n_key_head * key_dim, n_value_head * value_dim
    per_head = 2 * key_dim + 2 * r * value_dim
    mixed = layers.reshape(linear(x, n_key_head * per_head, name + ".qkvz"),
                           shape=[0, 0, n_key_head, per_head])
    ba = layers.reshape(linear(x, n_key_head * 2 * r, name + ".ba"),
                        shape=[0, 0, n_key_head, 2 * r])

    def flat(t, width):
        return layers.reshape(t, shape=[0, 0, width])

    qkv = layers.concat(
        [flat(last(mixed, 0, key_dim), wide_k),
         flat(last(mixed, key_dim, 2 * key_dim), wide_k),
         flat(last(mixed, 2 * key_dim, 2 * key_dim + r * value_dim), wide_v)],
        axis=2)
    z = layers.reshape(last(mixed, 2 * key_dim + r * value_dim, per_head),
                       shape=[0, 0, n_value_head, value_dim])
    q, k, v = conv_heads(qkv, n_key_head, n_value_head, key_dim, value_dim,
                         conv_kernel, name)
    o = delta_rule_normed(
        q, k, v, z, a=flat(last(ba, r, 2 * r), n_value_head),
        b=flat(last(ba, 0, r), n_value_head), rms_eps=rms_eps, name=name,
        a_log=a_log_init(n_value_head, seed))
    return linear(flat(o, wide_v), x.shape[-1], name + ".out")


def _sparse_experts(x, seq_len, n_expert, top_k, d_expert, d_shared,
                    first_expert, experts_held, norm_topk_prob, name):
    d_model = x.shape[-1]
    routed, routing = expert_rows(
        x, n_expert, top_k, d_expert, name,
        router=dict(norm_topk_prob=norm_topk_prob),
        experts=dict(first_expert=first_expert, experts_held=experts_held))
    shared = layers.elementwise_mul(
        gated_mlp(x, d_shared, name + ".shared"),
        layers.sigmoid(linear(x, 1, name + ".shared_gate")))
    out = layers.elementwise_add(
        layers.reshape(routed, shape=[-1, seq_len, d_model]), shared)
    return out, routing


def qwen3_next(vocab_size=151936, seq_len=4096, n_layer=48, d_model=2048,
               full_attention_interval=4, n_head=16, n_kv_head=2,
               head_dim=256, rotary_dim=64, rope_theta=1e7, n_key_head=16,
               n_value_head=32, key_dim=128, value_dim=128, conv_kernel=4,
               n_expert=512, top_k=10, d_expert=512, d_shared=512,
               norm_topk_prob=True, first_expert=0, experts_held=None,
               rms_eps=1e-6, aux_coef=0.001):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `experts_held` None holds all
    `n_expert` experts."""
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    routings = []
    for i in range(n_layer):
        name = f"l{i}"
        normed = _norm(x, rms_eps, name + ".in_norm")
        if layer_kind(i, full_attention_interval) == "full_attention":
            with name_scope(name + ".attn"):
                mixed = _attention(normed, n_head, n_kv_head, head_dim,
                                   rotary_dim, rope_theta, rms_eps,
                                   name + ".attn")
        else:
            with name_scope(name + ".gdn"):
                mixed = _gated_delta_net(normed, n_key_head, n_value_head,
                                         key_dim, value_dim, conv_kernel,
                                         rms_eps, name + ".gdn", seed=i)
        x = layers.elementwise_add(x, mixed)
        with name_scope(name + ".moe"):
            moe, routing = _sparse_experts(
                _norm(x, rms_eps, name + ".post_norm"), seq_len, n_expert,
                top_k, d_expert, d_shared, first_expert, experts_held,
                norm_topk_prob, name)
        x = layers.elementwise_add(x, moe)
        routings.append(routing)
    x = _norm(x, rms_eps, "final_norm")
    logits = linear(x, vocab_size, "head")

    return ({"tokens": tokens, "labels": labels},
            balanced_loss(logits, labels, routings, n_expert, top_k,
                          aux_coef))


def build(**kw):
    return qwen3_next(**kw)
