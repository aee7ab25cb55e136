"""Ling-3.0-flash-VL's language model (`inclusionAI/Ling-3.0-flash-VL`): a
decoder-only LM whose mixers come in groups of `layer_group_size` layers, all
but the last of a group Kimi Delta Attention (KDA, arXiv:2510.26692: a linear-
attention mixer whose state is rewritten by a delta rule under a decay PER KEY
CHANNEL) and the last one latent attention (MLA, DeepSeek-V2's, as
`models/kanana2.py`) with a head-wise sigmoid gate on its context; a dense
gated MLP in the first `n_dense_layer` published layers and a sparse-expert
feed-forward in the others, under DeepSeek-V3's group-limited `noaux_tc`
router (arXiv:2412.19437). Built for ONE CHIP'S SHARE of an expert-parallel
deployment, and for a RUN of consecutive published layers: `first_layer` is
the published index of the built layer 0, so that the kinds follow the
published indices.

    N(x) = x * rsqrt(mean(x^2) + eps) * w        every RMSNorm: statistics
           float32, a plain weight that starts at 1, eps 1e-6
    layer i, published index p = first_layer + i:
        h = x + Mixer_p(N(x));   y = h + FFN_p(N(h));   after the last layer
        N, then the untied head
        Mixer_p = MLA where (p + 1) % layer_group_size == 0, else KDA
        FFN_p   = dense down(silu(gate x) * up x), width d_dense, where
                  p < n_dense_layer; else MoE
    KDA:  q = x W_q, k = x W_k, v = x W_v         n_head heads of head_dim
          [q | k | v] <- silu(conv([q | k | v]))  depthwise, causal,
                                                  `conv_kernel` taps, no bias
          q <- q / sqrt(sum q^2 + 1e-6) * head_dim^-0.5;  k likewise unscaled
          g = kda_lower_bound * sigmoid(exp(A_log_h) * (x W_f + dt_bias))
              float32 [T, n_head, head_dim]: in (kda_lower_bound, 0), a head
              AND key channel (`kda_safe_gate`)
          beta = sigmoid(x W_b)                   float32 [T, n_head]
          a head's state S [head_dim, head_dim] float32, S_0 = 0, every token:
              S <- Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);
              S <- S + k_t d^T;  o_t = S^T q_t
          out = (o * rsqrt(mean(o^2) + eps) * w_norm * sigmoid(x W_g)) W_o
              the norm over one head, the gate per channel
    MLA:  `_decoder.latent_attention`, then ctx_h <- ctx_h *
          sigmoid(x W_gate)_h (one scalar a head and token) before W_o
    MoE:  s = sigmoid(x W_r) float32 over all experts; c = s + b (b the
          selection bias: no gradient, rewritten in the step, b <- b +
          bias_update_rate * sign(mean(n) - n) from the step's counts n);
          n_group groups of consecutive experts, a group's score the sum of
          its two largest c, the topk_group best groups stay; idx = the
          top-k of c over their experts; w = s[idx] / (sum + 1e-20) *
          routed_scaling_factor; routed = sum over the chosen experts HELD
          HERE of w_e * down_e(silu(gate_e x) * up_e x), dropless;
          out = routed + down_s(silu(gate_s x) * up_s x), width d_shared
    loss = mean cross-entropy (no auxiliary term: `noaux_tc` routing)

ASSUMED, the config having no key for them (each with its reason in
`benchmark/configs/ling_3_0_flash_vl.json`): the decay's form (the public
`safe_gate` / `lower_bound` gate) and its initial values (`A_log` the log of
uniform(1, 16), `dt_bias` the inverse softplus of a log-uniform draw in
[0.001, 0.1], both drawn from the PUBLISHED layer index); `W_f` and `W_g` at
full rank (`no_kda_lora`); KDA's output gate per channel and sigmoid, its norm
over one head; `head_wise` read as MLA's gate; no positions in KDA; the
interleaved rotary turn of MLA's 64 rotary dims; the bias rate 0.001; weights
normal(0, 0.02), norms 1, the convolution uniform(-0.5, 0.5). Left out: the
vision tower, multi-token prediction, the experts' swiglu clamp (0 = none in
the published layers 1-6). Float32 under AMP: the router (`moe_router`,
AMP_F32_OPS), `b` and its update, g and beta (`kda_gates`, AMP_F32_OPS), and
inside their rules the running sums, every `exp`, the l2-norms, the solve and
the state of `kda_delta_rule`, the convolution's sums, every norm's
statistics. Built from `fluid.layers` only; parameter names are fixed
(`l0.kda.q.w`, `l0.kda.f.w`, `l0.kda.A_log`, `l0.kda.dt_bias`,
`l0.kda.conv.w`, `l0.kda.norm.w`, `l4.mla.kv_a.w`, `l4.mla.gate.w`,
`l0.mlp.gate.w`, `l1.router.w`, `l1.router.bias`, `l1.experts.gate.w`,
`l1.shared.gate.w`, ...) so that a reference can be handed the same weights
by name. Each part's ops carry `fluid.name_scope("l<i>.kda" | "l<i>.mla" |
"l<i>.mlp" | "l<i>.moe")`.
"""

from __future__ import annotations

import numpy as np

from .. import initializer as init
from .. import layers
from ..core.ir import name_scope
from ..param_attr import ParamAttr
from ._decoder import (cross_entropy_fetches, dt_bias_init, embed, gated_mlp,
                       last, latent_attention, linear, noaux_experts, norm,
                       split_heads, token_feeds)


def layer_kind(p, layer_group_size):
    """The mixer of the PUBLISHED layer p."""
    return "mla" if (p + 1) % layer_group_size == 0 else "kda"


def _a_log(heads, seed):
    """log of uniform(1, 16), as the public KDA code initialises `A_log`;
    drawn here so that the startup program holds the values."""
    draws = np.random.RandomState(seed).uniform(1.0, 16.0, size=heads)
    return init.NumpyArrayInitializer(np.log(draws).astype("float32"))


def _kda(x, n_head, head_dim, conv_kernel, lower_bound, chunk, rms_eps, name,
         seed):
    wide = n_head * head_dim
    qkv = layers.concat([linear(x, wide, f"{name}.{part}") for part in "qkv"],
                        axis=2)
    qkv = layers.causal_conv1d(
        qkv, conv_kernel, param_attr=ParamAttr(
            name=name + ".conv.w",
            initializer=init.UniformInitializer(-0.5, 0.5)))
    q, k, v = (split_heads(last(qkv, j * wide, (j + 1) * wide), n_head,
                           head_dim) for j in range(3))
    o = layers.kda_delta_rule(
        q, k, v, f=linear(x, wide, name + ".f"),
        b=linear(x, n_head, name + ".b"), lower_bound=lower_bound,
        chunk=chunk,
        a_log_attr=ParamAttr(name=name + ".A_log",
                             initializer=_a_log(n_head, seed)),
        dt_bias_attr=ParamAttr(
            name=name + ".dt_bias", initializer=init.NumpyArrayInitializer(
                dt_bias_init(wide, seed))))
    gate = split_heads(linear(x, wide, name + ".g"), n_head, head_dim)
    o = layers.gated_rms_norm(o, gate, epsilon=rms_eps, activation="sigmoid",
                              param_attr=ParamAttr(name=name + ".norm.w"))
    return linear(layers.reshape(o, shape=[0, 0, wide]), x.shape[-1],
                  name + ".o")


def ling3(vocab_size=157184, seq_len=2048, n_layer=42, first_layer=0,
          layer_group_size=6, n_dense_layer=2, d_model=2560, d_dense=6144,
          n_head=32, head_dim=128, conv_kernel=4, kda_lower_bound=-5.0,
          chunk=64, kv_rank=512, qk_nope_dim=128, qk_rope_dim=64,
          v_head_dim=128, rope_theta=6e6, n_expert=512, top_k=8,
          d_expert=768, d_shared=768, n_group=8, topk_group=4,
          routed_scaling_factor=2.5, bias_update_rate=0.001, first_expert=0,
          experts_held=None, rms_eps=1e-6):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `n_layer` layers are built, the
    published layers `first_layer .. first_layer + n_layer - 1`;
    `n_dense_layer` counts PUBLISHED layers from 0 (`first_k_dense_replace`).
    `experts_held` None holds all `n_expert` experts."""
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    routings = []
    for i in range(n_layer):
        name, p = f"l{i}", first_layer + i
        normed = norm(x, rms_eps, name + ".in_norm")
        if layer_kind(p, layer_group_size) == "mla":
            with name_scope(name + ".mla"):
                mixed = latent_attention(
                    normed, n_head, kv_rank, qk_nope_dim, qk_rope_dim,
                    v_head_dim, rope_theta, rms_eps, name + ".mla",
                    head_gate=True)
        else:
            with name_scope(name + ".kda"):
                mixed = _kda(normed, n_head, head_dim, conv_kernel,
                             kda_lower_bound, chunk, rms_eps, name + ".kda",
                             seed=p)
        x = layers.elementwise_add(x, mixed)
        normed = norm(x, rms_eps, name + ".post_norm")
        if p < n_dense_layer:
            with name_scope(name + ".mlp"):
                fed = gated_mlp(normed, d_dense, name + ".mlp")
        else:
            with name_scope(name + ".moe"):
                fed, routing = noaux_experts(
                    normed, seq_len, n_expert, top_k, d_expert, d_shared,
                    first_expert, experts_held, routed_scaling_factor,
                    bias_update_rate, name, n_group, topk_group)
            routings.append(routing)
        x = layers.elementwise_add(x, fed)
    x = norm(x, rms_eps, "final_norm")
    logits = linear(x, vocab_size, "head")
    return ({"tokens": tokens, "labels": labels},
            cross_entropy_fetches(logits, labels, routings))


def build(**kw):
    return ling3(**kw)
