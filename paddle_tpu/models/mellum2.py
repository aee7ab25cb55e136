"""Mellum2: a decoder-only LM whose attention layers are of two kinds, three
sliding-window layers (a causal band of `sliding_window` keys) to one full
causal layer, each kind with rotary tables of its own (plain on the window
layers, YaRN on the full ones), grouped heads (32 query heads over 4 key-value
heads), and a sparse-expert feed-forward in every layer, no shared expert, no
dense layer (Mellum2-12B-A2.5B, `model_type: mellum`; the config's key set is
the Qwen3-MoE family's, whose layer equations these are). Built for ONE CHIP'S
SHARE of an expert-parallel deployment: the router chooses among all
`n_expert` experts, this chip holds `experts_held` of them from `first_expert`
on and computes their part.

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w      every RMSNorm: a plain
             weight that starts at 1
    layer i:  h = x + Attn_i(N(x));  y = h + MoE(N(h));  after the last layer
              N, then the untied head
    Attn_i: q = x W_q (`n_head` heads), k = x W_k, v = x W_v (`n_kv_head`
            heads), no bias;  q = N(q), k = N(k) over a head's `head_dim`
            dims, one learned weight each (ASSUMED: the config has no key
            for it, the family's attention has it);  rotary (rotate-half) on
            the whole head:
              sliding_attention: frequencies theta^(-2j/head_dim), tables
                  unscaled
              full_attention: the YaRN block `rope_scaling`
                  (`ops/decoder_block.py::rotary_frequencies`), cos and sin
                  times its `attention_factor`, at every length
            key-value head g serves query heads g * group .. g * group +
            group - 1 (the key and value heads are repeated in the Program);
            scores times head_dim^-0.5;  key j is visible to query i iff
            j <= i and, on a sliding layer, i - j < sliding_window;
            out = ctx W_o
    MoE:  p = softmax(x W_r) in float32 over all experts; the top-k of p
          divided by their sum (`norm_topk_prob`); sum over the chosen
          experts held here of p_k * down_e(silu(gate_e x) * up_e x),
          dropless
    loss = mean cross-entropy + aux_coef * n_expert * sum_e f_e P_e over all
           layers' router rows (ASSUMED, the form `models/olmoe.py` has; no
           z-loss)

Left out: a multi-token-prediction head (the config has no key for it) and
`intermediate_size` (no layer is dense). Float32 under AMP: the router
(`moe_router`, AMP_F32_OPS), every norm's statistics and rotary's
trigonometry (inside their rules). Built from `fluid.layers` only; parameter
names are fixed (`l0.attn.q.w`, `l0.attn.q_norm.w`, `l0.router.w`,
`l0.experts.gate.w`, ...) so that a reference can be handed the same weights
by name. A layer's mixer (its input norm included) carries
`fluid.name_scope("l<i>.swa")` where it is windowed and `"l<i>.attn"` where
it is full, so the device track reads by kind; its feed-forward `"l<i>.moe"`.
"""

from __future__ import annotations

from .. import layers
from ..core.ir import name_scope
from ._decoder import (PERIOD, balanced_loss, embed, grouped_attention,
                       layer_kinds, linear, norm, routed_experts, token_feeds)

# `rope_parameters.full_attention` of the published config
YARN = {"factor": 16.0, "original_max_position_embeddings": 8192,
        "beta_fast": 32.0, "beta_slow": 1.0,
        "attention_factor": 1.2772588722239782}


def mellum2(vocab_size=98304, seq_len=8192, n_layer=28, d_model=2304,
            n_head=32, n_kv_head=4, head_dim=128, layer_types=PERIOD,
            sliding_window=1024, rope_theta=5e5, rope_scaling=YARN,
            n_expert=64, top_k=8, d_expert=896,
            norm_topk_prob=True, first_expert=0, experts_held=None,
            rms_eps=1e-6, aux_coef=0.001):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `layer_types`: one period of the
    layers' kinds (see `layer_kinds`). `rope_scaling`: the YaRN block of the
    full layers (None: plain rotary there too). `experts_held` None holds
    all `n_expert` experts."""
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    routings = []
    kinds = layer_kinds(n_layer, layer_types)
    for i, kind in enumerate(kinds):
        name = f"l{i}"
        sliding = kind == "sliding_attention"
        with name_scope(name + (".swa" if sliding else ".attn")):
            mixed = grouped_attention(
                norm(x, rms_eps, name + ".in_norm"), n_head, n_kv_head,
                head_dim, rope_theta, None if sliding else rope_scaling,
                sliding_window if sliding else None, rms_eps, name + ".attn")
        x = layers.elementwise_add(x, mixed)
        with name_scope(name + ".moe"):
            moe, routing = routed_experts(
                norm(x, rms_eps, name + ".post_norm"), seq_len, n_expert,
                top_k, d_expert, name,
                router=dict(norm_topk_prob=norm_topk_prob),
                experts=dict(first_expert=first_expert,
                             experts_held=experts_held))
        x = layers.elementwise_add(x, moe)
        routings.append(routing)
    x = norm(x, rms_eps, "final_norm")
    logits = linear(x, vocab_size, "head")
    return ({"tokens": tokens, "labels": labels},
            balanced_loss(logits, labels, routings, n_expert, top_k,
                          aux_coef))


def build(**kw):
    return mellum2(**kw)
