"""Kanana-2-30B-A3B: a decoder-only LM of the `deepseek_v3` family. Latent
attention (MLA: keys and values come out of one 512-wide compressed row a
token, query/key heads of 192 over value heads of 128) in every layer, a
dense gated MLP in the leading layer(s) and a sparse-expert feed-forward in
the others: a sigmoid router whose choice is moved by a bias that the step
itself rewrites (`noaux_tc`), two shared experts beside the routed ones.
Layer equations as in the public `deepseek_v3` model code. Built for ONE
CHIP'S SHARE of an expert-parallel deployment: the router chooses among all
`n_expert` experts, this chip holds `experts_held` of them from
`first_expert` on and computes their part.

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w      every RMSNorm: a plain
             weight that starts at 1, eps 1e-6
    layer i:  h = x + MLA(N(x));  y = h + FFN_i(N(h));  FFN_i is the dense
              gated MLP for i < n_dense_layer, MoE after; after the last
              layer N, then the untied head

    MLA:  q = x W_q          (no low-rank query: `q_lora_rank` is null); per
                             head q = [q_n (qk_nope_dim) | q_r (qk_rope_dim)]
          [c | k_r] = x W_kva   (kv_rank + qk_rope_dim);  c = N_kv_rank(c);
                             k_r is ONE head for all query heads
          [k_n | v] = c W_kvb   (per head: its k_n, then its v)
          rotary, positions 0..T-1, on q_r and k_r only, INTERLEAVED: the
              pair (x[2i], x[2i+1]) turns by t * theta^(-2i/qk_rope_dim);
              as the public code does, the dims are laid [evens | odds]
              first and the halves rotated, q and k alike
          q = [q_n | q_r], k = [k_n | k_r repeated over the heads];
          ctx = causal softmax(q k^T * (qk_nope_dim + qk_rope_dim)^-0.5) v
              (no mscale: `rope_scaling` is null);  out = ctx W_o
    dense:  down(silu(gate x) * up x), width d_dense
    MoE:  s = sigmoid(x W_r) in float32 over all experts; choice = s + b,
          b the selection bias [n_expert], float32, NOT a parameter of the
          loss; one group of all experts (n_group 1), so idx = top-k of
          choice;  w = s[idx] (the scores WITHOUT b);  w = w / (sum_k w +
          1e-20) (`norm_topk_prob`);  w = routed_scaling_factor * w
          routed = sum over the chosen experts held here of w_k *
          down_e(silu(gate_e x) * up_e x), dropless;  shared =
          down_s(silu(gate_s x) * up_s x) at width n_shared * d_expert, no
          gate;  MoE(x) = routed + shared
    loss = mean cross-entropy (the public code has no router loss)
    after the forward pass of a step, per MoE layer, outside the gradient:
          c_e = assignments to expert e in this step (all experts);
          b_e <- b_e + bias_update_rate * sign(mean(c) - c_e)
          (DeepSeek-V3, arXiv:2412.19437, section 2.1.2; b starts at 0)

Left out: that report's sequence-wise balance loss and any multi-token-
prediction module (the public config has no key for either). Float32 under
AMP: the router's logits and scores (`moe_router`, AMP_F32_OPS), `b` and its
update (`cast`, `reduce_mean`, `elementwise_sub`, `sign`, `scale`, `sum`,
`assign` on float32 values: in no AMP list, and `reduce_mean` is float32 by
AMP_F32_OPS), every norm's statistics and rotary's trigonometry (inside
their rules). `fused_attention` (AMP_BF16_OPS) takes q, k at 192 and v at
128 and returns 128-wide heads; nothing is padded. k_r is repeated over the
heads in the Program (`layers.expand`). Built from `fluid.layers` only;
parameter names are fixed (`l0.mla.q.w`, `l0.mla.kv_a.w`, `l0.mla.kv_norm.w`,
`l0.mla.kv_b.w`, `l0.mla.o.w`, `l0.mlp.gate.w`, `l1.router.w`,
`l1.router.bias`, `l1.experts.gate.w`, `l1.shared.gate.w`, ...) so that a
reference can be handed the same weights by name. Each layer's ops carry
`fluid.name_scope("l<i>.mla" | "l<i>.mlp" | "l<i>.moe")`.
"""

from __future__ import annotations

from .. import layers
from ..core.ir import name_scope
from ._decoder import (cross_entropy_fetches, embed, gated_mlp,
                       latent_attention, linear, noaux_experts, norm,
                       token_feeds)


def kanana2(vocab_size=128256, seq_len=4096, n_layer=48, n_dense_layer=1,
            d_model=2048, d_dense=6144, n_head=32, kv_rank=512,
            qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, rope_theta=1e6,
            n_expert=128, top_k=6, d_expert=768, n_shared=2,
            routed_scaling_factor=2.448, bias_update_rate=0.001,
            first_expert=0, experts_held=None, rms_eps=1e-6):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `n_layer` counts the `n_dense_layer`
    leading dense layers too. `experts_held` None holds all `n_expert`
    experts."""
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    routings = []
    for i in range(n_layer):
        name = f"l{i}"
        with name_scope(name + ".mla"):
            mixed = latent_attention(
                norm(x, rms_eps, name + ".in_norm"), n_head, kv_rank,
                qk_nope_dim, qk_rope_dim, v_head_dim, rope_theta, rms_eps,
                name + ".mla")
        x = layers.elementwise_add(x, mixed)
        normed = norm(x, rms_eps, name + ".post_norm")
        if i < n_dense_layer:
            with name_scope(name + ".mlp"):
                fed = gated_mlp(normed, d_dense, name + ".mlp")
        else:
            with name_scope(name + ".moe"):
                fed, routing = noaux_experts(
                    normed, seq_len, n_expert, top_k, d_expert,
                    n_shared * d_expert, first_expert, experts_held,
                    routed_scaling_factor, bias_update_rate, name)
            routings.append(routing)
        x = layers.elementwise_add(x, fed)
    x = norm(x, rms_eps, "final_norm")
    logits = linear(x, vocab_size, "head")
    return ({"tokens": tokens, "labels": labels},
            cross_entropy_fetches(logits, labels, routings))


def build(**kw):
    return kanana2(**kw)
