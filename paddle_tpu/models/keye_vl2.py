"""Keye-VL-2.0's language model (`model_type: KeyeVL2`, 30B-A3B): a
decoder-only LM whose every layer CHOOSES the keys a query attends to. A
small indexer (16 heads of 64 over one key head) scores the keys below a
query's diagonal, the `topk` (2048) best are kept, and the softmax runs over
those alone (`sa_config`: DeepSeek-Sparse-Attention, DeepSeek-V3.2-Exp report,
here over grouped heads: 32 query heads over 4 key-value heads of 128). The
rest of the block is the Qwen3-MoE family's, as `models/mellum2.py` builds it
(`_decoder.grouped_attention`, `routed_experts`, `balanced_loss`):
QK-norm, rotary, a renormalised softmax top-8 router over 128 experts, no
shared expert, no dense layer. Built for ONE CHIP'S SHARE of an
expert-parallel deployment: the router chooses among all `n_expert` experts,
this chip holds `experts_held` of them from `first_expert` on.

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w      every RMSNorm
    layer:  h = x + Attn(N(x));  y = h + MoE(N(h));  after the last layer N,
            then the untied head; all layers are alike
    Attn:   q = x W_q (`n_head` heads), k = x W_k, v = x W_v (`n_kv_head`
            heads), no bias; q = N(q), k = N(k) over a head (ASSUMED, the
            family's, as Mellum2); rotary (rotate-half, the whole head,
            theta; `mrope_section` splits the frequencies over three position
            streams, which are equal for text: plain rotary, ASSUMED);
            key-value head g serves query heads g * group .. g * group +
            group - 1
    index:  qI = x W_qI (`n_index_head` heads of `index_dim`),
            kI = LN(x W_kI) (one head; LayerNorm with weight and bias, eps
            1e-6), w = x W_w (`n_index_head` a token), rotary on qI and kI
            (the whole `index_dim`, theta);
            I[t, s] = index_dim^-0.5 * n_index_head^-0.5
                      * sum_j w[t, j] * ReLU(qI[t, j] . kI[s])     for s <= t
            S_t = the `topk` keys of largest I[t, :t + 1] (all of them while
            t < topk; of equal scores the lower index)
    ctx[t, h] = sum over s in S_t of softmax_{s in S_t}(q[t, h] . k[s, g(h)]
            * head_dim^-0.5) v[s, g(h)];  out = ctx W_o
    MoE, loss: Mellum2's (`_decoder.routed_experts`, `balanced_loss`)

No gradient passes the selection: `dsa_index_scores` and `dsa_select` carry
none and the kept set is int8, so `append_backward` writes no grad op for the
indexer, its five parameters a layer (`l<i>.index.q.w`, `.k.w`,
`.k_norm.w`, `.k_norm.b`, `.w.w`) get no gradient and `minimize` gives them
no update and no moments (`frozen_parameters` on the compile event). That is
the published training's main stage seen from the language-model loss, whose
graph the indexer's input is detached from; the alignment loss that trains
the indexer there (a KL term against the head-summed attention weights) is
left out: it needs those weights out of the flash kernels, and no
`config.json` states a recipe.

ASSUMED, `sa_config` having no key for them (the published DSA's, adapted to
grouped heads): the LayerNorm on kI, rotary on the index heads (the whole 64:
there is no separate rotary part), the two scales, qI projected from the
hidden state (grouped heads have no query latent), the weights `w` from the
hidden state. Left out: the Hadamard rotation before the published FP8 index
product (orthogonal: it changes no score), the FP8 quantisation itself. Under
AMP: the projections, the index products and the attention in bf16 with
float32 accumulation; `w` is the bf16 result of its projection; the ReLU, the
weights' product, the sum over index heads, the scale and the comparison with
the threshold in float32 (inside `dsa_index_scores` and `dsa_select`); the
LayerNorm's and every RMSNorm's statistics, rotary's trigonometry and the
router in float32. Built from `fluid.layers` only; parameter names are fixed.
A layer's mixer (its input norm and its indexer included) carries
`fluid.name_scope("l<i>.dsa")`, its feed-forward `"l<i>.moe"`.
"""

from __future__ import annotations

from .. import layers
from ..core.ir import name_scope
from ..param_attr import ParamAttr
from ._decoder import (balanced_loss, embed, grouped_attention, heads_first,
                       linear, norm, routed_experts, split_heads, token_feeds)

LN_EPS = 1e-6


def _indexer(x, n_index_head, index_dim, rope_theta, topk, tile, name):
    """The kept set of one layer from its normed input x [B, T, D]: int8
    [B, T, T]."""
    def turned(t, n):       # [B, T, n * Di] -> [B, n, T, Di], rotary
        return layers.rotary_embedding(
            heads_first(split_heads(t, n, index_dim)), theta=rope_theta)

    q = turned(linear(x, n_index_head * index_dim, name + ".q"),
               n_index_head)
    k = layers.layer_norm(
        linear(x, index_dim, name + ".k"), begin_norm_axis=2,
        epsilon=LN_EPS, param_attr=ParamAttr(name=name + ".k_norm.w"),
        bias_attr=ParamAttr(name=name + ".k_norm.b"))
    weights = linear(x, n_index_head, name + ".w")
    scores = layers.dsa_index_scores(
        q, turned(k, 1), weights,
        scale=index_dim ** -0.5 * n_index_head ** -0.5, tile=tile)
    return layers.dsa_select(scores, topk)


def keye_vl2(vocab_size=151936, seq_len=8192, n_layer=48, d_model=2048,
             n_head=32, n_kv_head=4, head_dim=128, rope_theta=1e7,
             n_index_head=16, index_dim=64, topk=2048, index_tile=512,
             n_expert=128, top_k=8, d_expert=768, norm_topk_prob=True,
             first_expert=0, experts_held=None, rms_eps=1e-6,
             aux_coef=0.001):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels; `fetches["l<i>.kept"]` is layer i's
    kept set. `experts_held` None holds all `n_expert` experts."""
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    routings, kept_sets = [], {}
    for i in range(n_layer):
        name = f"l{i}"
        with name_scope(name + ".dsa"):
            normed = norm(x, rms_eps, name + ".in_norm")
            kept = _indexer(normed, n_index_head, index_dim, rope_theta,
                            topk, index_tile, name + ".index")
            mixed = grouped_attention(normed, n_head, n_kv_head, head_dim,
                                      rope_theta, None, None, rms_eps,
                                      name + ".attn", kept=kept, topk=topk)
        kept_sets[name + ".kept"] = kept
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
            {**balanced_loss(logits, labels, routings, n_expert, top_k,
                             aux_coef), **kept_sets})


def build(**kw):
    return keye_vl2(**kw)
