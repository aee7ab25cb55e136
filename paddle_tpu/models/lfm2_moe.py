"""LFM2-MoE (`model_type: lfm2_moe`; LiquidAI/LFM2-8B-A1B): a decoder-only LM
three quarters of whose mixers hold no attention and no recurrence: a GATED
SHORT CONVOLUTION, a depthwise causal convolution of three taps with no
activation between two gates that are projections of the same input, beside
grouped softmax attention with QK-norm and rotary; a dense gated MLP in the
leading layers and routed experts alone (no shared expert) in the others,
under a sigmoid router with a selection bias; one table as embedding and
head. The public `lfm2_moe` model code. Built for ONE CHIP'S SHARE of an
expert-parallel deployment, and for a RUN of consecutive published layers:
`first_layer` is the published index of the first layer built, so that names
and dense layers follow the published indices.

    N(x) = x * rsqrt(mean(x^2) + eps) * w       every RMSNorm: a plain weight
                                                that starts at 1, eps 1e-5
    h_0 = E[tokens]
    layer p (published index; its operator by `layer_types`):
        h = h + Op_p(N_op(h))                                  operator_norm
        h = h + FFN_p(N_ffn(h))                                ffn_norm
        FFN_p = W_2(silu(W_1 x) * W_3 x), width d_dense, for p < n_dense_layer
        FFN_p = the routed experts below                  for the others
    logits = N(h_L) E^T       the final norm (the public code's
                              `embedding_norm`), the table tied
    loss = mean cross-entropy (no balance loss, no z-loss)
    conv:   [B | C | x'] = x W_in               D -> 3 D, no bias
            u = B * x'                          the gate BEFORE the convolution
            v[t, c] = sum_{j < taps} w[c, j] u[t - (taps - 1) + j, c]
                                                depthwise, causal (zeros
                                                before t = 0), NO bias and NO
                                                activation
            y = C * v                           the gate AFTER it
            out = y W_out                       D -> D, no bias
    full_attention:  q = x W_q (`n_head` heads), k = x W_k, v = x W_v
            (`n_kv_head` heads), no bias; q = N(q), k = N(k) over a head's
            `head_dim` dims, one weight each a layer; rotary (rotate-half,
            the whole head, theta, no scaling) on q and k; causal
            softmax(q k^T head_dim^-0.5) v; key-value head h // group serves
            query head h (repeated in the Program); out = ctx W_o
    experts: s = sigmoid(x W_r) in float32 over all `n_expert`; idx = top-k
            of s + b (b the selection bias [n_expert], float32, NOT a
            parameter of the loss); w = s[idx] (the scores WITHOUT b);
            w = w / (sum_k w + route_norm_eps); w = route_scale * w
            FFN(x) = sum over the chosen experts HELD HERE of w_k *
            down_e(silu(gate_e x) * up_e x), dropless; NO shared expert
    after the forward pass of a step, per expert layer, outside the gradient:
            c_e = assignments to expert e in this step (all experts);
            b_e <- b_e + bias_update_rate * sign(mean(c) - c_e)   (b from 0)

ASSUMED, the config having no key for them (each with its reason in
`benchmark/configs/lfm2_8b_a1b.json`): the tied table (`tie_embeddings`
false builds an untied `head.w` instead: what a test compares the tied
gradient with); how b is rewritten while training (DeepSeek-V3's rule,
arXiv:2412.19437 section 2.1.2, at `bias_update_rate`, as `kanana2` and
`trinity`); the order of `W_in`'s columns `[B | C | x']` (the public code's
`chunk(3)`); the convolution's weight uniform(+-taps^-0.5), every other
matrix and the table normal(0, 0.02), norm weights 1. Float32 under AMP: the
router (`moe_router`, AMP_F32_OPS), b and its update, the convolution's sums,
every norm's statistics, rotary's trigonometry, the embedding's rows as the
look-up reads them, the loss; the gates' products run in the projections'
bfloat16. Built from `fluid.layers` and `models/_decoder.py` only; parameter
names are fixed and carry the PUBLISHED index (`embed.w`, `l1.op_norm.w`,
`l1.conv.in.w`, `l1.conv.conv.w`, `l1.conv.out.w`, `l2.attn.q.w`, `.k.w`,
`.v.w`, `.o.w`, `l2.attn.q_norm.w`, `.k_norm.w`, `l1.ffn_norm.w`,
`l1.mlp.gate.w`, `.up.w`, `.down.w`, `l2.router.w`, `l2.router.bias`,
`l2.experts.gate.w`, `.up.w`, `.down.w`, `final_norm.w`; no `head.w` when
tied) so that a reference can be handed the same weights by name. A layer's
operator with its norm and its residual add carries
`fluid.name_scope("l<p>.conv" | "l<p>.attn")`, the operator between its
projections (the two gates and the convolution) `"l<p>.conv/core"` inside it,
and its feed-forward with its norm and add `"l<p>.mlp" | "l<p>.moe"`.
"""

from __future__ import annotations

from .. import initializer as init
from .. import layers
from ..core.ir import name_scope
from ..param_attr import ParamAttr
from ._decoder import (cross_entropy_fetches, embed, gated_mlp,
                       grouped_attention, last, linear, noaux_router, norm,
                       routed_experts, tied_head, token_feeds)

KINDS = {"conv": "conv", "full_attention": "attn"}  # layer type -> its scope
# the published `layer_types`: attention at 2, 6, 10, 14, 18, 21 of 24
LFM2_8B_A1B = tuple("full_attention" if i in (2, 6, 10, 14, 18, 21)
                    else "conv" for i in range(24))


def _short_conv(x, taps, name):
    """The gated short convolution on the normed x `[B, T, D]`: `[B | C |
    x'] = x W_in` (`name.in.w`), `C * conv(B * x')` with `taps` causal taps a
    channel and nothing after them (`name.conv.w`), `name.out.w` back to D.
    The slices, the gates and the convolution stand under a scope of their
    own inside the layer's, so that what they own on the device is read
    apart from the projections."""
    d = x.shape[-1]
    mixed = linear(x, 3 * d, name + ".in")
    with name_scope("core"):
        b, c, xs = (last(mixed, j * d, (j + 1) * d) for j in range(3))
        v = layers.causal_conv1d(
            layers.elementwise_mul(b, xs), taps, activation=None,
            param_attr=ParamAttr(
                name=name + ".conv.w",
                initializer=init.UniformInitializer(-taps ** -0.5,
                                                    taps ** -0.5)))
        y = layers.elementwise_mul(c, v)
    return linear(y, d, name + ".out")


def lfm2_moe(vocab_size=65536, seq_len=4096, layer_types=LFM2_8B_A1B,
             first_layer=0, n_dense_layer=2, d_model=2048, d_dense=7168,
             conv_taps=3, n_head=32, n_kv_head=8, head_dim=64,
             rope_theta=1e6, n_expert=32, top_k=4, d_expert=1792,
             route_scale=1.0, route_norm_eps=1e-6, bias_update_rate=0.001,
             first_expert=0, experts_held=None, tie_embeddings=True,
             rms_eps=1e-5):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `layer_types`: "conv" or
    "full_attention" for each layer built, the published layers `first_layer
    .. first_layer + len(layer_types) - 1`; `n_dense_layer` counts PUBLISHED
    layers from 0. `experts_held` None holds all `n_expert` experts."""
    unknown = sorted(set(layer_types) - set(KINDS))
    if unknown or not layer_types:
        raise ValueError(f"layer_types holds {sorted(KINDS)}, got "
                         f"{list(layer_types)!r}")
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    routings = []
    for p, kind in enumerate(layer_types, start=first_layer):
        name = f"l{p}"
        with name_scope(f"{name}.{KINDS[kind]}"):
            normed = norm(x, rms_eps, name + ".op_norm")
            if kind == "conv":
                mixed = _short_conv(normed, conv_taps, name + ".conv")
            else:
                mixed = grouped_attention(
                    normed, n_head, n_kv_head, head_dim, rope_theta, None,
                    None, rms_eps, name + ".attn")
            x = layers.elementwise_add(x, mixed)
        dense = p < n_dense_layer
        with name_scope(name + (".mlp" if dense else ".moe")):
            normed = norm(x, rms_eps, name + ".ffn_norm")
            if dense:
                fed = gated_mlp(normed, d_dense, name + ".mlp")
            else:
                fed, routing = routed_experts(
                    normed, seq_len, n_expert, top_k, d_expert, name,
                    router=noaux_router(name, bias_update_rate, route_scale,
                                        norm_eps=route_norm_eps),
                    experts=dict(first_expert=first_expert,
                                 experts_held=experts_held))
                routings.append(routing)
            x = layers.elementwise_add(x, fed)
    x = norm(x, rms_eps, "final_norm")
    logits = tied_head(x, vocab_size) if tie_embeddings \
        else linear(x, vocab_size, "head")
    return ({"tokens": tokens, "labels": labels},
            cross_entropy_fetches(logits, labels, routings))


def build(**kw):
    return lfm2_moe(**kw)
