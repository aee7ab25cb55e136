"""Trinity-Mini (`model_type: afmoe`, 26B-A3B): a decoder-only LM whose
attention layers are of two kinds that differ in their mask AND in whether
they carry positions at all: three sliding-window layers (a causal band of
`sliding_window` keys) that turn q and k by rotary, to one full causal layer
with NO rotary and no other position signal. Grouped heads (32 query heads
over 4 key-value heads), QK-norm, an output gate `sigmoid(x W_g)` from a
projection of its own on the head-merged context, a norm on the way into AND
on the way out of every sublayer (four a layer), an embedding scaled by
`sqrt(hidden)` (`mup_enabled`), leading dense layers, then sparse-expert
layers: a sigmoid router whose choice is moved by a bias that the step itself
rewrites, one shared expert beside the routed ones. Layer equations as in the
public `afmoe` model code. Built for ONE CHIP'S SHARE of an expert-parallel
deployment: the router chooses among all `n_expert` experts, this chip holds
`experts_held` of them from `first_expert` on and computes their part.

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w      every RMSNorm: a plain
             weight that starts at 1, eps 1e-5
    x0 = embed(tokens) * sqrt(d_model)           (`mup_enabled`)
    layer i:  h = x + N(Attn_i(N(x)));  y = h + N(FFN_i(N(h)));  four norms a
              layer, each with its own weight;  FFN_i is the dense gated MLP
              for i < n_dense_layer, MoE after; after the last layer N, then
              the untied head
    Attn_i: q = x W_q (`n_head` heads), k = x W_k, v = x W_v (`n_kv_head`
            heads), g = x W_g (`n_head` x `head_dim`), no bias;
            q = N(q), k = N(k) over a head's `head_dim` dims, one weight each
            sliding_attention: rotary (rotate-half, the whole head, theta,
                no scaling) on q and k
            full_attention:    NO rotary, no positions at all
            key-value head h // group serves query head h (the key and value
            heads are repeated in the Program); scores times head_dim^-0.5;
            key j is visible to query i iff j <= i and, on a sliding layer,
            i - j < sliding_window
            out = (ctx * sigmoid(g)) W_o    the gate on the head-merged
                                            context, element by element
    dense:  down(silu(gate x) * up x), width d_dense
    MoE:  s = sigmoid(x W_r) in float32 over all experts; idx = top-k of
          s + b (one group: n_group 1), b the selection bias [n_expert],
          float32, NOT a parameter of the loss;  w = s[idx] (the scores
          WITHOUT b);  w = w / (sum_k w + 1e-20) (`route_norm`);
          w = route_scale * w
          routed = sum over the chosen experts held here of w_k *
          down_e(silu(gate_e x) * up_e x), dropless;  shared =
          down_s(silu(gate_s x) * up_s x) at width n_shared * d_expert, no
          gate;  MoE(x) = routed + shared
    loss = mean cross-entropy (no balance loss, no z-loss)
    after the forward pass of a step, per MoE layer, outside the gradient:
          c_e = assignments to expert e in this step (all experts);
          b_e <- b_e + bias_update_rate * sign(mean(c) - c_e)   (b from 0)

ASSUMED, the config having no key for them: the output gate, the four norms,
QK-norm and "no rotary on the full layers" are the public `afmoe` model
code's; the bias rule's form is Kanana-2's (DeepSeek-V3, arXiv:2412.19437,
section 2.1.2) at `load_balance_coeff` as its rate, not centred. Float32
under AMP: the router (`moe_router`, AMP_F32_OPS), `b` and its update, every
norm's statistics and rotary's trigonometry (inside their rules); the gate's
projection, its sigmoid and the product run in the step's bfloat16, as the
public code runs them in the model's dtype. Built from `fluid.layers` only;
parameter names are fixed (`l0.attn.q.w`, `l0.attn.gate.w`,
`l0.attn.q_norm.w`, `l0.in_norm.w`, `l0.post_attn_norm.w`,
`l0.pre_mlp_norm.w`, `l0.post_mlp_norm.w`, `l0.mlp.gate.w`, `l1.router.w`,
`l1.router.bias`, `l1.experts.gate.w`, `l1.shared.gate.w`, ...) so that a
reference can be handed the same weights by name. A layer's mixer (its two
norms included) carries `fluid.name_scope("l<i>.swa")` where it is windowed
and `"l<i>.attn"` where it is full, so the device track reads by kind; its
feed-forward (its two norms included) `"l<i>.mlp"` or `"l<i>.moe"`.
"""

from __future__ import annotations

from .. import layers
from ..core.ir import name_scope
from ._decoder import (PERIOD, cross_entropy_fetches, embed, gated_mlp,
                       heads_first, layer_kinds, linear, merge_heads,
                       noaux_experts, norm, serve_group, split_heads,
                       token_feeds)


def _gated_attention(x, n_head, n_kv_head, head_dim, rope_theta, window,
                     rotary, rms_eps, name):
    def heads(t, n, norm_name=None):    # [B, T, n * Dh] -> [B, n, T, Dh]
        t = split_heads(t, n, head_dim)
        if norm_name is not None:
            t = norm(t, rms_eps, norm_name)
        t = heads_first(t)
        if norm_name is not None and rotary:
            t = layers.rotary_embedding(t, theta=rope_theta)
        return t

    q = heads(linear(x, n_head * head_dim, name + ".q"), n_head,
              name + ".q_norm")
    k = heads(linear(x, n_kv_head * head_dim, name + ".k"), n_kv_head,
              name + ".k_norm")
    v = heads(linear(x, n_kv_head * head_dim, name + ".v"), n_kv_head)
    gate = linear(x, n_head * head_dim, name + ".gate")
    ctx = layers.fused_attention(
        q, serve_group(k, n_head, n_kv_head, head_dim),
        serve_group(v, n_head, n_kv_head, head_dim), causal=True,
        sm_scale=head_dim ** -0.5, window=window)
    ctx = layers.elementwise_mul(merge_heads(ctx, n_head * head_dim),
                                 layers.sigmoid(gate))
    return linear(ctx, x.shape[-1], name + ".o")


def trinity(vocab_size=200192, seq_len=4096, n_layer=32, n_dense_layer=2,
            d_model=2048, d_dense=6144, n_head=32, n_kv_head=4, head_dim=128,
            layer_types=PERIOD, sliding_window=2048, rope_theta=1e4,
            n_expert=128, top_k=8, d_expert=1024, n_shared=1,
            route_scale=2.826, bias_update_rate=0.001, first_expert=0,
            experts_held=None, rms_eps=1e-5):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `layer_types`: the kind of every layer,
    repeated as a period where it is shorter than `n_layer`
    (`_decoder.layer_kinds`). `n_layer` counts the `n_dense_layer` leading
    dense layers too. `experts_held` None holds all `n_expert` experts."""
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    x = layers.scale(x, scale=d_model ** 0.5)
    routings = []
    for i, kind in enumerate(layer_kinds(n_layer, layer_types)):
        name = f"l{i}"
        sliding = kind == "sliding_attention"
        with name_scope(name + (".swa" if sliding else ".attn")):
            mixed = _gated_attention(
                norm(x, rms_eps, name + ".in_norm"), n_head, n_kv_head,
                head_dim, rope_theta, sliding_window if sliding else None,
                sliding, rms_eps, name + ".attn")
            mixed = norm(mixed, rms_eps, name + ".post_attn_norm")
        x = layers.elementwise_add(x, mixed)
        dense = i < n_dense_layer
        with name_scope(name + (".mlp" if dense else ".moe")):
            normed = norm(x, rms_eps, name + ".pre_mlp_norm")
            if dense:
                fed = gated_mlp(normed, d_dense, name + ".mlp")
            else:
                fed, routing = noaux_experts(
                    normed, seq_len, n_expert, top_k, d_expert,
                    n_shared * d_expert, first_expert, experts_held,
                    route_scale, bias_update_rate, name)
                routings.append(routing)
            fed = norm(fed, rms_eps, name + ".post_mlp_norm")
        x = layers.elementwise_add(x, fed)
    x = norm(x, rms_eps, "final_norm")
    logits = linear(x, vocab_size, "head")
    return ({"tokens": tokens, "labels": labels},
            cross_entropy_fetches(logits, labels, routings))


def build(**kw):
    return trinity(**kw)
