"""Olmo-Hybrid: a dense decoder-only LM whose layers alternate a
linear-attention mixer (the gated delta rule with negative eigenvalues, three
layers in four) with OLMo's softmax attention (the fourth), every sublayer
normed on the way OUT, over a dense gated feed-forward (Olmo-Hybrid-7B,
`model_type` `olmo_hybrid`; the linear layer is flash-linear-attention's
`GatedDeltaNet`, the block OLMo 2 / OLMo 3's). Built for ONE CHIP'S SHARE of a
deployment that divides each layer's mixer by heads: of `n_head` heads this
chip holds `heads_held`, in the delta-rule layers and the attention layers
alike, and the feed-forward whole.

    N(x) = x * rsqrt(mean(x^2) + eps) * w            plain weight, starts at 1
    layer i:  h = x + N(Mixer_i(x));  y = h + N(MLP(h));  after the last
              layer N, then the untied head.  Mixer_i by `layer_types[i %
              len(layer_types)]`: "linear_attention" or "full_attention"
    MLP(h) = W_down(silu(W_gate h) * W_up h), no bias anywhere

    GDN:  q = silu(conv(x W_q)), k = silu(conv(x W_k)), v = silu(conv(x W_v))
          (causal depthwise, `conv_kernel` taps, no bias: one convolution
          over [q | k | v]);  beta = 2 sigmoid(x W_b) in (0, 2) under
          `allow_neg_eigval` (sigmoid alone without);  g = -exp(A_log) *
          softplus(x W_a + dt_bias) <= 0, one number a head;
          q = q / sqrt(sum q^2 + 1e-6) * key_dim^-0.5, k likewise unscaled;
          per head a state S [key_dim, value_dim], S_0 = 0, every token
              S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
              o_t = S^T q_t
          out = (o * rsqrt(mean(o^2) + eps) * w * silu(x W_g)) W_o   (the
          norm over one head's `value_dim` channels, one weight that wide)
          With beta > 1 a token's transition exp(g) (I - beta k k^T) has the
          eigenvalue exp(g) (1 - beta) < 0: what `allow_neg_eigval` turns on.
    Attn: q = N(x W_q), k = N(x W_k) with N over the WHOLE projection (all the
          heads held here) before the heads split, v = x W_v; heads of
          `head_dim`; rotary (rotate-half) at `rope_theta`, none where it is
          None (the published `rope_parameters.rope_theta` is null: the
          delta-rule layers and their convolutions carry the order); causal
          softmax attention at head_dim^-0.5; W_o
    loss = mean cross-entropy

Under a share every per-head projection has `heads_held` heads' columns
(`W_q`, `W_k` of a delta-rule layer `[d_model, heads_held * key_dim]`, `W_v`,
`W_g` `[d_model, heads_held * value_dim]`, `W_o` back, `W_a`, `W_b`
`[d_model, heads_held]`; the attention layer's four `heads_held * head_dim`
wide), the QK-norm's mean is over the held channels with a weight that wide
(the deployment's chips would exchange one sum of squares a token for q and
one for k; on one chip no exchange runs and no code stands in for it), and
the mixer's partial result is what the out-norm and the residual take on.
Which heads are held changes no computation on seeded weights.

Float32 under AMP: g and beta (`delta_rule_gates`, AMP_F32_OPS), and inside
their rules the decay's running sums, the l2-norms, the solve and the state
of `gated_delta_rule`, the convolution's sums and every norm's statistics.
Built from `fluid.layers` and `models/_decoder.py` only; parameter names are
fixed (`l0.gdn.q.w`, `.k.w`, `.v.w`, `.g.w`, `.a.w`, `.b.w`, `.conv.w`,
`.A_log`, `.dt_bias`, `.norm.w`, `.o.w`; `l3.attn.q.w`, `.q_norm.w`, `.k.w`,
`.k_norm.w`, `.v.w`, `.o.w`; `l0.mlp.gate.w`, `.up.w`, `.down.w`;
`l0.mixer_norm.w`, `l0.mlp_norm.w`; `embed.w`, `final_norm.w`, `head.w`) so
that a reference can be handed the same weights by name. Each part's ops
carry `fluid.name_scope("l<i>.gdn" | "l<i>.attn" | "l<i>.mlp")`.
"""

from __future__ import annotations

from .. import initializer as init
from .. import layers
from ..core.ir import name_scope
from ._decoder import (a_log_init, conv_heads, cross_entropy_fetches,
                       delta_rule_normed, dt_bias_init, embed, gated_mlp,
                       heads_first, layer_kinds, linear, merge_heads, norm,
                       qk_normed_projections, split_heads, token_feeds)

KINDS = ("linear_attention", "full_attention")
PERIOD = (KINDS[0],) * 3 + (KINDS[1],)      # the published `layer_types`


def _attention(x, n_head, heads, head_dim, rope_theta, rms_eps, name):
    width = heads * head_dim
    q, k, v = qk_normed_projections(x, width, rms_eps, name)
    share = {} if heads == n_head else {"heads_total": n_head}
    if rope_theta is None:      # nothing turns: the operands as they lie
        ctx = layers.fused_attention(
            *(split_heads(t, heads, head_dim) for t in (q, k, v)),
            causal=True, sm_scale=head_dim ** -0.5, layout="BTHD", **share)
        ctx = layers.reshape(ctx, shape=[0, 0, width])
    else:
        q, k = (layers.rotary_embedding(
            heads_first(split_heads(t, heads, head_dim)), theta=rope_theta)
            for t in (q, k))
        ctx = merge_heads(layers.fused_attention(
            q, k, heads_first(split_heads(v, heads, head_dim)), causal=True,
            sm_scale=head_dim ** -0.5, **share), width)
    return linear(ctx, x.shape[-1], name + ".o")


def _gated_delta_net(x, heads, key_dim, value_dim, conv_kernel, beta_scale,
                     rms_eps, name, seed):
    wide_k, wide_v = heads * key_dim, heads * value_dim
    qkv = layers.concat([linear(x, wide_k, name + ".q"),
                         linear(x, wide_k, name + ".k"),
                         linear(x, wide_v, name + ".v")], axis=2)
    q, k, v = conv_heads(qkv, heads, heads, key_dim, value_dim, conv_kernel,
                         name)
    z = split_heads(linear(x, wide_v, name + ".g"), heads, value_dim)
    o = delta_rule_normed(
        q, k, v, z, a=linear(x, heads, name + ".a"),
        b=linear(x, heads, name + ".b"), rms_eps=rms_eps, name=name,
        a_log=a_log_init(heads, seed),
        dt_bias=init.NumpyArrayInitializer(dt_bias_init(heads, seed)),
        beta_scale=beta_scale)
    return linear(layers.reshape(o, shape=[0, 0, wide_v]), x.shape[-1],
                  name + ".o")


def olmo_hybrid(vocab_size=100352, seq_len=4096, n_layer=32,
                layer_types=PERIOD, d_model=3840, d_ff=11008, n_head=30,
                heads_held=None, head_dim=128, key_dim=96, value_dim=192,
                conv_kernel=4, allow_neg_eigval=True, rope_theta=None,
                rms_eps=1e-6):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `layer_types` is repeated as a period
    over the `n_layer` layers. `heads_held` None holds all `n_head` heads of
    every mixer."""
    kinds = layer_kinds(n_layer, layer_types, KINDS)
    heads = n_head if heads_held is None else heads_held
    if not 0 < heads <= n_head:
        raise ValueError(f"heads_held is 1..{n_head}, got {heads_held}")
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    for i, kind in enumerate(kinds):
        name = f"l{i}"
        if kind == "full_attention":
            with name_scope(name + ".attn"):
                mixed = _attention(x, n_head, heads, head_dim, rope_theta,
                                   rms_eps, name + ".attn")
        else:
            with name_scope(name + ".gdn"):
                mixed = _gated_delta_net(
                    x, heads, key_dim, value_dim, conv_kernel,
                    2.0 if allow_neg_eigval else 1.0, rms_eps, name + ".gdn",
                    seed=i)
        x = layers.elementwise_add(
            x, norm(mixed, rms_eps, name + ".mixer_norm"))
        with name_scope(name + ".mlp"):
            fed = gated_mlp(x, d_ff, name + ".mlp")
        x = layers.elementwise_add(x, norm(fed, rms_eps, name + ".mlp_norm"))
    x = norm(x, rms_eps, "final_norm")
    logits = linear(x, vocab_size, "head")
    return ({"tokens": tokens, "labels": labels},
            cross_entropy_fetches(logits, labels, []))


def build(**kw):
    return olmo_hybrid(**kw)
