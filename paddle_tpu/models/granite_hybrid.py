"""Granite 4.0-H (`model_type: granitemoehybrid` with `num_local_experts` 0;
granite-4.0-h-micro): a dense decoder-only LM whose every layer is a mixer
AND a gated feed-forward, the mixer a Mamba-2 state-space mixer or, one layer
in ten, grouped softmax attention without positions, under four scalar
multipliers and one table that is both embedding and head. The public
`granitemoehybrid` model code; the mixer as in Mamba-2 (Dao & Gu 2024,
arXiv:2405.21060).

    N(x) = x * rsqrt(mean(x^2) + eps) * w       every RMSNorm: a plain weight
                                                that starts at 1, eps 1e-5
    h_0 = embedding_multiplier * E[tokens]                              (12)
    layer l, its mixer by `layer_types[l]` ("mamba" | "attention"):
        h = h + residual_multiplier * Mixer_l(N(h))                   (0.22)
        h = h + residual_multiplier * W_down(silu(W_gate n) * W_up n),
                                                 n = N(h): the shared_mlp
    logits = N(h_L) E^T / logits_scaling        tie_word_embeddings    (8)
    loss = mean cross-entropy
    mamba:  [z | xBC | dt_raw] = x W_in      d_inner | d_inner + 2 G N | H
                                             (d_inner = H * P), no bias
        xBC = silu(conv(xBC) + b_conv)       causal, depthwise, `conv_kernel`
                                             taps, WITH a bias
        [xs | B | C] = xBC                   H heads of P | G x N | G x N
        dt = softplus(dt_raw + dt_bias), a = -exp(A_log) dt    float32, a head
        S_t = exp(a_t) S_{t-1} + dt_t xs_t B_t^T;  y_t = S_t C_t + D xs_t
                                             S [P, N] a head, S_0 = 0; head h
                                             reads group h // (H / G): with
                                             the published ONE group all 64
                                             heads read the same B and C; in
                                             chunks of `chunk` tokens (256)
        y = N(y * silu(z))                   the gate BEFORE the norm, the
                                             mean over each group of d_inner /
                                             G lanes: all 4096 at one group
        out = y W_out
    attention:  q = x W_q (`n_head` heads), k = x W_k, v = x W_v (`n_kv_head`
        heads), no bias, NO rotary (`position_embedding_type` "nope"); causal
        softmax(q k^T * attention_multiplier) v: the scale is the published
        number (0.015625 = 1/64), NOT head_dim^-0.5; key-value head g serves
        query heads g * group .. (g + 1) * group - 1 (repeated in the
        Program); out = ctx W_o

Set to 1, each multiplier is another function: all four are build arguments
and `layers.scale` ops in the Program (the softmax's goes to
`fused_attention(sm_scale=)`). `tie_embeddings` false builds an untied
`head.w` instead (what a test compares the tied gradient with).

ASSUMED, the config having no key for them: the order of `W_in`'s columns,
the gate before the norm (`models/nemotron_h.py`'s, the public Mamba-2
code's), the public Mamba-2 initialisation (`A_log` = log(1..H), `D` = 1,
`dt_bias` the inverse softplus of a log-uniform draw in `time_step` = (min,
max, floor), the convolution's weight uniform(+-`conv_kernel`^-0.5), its
bias 0), every matrix and the table normal(0, 0.02). Float32 under AMP: the
embedding's rows and their multiplier (`lookup_table` reads the float32
table; the head's product casts it to bf16), dt and a (`ssd_gates`,
AMP_F32_OPS), and inside their rules the running sums, decays and state of
`ssd_scan`, the convolution's sums, every norm's statistics, the loss. Built
from `fluid.layers` and `models/_decoder.py` only; parameter names are fixed
(`embed.w`, `l0.norm.w`, `l0.mamba.in.w`, `l0.mamba.conv.w`,
`l0.mamba.conv.b`, `l0.mamba.A_log`, `l0.mamba.dt_bias`, `l0.mamba.D`,
`l0.mamba.norm.w`, `l0.mamba.out.w`, `l5.attn.q.w`, `.k.w`, `.v.w`, `.o.w`,
`l0.mlp_norm.w`, `l0.mlp.gate.w`, `.up.w`, `.down.w`, `final_norm.w`; no
`head.w` when tied) so that a reference can be handed the same weights by
name. A layer's ops carry `fluid.name_scope("l<i>.mamba" | "l<i>.attn")` and
`fluid.name_scope("l<i>.mlp")`, each sublayer's norm and scale inside its
scope.
"""

from __future__ import annotations

from .. import layers
from ..core.ir import name_scope
from ._decoder import (cross_entropy_fetches, embed, gated_mlp, linear,
                       mamba_mixer, norm, tied_head, token_feeds,
                       unrotated_attention)

KINDS = {"mamba": "mamba", "attention": "attn"}     # layer type -> its scope
# the published `layer_types`: attention at 5, 15, 25, 35 of 40
GRANITE_4_0_H = tuple("attention" if i % 10 == 5 else "mamba"
                      for i in range(40))


def granite_hybrid(vocab_size=100352, seq_len=2048, layer_types=GRANITE_4_0_H,
                   d_model=2048, d_ff=8192, mamba_heads=64, mamba_head_dim=64,
                   n_groups=1, ssm_state=128, conv_kernel=4, chunk=256,
                   time_step=(0.001, 0.1, 1e-4), n_head=32, n_kv_head=8,
                   head_dim=64, embedding_multiplier=12.0,
                   residual_multiplier=0.22, attention_multiplier=0.015625,
                   logits_scaling=8.0, tie_embeddings=True, rms_eps=1e-5):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `layer_types`: "mamba" or "attention" a
    layer. `time_step`: (min, max, floor) of the draw behind `dt_bias`."""
    unknown = sorted(set(layer_types) - set(KINDS))
    if unknown or not layer_types:
        raise ValueError(f"layer_types holds {sorted(KINDS)}, got "
                         f"{list(layer_types)!r}")
    tokens, labels = token_feeds(seq_len)
    x = layers.scale(embed(tokens, vocab_size, d_model),
                     scale=float(embedding_multiplier))

    def add(x, part):           # the residual takes a scaled branch on
        return layers.elementwise_add(
            x, layers.scale(part, scale=float(residual_multiplier)))

    for i, kind in enumerate(layer_types):
        name = f"l{i}"
        with name_scope(f"{name}.{KINDS[kind]}"):
            normed = norm(x, rms_eps, name + ".norm")
            if kind == "mamba":
                part = mamba_mixer(
                    normed, mamba_heads, mamba_head_dim, n_groups, ssm_state,
                    conv_kernel, chunk, rms_eps, time_step, name + ".mamba",
                    seed=i)
            else:
                part = unrotated_attention(
                    normed, n_head, n_kv_head, head_dim,
                    float(attention_multiplier), name + ".attn")
            x = add(x, part)
        with name_scope(name + ".mlp"):
            x = add(x, gated_mlp(norm(x, rms_eps, name + ".mlp_norm"), d_ff,
                                 name + ".mlp"))
    x = norm(x, rms_eps, "final_norm")
    logits = tied_head(x, vocab_size) if tie_embeddings \
        else linear(x, vocab_size, "head")
    logits = layers.scale(logits, scale=1.0 / float(logits_scaling))
    return ({"tokens": tokens, "labels": labels},
            cross_entropy_fetches(logits, labels, []))


def build(**kw):
    return granite_hybrid(**kw)
