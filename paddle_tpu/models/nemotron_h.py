"""Nemotron-H (`model_type: nemotron_h`; NVIDIA-Nemotron-3-Nano-30B-A3B): a
decoder-only LM whose layers are ONE sublayer each, a mixer or a feed-forward
part alone, laid out by a pattern string over `M` (a Mamba-2 state-space
mixer), `E` (a sparse-expert layer) and `*` (softmax attention). Block as in
Nemotron-H (arXiv:2504.03624) and the public `nemotron_h` model code, mixer
as in Mamba-2 (Dao & Gu 2024, arXiv:2405.21060), router as DeepSeek-V3's
(`models/kanana2.py`). Built for ONE CHIP'S SHARE of an expert-parallel
deployment: the router chooses among all `n_expert` experts, this chip holds
`experts_held` of them from `first_expert` on and computes their part.

    N_w(x) = x * rsqrt(mean(x^2) + eps) * w      every RMSNorm: a plain
             weight that starts at 1, eps 1e-5
    layer l:  x = x + F_l(N(x));  F_l is ONE of M, E, * by `layer_pattern[l]`;
              after the last layer N, then the untied head
    M:  [z | u | dt_raw] = x W_in      widths d_inner | d_inner + 2 G N | H
                                       (d_inner = H * P), no bias
        u = silu(conv(u) + b_conv)     depthwise, causal, `conv_kernel` taps
        [xs | B | C] = u               d_inner (H heads of P) | G x N | G x N
        dt = softplus(dt_raw + dt_bias)   float32, a head;  a = -exp(A_log) dt
                                          (`time_step_limit` (0, inf): no clamp)
        S_t = exp(a_t) S_{t-1} + dt_t xs_t B_t^T    S [P, N] a head, float32,
                                          S_0 = 0; head h reads group h // (H/G)
        y_t = S_t C_t + D xs_t            D a head; in chunks of `chunk` tokens
        y = N_w(y * silu(z))              the gate BEFORE the norm, the mean
                                          over each group of d_inner / G
        out = y W_out
    *:  q = x W_q (`n_head` heads), k = x W_k, v = x W_v (`n_kv_head` heads),
        no bias, NO rotary, no QK-norm; causal softmax(q k^T head_dim^-0.5) v,
        key-value head g serves query heads g * group .. (g + 1) * group - 1
        (repeated in the Program); out = ctx W_o
    E:  s = sigmoid(x W_r) in float32 over all experts; idx = top-k of s + b
        (one group: n_group 1), b the selection bias [n_expert], float32,
        NOT a parameter of the loss;  w = s[idx] / (sum_k s[idx] + 1e-20)
        (`norm_topk_prob`) * routed_scaling_factor
        routed = sum over the chosen experts held here of w_k *
        down_e(relu(up_e x)^2), dropless: TWO matrices an expert, no gate
        out = routed + down_s(relu(up_s x)^2)    the shared expert, `d_shared`
    loss = mean cross-entropy (no auxiliary term: `noaux_tc` routing)
    after the forward pass of a step, per E layer, outside the gradient:
        c_e = assignments to expert e in this step (all experts);
        b_e <- b_e + bias_update_rate * sign(mean(c) - c_e)   (b from 0)

ASSUMED, the config having no key for them: no positions in the attention
layers (Nemotron-H and the public code: `rope_theta` is read by nothing); the
gate before the grouped norm (the public `MambaRMSNormGated`,
`norm_before_gate` false); the order of `W_in`'s columns; the public Mamba-2
initialisation (`A_log` = log(1..H), `D` = 1, `dt_bias` the inverse softplus
of a log-uniform draw in [`time_step_min`, `time_step_max`] floored at
`time_step_floor`, the convolution's weight uniform(+-`conv_kernel`^-0.5),
its bias 0); weights normal(0, 0.02), the out projections of every sublayer
divided by sqrt(`rescale_layers`) (`rescale_prenorm_residual`: the PUBLISHED
depth, whatever `layer_pattern` holds here); the bias rule's form and rate
(DeepSeek-V3, arXiv:2412.19437, section 2.1.2; 0.001). Float32 under AMP: the
router (`moe_router`, AMP_F32_OPS), `b` and its update, dt and a (`ssd_gates`,
AMP_F32_OPS), and inside their rules the running sums, decays and state of
`ssd_scan`, the convolution's sums and every norm's statistics. Built from
`fluid.layers` only; parameter names are fixed (`l0.norm.w`, `l0.mamba.in.w`,
`l0.mamba.conv.w`, `l0.mamba.conv.b`, `l0.mamba.A_log`, `l0.mamba.dt_bias`,
`l0.mamba.D`, `l0.mamba.norm.w`, `l0.mamba.out.w`, `l5.attn.q.w`,
`l1.router.w`, `l1.router.bias`, `l1.experts.up.w`, `l1.experts.down.w`,
`l1.shared.up.w`, ...) so that a reference can be handed the same weights by
name. A layer's ops (its norm included) carry `fluid.name_scope("l<i>.mamba"
| "l<i>.attn" | "l<i>.moe")`.
"""

from __future__ import annotations

from .. import initializer as init
from .. import layers
from ..core.ir import name_scope
from ._decoder import (INIT_STD, cross_entropy_fetches, embed, expert_rows,
                       linear, mamba_mixer, noaux_router, norm, out_linear,
                       token_feeds, unrotated_attention)

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
NEMOTRON_3_NANO = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _out_init(rescale_layers):
    return init.NormalInitializer(0.0, INIT_STD / rescale_layers ** 0.5)


def _sparse_experts(x, seq_len, n_expert, top_k, d_expert, d_shared,
                    first_expert, experts_held, routed_scaling_factor,
                    bias_update_rate, rescale_layers, name):
    d_model = x.shape[-1]
    routed, routing = expert_rows(
        x, n_expert, top_k, d_expert, name,
        router=noaux_router(name, bias_update_rate, routed_scaling_factor),
        experts=dict(down_attr=_out_init(rescale_layers), gated=False,
                     activation="relu2", first_expert=first_expert,
                     experts_held=experts_held))
    shared = out_linear(
        layers.relu2(linear(x, d_shared, name + ".shared.up")), d_model,
        name + ".shared.down", _out_init(rescale_layers))
    out = layers.elementwise_add(
        layers.reshape(routed, shape=[-1, seq_len, d_model]), shared)
    return out, routing


def nemotron_h(vocab_size=131072, seq_len=2048, layer_pattern=NEMOTRON_3_NANO,
               d_model=2688, mamba_heads=64, mamba_head_dim=64, n_groups=8,
               ssm_state=128, conv_kernel=4, chunk=128,
               time_step=(0.001, 0.1, 1e-4), n_head=32, n_kv_head=2,
               head_dim=128, n_expert=128, top_k=6, d_expert=1856,
               d_shared=3712, routed_scaling_factor=2.5,
               bias_update_rate=0.001, first_expert=0, experts_held=None,
               rms_eps=1e-5, rescale_layers=52):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `layer_pattern`: one of `M`, `E`, `*` a
    layer. `time_step`: (min, max, floor) of the draw behind `dt_bias`.
    `rescale_layers`: the depth the out projections' initial scale follows
    (the published 52). `experts_held` None holds all `n_expert` experts."""
    unknown = set(layer_pattern) - set(KINDS)
    if unknown or not layer_pattern:
        raise ValueError(f"a layer pattern is a string over M, E and *, got "
                         f"{layer_pattern!r}")
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    routings = []
    for i, kind in enumerate(layer_pattern):
        name = f"l{i}"
        with name_scope(f"{name}.{KINDS[kind]}"):
            normed = norm(x, rms_eps, name + ".norm")
            if kind == "M":
                part = mamba_mixer(
                    normed, mamba_heads, mamba_head_dim, n_groups, ssm_state,
                    conv_kernel, chunk, rms_eps, time_step, name + ".mamba",
                    seed=i, out_init=_out_init(rescale_layers))
            elif kind == "*":
                part = unrotated_attention(
                    normed, n_head, n_kv_head, head_dim, head_dim ** -0.5,
                    name + ".attn", out_init=_out_init(rescale_layers))
            else:
                part, routing = _sparse_experts(
                    normed, seq_len, n_expert, top_k, d_expert, d_shared,
                    first_expert, experts_held, routed_scaling_factor,
                    bias_update_rate, rescale_layers, name)
                routings.append(routing)
        x = layers.elementwise_add(x, part)
    x = norm(x, rms_eps, "final_norm")
    logits = linear(x, vocab_size, "head")
    return ({"tokens": tokens, "labels": labels},
            cross_entropy_fetches(logits, labels, routings))


def build(**kw):
    return nemotron_h(**kw)
