"""Ouro: a looped decoder-only LM. One stack of layers is applied
`total_ut_steps` times under the same weights, every pass ends in the one
final norm, the one head and an exit gate, and the loss is the expected
cross-entropy under the gates' exit distribution less an entropy bonus (Zhu
et al. 2025, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741, stage I; config of ByteDance/Ouro-2.6B).

    h_0 = Embed(tokens)
    for t = 1..R, the SAME L layers each time, same weights:
        for l = 1..L:   h = h + N2_l(Attn_l(N1_l(h)))     # "sandwich": an
                        h = h + N4_l(MLP_l(N3_l(h)))      #  RMSNorm before and
                                                          #  after each sub-layer
        g_t = Norm_f(h)     # the one final RMSNorm, at the end of every pass;
                            # the next pass starts from g_t (assumed)
        logits_t = g_t W_head                   # the one untied head, R times
        lambda_t = sigmoid(g_t w_gate + b_gate) # exit gate, per token
    Attn: q, k, v = x W_q, x W_k, x W_v (no bias, no QK-norm); rotary
          (rotate-half) on q and k per head; causal softmax attention at
          head_dim^-0.5; W_o.         MLP: W_down(silu(x W_gate) * (x W_up))
    exit distribution per token:
        p_t = lambda_t * prod_{j<t}(1 - lambda_j)  for t < R,
        p_R = prod_{j<R}(1 - lambda_j)
    loss = mean over tokens of [ sum_t p_t * ce_t  -  beta * H(p) ],
        ce_t = cross-entropy of logits_t against the label,
        H(p) = - sum_t p_t log p_t

The exit distribution is built from the gate's logit z_t in log space, which
is the same function and has no 0 * log 0:
`log lambda_t = logsigmoid(z_t)`, `log(1 - lambda_t) = logsigmoid(-z_t)`,
`log p_t = log lambda_t + sum_{j<t} log(1 - lambda_j)`, `p_t = exp(log p_t)`.
The gate of the last pass is not part of the loss (p_R takes what is left)
and is not built.

The loop is unrolled in the Program: every layer's `ParamAttr(name=...)` is
repeated across the R passes, `LayerHelper.create_parameter` returns the
existing parameter for a repeated name, so the scope holds L sets of layer
weights and `append_backward` sums R gradient contributions into each. Built
from `fluid.layers` only; parameter names are fixed (`l0.q.w`,
`l0.attn_post_norm.w`, `exit_gate.w`, ...) so that a reference can be handed
the same weights by name.
"""

from __future__ import annotations

from .. import layers
from ..core.ir import name_scope
from ..param_attr import ParamAttr
from ._decoder import (embed, gated_mlp, heads_first, linear, merge_heads,
                       norm, split_heads, token_feeds, w)


def _attention(x, d_model, n_head, rope_theta, name):
    d_head = d_model // n_head

    def heads(t):
        return heads_first(split_heads(t, n_head, d_head))

    q = layers.rotary_embedding(heads(linear(x, d_model, name + ".q")),
                                theta=rope_theta)
    k = layers.rotary_embedding(heads(linear(x, d_model, name + ".k")),
                                theta=rope_theta)
    v = heads(linear(x, d_model, name + ".v"))
    ctx = layers.fused_attention(q, k, v, causal=True, sm_scale=d_head ** -0.5)
    return linear(merge_heads(ctx, d_model), d_model, name + ".o")


def _layer(x, name, d_model, n_head, d_ff, rope_theta, rms_eps):
    attn = _attention(norm(x, rms_eps, name + ".attn_norm"), d_model, n_head,
                      rope_theta, name)
    x = layers.elementwise_add(x, norm(attn, rms_eps,
                                       name + ".attn_post_norm"))
    mlp = gated_mlp(norm(x, rms_eps, name + ".mlp_norm"), d_ff, name)
    return layers.elementwise_add(x, norm(mlp, rms_eps,
                                          name + ".mlp_post_norm"))


def ouro(vocab_size=49152, seq_len=4096, n_layer=48, d_model=2048, n_head=16,
         d_ff=5632, n_loop=4, rope_theta=1e6, rms_eps=1e-6, beta=0.1):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels. `exit_probs` is the mean of p_t over
    the tokens, `[n_loop]`; `logits` are the last pass's."""
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    ce, log_p = [], []          # per pass, float32 [batch, seq_len, 1]
    log_stay = None             # sum_{j<t} log(1 - lambda_j)
    for t in range(1, n_loop + 1):
        with name_scope(f"ut_step{t}"):
            for i in range(n_layer):
                x = _layer(x, f"l{i}", d_model, n_head, d_ff, rope_theta,
                           rms_eps)
            x = norm(x, rms_eps, "final_norm")
            logits = linear(x, vocab_size, "head")
            ce.append(layers.softmax_with_cross_entropy(logits=logits,
                                                        label=labels))
            if t == n_loop:     # no gate of its own: it takes what is left
                break
            z = layers.exit_gate(x, param_attr=w("exit_gate.w"),
                                 bias_attr=ParamAttr(name="exit_gate.b"))
            log_exit = layers.logsigmoid(z)
            log_p.append(log_exit if log_stay is None else
                         layers.elementwise_add(log_stay, log_exit))
            stay = layers.logsigmoid(layers.scale(z, scale=-1.0))
            log_stay = stay if log_stay is None else \
                layers.elementwise_add(log_stay, stay)

    if n_loop == 1:             # no gate: p_1 = 1, H = 0
        expected_ce = layers.mean(ce[0])
        entropy = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        exit_probs = layers.fill_constant(shape=[1], dtype="float32",
                                          value=1.0)
        loss = expected_ce
    else:
        log_p.append(log_stay)
        p = [layers.exp(lp) for lp in log_p]
        expected_ce = layers.mean(layers.sums(
            [layers.elementwise_mul(pt, ct) for pt, ct in zip(p, ce)]))
        entropy = layers.scale(layers.mean(layers.sums(
            [layers.elementwise_mul(pt, lp) for pt, lp in zip(p, log_p)])),
            scale=-1.0)
        exit_probs = layers.concat([layers.mean(pt) for pt in p], axis=0)
        loss = layers.elementwise_sub(expected_ce,
                                      layers.scale(entropy, scale=beta))
    return ({"tokens": tokens, "labels": labels},
            {"loss": loss, "expected_ce": expected_ce, "entropy": entropy,
             "exit_probs": exit_probs, "logits": logits})


def build(**kw):
    return ouro(**kw)
