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

from .. import initializer as init
from .. import layers
from ..core.ir import name_scope
from ..param_attr import ParamAttr

INIT_STD = 0.02

KINDS = ("sliding_attention", "full_attention")
PERIOD = (KINDS[0],) * 3 + (KINDS[1],)      # the published `layer_types`

# `rope_parameters.full_attention` of the published config
YARN = {"factor": 16.0, "original_max_position_embeddings": 8192,
        "beta_fast": 32.0, "beta_slow": 1.0,
        "attention_factor": 1.2772588722239782}


def _normal():
    return init.NormalInitializer(0.0, INIT_STD)


def _w(name):
    return ParamAttr(name=name, initializer=_normal())


def _linear(x, size, name):
    return layers.fc(input=x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=_w(name + ".w"))


def _norm(x, rms_eps, name):
    return layers.rms_norm(x, epsilon=rms_eps,
                           param_attr=ParamAttr(name=name + ".w"))


def layer_kinds(n_layer, layer_types=PERIOD):
    """The kind of each of `n_layer` layers: `layer_types` (a list of
    `KINDS`) repeated as a period."""
    unknown = sorted(set(layer_types) - set(KINDS))
    if unknown or not layer_types:
        raise ValueError(f"layer_types holds {KINDS}, got {layer_types!r}")
    return [layer_types[i % len(layer_types)] for i in range(n_layer)]


def _attention(x, n_head, n_kv_head, head_dim, rope_theta, rope_scaling,
               window, rms_eps, name, kept=None, topk=None):
    """`kept`: the keys each query keeps (`layers.dsa_select`), of at most
    `topk` a row, where a layer chooses them (`models/keye_vl2.py`)."""
    def heads(t, n):        # [B, T, n * Dh] -> [B, n, T, Dh]
        return layers.reshape(t, shape=[0, 0, n, head_dim])

    def turned(t, norm_name):
        t = layers.transpose(_norm(t, rms_eps, norm_name), perm=[0, 2, 1, 3])
        return layers.rotary_embedding(t, theta=rope_theta,
                                       scaling=rope_scaling)

    q = turned(heads(_linear(x, n_head * head_dim, name + ".q"), n_head),
               name + ".q_norm")
    k = turned(heads(_linear(x, n_kv_head * head_dim, name + ".k"),
                     n_kv_head), name + ".k_norm")
    v = layers.transpose(
        heads(_linear(x, n_kv_head * head_dim, name + ".v"), n_kv_head),
        perm=[0, 2, 1, 3])

    def serve_group(t):     # [B, kv, T, Dh] -> [B, heads, T, Dh], h // group
        group = n_head // n_kv_head
        t = layers.expand(layers.unsqueeze(t, axes=[2]),
                          expand_times=[1, 1, group, 1, 1])
        return layers.reshape(t, shape=[0, n_head, -1, head_dim])

    ctx = layers.fused_attention(q, serve_group(k), serve_group(v),
                                 causal=True, sm_scale=head_dim ** -0.5,
                                 window=window, kept=kept, topk=topk)
    ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                         shape=[0, 0, n_head * head_dim])
    return _linear(ctx, x.shape[-1], name + ".o")


def _sparse_experts(x, seq_len, n_expert, top_k, d_expert, first_expert,
                    experts_held, norm_topk_prob, name):
    d_model = x.shape[-1]
    tokens = layers.reshape(x, shape=[-1, d_model])
    routing = layers.moe_router(tokens, n_expert, top_k,
                                param_attr=_w(name + ".router.w"),
                                norm_topk_prob=norm_topk_prob)
    routed = layers.moe_experts(
        tokens, routing, n_expert, d_expert, param_attr=_normal(),
        name=name + ".experts", first_expert=first_expert,
        experts_held=experts_held)
    return layers.reshape(routed, shape=[-1, seq_len, d_model]), routing


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
    tokens = layers.data(name="tokens", shape=[-1, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data(name="labels", shape=[-1, seq_len], dtype="int64",
                         append_batch_size=False)

    x = layers.embedding(tokens, size=[vocab_size, d_model],
                         param_attr=_w("embed.w"))
    routings = []
    kinds = layer_kinds(n_layer, layer_types)
    for i, kind in enumerate(kinds):
        name = f"l{i}"
        sliding = kind == "sliding_attention"
        with name_scope(name + (".swa" if sliding else ".attn")):
            mixed = _attention(
                _norm(x, rms_eps, name + ".in_norm"), n_head, n_kv_head,
                head_dim, rope_theta, None if sliding else rope_scaling,
                sliding_window if sliding else None, rms_eps, name + ".attn")
        x = layers.elementwise_add(x, mixed)
        with name_scope(name + ".moe"):
            moe, routing = _sparse_experts(
                _norm(x, rms_eps, name + ".post_norm"), seq_len, n_expert,
                top_k, d_expert, first_expert, experts_held, norm_topk_prob,
                name)
        x = layers.elementwise_add(x, moe)
        routings.append(routing)
    x = _norm(x, rms_eps, "final_norm")
    logits = _linear(x, vocab_size, "head")
    return ({"tokens": tokens, "labels": labels},
            _balanced_loss(logits, labels, routings, n_expert, top_k,
                           aux_coef))


def _balanced_loss(logits, labels, routings, n_expert, top_k, aux_coef):
    """The fetches of a step: mean cross-entropy plus `aux_coef` times the
    load-balance term over all layers' router rows."""
    n_layer = len(routings)
    ce = layers.mean(layers.softmax_with_cross_entropy(logits=logits,
                                                       label=labels))
    # all layers' router rows taken together, as `models/olmoe.py` does:
    # f_e = assignments to e / rows, P_e = mean probability of e, over all
    # `n_expert` experts wherever they live
    counts = layers.sums([layers.cast(r["tokens_per_expert"], "float32")
                          for r in routings])
    rows = layers.scale(layers.reduce_sum(counts), scale=1.0 / top_k)
    share = layers.elementwise_div(counts, rows)
    share.stop_gradient = True      # counts: nothing to differentiate
    mean_prob = layers.scale(
        layers.sums([layers.reduce_mean(r["probs"], dim=0)
                     for r in routings]), scale=1.0 / n_layer)
    load_balance = layers.scale(
        layers.reduce_sum(layers.elementwise_mul(share, mean_prob)),
        scale=float(n_expert))
    loss = layers.sums([ce, layers.scale(load_balance, scale=aux_coef)])
    tokens_per_expert = layers.stack(
        [r["tokens_per_expert"] for r in routings], axis=0)
    return {"loss": loss, "ce": ce, "load_balance": load_balance,
            "logits": logits, "tokens_per_expert": tokens_per_expert}


def build(**kw):
    return mellum2(**kw)
