"""What two or more of the decoder models (`olmoe`, `ouro`, `qwen3_next`,
`kanana2`, `mellum2`, `trinity`, `keye_vl2`, `nemotron_h`, `ling3`,
`olmo_hybrid`, `granite_hybrid`, `lfm2_moe`) build the same way, written
once: named weights and projections, the token feeds, the heads-first reshape
and its inverse, a key-value head serving its group of query heads, the gated
MLP, the routed half of an expert layer, the period of layer kinds, the
grouped-query block, q and k normed over the whole projection before the
heads split (`olmoe`, `olmo_hybrid`), a gated-delta-rule layer from its
convolution to its gated norm (`qwen3_next`, `olmo_hybrid`), a Mamba-2 mixer
from its in projection to its out projection and grouped-query attention
without positions (`nemotron_h`, `granite_hybrid`), `embed`'s other half, the
head that reads the table `embed` made (`granite_hybrid`, `lfm2_moe`), and
the losses. Nothing here asks which model calls it (latent attention, built
by `kanana2` and `ling3`, takes the one thing they differ in, a head-wise
gate, as a parameter; the Mamba-2 mixer and the unrotated attention take
their out projection's initialiser, which Nemotron-H makes smaller, and the
attention its softmax scale, which Granite 4.0-H publishes; `noaux_router`
the epsilon of its renormalisation, which LFM2 publishes): a model whose form
differs keeps its own. Each model keeps its mixer's composition, its layer
loop, its defaults and `build`. Built from `fluid.layers` only; parameter
names are the caller's.
"""

from __future__ import annotations

import numpy as np

from .. import initializer as init
from .. import layers
from ..param_attr import ParamAttr

INIT_STD = 0.02

KINDS = ("sliding_attention", "full_attention")
PERIOD = (KINDS[0],) * 3 + (KINDS[1],)      # the published `layer_types`


def normal():
    return init.NormalInitializer(0.0, INIT_STD)


def w(name):
    return ParamAttr(name=name, initializer=normal())


def linear(x, size, name):
    return layers.fc(input=x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=w(name + ".w"))


def out_linear(x, size, name, initializer=None):
    """A sublayer's projection back into the residual stream, `name.w`,
    under the caller's initialiser (normal at INIT_STD by default;
    Nemotron-H's start smaller)."""
    return layers.fc(input=x, size=size, num_flatten_dims=2, bias_attr=False,
                     param_attr=ParamAttr(name=name + ".w",
                                          initializer=initializer or normal()))


def norm(x, rms_eps, name, zero_centered=False):
    return layers.rms_norm(x, epsilon=rms_eps, zero_centered=zero_centered,
                           param_attr=ParamAttr(name=name + ".w"))


def last(x, first, end):
    """x[..., first:end]."""
    axis = len(x.shape) - 1
    return layers.slice(x, axes=[axis], starts=[first], ends=[end])


def token_feeds(seq_len):
    """The `[batch, seq_len]` token ids and next-token labels of a step."""
    return [layers.data(name=name, shape=[-1, seq_len], dtype="int64",
                        append_batch_size=False)
            for name in ("tokens", "labels")]


def embed(tokens, vocab_size, d_model):
    return layers.embedding(tokens, size=[vocab_size, d_model],
                            param_attr=w("embed.w"))


def tied_head(x, vocab_size):
    """Logits `x E^T` against the table `embed` made (`embed.w` `[vocab,
    width]`): the one parameter read twice, by `lookup_table` as rows and
    here transposed, so its gradient is the sum of a row scatter and a dense
    product (`core/backward.py`'s fan-in sum) and no `head.w` exists. Under
    AMP the product casts the table to bf16 (`matmul`, AMP_BF16_OPS) while
    the look-up reads the float32 rows."""
    block = x.block.program.global_block()
    table = block.var("embed.w")
    if tuple(table.shape) != (vocab_size, x.shape[-1]):
        raise ValueError(f"the tied head reads embed.w as [{vocab_size}, "
                         f"{x.shape[-1]}], found {tuple(table.shape)}")
    return layers.matmul(x, table, transpose_y=True)


def split_heads(t, n, head_dim):    # [B, T, n * Dh] -> [B, T, n, Dh]
    return layers.reshape(t, shape=[0, 0, n, head_dim])


def heads_first(t):                 # [B, T, n, Dh] <-> [B, n, T, Dh]
    return layers.transpose(t, perm=[0, 2, 1, 3])


def merge_heads(ctx, width):        # [B, n, T, Dh] -> [B, T, n * Dh]
    return layers.reshape(heads_first(ctx), shape=[0, 0, width])


def serve_group(t, n_head, n_kv_head, head_dim):
    """[B, kv, T, Dh] -> [B, heads, T, Dh]: key-value head h // group
    serves query head h (the heads are repeated in the Program)."""
    group = n_head // n_kv_head
    t = layers.expand(layers.unsqueeze(t, axes=[2]),
                      expand_times=[1, 1, group, 1, 1])
    return layers.reshape(t, shape=[0, n_head, -1, head_dim])


def gated_mlp(x, width, name):
    hidden = layers.swiglu(linear(x, width, name + ".gate"),
                           linear(x, width, name + ".up"))
    return linear(hidden, x.shape[-1], name + ".down")


def expert_rows(x, n_expert, top_k, d_expert, name, router=None,
                experts=None):
    """x [B, T, D] as rows [B * T, D] through the router (`name.router.w`)
    and the experts (`name.experts.*`). `router` and `experts` are the
    keywords of `layers.moe_router` and `layers.moe_experts` as the caller
    states them. Returns the routed rows and the routing."""
    tokens = layers.reshape(x, shape=[-1, x.shape[-1]])
    routing = layers.moe_router(tokens, n_expert, top_k,
                                param_attr=w(name + ".router.w"),
                                **(router or {}))
    routed = layers.moe_experts(tokens, routing, n_expert, d_expert,
                                param_attr=normal(), name=name + ".experts",
                                **(experts or {}))
    return routed, routing


def routed_experts(x, seq_len, n_expert, top_k, d_expert, name, router=None,
                   experts=None):
    """The routed half of an expert layer: `expert_rows` and back to
    [B, T, D]. A model adds its own shared expert (one whose Program holds
    it before the reshape back calls `expert_rows`)."""
    routed, routing = expert_rows(x, n_expert, top_k, d_expert, name, router,
                                  experts)
    return layers.reshape(routed, shape=[-1, seq_len, x.shape[-1]]), routing


def noaux_router(name, bias_update_rate, scaling_factor, n_group=None,
                 topk_group=None, norm_eps=1e-20):
    """`router` of `routed_experts` for DeepSeek-V3's `noaux_tc` routing:
    sigmoid scores, the choice moved by a selection bias (`name.router.bias`)
    that the step itself rewrites, the chosen scores renormalised (their sum
    plus `norm_eps`: LFM2 publishes 1e-6) and scaled; among the experts of
    the `topk_group` best of `n_group` groups where those are given (one
    group otherwise)."""
    return dict(norm_topk_prob=True, score_func="sigmoid",
                bias_attr=ParamAttr(name=name + ".router.bias"),
                bias_update_rate=bias_update_rate, norm_eps=norm_eps,
                scaling_factor=scaling_factor, n_group=n_group,
                topk_group=topk_group)


def noaux_experts(x, seq_len, n_expert, top_k, d_expert, d_shared,
                  first_expert, experts_held, scaling_factor,
                  bias_update_rate, name, n_group=None, topk_group=None):
    """An expert layer under `noaux_router`: this chip's share of the routed
    experts plus a shared gated MLP of width `d_shared`, no gate on it."""
    routed, routing = routed_experts(
        x, seq_len, n_expert, top_k, d_expert, name,
        router=noaux_router(name, bias_update_rate, scaling_factor, n_group,
                            topk_group),
        experts=dict(first_expert=first_expert, experts_held=experts_held))
    out = layers.elementwise_add(routed,
                                 gated_mlp(x, d_shared, name + ".shared"))
    return out, routing


def latent_attention(x, n_head, kv_rank, qk_nope_dim, qk_rope_dim,
                      v_head_dim, rope_theta, rms_eps, name, head_gate=False):
    """Latent attention (MLA) as `models/kanana2.py`'s docstring writes it
    out: keys and values out of one `kv_rank`-wide normed row a token, one
    interleaved rotary key head for all query heads, heads of `qk_nope_dim +
    qk_rope_dim` over values of `v_head_dim` through `fused_attention`.
    `head_gate`: the context of head h times `sigmoid(x W_gate)_h`, one
    scalar a head and token (`name.gate.w`, `[width, n_head]`), before W_o
    (`models/ling3.py`'s latent layers; Kanana-2's have none)."""
    qk_dim = qk_nope_dim + qk_rope_dim

    def rotary(t):
        return layers.rotary_embedding(t, theta=rope_theta, interleaved=True)

    q = heads_first(split_heads(linear(x, n_head * qk_dim, name + ".q"),
                                n_head, qk_dim))
    q = layers.concat([last(q, 0, qk_nope_dim),
                       rotary(last(q, qk_nope_dim, qk_dim))], axis=3)
    kv_a = linear(x, kv_rank + qk_rope_dim, name + ".kv_a")
    latent = norm(last(kv_a, 0, kv_rank), rms_eps, name + ".kv_norm")
    # one rotary key head, [B, 1, T, rope], serves every query head
    k_rope = rotary(layers.unsqueeze(last(kv_a, kv_rank,
                                          kv_rank + qk_rope_dim), axes=[1]))
    kv = heads_first(split_heads(
        linear(latent, n_head * (qk_nope_dim + v_head_dim), name + ".kv_b"),
        n_head, qk_nope_dim + v_head_dim))
    k = layers.concat(
        [last(kv, 0, qk_nope_dim),
         layers.expand(k_rope, expand_times=[1, n_head, 1, 1])], axis=3)
    v = last(kv, qk_nope_dim, qk_nope_dim + v_head_dim)
    ctx = layers.fused_attention(q, k, v, causal=True,
                                 sm_scale=qk_dim ** -0.5)
    if head_gate:
        gate = layers.sigmoid(linear(x, n_head, name + ".gate"))
        ctx = heads_first(layers.elementwise_mul(
            heads_first(ctx), layers.unsqueeze(gate, axes=[3])))
    return linear(merge_heads(ctx, n_head * v_head_dim), x.shape[-1],
                  name + ".o")


def qk_normed_projections(x, width, rms_eps, name):
    """q, k, v `[B, T, width]` of an OLMo attention layer: three projections
    without bias, q and k each through an RMSNorm with a learned weight over
    the WHOLE `width` (every head this chip holds), before the heads split
    (`name.q.w`, `name.q_norm.w`, ...). Under a share of the heads `width`
    is what is held, and so is the norm's mean."""
    q = norm(linear(x, width, name + ".q"), rms_eps, name + ".q_norm")
    k = norm(linear(x, width, name + ".k"), rms_eps, name + ".k_norm")
    return q, k, linear(x, width, name + ".v")


def a_log_init(heads, seed):
    """log of uniform(0, 16), as the public gated-delta-rule code initialises
    `A_log`; drawn here so that the startup program holds the values."""
    draws = np.random.RandomState(seed).uniform(0.0, 16.0, size=heads)
    return init.NumpyArrayInitializer(
        np.log(np.maximum(draws, 1e-3)).astype("float32"))


def conv_heads(qkv, n_key_head, n_value_head, key_dim, value_dim,
               conv_kernel, name):
    """`[q | k | v]` `[B, T, channels]` of a gated-delta-rule layer through
    its causal depthwise convolution and silu (`name.conv.w`, no bias), then
    apart and by heads: q, k `[B, T, key heads, key_dim]`, v `[B, T, value
    heads, value_dim]`."""
    wide_k, wide_v = n_key_head * key_dim, n_value_head * value_dim
    qkv = layers.causal_conv1d(
        qkv, conv_kernel, param_attr=ParamAttr(
            name=name + ".conv.w",
            initializer=init.UniformInitializer(-conv_kernel ** -0.5,
                                                conv_kernel ** -0.5)))
    return (split_heads(last(qkv, 0, wide_k), n_key_head, key_dim),
            split_heads(last(qkv, wide_k, 2 * wide_k), n_key_head, key_dim),
            split_heads(last(qkv, 2 * wide_k, 2 * wide_k + wide_v),
                        n_value_head, value_dim))


def delta_rule_normed(q, k, v, z, a, b, rms_eps, name, a_log, dt_bias=None,
                      beta_scale=1.0):
    """The gated delta rule on `conv_heads`' q, k, v under the gates that
    `a`, `b` `[B, T, value heads]` make with the learned `name.A_log` and
    `name.dt_bias` (initialisers `a_log`, `dt_bias`: 1 by default), then the
    layer's output norm over a head, gated by `silu(z)` (`name.norm.w`)."""
    o = layers.gated_delta_rule(
        q, k, v, a=a, b=b, beta_scale=beta_scale,
        a_log_attr=ParamAttr(name=name + ".A_log", initializer=a_log),
        dt_bias_attr=ParamAttr(name=name + ".dt_bias", initializer=dt_bias))
    return layers.gated_rms_norm(o, z, epsilon=rms_eps,
                                 param_attr=ParamAttr(name=name + ".norm.w"))


def dt_bias_init(heads, seed, dt_min=0.001, dt_max=0.1, dt_floor=1e-4):
    """The public Mamba-2 draw: dt log-uniform in [dt_min, dt_max], floored,
    and the bias its inverse softplus; drawn here so that the startup
    program holds the values."""
    u = np.random.RandomState(seed).uniform(size=heads)
    dt = np.exp(u * (np.log(dt_max) - np.log(dt_min)) + np.log(dt_min))
    dt = np.maximum(dt, dt_floor)
    return (dt + np.log(-np.expm1(-dt))).astype("float32")


def mamba_mixer(x, n_head, head_dim, n_groups, state, conv_kernel, chunk,
                rms_eps, time_step, name, seed, out_init=None):
    """A Mamba-2 mixer on the normed x `[B, T, D]`, as `models/nemotron_h.py`
    writes it out: `[z | xs B C | dt_raw] = x W_in` (`name.in.w`), the causal
    depthwise convolution with a bias and silu over `[xs | B | C]`
    (`name.conv.w` / `.b`), `ssd_scan` at `chunk` over `n_head` heads of
    `head_dim` reading `n_groups` groups of B and C of width `state`
    (`name.A_log`, `.dt_bias` drawn from `time_step` = (min, max, floor) and
    `seed`, `.D`), the gate before the norm over each group of `n_head *
    head_dim / n_groups` lanes (`name.norm.w`), and `name.out.w` back to D,
    initialised by `out_init` (normal at INIT_STD by default)."""
    inner, bc = n_head * head_dim, n_groups * state
    mixed = linear(x, 2 * inner + 2 * bc + n_head, name + ".in")
    z = last(mixed, 0, inner)
    u = layers.causal_conv1d(
        last(mixed, inner, 2 * inner + 2 * bc), conv_kernel,
        param_attr=ParamAttr(
            name=name + ".conv.w",
            initializer=init.UniformInitializer(-conv_kernel ** -0.5,
                                                conv_kernel ** -0.5)),
        bias_attr=ParamAttr(name=name + ".conv.b"))
    dt_raw = last(mixed, 2 * inner + 2 * bc, 2 * inner + 2 * bc + n_head)
    xs = layers.reshape(last(u, 0, inner), shape=[0, 0, n_head, head_dim])
    b = layers.reshape(last(u, inner, inner + bc),
                       shape=[0, 0, n_groups, state])
    c = layers.reshape(last(u, inner + bc, inner + 2 * bc),
                       shape=[0, 0, n_groups, state])
    y = layers.ssd_scan(
        xs, b, c, dt_raw, chunk=chunk,
        a_log_attr=ParamAttr(
            name=name + ".A_log", initializer=init.NumpyArrayInitializer(
                np.log(np.arange(1, n_head + 1)).astype("float32"))),
        dt_bias_attr=ParamAttr(
            name=name + ".dt_bias", initializer=init.NumpyArrayInitializer(
                dt_bias_init(n_head, seed, *time_step))),
        d_attr=ParamAttr(name=name + ".D"))
    y = layers.gated_rms_norm(
        layers.reshape(y, shape=[0, 0, inner]), z, epsilon=rms_eps,
        param_attr=ParamAttr(name=name + ".norm.w"), gate_first=True,
        group_size=inner // n_groups)
    return out_linear(y, x.shape[-1], name + ".out", out_init)


def layer_kinds(n_layer, layer_types=PERIOD, kinds=KINDS):
    """The kind of each of `n_layer` layers: `layer_types` (a list of
    `kinds`) repeated as a period."""
    unknown = sorted(set(layer_types) - set(kinds))
    if unknown or not layer_types:
        raise ValueError(f"layer_types holds {kinds}, got {layer_types!r}")
    return [layer_types[i % len(layer_types)] for i in range(n_layer)]


def grouped_attention(x, n_head, n_kv_head, head_dim, rope_theta,
                      rope_scaling, window, rms_eps, name, kept=None,
                      topk=None):
    """Causal softmax attention of `n_head` query heads over `n_kv_head`
    key-value heads, q and k normed over a head and turned by rotary.
    `window`: a causal band of that many keys (None: all). `kept`: the keys
    each query keeps (`layers.dsa_select`), of at most `topk` a row, where a
    layer chooses them."""
    def turned(t, n, norm_name):
        t = heads_first(norm(split_heads(t, n, head_dim), rms_eps, norm_name))
        return layers.rotary_embedding(t, theta=rope_theta,
                                       scaling=rope_scaling)

    q = turned(linear(x, n_head * head_dim, name + ".q"), n_head,
               name + ".q_norm")
    k = turned(linear(x, n_kv_head * head_dim, name + ".k"), n_kv_head,
               name + ".k_norm")
    v = heads_first(split_heads(
        linear(x, n_kv_head * head_dim, name + ".v"), n_kv_head, head_dim))
    ctx = layers.fused_attention(
        q, serve_group(k, n_head, n_kv_head, head_dim),
        serve_group(v, n_head, n_kv_head, head_dim), causal=True,
        sm_scale=head_dim ** -0.5, window=window, kept=kept, topk=topk)
    return linear(merge_heads(ctx, n_head * head_dim), x.shape[-1],
                  name + ".o")


def unrotated_attention(x, n_head, n_kv_head, head_dim, sm_scale, name,
                        out_init=None):
    """Causal softmax attention of `n_head` query heads over `n_kv_head`
    key-value heads with NO positions and no QK-norm (`models/nemotron_h.py`,
    `models/granite_hybrid.py`): the scores times `sm_scale` as the caller
    states it, `name.o.w` initialised by `out_init` (normal at INIT_STD by
    default)."""
    def heads(t, n):            # [B, T, n * Dh] -> [B, n, T, Dh]
        return heads_first(split_heads(t, n, head_dim))

    q = heads(linear(x, n_head * head_dim, name + ".q"), n_head)
    k = heads(linear(x, n_kv_head * head_dim, name + ".k"), n_kv_head)
    v = heads(linear(x, n_kv_head * head_dim, name + ".v"), n_kv_head)
    ctx = layers.fused_attention(
        q, serve_group(k, n_head, n_kv_head, head_dim),
        serve_group(v, n_head, n_kv_head, head_dim), causal=True,
        sm_scale=sm_scale)
    return out_linear(merge_heads(ctx, n_head * head_dim), x.shape[-1],
                      name + ".o", out_init)


def mean_cross_entropy(logits, labels):
    return layers.mean(layers.softmax_with_cross_entropy(logits=logits,
                                                         label=labels))


def tokens_per_expert(routings):
    """[expert layers, n_expert]: every layer's assignments per expert."""
    return layers.stack([r["tokens_per_expert"] for r in routings], axis=0)


def cross_entropy_fetches(logits, labels, routings):
    """The fetches of a step whose loss is the mean cross-entropy alone."""
    ce = mean_cross_entropy(logits, labels)
    fetches = {"loss": ce, "ce": ce, "logits": logits}
    if routings:
        fetches["tokens_per_expert"] = tokens_per_expert(routings)
    return fetches


def load_balance(routings, n_expert, top_k):
    """`n_expert * sum_e f_e * P_e` over all layers' router rows taken
    together, as the `olmoe` code does: f_e = assignments to e / rows, P_e =
    mean probability of e, over all `n_expert` experts wherever they
    live."""
    counts = layers.sums([layers.cast(r["tokens_per_expert"], "float32")
                          for r in routings])
    rows = layers.scale(layers.reduce_sum(counts), scale=1.0 / top_k)
    share = layers.elementwise_div(counts, rows)
    share.stop_gradient = True      # counts: nothing to differentiate
    mean_prob = layers.scale(
        layers.sums([layers.reduce_mean(r["probs"], dim=0)
                     for r in routings]), scale=1.0 / len(routings))
    return layers.scale(
        layers.reduce_sum(layers.elementwise_mul(share, mean_prob)),
        scale=float(n_expert))


def balanced_loss(logits, labels, routings, n_expert, top_k, aux_coef):
    """The fetches of a step: mean cross-entropy plus `aux_coef` times the
    load-balance term."""
    ce = mean_cross_entropy(logits, labels)
    balance = load_balance(routings, n_expert, top_k)
    loss = layers.sums([ce, layers.scale(balance, scale=aux_coef)])
    return {"loss": loss, "ce": ce, "load_balance": balance,
            "logits": logits, "tokens_per_expert": tokens_per_expert(routings)}
