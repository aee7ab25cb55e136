"""Phi-4-mini-flash (`model_type: phi4flash`; Phi-4-mini-flash-reasoning,
3.8B): a dense decoder-decoder hybrid (SambaY, arXiv:2507.06607). The first
half of the depth alternates Mamba-1 mixers (arXiv:2312.00752) with
differential attention (arXiv:2410.05258) under a window of 512; layer 16's
scan output and layer 17's keys and values (the one full-attention layer)
are kept, and the second half reads them: its even layers are gated memory
units on layer 16's scan, its odd layers cross attention with a query of
their own on layer 17's keys and values. LayerNorm with weight and bias, a
gated MLP in every layer, no positions anywhere, one table as embedding and
head. The public `phi4flash` model code.

    LN(x) = (x - mean) * rsqrt(var + eps) * w + b       eps 1e-5
    h_0 = E[tokens]                                     no multiplier
    layer l:  h = h + Mixer_l(LN_1(h));  h = h + W_down(silu(W_gate n) *
              W_up n), n = LN_2(h)                      no bias in the MLP
    logits = LN_f(h_L) E^T                              tie_word_embeddings
    loss = mean cross-entropy
    Mixer_l by the published rule (`layer_kind`; n = 32, `mb_per_layer` 2):
      l even, l < n/2 + 2    "mamba"   (l = n/2 = 16: its scan output y,
                                       before the gate, is kept as memory m)
      l even, l >= n/2 + 2   "gmu"     gated memory unit on m
      l odd,  l < n/2        "window"  differential attention, window 512
      l = n/2 + 1 = 17       "full"    differential attention, full causal;
                                       its k and v are kept
      l odd,  l > n/2 + 1    "cross"   own W_q and W_o, k and v of layer 17
    mamba (d_inner I = expand * D = 5120, N = 16, R = dt_rank = ceil(D / 16)
    = 160, 4 taps):
        [x | z] = u W_in;  x = silu(conv(x) + b_conv)    causal, depthwise
        [dt_r | B | C] = x W_x;  dt = softplus(dt_r W_dt + b_dt)    [T, I]
        A = -exp(A_log) [I, N];  per channel c and state n, float32, S_0 = 0:
          S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
          y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]
        out = (y * silu(z)) W_out;  layer n/2 also hands y on as m
    gmu:  out = (silu(u W_1) * m) W_2                   D -> I -> D
    differential attention (`n_head` 40 query heads, `n_kv_head` 20 key and
    20 value heads of 64; query pair j = heads 2j, 2j + 1; key-value pair g
    = j // 2 serves it: 20 query pairs over 10 key-value pairs):
        q = u W_q + b, k = u W_k + b, v = u W_v + b;  for pair j:
          a1 = softmax(q_{2j} k_{2g}^T / 8) [v_{2g} | v_{2g+1}]
          a2 = softmax(q_{2j+1} k_{2g+1}^T / 8) [v_{2g} | v_{2g+1}]
          causal, and `0 <= t - s < window` on a window layer
          lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)
          lam0(l) = 0.8 - 0.6 exp(-0.3 l), l the PUBLISHED index
          o_j = RMSNorm_128(a1 - lam a2; eps 1e-5, weight) * (1 - lam0(l))
        out = concat_j(o_j) W_o + b
    cross:  the same with q = u W_q + b of its own and k, v of layer 17.

`first_layer`, `layers_held`: the run of the model's OWN layers this program
holds (kinds, windows and lam0 follow the published index; parameter names
carry it). A run with a "gmu" layer and without layer n/2, or with a "cross"
layer and without layer n/2 + 1, raises: nothing stands in for a memory the
program does not compute.

Through the flash kernels each map is one `fused_attention` call of 20 heads
under "BHTD", q and k at 64 and v at 128: two calls a layer, the window
layer's under `window=`. The key-value pairs are repeated in the Program
(`serve_group`); layer 17's repeated k (even and odd heads apart) and v are
what the cross layers' calls read, so their gradients are sums over layers.

ASSUMED, the config having no key for them: Mamba-1's sizes (state 16, 4
taps, expand 2, dt_rank ceil(D / 16)) and its public initialisation (`A_log`
= log(1..N) a channel, `D` = 1, `dt.w` uniform(+-R^-0.5), `dt.b` the inverse
softplus of a log-uniform draw in `time_step` = (min, max, floor), the
convolution uniform(+-`conv_kernel`^-0.5) with bias 0); biases on q, k, v
and o and none elsewhere; the pairing of heads and of key-value pairs; `lq*`,
`lk*` normal(0, 0.1); every matrix and the table normal(0, 0.02). Float32
under AMP: the embedding's rows, the whole scan (`selective_scan`,
AMP_F32_OPS: dt, A, the decays, the state, y), the convolution's sums, every
norm's statistics, lam, the loss. Built from `fluid.layers` and
`models/_decoder.py` only; parameter names are fixed (`embed.w`,
`l14.norm.w`, `.b`, `l14.mamba.in.w`, `.conv.w`, `.conv.b`, `.x.w`, `.dt.w`,
`.dt.b`, `.A_log`, `.D`, `.out.w`, `l15.attn.q.w`, `.q.b`, `.k.w`, `.k.b`,
`.v.w`, `.v.b`, `.o.w`, `.o.b`, `.lq1`, `.lk1`, `.lq2`, `.lk2`, `.subln.w`,
`l18.gmu.in.w`, `.out.w`, `l19.cross.q.w`, `.q.b`, `.o.w`, `.o.b`, `.lq1` ..
`.subln.w`, `l<i>.mlp_norm.w`, `.b`, `l<i>.mlp.gate.w`, `.up.w`, `.down.w`,
`final_norm.w`, `.b`) so that a reference can be handed the same weights by
name. A layer's ops carry `fluid.name_scope("l<i>.mamba" | ".attn" | ".gmu" |
".cross")` and `fluid.name_scope("l<i>.mlp")`, each sublayer's norm inside
its scope.
"""

from __future__ import annotations

import math

from .. import initializer as init
from .. import layers
from ..core.ir import name_scope
from ..param_attr import ParamAttr
from ._decoder import (cross_entropy_fetches, dt_bias_init, embed, gated_mlp,
                       heads_first, last, linear, merge_heads, out_linear,
                       serve_group, tied_head, token_feeds, w)

# layer kind -> its scope and parameter prefix
SCOPES = {"mamba": "mamba", "window": "attn", "full": "attn", "gmu": "gmu",
          "cross": "cross"}


def layer_kind(l, n_layer=32, mb_per_layer=2):
    """The mixer of the model's layer `l` by the published rule (module
    docstring)."""
    half = n_layer // 2
    if l % mb_per_layer == 0:
        return "mamba" if l < half + 2 else "gmu"
    if l < half:
        return "window"
    return "full" if l == half + 1 else "cross"


def lambda_init(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def layer_norm(x, eps, name):
    return layers.layer_norm(x, begin_norm_axis=2, epsilon=eps,
                             param_attr=ParamAttr(name=name + ".w"),
                             bias_attr=ParamAttr(name=name + ".b"))


def biased(x, size, name):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=w(name + ".w"),
                     bias_attr=ParamAttr(name=name + ".b"))


def mamba1_mixer(x, inner, state, dt_rank, conv_kernel, time_step, chunk,
                 name, seed):
    """A Mamba-1 mixer on the normed x `[B, T, D]` (module docstring).
    Returns the mixer's output and the scan's y `[B, T, inner]` before its
    gate."""
    mixed = linear(x, 2 * inner, name + ".in")
    z = last(mixed, inner, 2 * inner)
    u = layers.causal_conv1d(
        last(mixed, 0, inner), conv_kernel,
        param_attr=ParamAttr(
            name=name + ".conv.w",
            initializer=init.UniformInitializer(-conv_kernel ** -0.5,
                                                conv_kernel ** -0.5)),
        bias_attr=ParamAttr(name=name + ".conv.b"))
    proj = linear(u, dt_rank + 2 * state, name + ".x")
    dt_raw = layers.fc(
        input=last(proj, 0, dt_rank), size=inner, num_flatten_dims=2,
        bias_attr=False, param_attr=ParamAttr(
            name=name + ".dt.w",
            initializer=init.UniformInitializer(-dt_rank ** -0.5,
                                                dt_rank ** -0.5)))
    y = layers.selective_scan(
        u, dt_raw, last(proj, dt_rank, dt_rank + state),
        last(proj, dt_rank + state, dt_rank + 2 * state), state, chunk=chunk,
        a_log_attr=ParamAttr(name=name + ".A_log"),
        dt_bias_attr=ParamAttr(
            name=name + ".dt.b", initializer=init.NumpyArrayInitializer(
                dt_bias_init(inner, seed, *time_step))),
        d_attr=ParamAttr(name=name + ".D"))
    return out_linear(layers.swiglu(z, y), x.shape[-1], name + ".out"), y


def gated_memory(x, memory, name):
    """`(silu(x W_1) * m) W_2` on another layer's scan output m."""
    hidden = layers.swiglu(linear(x, memory.shape[-1], name + ".in"), memory)
    return linear(hidden, x.shape[-1], name + ".out")


def _halves(t, pairs, head_dim):
    """`[B, T, pairs * 2 * head_dim]` -> the pairs' first and second heads,
    each `[B, pairs, T, head_dim]`."""
    t = layers.reshape(t, shape=[0, 0, pairs, 2, head_dim])
    return [heads_first(layers.reshape(
        layers.slice(t, axes=[3], starts=[i], ends=[i + 1]),
        shape=[0, 0, pairs, head_dim])) for i in (0, 1)]


def served_keys_values(x, n_head, n_kv_head, head_dim, name):
    """(k of the pairs' first heads, k of their second heads, v of the pairs)
    as a differential layer's two flash calls read them: `[B, n_head / 2, T,
    head_dim]` twice and `[B, n_head / 2, T, 2 * head_dim]`, key-value pair
    j // group serving query pair j."""
    pairs, kv_pairs = n_head // 2, n_kv_head // 2
    k1, k2 = _halves(biased(x, n_kv_head * head_dim, name + ".k"), kv_pairs,
                     head_dim)
    v = heads_first(layers.reshape(
        biased(x, n_kv_head * head_dim, name + ".v"),
        shape=[0, 0, kv_pairs, 2 * head_dim]))
    return (serve_group(k1, pairs, kv_pairs, head_dim),
            serve_group(k2, pairs, kv_pairs, head_dim),
            serve_group(v, pairs, kv_pairs, 2 * head_dim))


def differential_attention(x, kv, n_head, head_dim, window, lam0, eps, name):
    """The two softmax maps of every query pair on the served `kv` (this
    layer's or layer 17's), their difference under lam, its norm and scale,
    and W_o (module docstring)."""
    pairs = n_head // 2
    k1, k2, v = kv
    q1, q2 = _halves(biased(x, n_head * head_dim, name + ".q"), pairs,
                     head_dim)
    a1, a2 = (layers.fused_attention(q, k, v, causal=True,
                                     sm_scale=head_dim ** -0.5, window=window)
              for q, k in ((q1, k1), (q2, k2)))

    def dot_exp(a, b):      # exp(lq . lk), [1]
        vec = [layers.create_parameter(
            [head_dim], "float32", name=f"{name}.{n}",
            default_initializer=init.NormalInitializer(0.0, 0.1))
            for n in (a, b)]
        return layers.exp(layers.reduce_sum(
            layers.elementwise_mul(vec[0], vec[1]), keep_dim=True))

    lam = layers.scale(layers.elementwise_sub(dot_exp("lq1", "lk1"),
                                              dot_exp("lq2", "lk2")),
                       bias=float(lam0))
    diff = layers.elementwise_sub(a1, layers.elementwise_mul(a2, lam))
    normed = layers.scale(
        layers.rms_norm(diff, epsilon=eps,
                        param_attr=ParamAttr(name=name + ".subln.w")),
        scale=1.0 - float(lam0))
    return biased(merge_heads(normed, n_head * head_dim), x.shape[-1],
                  name + ".o")


def phi4_flash(vocab_size=200064, seq_len=4096, n_layer=32, mb_per_layer=2,
               window=512, first_layer=0, layers_held=None, d_model=2560,
               d_ff=10240, n_head=40, n_kv_head=20, head_dim=64, ssm_state=16,
               conv_kernel=4, expand=2, dt_rank=None,
               time_step=(0.001, 0.1, 1e-4), norm_eps=1e-5, chunk=128):
    """Returns (feeds, fetches) of one training step on `[batch, seq_len]`
    token ids and next-token labels, over the model's own layers
    `first_layer .. first_layer + layers_held - 1` (all `n_layer` by
    default)."""
    held = range(first_layer, first_layer + (n_layer - first_layer
                                             if layers_held is None
                                             else layers_held))
    if not held or held[0] < 0 or held[-1] >= n_layer:
        raise ValueError(f"the held layers lie in 0..{n_layer - 1}, got "
                         f"{first_layer}..{held[-1] if held else None}")
    kinds = {l: layer_kind(l, n_layer, mb_per_layer) for l in held}
    half = n_layer // 2
    for kind, source in (("gmu", half), ("cross", half + 1)):
        readers = [l for l in held if kinds[l] == kind]
        if readers and source not in held:
            raise ValueError(
                f"layers {readers} ({kind}) read what layer {source} keeps, "
                f"and the held layers {held[0]}..{held[-1]} are without it")
    inner = expand * d_model
    dt_rank = dt_rank or -(-d_model // 16)
    tokens, labels = token_feeds(seq_len)
    x = embed(tokens, vocab_size, d_model)
    memory = kept = None
    for l in held:
        kind, name = kinds[l], f"l{l}"
        prefix = f"{name}.{SCOPES[kind]}"
        with name_scope(prefix):
            normed = layer_norm(x, norm_eps, name + ".norm")
            if kind == "mamba":
                part, y = mamba1_mixer(normed, inner, ssm_state, dt_rank,
                                       conv_kernel, time_step, chunk, prefix,
                                       seed=l)
                if l == half:
                    memory = y
            elif kind == "gmu":
                part = gated_memory(normed, memory, prefix)
            else:
                kv = kept if kind == "cross" else served_keys_values(
                    normed, n_head, n_kv_head, head_dim, prefix)
                if kind == "full":
                    kept = kv
                part = differential_attention(
                    normed, kv, n_head, head_dim,
                    window if kind == "window" else None, lambda_init(l),
                    norm_eps, prefix)
            x = layers.elementwise_add(x, part)
        with name_scope(name + ".mlp"):
            x = layers.elementwise_add(
                x, gated_mlp(layer_norm(x, norm_eps, name + ".mlp_norm"),
                             d_ff, name + ".mlp"))
    logits = tied_head(layer_norm(x, norm_eps, "final_norm"), vocab_size)
    return ({"tokens": tokens, "labels": labels},
            cross_entropy_fetches(logits, labels, []))


def build(**kw):
    return phi4_flash(**kw)
