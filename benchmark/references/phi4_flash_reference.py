"""Phi-4-mini-flash (`model_type: phi4flash`: Mamba-1 mixers, differential
attention under a window and full, gated memory units on layer 16's scan
output, cross attention on layer 17's keys and values, LayerNorm, a gated MLP
in every layer, one table as embedding and head) in plain `jax.numpy`: the
forward pass, the loss and its gradients over a held run of the model's own
layers. What the program (`paddle_tpu/models/phi4_flash.py`) is compared
with.

Float32 throughout, every product at `jax.default_matmul_precision("highest")`
(a TPU's default float32 product rounds its inputs to bf16). No kernel and no
chunks: the recurrence runs TOKEN BY TOKEN (a `lax.scan` over t of the
state's update); the convolution is four shifted products plus its bias;
each softmax map is a masked softmax over the whole row with the key-value
pairs repeated by `jnp.repeat`; the table is used twice, as rows and
transposed. Weights come as a dict under the program's parameter names,
matrices stored `[in, out]` (D hidden, V the vocabulary rows held, F the
MLP's width, I = 2 D the inner width, N the state, R the rank of dt, K taps,
Dh the head size):

    embed.w [V, D]   final_norm.w, final_norm.b [D]
    l<i>.norm.w, .b [D]   l<i>.mlp_norm.w, .b [D]
    l<i>.mamba.in.w [D, 2 I]   columns [x | z]
    l<i>.mamba.conv.w [I, K]   l<i>.mamba.conv.b [I]
    l<i>.mamba.x.w [I, R + 2 N]   columns [dt_r | B | C]
    l<i>.mamba.dt.w [R, I]   l<i>.mamba.dt.b [I]
    l<i>.mamba.A_log [I, N]   l<i>.mamba.D [I]   l<i>.mamba.out.w [I, D]
    l<i>.attn.q.w [D, heads Dh], .q.b   l<i>.attn.k.w, .v.w [D, kv_heads Dh],
    .k.b, .v.b   l<i>.attn.o.w [heads Dh, D], .o.b
    l<i>.attn.lq1, .lk1, .lq2, .lk2 [Dh]   l<i>.attn.subln.w [2 Dh]
    l<i>.gmu.in.w [D, I]   l<i>.gmu.out.w [I, D]
    l<i>.cross.q.w, .q.b, .o.w, .o.b, .lq1 .. .subln.w   (no k, no v)
    l<i>.mlp.gate.w, l<i>.mlp.up.w [D, F]   l<i>.mlp.down.w [F, D]

The equations (the public `phi4flash` model code; SambaY, arXiv:2507.06607;
Mamba, arXiv:2312.00752; Differential Transformer, arXiv:2410.05258), n the
published depth 32, every second layer a Mamba layer (`mb_per_layer` 2):

    LN(x) = (x - mean) * rsqrt(var + eps) * w + b
    h_0 = E[tokens];  layer l: h = h + Mixer_l(LN_1(h));
    h = h + W_down(silu(W_gate m) * W_up m), m = LN_2(h)
    logits = LN_f(h_L) E^T;   loss = mean cross-entropy
    l even, l < n/2 + 2: Mamba-1;  l = n/2 also keeps its scan output y
    l even, l >= n/2 + 2: gated memory unit  (silu(u W_1) * y_{n/2}) W_2
    l odd, l < n/2: differential attention under `window`
    l = n/2 + 1: differential attention, full causal; its k, v are kept
    l odd, l > n/2 + 1: cross attention: own q and o, k and v of layer n/2 + 1
    Mamba-1:  [x | z] = u W_in;  x = silu(conv(x) + b_conv);  [dt_r | B | C] =
        x W_x;  dt = softplus(dt_r W_dt + b_dt);  A = -exp(A_log);  S_0 = 0:
        S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
        y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c];  out = (y silu(z)) W_out
    differential attention: q, k, v = u W + b; query pair j = heads 2j, 2j+1,
        key-value pair g = j // (query pairs / key-value pairs):
        a1 = softmax(q_{2j} k_{2g}^T / sqrt(Dh)) [v_{2g} | v_{2g+1}]
        a2 = softmax(q_{2j+1} k_{2g+1}^T / sqrt(Dh)) [v_{2g} | v_{2g+1}]
        key s visible to query t iff s <= t, and t - s < window on a window
        layer;  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l), lam0(l) =
        0.8 - 0.6 exp(-0.3 l) with l the PUBLISHED index
        o_j = (a1 - lam a2) rsqrt(mean((a1 - lam a2)^2) + eps) w_subln
              (1 - lam0(l));   out = concat_j(o_j) W_o + b

Departures from the public code: the fused `W_qkv` and `W_gate_up` are two
and three matrices of the same columns; dropouts (all 0 in the config) are
absent. The vocabulary is the slice the table has, and the layers are the
held run `first_layer .. first_layer + layers_held - 1`.

`dtype` other than float32 computes everything, the recurrence, the softmax
and the loss included, in that precision: the comparison's tolerance has to
refuse it. `q_block` computes the attention a block of queries at a time and
the head's cross-entropy a block of positions at a time; `token_block` runs
the recurrence as an outer scan over blocks of that many tokens under
`jax.checkpoint` around the scan over a block's tokens, so that a gradient
keeps a state a block, not a token; `remat` wraps each layer in
`jax.checkpoint`: all three are this reference's memory at published widths,
not its mathematics (a test holds that they change nothing). A layer is one
jitted function of its own weights and of what earlier layers kept.

`fault` plants one named fault (`FAULTS`): what a comparison with this
reference has to refuse. A test and `reference_check_phi4_flash.py` hold that
each moves at least one compared quantity past its limit.

Two copies of this file are kept byte-identical (a test holds them so): one
under `tests/`, one under `benchmark/references/`, since the benchmark stands
alone under its own directory.
"""

import functools
import math

import jax
import jax.numpy as jnp

FAULTS = {
    "decay_shared_over_states": "exp(dt_t[c] A[c, 0]) for every state n: one "
                                "decay a channel, Mamba-2's rule",
    "dt_without_bias": "dt = softplus(dt_r W_dt)",
    "no_skip": "y_t = sum_n S_t C_t: no D term",
    "b_c_swapped": "the state is written by C and read by B",
    "no_conv_bias": "x = silu(conv(x))",
    "state_reset_at_chunk": "the state starts from 0 again at every chunk's "
                            "first token (`chunk`: read by this fault alone)",
    "lambda_init_of_local_index": "lam0 of the layer's index in the held run "
                                  "(0, 1, ..), not of its published index",
    "no_subtraction": "o_j = RMSNorm(a1) (1 - lam0): the second map dropped",
    "no_subln": "o_j = (a1 - lam a2) (1 - lam0): no norm over the pair",
    "no_one_minus_lambda_init": "o_j = RMSNorm(a1 - lam a2): not scaled",
    "pairs_by_halves": "query pair j is heads j and j + pairs, not 2j and "
                       "2j + 1",
    "kv_pair_order": "query pair j reads key-value pair j % kv_pairs, not "
                     "j // group",
    "window_on_full_layer": "layer n/2 + 1 and the cross layers under the "
                            "window too",
    "window_off_by_one": "t - s <= window: one key too many",
    "cross_reads_window_layer_kv": "the cross layers read the k and v of the "
                                   "last window layer, not of layer n/2 + 1",
    "memory_after_gate": "the memory is y silu(z), not y",
    "memory_from_layer_14": "the memory is the scan output of the Mamba "
                            "layer before layer n/2",
    "gate_on_memory": "(u W_1 * silu(m)) W_2: the silu on the memory",
    "rms_for_layer_norm": "every LayerNorm an RMSNorm: no mean taken off, no "
                          "bias",
    "untied_head": "the head's table is a copy the embedding's gradient does "
                   "not reach: embed.w's gradient is the look-up's alone",
    "mlp_not_gated": "W_down(silu(W_gate m)): no product with W_up m",
}


def layer_kind(l, n_layer=32, mb_per_layer=2):
    half = n_layer // 2
    if l % mb_per_layer == 0:
        return "mamba" if l < half + 2 else "gmu"
    if l < half:
        return "window"
    return "full" if l == half + 1 else "cross"


def lambda_init(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def layer_norm(x, w, b, eps, fault=None):
    if fault == "rms_for_layer_norm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centred = x - mean
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * w + b


def selective_scan(x, dt, A, b, c, skip, token_block=None, reset=None):
    """The recurrence token by token: x, dt [B, T, I], A [I, N], b, c [B, T,
    N], skip [I] or None -> y [B, T, I]. `reset`: the state starts from 0
    again at every multiple of it (a planted fault's)."""
    bsz, t, inner = x.shape

    def token(S, inputs):
        i, x_t, dt_t, b_t, c_t = inputs
        if reset is not None:
            S = jnp.where(i % reset == 0, jnp.zeros_like(S), S)
        S = jnp.exp(dt_t[..., None] * A) * S \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return S, jnp.einsum("bcn,bn->bc", S, c_t)

    @jax.checkpoint
    def block(S, inputs):
        return jax.lax.scan(token, S, inputs)

    step = token_block or t
    seq = [jnp.arange(t).reshape(t // step, step)] + [
        jnp.moveaxis(v, 1, 0).reshape((t // step, step) + v.shape[:1]
                                      + v.shape[2:])
        for v in (x, dt, b, c)]
    S0 = jnp.zeros((bsz, inner, A.shape[1]), x.dtype)
    _, y = jax.lax.scan(block, S0, seq)                 # [T/step, step, B, I]
    y = jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)
    if skip is not None:
        y = y + skip * x
    return y


def causal_conv_silu(x, w, bias):
    """x [B, T, C], w [C, K], bias [C] or None: output t is `sum_j w[:, j]
    x[t - (K-1) + j]` with zeros before the start, plus the bias, then
    silu."""
    t, kernel = x.shape[1], w.shape[1]
    padded = jnp.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + t] * w[:, j] for j in range(kernel))
    if bias is not None:
        y = y + bias
    return jax.nn.silu(y)


def mamba(w, u, *, chunk=128, token_block=None, fault=None):
    """One Mamba-1 mixer (weights by their names after `l<i>.mamba.`) on the
    normed u [B, T, D]. Returns (the mixer's output, the scan's y, y after
    its gate)."""
    inner, state = w["A_log"].shape
    rank = w["dt.w"].shape[0]
    mixed = u @ w["in.w"]
    x, z = mixed[..., :inner], mixed[..., inner:]
    x = causal_conv_silu(x, w["conv.w"],
                         None if fault == "no_conv_bias" else w["conv.b"])
    proj = x @ w["x.w"]
    dt_r, b, c = proj[..., :rank], proj[..., rank:rank + state], \
        proj[..., rank + state:]
    if fault == "b_c_swapped":
        b, c = c, b
    dt = dt_r @ w["dt.w"]
    if fault != "dt_without_bias":
        dt = dt + w["dt.b"]
    dt = jax.nn.softplus(dt)
    A = -jnp.exp(w["A_log"])
    if fault == "decay_shared_over_states":
        A = jnp.broadcast_to(A[:, :1], A.shape)
    y = selective_scan(x, dt, A, b, c,
                       None if fault == "no_skip" else w["D"], token_block,
                       reset=chunk if fault == "state_reset_at_chunk"
                       else None)
    gated = y * jax.nn.silu(z)
    return gated @ w["out.w"], y, gated


def keys_values(w, u):
    """k, v [B, T, kv_heads * Dh] of a differential layer."""
    return u @ w["k.w"] + w["k.b"], u @ w["v.w"] + w["v.b"]


def softmax_map(q, k, v, *, scale, window=None, q_block=None, fault=None):
    """One causal softmax map: q, k [B, H, T, Dh], v [B, H, T, Dv] -> [B, H,
    T, Dv], a masked softmax over the whole row, `q_block` queries at a time.
    Key s is visible to query t iff s <= t and, under `window`, t - s <
    window (`window_off_by_one`: t - s <= window)."""
    t = q.shape[2]
    step = q_block or t
    blocks = []
    for first in range(0, t, step):
        end = min(first + step, t)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, first:end],
                            k[:, :, :end]) * scale
        behind = jnp.arange(first, end)[:, None] - jnp.arange(end)[None, :]
        visible = behind >= 0
        if window is not None:
            visible &= (behind <= window) \
                if fault == "window_off_by_one" else (behind < window)
        weights = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf),
                                 axis=-1)
        blocks.append(jnp.einsum("bhqk,bhkd->bhqd", weights, v[:, :, :end]))
    return jnp.concatenate(blocks, axis=2)


def differential_attention(w, u, k, v, *, n_head, n_kv_head, head_dim,
                           window, lam0, eps, q_block=None, fault=None):
    """One differential attention layer's mixer (weights by their names
    after `l<i>.attn.` or `l<i>.cross.`) on the normed u [B, T, D] and the
    k, v [B, T, kv_heads * Dh] it reads (its own or an earlier layer's)."""
    bsz, t, _ = u.shape
    pairs, kv_pairs = n_head // 2, n_kv_head // 2
    q = (u @ w["q.w"] + w["q.b"]).reshape(bsz, t, n_head, head_dim)
    if fault == "pairs_by_halves":
        q1, q2 = q[:, :, :pairs], q[:, :, pairs:]
    else:
        q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
    k = k.reshape(bsz, t, n_kv_head, head_dim)
    k1, k2 = k[:, :, 0::2], k[:, :, 1::2]               # [B, T, kv_pairs, Dh]
    v = v.reshape(bsz, t, kv_pairs, 2 * head_dim)
    group = pairs // kv_pairs

    def serve(m):       # -> [B, pairs, T, .]
        m = m.transpose(0, 2, 1, 3)
        if fault == "kv_pair_order":    # pair j reads kv pair j % kv_pairs
            return jnp.tile(m, (1, group, 1, 1))
        return jnp.repeat(m, group, axis=1)             # j // group

    k1, k2, v = serve(k1), serve(k2), serve(v)
    q1, q2 = q1.transpose(0, 2, 1, 3), q2.transpose(0, 2, 1, 3)
    a1, a2 = (softmax_map(qs, ks, v, scale=head_dim ** -0.5, window=window,
                          q_block=q_block, fault=fault)
              for qs, ks in ((q1, k1), (q2, k2)))       # [B, pairs, T, 2 Dh]
    lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) \
        - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + jnp.asarray(lam0, u.dtype)
    diff = a1 if fault == "no_subtraction" else a1 - lam * a2
    if fault != "no_subln":
        diff = diff * jax.lax.rsqrt(
            jnp.mean(diff * diff, -1, keepdims=True) + eps) * w["subln.w"]
    if fault != "no_one_minus_lambda_init":
        diff = diff * jnp.asarray(1.0 - lam0, u.dtype)
    ctx = diff.transpose(0, 2, 1, 3).reshape(bsz, t, n_head * head_dim)
    return ctx @ w["o.w"] + w["o.b"]


def gated_mlp(w, x, fault=None):
    hidden = jax.nn.silu(x @ w["gate.w"])
    if fault != "mlp_not_gated":
        hidden = hidden * (x @ w["up.w"])
    return hidden @ w["down.w"]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def layer(w, x, kept, kind, index, sizes):
    """One layer (its weights by their names after `l<i>.`) on x [B, T, D]:
    the mixer `kind`, then the MLP. `kept`: what earlier layers handed on
    ("memory", "k", "v" and, for two faults, "memory_before", "k_window",
    "v_window"); `index` = (published index, index in the held run). Returns
    x and what this layer keeps."""
    s = dict(sizes)
    eps, fault = s["norm_eps"], s["fault"]
    published, local = index
    sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                          if k.startswith(prefix)}
    u = layer_norm(x, w["norm.w"], w["norm.b"], eps, fault)
    half = s["n_layer"] // 2
    keeps = {}
    if kind == "mamba":
        mixed, y, gated = mamba(sub("mamba."), u, chunk=s["chunk"],
                                token_block=s["token_block"], fault=fault)
        memory = gated if fault == "memory_after_gate" else y
        if published == half:
            keeps["memory"] = memory
        elif fault == "memory_from_layer_14":
            keeps["memory_before"] = memory
    elif kind == "gmu":
        g = sub("gmu.")
        memory = kept["memory_before"] if fault == "memory_from_layer_14" \
            else kept["memory"]
        first = u @ g["in.w"]
        hidden = first * jax.nn.silu(memory) if fault == "gate_on_memory" \
            else jax.nn.silu(first) * memory
        mixed = hidden @ g["out.w"]
    else:
        a = sub("cross." if kind == "cross" else "attn.")
        if kind == "cross":
            k, v = (kept["k_window"], kept["v_window"]) \
                if fault == "cross_reads_window_layer_kv" \
                else (kept["k"], kept["v"])
        else:
            k, v = keys_values(a, u)
            if kind == "full":
                keeps.update(k=k, v=v)
            elif fault == "cross_reads_window_layer_kv":
                keeps.update(k_window=k, v_window=v)
        windowed = kind == "window" or fault == "window_on_full_layer"
        mixed = differential_attention(
            a, u, k, v, n_head=s["n_head"], n_kv_head=s["n_kv_head"],
            head_dim=s["head_dim"], window=s["window"] if windowed else None,
            lam0=lambda_init(local if fault == "lambda_init_of_local_index"
                             else published),
            eps=eps, q_block=s["q_block"], fault=fault)
    x = x + mixed
    m = layer_norm(x, w["mlp_norm.w"], w["mlp_norm.b"], eps, fault)
    return x + gated_mlp(sub("mlp."), m, fault), keeps


@functools.partial(jax.jit, static_argnums=(3,))
def head_ce(x, w_head, labels, block=None):
    """Cross-entropy per token [B, T] of `x W_head` against `labels`, `block`
    positions at a time (all at once by default)."""
    t = x.shape[1]
    step = block or t
    out = []
    for first in range(0, t, step):
        logits = x[:, first:first + step] @ w_head
        picked = jnp.take_along_axis(
            logits, labels[:, first:first + step, None], axis=-1)[..., 0]
        out.append(jax.nn.logsumexp(logits, axis=-1) - picked)
    return jnp.concatenate(out, axis=1)


def loss_parts(params, tokens, labels, *, n_layer=32, mb_per_layer=2,
               window=512, first_layer=0, layers_held=None, n_head=40,
               n_kv_head=20, head_dim=64, norm_eps=1e-5, chunk=128,
               dtype=jnp.float32, q_block=None, token_block=None, remat=False,
               last=None, fault=None):
    """The loss that is minimised and its parts: `loss` and `ce` (the mean
    cross-entropy, twice: nothing else is in the loss). With `last`, also
    `logits` on the final `last` positions, [B, last, V]."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault is one of {sorted(FAULTS)}, got {fault!r}")
    held = range(first_layer, n_layer if layers_held is None
                 else first_layer + layers_held)
    sizes = tuple(sorted(dict(
        n_layer=n_layer, window=window, n_head=n_head, n_kv_head=n_kv_head,
        head_dim=head_dim, norm_eps=norm_eps, chunk=chunk, q_block=q_block,
        token_block=token_block, fault=fault).items()))
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        apply = jax.checkpoint(layer, static_argnums=(3, 4, 5)) if remat \
            else layer
        x = jnp.take(p["embed.w"], tokens, axis=0)
        kept = {}
        for local, l in enumerate(held):
            prefix = f"l{l}."
            w = {k[len(prefix):]: v for k, v in p.items()
                 if k.startswith(prefix)}
            x, keeps = apply(w, x, kept, layer_kind(l, n_layer, mb_per_layer),
                             (l, local), sizes)
            kept = {**kept, **keeps}
        x = layer_norm(x, p["final_norm.w"], p["final_norm.b"], norm_eps,
                       fault)
        w_head = p["embed.w"].T
        if fault == "untied_head":
            w_head = jax.lax.stop_gradient(w_head)
        ce_of = jax.checkpoint(head_ce, static_argnums=(3,)) if remat \
            else head_ce
        ce = jnp.mean(ce_of(x, w_head, labels, q_block))
        out = {"loss": ce, "ce": ce}
        if last is not None:
            out["logits"] = x[:, -last:] @ w_head
        return out


def loss_and_grads(params, tokens, labels, wrt=None, **kw):
    """(parts, {name: gradient of `loss`}) for the parameters named in `wrt`
    (all of them by default)."""
    names = sorted(params) if wrt is None else list(wrt)

    def f(sub):
        parts = loss_parts({**params, **sub}, tokens, labels, **kw)
        return parts["loss"], parts

    (_, parts), grads = jax.value_and_grad(f, has_aux=True)(
        {n: jnp.asarray(params[n], jnp.float32) for n in names})
    return parts, grads
