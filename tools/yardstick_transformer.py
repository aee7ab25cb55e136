"""Hand-written JAX transformer-base — the framework-overhead yardstick.

Same architecture, precision policy, and step semantics as
`paddle_tpu.models.transformer.build(seq_len=256, fused_attention=False)`
+ Adam(1e-3): embeddings*sqrt(d)+sinusoid, 6 enc / 6 dec post-LN blocks,
unfused attention (bf16 matmuls, bf16 max-subtracted softmax), dropout 0.1
via uint8 bit-compare (threshold on 8 random bits — the same trick
`ops/nn.py::_dropout` uses), f32 master params, f32
softmax-cross-entropy loss.

Purpose (docs/PERF.md): this is what an expert would write *without* the
Program/IR parity layer; the delta between its step time and the
framework's step time is the true cost of the layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _dropout(key, x, rate):
    # counter-hash bits (murmur3 fmix32 over the element index), not
    # jax.random.bits: threefry is a ~100-op block chain per tensor and
    # dominates VPU time at transformer scale; the hash fuses into the
    # surrounding chain (same trick as paddle_tpu/ops/nn.py:_hash_bits8)
    if not rate:
        return x
    t = round((1.0 - rate) * 256.0) - 1
    if t < 0:                      # rate ~ 1: drop everything
        return jnp.zeros_like(x)
    thresh = np.uint8(min(255, t))
    kd = jax.random.key_data(key).reshape(-1).astype(jnp.uint32)
    seed = kd[0] ^ (kd[-1] * np.uint32(0x9E3779B9))
    idx, stride = None, 1
    for d in range(x.ndim - 1, -1, -1):
        term = jax.lax.broadcasted_iota(jnp.uint32, x.shape, d)
        if stride != 1:
            term = term * np.uint32(stride)
        idx = term if idx is None else idx + term
        stride *= int(x.shape[d])
    h = idx * np.uint32(2654435761) + seed
    h = (h ^ (h >> 16)) * np.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * np.uint32(0xC2B2AE35)
    keep = ((h ^ (h >> 16)) & np.uint32(0xFF)).astype(jnp.uint8) <= thresh
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros((), x.dtype))


def _layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _attn(key, q_in, kv_in, p, rate, causal, n_head):
    d_model = q_in.shape[-1]
    d_head = d_model // n_head
    b16 = jnp.bfloat16

    def proj(x, w):
        return (x.astype(b16) @ w.astype(b16))

    def heads(x):  # [B,T,D] -> [B,H,T,dh]
        b, t, _ = x.shape
        return x.reshape(b, t, n_head, d_head).transpose(0, 2, 1, 3)

    q, k, v = heads(proj(q_in, p["wq"])), heads(proj(kv_in, p["wk"])), \
        heads(proj(kv_in, p["wv"]))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d_head ** -0.5)
    if causal:
        t = scores.shape[-1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask, scores, jnp.asarray(-1e9, scores.dtype))
    w = jax.nn.softmax(scores, axis=-1)          # bf16, max-subtracted
    w = _dropout(key, w, rate)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    b, h, t, dh = ctx.shape
    merged = ctx.transpose(0, 2, 1, 3).reshape(b, t, h * dh)
    return merged @ p["wo"].astype(b16)


def _ffn(x, p):
    b16 = jnp.bfloat16
    h = jax.nn.relu(x.astype(b16) @ p["w1"].astype(b16) + p["b1"].astype(b16))
    return h @ p["w2"].astype(b16) + p["b2"].astype(b16)


def _add_norm(key, x, sub, p, rate):
    sub = _dropout(key, sub, rate)
    return _layer_norm(x + sub, p["g"], p["b"])


def _embed(key, ids, table, pos, rate):
    d_model = table.shape[1]
    e = table[ids].astype(jnp.bfloat16) * (d_model ** 0.5)
    e = e + pos.astype(jnp.bfloat16)
    return _dropout(key, e, rate)


def _sinusoid(seq_len, d_model):
    pos = np.arange(seq_len)[:, None]
    i = np.arange(d_model)[None, :]
    angle = pos / np.power(10000.0, (2 * (i // 2)) / d_model)
    t = np.zeros((seq_len, d_model), np.float32)
    t[:, 0::2] = np.sin(angle[:, 0::2])
    t[:, 1::2] = np.cos(angle[:, 1::2])
    return jnp.asarray(t)


def init_params(rng, src_vocab=30000, trg_vocab=30000, n_layer=6, n_head=8,
                d_model=512, d_inner=2048):
    r = np.random.RandomState(rng)

    def mat(a, b, std=None):
        std = std if std is not None else (6.0 / (a + b)) ** 0.5
        return jnp.asarray(r.uniform(-std, std, (a, b)).astype(np.float32))

    def attn_p():
        return {"wq": mat(d_model, d_model), "wk": mat(d_model, d_model),
                "wv": mat(d_model, d_model), "wo": mat(d_model, d_model)}

    def ln_p():
        return {"g": jnp.ones((d_model,), jnp.float32),
                "b": jnp.zeros((d_model,), jnp.float32)}

    def ffn_p():
        return {"w1": mat(d_model, d_inner), "b1": jnp.zeros((d_inner,), jnp.float32),
                "w2": mat(d_inner, d_model), "b2": jnp.zeros((d_model,), jnp.float32)}

    p = {"src_emb": jnp.asarray(
            r.normal(0, d_model ** -0.5, (src_vocab, d_model)).astype(np.float32)),
         "trg_emb": jnp.asarray(
            r.normal(0, d_model ** -0.5, (trg_vocab, d_model)).astype(np.float32)),
         "out": mat(d_model, trg_vocab),
         "enc": [], "dec": []}
    for _ in range(n_layer):
        p["enc"].append({"attn": attn_p(), "ln1": ln_p(), "ffn": ffn_p(),
                         "ln2": ln_p()})
        p["dec"].append({"self": attn_p(), "ln1": ln_p(), "cross": attn_p(),
                         "ln2": ln_p(), "ffn": ffn_p(), "ln3": ln_p()})
    return p


def loss_fn(params, batch, key, seq_len=256, n_head=8, rate=0.1):
    keys = iter(jax.random.split(key, 64))
    pos = _sinusoid(seq_len, params["src_emb"].shape[1])

    enc = _embed(next(keys), batch["src"], params["src_emb"], pos, rate)
    for lp in params["enc"]:
        a = _attn(next(keys), enc, enc, lp["attn"], rate, False, n_head)
        enc = _add_norm(next(keys), enc, a, lp["ln1"], rate)
        f = _ffn(enc, lp["ffn"])
        enc = _add_norm(next(keys), enc, f, lp["ln2"], rate)

    dec = _embed(next(keys), batch["trg"], params["trg_emb"], pos, rate)
    for lp in params["dec"]:
        a = _attn(next(keys), dec, dec, lp["self"], rate, True, n_head)
        dec = _add_norm(next(keys), dec, a, lp["ln1"], rate)
        c = _attn(next(keys), dec, enc, lp["cross"], rate, False, n_head)
        dec = _add_norm(next(keys), dec, c, lp["ln2"], rate)
        f = _ffn(dec, lp["ffn"])
        dec = _add_norm(next(keys), dec, f, lp["ln3"], rate)

    logits = (dec.astype(jnp.bfloat16) @ params["out"].astype(jnp.bfloat16))
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["lbl"][..., None],
                               axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def adam_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"m": z, "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adam_update(params, grads, state, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    t = state["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    tf = t.astype(jnp.float32)
    corr = jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
    new_p = jax.tree.map(
        lambda p, m, v: p - lr * corr * m / (jnp.sqrt(v) + eps), params, m, v)
    return new_p, {"m": m, "v": v, "t": t}


@functools.partial(jax.jit, donate_argnums=(0, 1))
def train_step(params, opt_state, batch, key):
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
    params, opt_state = adam_update(params, grads, opt_state)
    return params, opt_state, loss


def make_batch(batch_size=64, seq_len=256, vocab=30000, seed=0):
    r = np.random.RandomState(seed)
    return {k: jnp.asarray(r.randint(1, vocab, (batch_size, seq_len)),
                           jnp.int32)
            for k in ("src", "trg", "lbl")}


if __name__ == "__main__":
    import time

    params = init_params(0)
    opt = adam_init(params)
    batch = make_batch()
    key = jax.random.key(0)
    params, opt, loss = train_step(params, opt, batch, key)
    np.asarray(loss)  # sync
    t0 = time.perf_counter()
    steps = 15
    for i in range(steps):
        params, opt, loss = train_step(params, opt, batch,
                                       jax.random.fold_in(key, i))
    np.asarray(loss)
    dt = (time.perf_counter() - t0) / steps
    print(f"yardstick: {dt * 1e3:.1f} ms/step, "
          f"{64 * 256 / dt:.0f} tok/s, loss={float(loss):.3f}")
