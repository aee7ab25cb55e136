"""The gated delta rule at head dims off the lane tile (Olmo-Hybrid's 96 / 192,
15 heads held, 4096 tokens, bf16): the XLA form the op keeps there
(`ops/linear_attention.py::_plan` answers "xla") against `gdn_fwd` / `gdn_bwd`
on operands zero-padded to whole lanes (q, k to 128, v and dO to 256), the
pads and the slices back counted. What a `perf_opt` PR that widens `_plan`'s
envelope would start from. TPU-only.

    python tools/gdn_offtile_probe.py

Zero key channels add nothing to `k k^T`, `q k^T` or the l2-norms, and a
zero value column stays zero through the solve and the state, so the padded
kernels compute the same rule but for q's scale, which they take from the
padded width (128^-0.5 where the rule has 96^-0.5): the probe multiplies
their output by sqrt(128 / 96) before it compares, and a PR that ships them
hands the kernels the scale. Each form: forward alone (the op), forward and
backward under `jax.vjp` (the grad op of the XLA form traces the rule again;
the kernels' runs `gdn_bwd` alone on the saved states), ms a call as the
median of ten after a warm-up, on the host's clock around
`block_until_ready`.
"""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

B, T, H, DK, DV, CHUNK = 1, 4096, 15, 96, 192, 64
PAD_K, PAD_V = 128, 256


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import linear_attention as la

    print(jax.devices(), flush=True)
    rng = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    q, k = (jnp.asarray(rng.randn(B, T, H, DK), bf16) for _ in range(2))
    v, d_out = (jnp.asarray(rng.randn(B, T, H, DV), bf16) for _ in range(2))
    g = jnp.asarray(-np.exp(rng.uniform(-3, 2.5, H))
                    * np.log1p(np.exp(rng.randn(B, T, H) - 3)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 1.95, (B, T, H)), jnp.float32)

    def xla(q, k, v, g, beta):
        qn = la.l2_normalize(q.astype(jnp.float32)) * DK ** -0.5
        kn = la.l2_normalize(k.astype(jnp.float32))
        return la.chunked_gated_delta_rule(
            qn, kn, v.astype(jnp.float32), g, beta, CHUNK).astype(v.dtype)

    def pad(x, width):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))

    def kernels_forward(q, k, v, g, beta):
        out, states = la._gdn_forward(pad(q, PAD_K), pad(k, PAD_K),
                                      pad(v, PAD_V), g, beta, CHUNK)
        return out[..., :DV], states

    def kernels_both(q, k, v, g, beta, d_out):
        qp, kp, vp = pad(q, PAD_K), pad(k, PAD_K), pad(v, PAD_V)
        out, states = la._gdn_forward(qp, kp, vp, g, beta, CHUNK)
        dq, dk, dv, dg, dbeta = la._gdn_backward(
            qp, kp, vp, g, beta, states, pad(d_out, PAD_V), CHUNK)
        return out[..., :DV], (dq[..., :DK], dk[..., :DK], dv[..., :DV], dg,
                               dbeta)

    def xla_both(q, k, v, g, beta, d_out):
        out, vjp = jax.vjp(xla, q, k, v, g, beta)
        return out, vjp(d_out)

    def ms(fn, *args):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(10):
            start = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - start)
        return float(np.median(times)) * 1e3

    def frob(a, b):
        a, b = (np.asarray(x, np.float64) for x in (a, b))
        return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))

    args = (q, k, v, g, beta)
    assert la._plan(DK, DV, CHUNK, T // CHUNK) == ("xla", 0)
    print(f"padded plan {la._plan(PAD_K, PAD_V, CHUNK, T // CHUNK)}, "
          f"[{B}, {T}, {H}, {DK} / {DV}] bf16", flush=True)
    rows = [("xla forward (the op)", ms(xla, *args)),
            ("xla forward + backward (the grad op)",
             ms(xla_both, *args, d_out)),
            ("padded kernels forward", ms(kernels_forward, *args)),
            ("padded kernels forward + backward", ms(kernels_both, *args,
                                                     d_out))]
    for what, value in rows:
        print(f"gdn_offtile_probe: {what}: {value:.3f} ms a layer",
              flush=True)
    fix = (PAD_K / DK) ** 0.5
    want, want_grads = jax.jit(xla_both)(*args, d_out)
    got, got_grads = jax.jit(kernels_both)(*args, d_out)
    print(f"gdn_offtile_probe: kernels against the xla form, Frobenius: out "
          f"{frob(np.asarray(got, np.float32) * fix, want):.4f}, "
          + ", ".join(f"d{n} {frob(np.asarray(a, np.float32) * fix, b):.4f}"
                      for n, a, b in zip("q k v g beta".split(), got_grads,
                                         want_grads)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
