"""The gated delta rule at head dims off the lane tile (Olmo-Hybrid's 96 / 192,
15 heads held, 4096 tokens, bf16), one layer alone: the XLA form
(`chunked_gated_delta_rule` and its `jax.vjp`, what the op ran there until
PR 64 and still runs on a CPU backend) against the op's own kernel path
(`ops/linear_attention.py::_gdn_forward` / `_gdn_backward`: `gdn_fwd` /
`gdn_bwd` on q, k, v and dO filled out with zero channels to 128 / 256
lanes, the fills and the cuts back counted, q's scale the true `96^-0.5`,
`States` at `[.., 96, 192]`). The before and after of PR 64. TPU-only.

    python tools/gdn_offtile_probe.py

What it read (TPU v5 lite, ms a layer):
  PR 63 (call 2; the kernels' side was hand-made pads around the kernels and a
         `sqrt(128 / 96)` correction of q's scale, `_plan` answered "xla"):
         XLA form forward 3.70, forward + backward 9.00; padded kernels 2.50
         and 5.01; within 0.0034-0.0050 (Frobenius) of the XLA form.
  PR 64 (call 1; the op's own path, `_plan` answers ("kernel", 2)): XLA form
         forward 3.655, forward + backward 8.864; kernels 2.453 and 5.085;
         `Out` within 0.0024 of the XLA form, the gradients 0.0021-0.0034. In
         the cell's step the pair takes 3.25 ms a layer (`gdn_fwd` 1.24,
         `gdn_bwd` 2.01) where the XLA form took 9.2: `PERF.md` section 5.

Each form: forward alone (the op), forward and backward (the XLA form's grad
op traces the rule again under `jax.vjp`; the kernels' runs `gdn_bwd` alone
on the saved states), ms a call as the median of ten after a warm-up, on
the host's clock around `block_until_ready`.
"""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

B, T, H, DK, DV, CHUNK = 1, 4096, 15, 96, 192, 64


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

    def kernels_forward(q, k, v, g, beta):
        return la._gdn_forward(q, k, v, g, beta, CHUNK)

    def kernels_both(q, k, v, g, beta, d_out):
        out, states = la._gdn_forward(q, k, v, g, beta, CHUNK)
        return out, la._gdn_backward(q, k, v, g, beta, states, d_out, CHUNK)

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
    print(f"plan {la._plan(DK, DV, CHUNK, T // CHUNK)}, "
          f"[{B}, {T}, {H}, {DK} / {DV}] bf16", flush=True)
    rows = [("xla forward (the op)", ms(xla, *args)),
            ("xla forward + backward (the grad op)",
             ms(xla_both, *args, d_out)),
            ("kernels forward (the op)", ms(kernels_forward, *args)),
            ("kernels forward + backward (the grad op)",
             ms(kernels_both, *args, d_out))]
    for what, value in rows:
        print(f"gdn_offtile_probe: {what}: {value:.3f} ms a layer",
              flush=True)
    want, want_grads = jax.jit(xla_both)(*args, d_out)
    got, got_grads = jax.jit(kernels_both)(*args, d_out)
    print(f"gdn_offtile_probe: kernels against the xla form, Frobenius: out "
          f"{frob(got, want):.4f}, "
          + ", ".join(f"d{n} {frob(a, b):.4f}" for n, a, b
                      in zip("q k v g beta".split(), got_grads, want_grads)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
