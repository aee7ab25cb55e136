"""Block-size sweep for the streamed flash kernels (round-4 item 5).

Times fwd+bwd of flash_attention directly (same-process interleaved,
two-point slope) for BQ x BK combinations at transformer-shaped sizes.

Usage: python tools/flash_block_sweep.py [--seq 4096] [--causal]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

COMBOS = [(256, 256), (256, 512), (512, 256), (512, 512),
          (512, 1024), (1024, 512), (1024, 1024),
          (256, 1024), (512, 2048), (256, 2048), (128, 1024)]


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_attention as pa

    seq = 4096
    causal = "--causal" in sys.argv
    for i, a in enumerate(sys.argv):
        if a == "--seq":
            seq = int(sys.argv[i + 1])

    B, H, D = 4, 8, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, seq, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, seq, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, seq, D), jnp.bfloat16)
    seed = jnp.int32(0)

    N_CHAIN = 16

    def make_step(bq, bk):
        pa._BLOCK_OVERRIDE = (bq, bk)

        def f(q, k, v):
            o = pa.flash_attention(q, k, v, seed, causal,
                                   1.0 / np.sqrt(D), 0.0)
            return jnp.sum(o.astype(jnp.float32))

        @jax.jit
        def step(q, k, v):
            # chain N fwd+bwd passes inside one jit (the grads feed the
            # next iteration, so nothing can be CSE'd away) — per-call
            # device time is big enough to dwarf dispatch jitter
            def body(c, _):
                q, k, v = c
                l, (dq, dk, dv) = jax.value_and_grad(
                    f, argnums=(0, 1, 2))(q, k, v)
                eps = jnp.asarray(1e-3, q.dtype)
                return (q - eps * dq, k - eps * dk, v - eps * dv), l
            (q, k, v), ls = jax.lax.scan(body, (q, k, v), None,
                                         length=N_CHAIN)
            return ls.sum()
        return step

    print(f"seq={seq} causal={causal} B={B} H={H} D={D}")
    for bq, bk in COMBOS:
        if seq % bq or seq % bk:
            continue
        try:
            step = make_step(bq, bk)
            np.asarray(step(q, k, v))  # compile
        except Exception as e:
            print(f"BQ{bq} x BK{bk}: FAILED ({type(e).__name__})")
            continue

        def window(n):
            t0 = time.perf_counter()
            for _ in range(n):
                out = step(q, k, v)
            np.asarray(out)
            return time.perf_counter() - t0

        slopes = []
        for _ in range(3):
            t_lo, t_hi = window(1), window(3)
            slopes.append((t_hi - t_lo) / 2)
        dt = sorted(slopes)[1] / N_CHAIN
        # fwd 2*T^2*D*2 (qk + pv) + bwd ~2.5x fwd matmul flops, per head
        flops = B * H * (2 * seq * seq * D * 2) * 3.5
        if causal:
            flops /= 2
        print(f"BQ{bq} x BK{bk}: {dt * 1e3:7.2f} ms  "
              f"~{flops / dt / 1e12:5.1f} TFLOP/s")
    pa._BLOCK_OVERRIDE = None


if __name__ == "__main__":
    main()
