"""Measure this chip's sustained HBM bandwidth (round-4 MFU roofline).

A `lax.scan`-chained elementwise update on a large array: every iteration
reads and writes the full buffer, so traffic per call is known exactly
(2 * bytes * iters) and long enough (~10s of GB) to amortize dispatch
jitter. Slope-timed (1 vs 3 reps), median of 3 — the same methodology as
bench.py's matmul-peak probe.

The elementwise kernel is the upper bound for what a fused
transformer-step kernel mix can sustain; docs/PERF.md uses this number
as the denominator of the byte roofline.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def measure(size_mb=512, iters=48, dtype="float32"):
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = size_mb * (1 << 20) // np.dtype(dtype).itemsize

    @jax.jit
    def chain(x):
        a = jnp.asarray(1.0000001, dtype)
        b = jnp.asarray(1e-7, dtype)

        def body(c, _):
            # multiply-add: cannot be strength-reduced away, stays
            # elementwise, no MXU involvement
            return c * a + b, ()
        out, _ = lax.scan(body, x, None, length=iters)
        return out.sum()

    i = jnp.arange(n, dtype=jnp.float32)
    x = jnp.sin(i * 1e-3).astype(dtype)
    np.asarray(chain(x))  # compile + warm

    def run(reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = chain(x)
        np.asarray(out)
        return time.perf_counter() - t0

    slopes = []
    for _ in range(3):
        t_lo, t_hi = run(1), run(3)
        slopes.append((t_hi - t_lo) / 2)
    per_call = sorted(slopes)[1]
    nbytes = n * np.dtype(dtype).itemsize
    traffic = 2 * nbytes * iters          # read + write per iteration
    return traffic / per_call / 1e9, per_call


def main():
    for dtype in ("float32", "bfloat16"):
        bw, t = measure(dtype=dtype)
        print(f"{dtype}: sustained {bw:,.0f} GB/s  ({t * 1e3:.1f} ms/call)")


if __name__ == "__main__":
    main()
