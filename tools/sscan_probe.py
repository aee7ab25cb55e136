"""Mamba-1's selective scan at the cell's sizes (`phi_4_mini_flash_reasoning
.s4096`: one sequence of 4096 tokens, 5120 channels, 16 states, float32),
one layer alone: the plain chunked form (`ops/selective_scan.py::scan_plain`
and its `jax.vjp`, what the op runs on a CPU backend and off the plan)
against the kernel pair `sscan_fwd` / `sscan_bwd`, at each channel block the
kernels take. TPU-only.

    python tools/sscan_probe.py [--blocks 512 256 128] [--plain-chunk 128]

Each form: forward alone, forward and backward (the plain form's vjp computes
a chunk again from its saved state; the kernels' runs `sscan_bwd` alone on
`sscan_fwd`'s states), ms a call as the median of ten after a warm-up, on the
host's clock around `block_until_ready`; the kernels' outputs against the
plain form's in the Frobenius norm. What it read is in `PERF.md` section 6
(PR 73).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

B, T, CHANNELS, N = 1, 4096, 5120, 16


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import selective_scan as ss

    parser = argparse.ArgumentParser()
    parser.add_argument("--blocks", type=int, nargs="*",
                        default=[512, 256, 128])
    parser.add_argument("--plain-chunk", type=int, default=128)
    args = parser.parse_args()
    print(jax.devices(), flush=True)
    rng = np.random.RandomState(0)
    f32 = jnp.float32
    x, d_out = (jnp.asarray(rng.randn(B, T, CHANNELS), f32) for _ in "xd")
    dt = jax.nn.softplus(jnp.asarray(rng.randn(B, T, CHANNELS) - 4.0, f32))
    A = -jnp.asarray(np.tile(np.arange(1, N + 1), (CHANNELS, 1)), f32)
    Bm, Cm = (jnp.asarray(rng.randn(B, T, N) * 0.5, f32) for _ in "bc")
    D = jnp.ones((CHANNELS,), f32)
    ins = (x, dt, A, Bm, Cm, D)

    def ms(fn, *a):
        jax.block_until_ready(fn(*a))
        times = []
        for _ in range(10):
            start = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - start)
        return 1e3 * float(np.median(times))

    def frob(got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    plain = jax.jit(lambda *a: ss.scan_plain(*a, chunk=args.plain_chunk))

    @jax.jit
    def plain_both(*a):
        y, vjp = jax.vjp(lambda *v: ss.scan_plain(*v, chunk=args.plain_chunk),
                         *a[:-1])
        return (y,) + vjp(a[-1])

    want = plain_both(*ins, d_out)
    print(f"plain form (chunk {args.plain_chunk}): forward "
          f"{ms(plain, *ins):.3f} ms, forward + backward "
          f"{ms(plain_both, *ins, d_out):.3f} ms", flush=True)
    for block in args.blocks:
        fwd = jax.jit(lambda *a, w=block: ss._sscan_forward(*a, widest=w))
        bwd = jax.jit(lambda *a, w=block: ss._sscan_backward(*a, widest=w))
        y, states = fwd(*ins)
        grads = bwd(*ins, states, d_out)
        print(f"kernels at a block of {block}: sscan_fwd "
              f"{ms(fwd, *ins):.3f} ms, sscan_bwd "
              f"{ms(bwd, *ins, states, d_out):.3f} ms; y within "
              f"{frob(y, want[0]):.2e}, gradients (x, dt, A, B, C, D) "
              + ", ".join(f"{frob(g, w):.2e}"
                          for g, w in zip(grads, want[1:])), flush=True)


if __name__ == "__main__":
    main()
