"""Which tiles the windowed flash kernels should take: the measurement behind
`ops/pallas_attention.py::_blk`'s rule for a window ("half the window, 512 at
most"). TPU-only. Two modes:

    python tools/window_tile_probe.py [--seq 4096] [--window 2048]
        [--heads 32] [--head-dim 128] [--value-dim DV]
      the kernels alone: forward + fused backward a call at
      bf16[1, heads, seq, head_dim] (v at `--value-dim` where given: a
      differential layer's 64 / 128), causal, sixteen calls chained in one
      jitted loop, median of five slopes on the host's clock, for the rule's
      own choice and for each tile forced through `_BLOCK_OVERRIDE`; then the
      same without a window at the rule's choice, 1024^2 and 512^2.

    python tools/window_tile_probe.py --cell <cell> --tiles BQ BK
        --seed N [--seconds 36] [run.py's other options]
      one benchmark cell end to end (`benchmark/run.py`, untraced) with every
      flash call's tiles forced: run it beside the same cell and seed without
      this wrapper for an A/B of the rule in the step.

Read on the chip (PR 49, calls 1 and 5; PR 73 at a window of 512 over 4096
tokens, 20 heads of 64 / 128): `PERF.md` section 6.
"""

from __future__ import annotations

import argparse
import os
import runpy
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N = 16      # calls chained in one jitted loop
TILES = (None, (1024, 1024), (512, 512), (256, 256), (512, 1024),
         (1024, 512), (256, 512), (512, 256))


def kernels_alone(T, W, H, D, Dv=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_attention as pa

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, H, T, width), jnp.bfloat16)
               for width in (D, D, Dv or D))
    seed = jnp.int32(0)

    def make_step(tiles, window):
        pa._BLOCK_OVERRIDE = tiles

        def f(q, k, v):
            o = pa.flash_attention(q, k, v, seed, True, D ** -0.5, 0.0,
                                   window)
            return jnp.sum(o.astype(jnp.float32))

        @jax.jit
        def step(q, k, v):
            def body(c, _):
                q, k, v = c
                loss, (dq, dk, dv) = jax.value_and_grad(
                    f, argnums=(0, 1, 2))(q, k, v)
                eps = jnp.asarray(1e-3, q.dtype)
                return (q - eps * dq, k - eps * dk, v - eps * dv), loss
            _, losses = jax.lax.scan(body, (q, k, v), None, length=N)
            return losses.sum()
        return step

    print(jax.devices(), flush=True)
    for window in (W, None):
        for tiles in TILES:
            if window is None and tiles not in (None, (1024, 1024),
                                                (512, 512)):
                continue
            try:
                step = make_step(tiles, window)
                np.asarray(step(q, k, v))       # traced under the override
            except Exception as e:      # a tile the compiler refuses
                print(f"window {window} tiles {tiles}: FAILED "
                      f"{type(e).__name__}: {str(e)[:200]}", flush=True)
                continue
            finally:
                pa._BLOCK_OVERRIDE = None

            def run(n):
                t0 = time.perf_counter()
                for _ in range(n):
                    out = step(q, k, v)
                np.asarray(out)
                return time.perf_counter() - t0
            slopes = sorted((run(4) - run(1)) / 3 for _ in range(5))
            chosen = pa._blk(T, True, window) if tiles is None else tiles
            print(f"window {window} tiles {tiles} (-> {chosen}): "
                  f"{slopes[2] / N * 1e3:.3f} ms a fwd+bwd call (min "
                  f"{slopes[0] / N * 1e3:.3f}, max "
                  f"{slopes[-1] / N * 1e3:.3f})", flush=True)


def cell_with_tiles(cell, tiles, seed, seconds, rest):
    from paddle_tpu.ops import pallas_attention as pa
    pa._BLOCK_OVERRIDE = tuple(tiles)
    print(f"window_tile_probe: every flash call's tiles forced to "
          f"{pa._BLOCK_OVERRIDE}", flush=True)
    sys.argv = ["run.py", "--workload", cell, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", "0", *rest]
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    runpy.run_path(os.path.join(ROOT, "benchmark", "run.py"),
                   run_name="__main__")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--value-dim", type=int)
    ap.add_argument("--cell")
    ap.add_argument("--tiles", type=int, nargs=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=36)
    args, rest = ap.parse_known_args()      # the rest goes to run.py
    if args.cell:
        cell_with_tiles(args.cell, args.tiles, args.seed, args.seconds, rest)
    else:
        kernels_alone(args.seq, args.window, args.heads, args.head_dim,
                      args.value_dim)


if __name__ == "__main__":
    main()
