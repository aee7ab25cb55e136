"""What a kept set costs the `dsa_` flash kernels and which part of it: the
measurement behind `ops/pallas_attention.py::_kept_spec`, which holds the
set's tile on the live neighbour of a step above the diagonal. TPU-only.

    python tools/kept_set_probe.py [--root TREE] [--forms set no_set ...]

The kernels alone at `keye_vl_2_30b_a3b.s8192`'s shapes (`[1, 32, 8192,
128]` bf16 under an int8 `[1, 8192, 8192]`, 1024 x 1024 tiles), sixteen calls
chained in one jitted loop, the median of five loops on the host's clock,
the forward and the backward each alone, in five forms:

    set          the call as the op makes it
    every_step   the set's own tile fetched on every grid step, the 28 of a
                 head's 64 that lie above the diagonal too (the kernels
                 before PR 68; the same results)
    fetched      as every_step, and not applied (wrong, timed only)
    applied      applied from one tile that no step fetches again (the index
                 map held at tile 0: wrong, timed only)
    no_set       the plain causal call of the same shapes

`--root` is the tree whose `paddle_tpu` is timed (a copy of an older commit,
where `set` is `every_step`). `every_step` - `applied` is what fetching
costs, `every_step` - `fetched` what applying does.

Read on the chip (PR 68): `PERF.md` section 6. The chain ranks forms within
one tree; its calls carry ~0.25 (forward) and ~0.4 ms (backward) of the
elementwise op that chains them, so a time a call is settled by a traced
run of the cell.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

N = 16      # calls chained in one jitted loop
H, T, D = 32, 8192, 128
FORMS = ("set", "every_step", "fetched", "applied", "no_set")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--forms", nargs="*", default=list(FORMS), choices=FORMS)
    args = ap.parse_args()
    sys.path.insert(0, args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from paddle_tpu.ops import pallas_attention as pa

    if jax.default_backend() != "tpu":
        sys.exit("kept_set_probe times Mosaic kernels: it needs a TPU")
    print(jax.devices(), args.root, flush=True)
    rng = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rng.randn(1, H, T, D), jnp.bfloat16)
                  for _ in range(4))
    kept = jnp.asarray(np.tril(rng.rand(1, T, T) < 0.3)
                       | np.eye(T, dtype=bool), jnp.int8)
    scale = D ** -0.5
    apply, spec = pa._apply_kept, pa._kept_spec

    def every_step(heads, BQ, BK, at_q, at_k, q_inner=False):
        return pl.BlockSpec((1, BQ, BK), lambda *g: (
            g[0] // heads, at_q(*g)[1], at_k(*g)[1]))

    def held(heads, BQ, BK, *a, **kw):
        return pl.BlockSpec((1, BQ, BK), lambda *g: (0, 0, 0))

    def checksum(x):
        """A scalar comes back to the host, not 64 MiB: 6 ms a call."""
        return jnp.sum(x.astype(jnp.float32))

    sums = {}

    def time_form(form):
        pa._apply_kept = (lambda s, kept_ref: s) if form == "fetched" \
            else apply
        pa._kept_spec = {"set": spec, "applied": held}.get(form, every_step)
        the_set = None if form == "no_set" else kept

        @jax.jit
        def forward(q, k, v):
            def body(c, _):     # `Out` has v's shape: chained through v
                o, _ = pa._flash_forward(q, k, c, True, scale, kept=the_set)
                return c + jnp.asarray(1e-3, c.dtype) * o, None
            return checksum(jax.lax.scan(body, v, None, length=N)[0])

        @jax.jit
        def backward(q, k, v, o, lse, g):
            def body(c, _):     # dQ has dOut's shape: chained through it
                dq, dk, dv = pa._flash_backward(q, k, v, o, lse, c, True,
                                                scale, 0.0, 0, kept=the_set)
                return c + jnp.asarray(1e-3, c.dtype) * (dq + dk + dv), None
            return checksum(jax.lax.scan(body, g, None, length=N)[0])

        o, lse = jax.jit(lambda q, k, v: pa._flash_forward(
            q, k, v, True, scale, kept=the_set))(q, k, v)
        times = {}
        for what, fn, operands in (("fwd", forward, (q, k, v)),
                                   ("bwd", backward, (q, k, v, o, lse, g))):
            t0 = time.perf_counter()
            sums[form, what] = float(np.asarray(fn(*operands)))
            first_s = time.perf_counter() - t0
            laps = []
            for _ in range(5):
                t0 = time.perf_counter()
                np.asarray(fn(*operands))
                laps.append((time.perf_counter() - t0) / N * 1e3)
            times[what] = (float(np.median(laps)), first_s)
        print(f"  {form}: " + ", ".join(
            f"{what} {ms:.3f} ms a call (first call {s:.1f} s, sum "
            f"{sums[form, what]:.6g})" for what, (ms, s) in times.items()),
            flush=True)

    for form in args.forms:
        time_form(form)
    if {"set", "every_step"} <= set(args.forms):    # the same results
        assert all(sums["set", w] == sums["every_step", w]
                   for w in ("fwd", "bwd")), sums


if __name__ == "__main__":
    main()
