"""What the steps above the diagonal cost the flash kernels and which part
of it: the measurement behind `ops/pallas_attention.py::_dead_steps`, which
holds every operand's index map (the kept set's tile since PR 68; K and V,
and Q, dOut, `Lse` and delta since PR 70) on the live neighbour of such a
step. TPU-only.

    python tools/kept_set_probe.py [--root TREE] [--seq 8192] [--forms ...]

The kernels alone at `keye_vl_2_30b_a3b.s8192`'s shapes (`[1, 32, 8192,
128]` bf16 under an int8 `[1, 8192, 8192]`, 1024 x 1024 tiles; `--seq 4096`:
the one full layer of five cells), sixteen calls chained in one jitted loop,
the median of five loops on the host's clock, the forward and the backward
each alone, in seven forms:

    set          the call as the op makes it
    every_step   every operand's own block fetched on every grid step, the
                 28 of a head's 64 that lie above the diagonal too (the
                 kernels before PR 68; the same results)
    fetched      as every_step, and the set not applied (wrong, timed only)
    applied      applied from one tile that no step fetches again (the index
                 map held at tile 0: wrong, timed only)
    no_set       the plain causal call of the same shapes
    no_set_every_step
                 the plain call with each operand's own block on every step
                 (the kernels before PR 70; the same results)
    dead_steps_bare
                 the plain call's live tiles on a grid that has no dead
                 step: two rows (columns) of the triangle a row of T / tile
                 + 1 steps, 4 x 9 for 8 x 8. `no_set` less this is what the
                 dead steps still cost once they fetch nothing, which is
                 what a folded grid would return (the backward's is wrong,
                 its accumulators are not cleared a column: timed only)

`--root` is the tree whose `paddle_tpu` is timed (a copy of an older commit:
before PR 70 `no_set` is `no_set_every_step`, before PR 68 `set` is
`every_step`). `every_step` - `applied` is what fetching the set costs,
`every_step` - `fetched` what applying does, `no_set_every_step` - `no_set`
what PR 70's hold returns.

Read on the chip (PRs 68, 70): `PERF.md` section 6. The chain ranks forms
within one tree; its calls carry ~0.25 (forward) and ~0.4 ms (backward) of
the elementwise op that chains them, so a time a call is settled by a traced
run of the cell.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

N = 16      # calls chained in one jitted loop
H, D = 32, 128
FORMS = ("set", "every_step", "fetched", "applied", "no_set",
         "no_set_every_step", "dead_steps_bare")


def folded(pl, jnp):
    """(`pallas_call`, `_grid_ids`) that run a causal call's live tiles on
    a grid without a dead step: where the k blocks run innermost rows r and
    n - 1 - r of the triangle share a grid row of n + 1 steps, where the q
    blocks do columns c and n - 1 - c. Square tiles, an even n."""
    real_call, q_inner = pl.pallas_call, [False]

    def unfold(a, s, n):
        if q_inner[0]:      # column a from its diagonal, then column n-1-a
            first = s < n - a
            return (jnp.where(first, a, n - 1 - a),
                    jnp.where(first, a + s, s - 1))
        first = s <= a      # row a up to its diagonal, then row n-1-a
        return jnp.where(first, a, n - 1 - a), jnp.where(first, s, s - a - 1)

    def pallas_call(kernel, *, grid, in_specs, out_specs, **kw):
        bh, n, m = grid
        assert n == m and n % 2 == 0, grid
        q_inner[0] = "dkv" in kw["name"]    # read when the kernel is traced

        def fold(spec):
            return pl.BlockSpec(spec.block_shape, lambda b, a, s: (
                spec.index_map(b, *unfold(a, s, n))))
        outs = fold(out_specs) if isinstance(out_specs, pl.BlockSpec) \
            else [fold(spec) for spec in out_specs]
        return real_call(kernel, grid=(bh, n // 2, n + 1),
                         in_specs=[fold(spec) for spec in in_specs],
                         out_specs=outs, **kw)

    def grid_ids(heads, tile_axes=2):
        n = 2 * pl.num_programs(1)
        outer, inner = unfold(pl.program_id(1), pl.program_id(2), n)
        # a row ends on its diagonal tile, a column on the last q block
        return (pl.program_id(0), outer, inner,
                (lambda: n) if q_inner[0] else (lambda: outer + 1),
                lambda: n)
    return pallas_call, grid_ids


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--forms", nargs="*", default=list(FORMS), choices=FORMS)
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    T = args.seq

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from paddle_tpu.ops import pallas_attention as pa

    if jax.default_backend() != "tpu":
        sys.exit("kept_set_probe times Mosaic kernels: it needs a TPU")
    print(jax.devices(), args.root, (H, T, D), flush=True)
    rng = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rng.randn(1, H, T, D), jnp.bfloat16)
                  for _ in range(4))
    kept = jnp.asarray(np.tril(rng.rand(1, T, T) < 0.3)
                       | np.eye(T, dtype=bool), jnp.int8)
    scale = D ** -0.5
    apply, spec = pa._apply_kept, pa._kept_spec
    # a tree from before PR 70 has no `_dead_steps`: its maps move anyway
    dead_steps = getattr(pa, "_dead_steps", None)
    real_call, grid_ids = pl.pallas_call, pa._grid_ids

    def every_step(heads, BQ, BK, at_q, at_k, **kw):
        return pl.BlockSpec((1, BQ, BK), lambda *g: (
            g[0] // heads, at_q(*g)[1], at_k(*g)[1]))

    def held(heads, BQ, BK, *a, **kw):
        return pl.BlockSpec((1, BQ, BK), lambda *g: (0, 0, 0))

    def checksum(x):
        """A scalar comes back to the host, not 64 MiB: 6 ms a call."""
        return jnp.sum(x.astype(jnp.float32))

    sums = {}

    def time_form(form):
        pa._apply_kept = (lambda s, *kept: s) if form == "fetched" \
            else apply
        pa._kept_spec = {"set": spec, "applied": held}.get(form, every_step)
        pa._dead_steps = (lambda *a: 0) if form in (
            "every_step", "fetched", "no_set_every_step") else dead_steps
        pl.pallas_call, pa._grid_ids = folded(pl, jnp) \
            if form == "dead_steps_bare" else (real_call, grid_ids)
        the_set = kept if form in FORMS[:4] else None

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
    for a, b in (("set", "every_step"), ("no_set", "no_set_every_step")):
        if {a, b} <= set(args.forms):               # the same results
            assert all(sums[a, w] == sums[b, w] for w in ("fwd", "bwd")), sums
    if {"no_set", "dead_steps_bare"} <= set(args.forms):
        assert sums["no_set", "fwd"] == sums["dead_steps_bare", "fwd"], sums


if __name__ == "__main__":
    main()
