"""What the causal mask costs on tiles it cannot change, which form of
leaving it out runs fastest, and for which calls it pays: the measurement
behind `ops/pallas_attention.py::_on_live_tile` (two `pl.when` bodies) and
`_interior_apart` (under a window or a kept set only). TPU-only.

    python tools/interior_mask_probe.py [--shapes keye full ...]

The kernels alone, forward + fused backward a call, bf16, causal, sixteen
calls chained in one jitted loop, the median of five loops on the host's
clock, and the forward alone the same way, at the cells' shapes (`SHAPES`),
in four forms:

    every_tile   the mask on every live tile (the interior predicate patched
                 to answer "edge" always: the kernels before PR 55, and a
                 plain causal call's since)
    two_bodies   interior tiles in a body without the mask (`_interior_apart`
                 patched to say so of every causal call: what a windowed
                 call and one under a kept set run)
    cond         one body, a `lax.cond` on the score tile around the mask
    no_tile      the mask on no tile (every live tile called interior): wrong
                 on the diagonal, timed only, for what the whole pass costs

and the seconds the first calls of each took (trace, Mosaic, XLA).

Read on the chip (PR 55): `PERF.md` section 6.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N = 16      # calls chained in one jitted loop

# name -> (heads, seq, head width, value width, window, kept set)
SHAPES = {
    "keye": (32, 8192, 128, 128, None, True),       # keye_vl_2_30b_a3b.s8192
    "full": (32, 8192, 128, 128, None, False),      # mellum2's full layer
    "w1024": (32, 8192, 128, 128, 1024, False),     # mellum2's windowed ones
    "w2048": (32, 4096, 128, 128, 2048, False),     # trinity_mini's
    "mla": (32, 4096, 192, 128, None, False),       # kanana_2_30b_a3b.bs1
    "ouro": (16, 4096, 128, 128, None, False),      # ouro_2_6b.bs1, olmoe
}


def forms(pa):
    """name -> the (interior predicate, mask) pair that gives the form."""
    from jax import lax
    interior, mask = pa._causal_interior, pa._apply_causal_mask

    def edge_always(*a, **kw):
        return False

    def cond_mask(s, qi, kj, blk_q, blk_k, window=None):
        inside = interior(qi, kj, blk_q, blk_k, window)
        if inside is False:
            return mask(s, qi, kj, blk_q, blk_k, window)
        return lax.cond(inside, lambda s: s,
                        lambda s: mask(s, qi, kj, blk_q, blk_k, window), s)

    return {"every_tile": (edge_always, mask),
            "two_bodies": (interior, mask),
            "cond": (edge_always, cond_mask),
            "no_tile": (pa._causal_live, mask)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_attention as pa

    if jax.default_backend() != "tpu":
        sys.exit("interior_mask_probe times Mosaic kernels: it needs a TPU")
    print(jax.devices(), flush=True)
    all_forms, apart = forms(pa), pa._interior_apart
    pa._interior_apart = lambda window, kept: True
    for name in args.shapes:
        H, T, D, Dv, window, kept = SHAPES[name]
        rng = np.random.RandomState(0)
        q, k = (jnp.asarray(rng.randn(1, H, T, D), jnp.bfloat16)
                for _ in range(2))
        v = jnp.asarray(rng.randn(1, H, T, Dv), jnp.bfloat16)
        if kept:
            kept = jnp.asarray(np.tril(rng.rand(1, T, T) < 0.3)
                               | np.eye(T, dtype=bool), jnp.int8)
        else:
            kept = None
        pa._causal_interior, pa._apply_causal_mask = all_forms["two_bodies"]
        print(f"{name}: q, k [1, {H}, {T}, {D}], v {Dv} wide, window "
              f"{window}, kept set {kept is not None}, tiles "
              f"{pa._blk(T, True, pa._window_of(window, T))}, interior "
              f"{pa.interior_tiles(T, window)} of "
              f"{pa.window_tiles(T, window) if window else pa.causal_tiles(T)}"
              f" a head", flush=True)
        results = {}
        for form, (interior, mask) in all_forms.items():
            pa._causal_interior, pa._apply_causal_mask = interior, mask

            def f(q, k, v):
                o = pa.flash_attention(q, k, v, jnp.int32(0), True,
                                       D ** -0.5, 0.0, window, kept=kept)
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

            @jax.jit
            def forward(q, k, v):
                def body(c, _):     # `Out` has v's shape: chained through v
                    o = pa.flash_attention(q, k, c, jnp.int32(0), True,
                                           D ** -0.5, 0.0, window, kept=kept)
                    return c + jnp.asarray(1e-3, c.dtype) * o, None
                return jax.lax.scan(body, v, None, length=N)[0]

            t0 = time.perf_counter()
            try:
                first = float(np.asarray(step(q, k, v)))
                np.asarray(forward(q, k, v))
            except Exception as e:      # a form the compiler refuses
                print(f"  {form}: FAILED {type(e).__name__}: "
                      f"{str(e)[:300]}", flush=True)
                continue
            first_s = time.perf_counter() - t0
            times = {}
            for what, fn in (("fwd+bwd", step), ("fwd", forward)):
                laps = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    np.asarray(fn(q, k, v))
                    laps.append((time.perf_counter() - t0) / N * 1e3)
                times[what] = float(np.median(laps))
            results[form] = (first, times)
            print(f"  {form}: fwd+bwd {times['fwd+bwd']:.3f} ms a call, fwd "
                  f"{times['fwd']:.3f}, first calls {first_s:.1f} s, loss sum "
                  f"{first!r}", flush=True)
        sums = {r[0] for form, r in results.items() if form != "no_tile"}
        print(f"  loss sums agree across the three forms that mask the "
              f"diagonal: {len(sums) == 1}", flush=True)
    pa._causal_interior, pa._apply_causal_mask = all_forms["two_bodies"]
    pa._interior_apart = apart


if __name__ == "__main__":
    main()
