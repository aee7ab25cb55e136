"""What the causal mask costs on tiles it cannot change, what an edge tile
costs on the half of it that is masked, which form of leaving either out
runs fastest, and for which calls it pays: the measurement behind
`ops/pallas_attention.py::_on_live_tile` (two `pl.when` bodies, a third and
a fourth for the aligned edge tiles), `_interior_apart` (under a window or a
kept set only) and `_strip_side` (how wide a strip of an edge tile is).
TPU-only.

    python tools/interior_mask_probe.py [--shapes keye full ...]
                                        [--forms whole_edge strips_4 ...]
                                        [--tiles 1024 1024]

The kernels alone, forward + fused backward a call, bf16, causal, sixteen
calls chained in one jitted loop, the median of five loops on the host's
clock, and the forward alone the same way, at the cells' shapes (`SHAPES`),
in these forms (`FORMS`; the first three and `as_called` by default):

    whole_edge   every edge tile whole under the mask (`_strip_side` patched
                 to say that no call is aligned): the kernels before PR 72,
                 the same lowered text
    strips_4     an aligned edge tile (the diagonal one, a window's lower
                 one) in four strips of rows or keys, each over its live
                 extent: 10 of its 16 sub-blocks
    strips_2     in two: 3 of 4
    as_called    what `_strip_side` picks

    every_tile   the mask on every live tile, whole (the interior predicate
                 patched to answer "edge" always: the kernels before PR 55)
    two_bodies   interior tiles in a body without the mask, of every causal
                 call (`_interior_apart` patched to say so), edge tiles whole
    cond         one body, a `lax.cond` on the score tile around the mask
    no_tile      the mask on no tile (every live tile called interior): wrong
                 on the diagonal, timed only, for what the whole pass costs

and the seconds the first calls of each took (trace, Mosaic, XLA). `--tiles`
forces every call's tiles (`_BLOCK_OVERRIDE`), for a windowed call's strips
at other tiles than `_blk` gives it. One call
of every form is held against `whole_edge`'s, or the first form's: `Out`,
`Lse`, dQ, dK and dV bit for bit, or the largest difference.

Read on the chip (PRs 55 and 72): `PERF.md` section 6.
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
    "d256": (16, 4096, 256, 256, None, False),      # qwen3_next_80b_a3b.bs1
    "d64": (32, 4096, 64, 64, None, False),         # lfm2_8b_a1b.s4096
}

NAMES = ("Out", "Lse", "dQ", "dK", "dV")


def forms(pa):
    """name -> the attributes of `pa` that give the form."""
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

    def whole(*a, **kw):
        return None

    def strips_of(parts):
        """Of every aligned call, whatever its tiles."""
        def strip_side(blk_q, blk_k, window=None):
            aligned = blk_q == blk_k and (blk_q // parts) % 128 == 0 \
                and not (window is not None and window % blk_k)
            return blk_q // parts if aligned else None
        return strip_side

    def always(window, kept):
        return True

    return {"whole_edge": {"_strip_side": whole},
            "strips_4": {"_strip_side": strips_of(4)},
            "strips_2": {"_strip_side": strips_of(2)},
            "as_called": {},
            "every_tile": {"_strip_side": whole, "_interior_apart": always,
                           "_causal_interior": edge_always},
            "two_bodies": {"_strip_side": whole, "_interior_apart": always},
            "cond": {"_strip_side": whole, "_interior_apart": always,
                     "_causal_interior": edge_always,
                     "_apply_causal_mask": cond_mask},
            "no_tile": {"_strip_side": whole, "_interior_apart": always,
                        "_causal_interior": pa._causal_live}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--forms", nargs="*", default=[
        "whole_edge", "strips_4", "strips_2", "as_called"])
    ap.add_argument("--tiles", nargs=2, type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_attention as pa

    if jax.default_backend() != "tpu":
        sys.exit("interior_mask_probe times Mosaic kernels: it needs a TPU")
    print(jax.devices(), flush=True)
    if args.tiles:
        pa._BLOCK_OVERRIDE = tuple(args.tiles)
    all_forms = forms(pa)
    as_called = {name: getattr(pa, name) for patch in all_forms.values()
                 for name in patch}
    for name in args.shapes:
        H, T, D, Dv, window, kept = SHAPES[name]
        rng = np.random.RandomState(0)
        q, k = (jnp.asarray(rng.randn(1, H, T, D), jnp.bfloat16)
                for _ in range(2))
        v, g = (jnp.asarray(rng.randn(1, H, T, Dv), jnp.bfloat16)
                for _ in range(2))
        if kept:
            kept = jnp.asarray(np.tril(rng.rand(1, T, T) < 0.3)
                               | np.eye(T, dtype=bool), jnp.int8)
        else:
            kept = None
        tiles = pa._blk(T, True, pa._window_of(window, T))
        print(f"{name}: q, k [1, {H}, {T}, {D}], v {Dv} wide, window "
              f"{window}, kept set {kept is not None}, tiles {tiles}, a "
              f"strip as called "
              f"{pa._strip_side(*tiles, pa._window_of(window, T))}, interior "
              f"{pa.interior_tiles(T, window)} of "
              f"{pa.window_tiles(T, window) if window else pa.causal_tiles(T)}"
              f" a head, (edge tiles in strips, sub-blocks skipped) "
              f"{pa.edge_strips(T, window)}", flush=True)
        results, held = {}, None
        for form in args.forms:
            for attr, value in {**as_called, **all_forms[form]}.items():
                setattr(pa, attr, value)

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

            @jax.jit
            def once(q, k, v, g):
                out, lse = pa._flash_forward(q, k, v, True, D ** -0.5,
                                             window=window, kept=kept)
                return (out, lse) + tuple(pa._flash_backward(
                    q, k, v, out, lse, g, True, D ** -0.5, 0.0, 0, window,
                    kept=kept))

            t0 = time.perf_counter()
            try:
                first = float(np.asarray(step(q, k, v)))
                np.asarray(forward(q, k, v))
                got = [np.asarray(x, np.float32) for x in once(q, k, v, g)]
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
            if held is None:
                held, same = (form, got), "the form the others are held to"
            else:
                same = ", ".join(
                    f"{n} " + ("bitwise" if np.array_equal(a, b) else
                               f"off by {np.abs(a - b).max():.3e} of "
                               f"{np.abs(b).max():.3e}")
                    for n, a, b in zip(NAMES, got, held[1]))
                same = f"against {held[0]}: {same}"
            print(f"  {form}: fwd+bwd {times['fwd+bwd']:.3f} ms a call, fwd "
                  f"{times['fwd']:.3f}, first calls {first_s:.1f} s, loss sum "
                  f"{first!r}; {same}", flush=True)
        for attr, value in as_called.items():
            setattr(pa, attr, value)
        sums = {r[0] for form, r in results.items() if form != "no_tile"}
        print(f"  loss sums agree across the forms that mask the diagonal: "
              f"{len(sums) == 1}", flush=True)


if __name__ == "__main__":
    main()
