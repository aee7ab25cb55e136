"""A share's token-side sums alone: `moe_token_sum` against the loop it
replaced (`ops/moe.py::_token_sum_call`, `_token_sum_loop`), at the four
share cells' shapes and about the rows a run uses. The measurement behind
`_token_sum_plan`'s one column block and `_TOKEN_SUM_ACC_BYTES`. TPU-only.

    python tools/token_sum_probe.py [cell ...]

For each cell (`qwen3_next`, `kanana2`, `trinity`, `mellum2` at even
routing, `mellum2_late` at the load late in a run) and both callers (with
the router weights as `moe_combine` calls it, without as
`moe_dispatch_grad` does): a routing drawn from Gumbel noise, the layout
`_dispatch_share` makes of it, random bf16 rows; the loop, the plan's call
and the call at every narrower column block, each as the slope between a
scan of 16 and a scan of 48 calls on the host's clock (median of five),
us a call and us a used row, and whether the call's result is the loop's
bitwise.

Read on the chip (PR 50, calls 1, 2 and 6): `PERF.md` section 6.
"""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# tokens, top k, experts, first held, held, width, bias on the held experts
CELLS = {
    "qwen3_next": (4096, 10, 512, 64, 32, 2048, 0.0),
    "kanana2": (4096, 6, 128, 16, 16, 2048, 0.0),
    "trinity": (4096, 8, 128, 8, 8, 2048, 0.0),
    "mellum2": (8192, 8, 64, 8, 8, 2304, 0.0),
    "mellum2_late": (8192, 8, 64, 8, 8, 2304, 0.45),
}


def main(cells):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from paddle_tpu.ops import moe

    def per_call(fn, args):
        """us a call: the slope between a chain of 16 and one of 48, each
        call's `GroupSizes` made to depend on the call before."""
        def timed(length):
            @jax.jit
            def chain(sizes, *rest):
                def body(s, _):
                    out = fn(s, *rest)
                    return s + (out[0, 0] != out[0, 0]).astype(s.dtype), \
                        out[0, 0].astype(jnp.float32)
                return lax.scan(body, sizes, None, length=length)[1].sum()
            np.asarray(chain(*args))
            times = []
            for _ in range(5):
                start = time.perf_counter()
                np.asarray(chain(*args))
                times.append(time.perf_counter() - start)
            return float(np.median(times))
        return (timed(48) - timed(16)) / 32 * 1e6

    print(jax.devices(), flush=True)
    for name in cells:
        n, k, experts, first, held, width, bias = CELLS[name]
        logits = np.random.RandomState(0).gumbel(size=(n, experts))
        logits[:, first:first + held] += bias
        index = np.argsort(-logits, axis=1)[:, :k].astype(np.int32)
        counts = np.bincount(index.reshape(-1), minlength=experts)
        layout = jax.jit(lambda x, i, c: moe._dispatch_share(
            x, i, c, moe.ROW_TILE, first, held))(
                jnp.zeros((n, width), jnp.bfloat16), jnp.asarray(index),
                jnp.asarray(counts, jnp.int32))
        source, sizes = layout["Source"], layout["GroupSizes"]
        rows, used = source.shape[0], int(sizes.sum())
        moved = jax.random.normal(jax.random.PRNGKey(1), (rows, width),
                                  jnp.bfloat16)
        weights = jax.random.uniform(jax.random.PRNGKey(2), (n * k,),
                                     jnp.float32)
        plan = moe._token_sum_plan(n, k, rows, width, moved.dtype)
        print(f"== {name}: {used} used rows of {rows} "
              f"({int((np.asarray(source) >= 0).sum())} assignments), "
              f"{n} tokens x top {k}, {width} wide, plan {plan}", flush=True)
        for scale in ((weights,), ()):
            def loop(s, m, src, *scale):
                return moe._token_sum_loop(m, src, k, n, s, *scale) \
                    .astype(m.dtype)
            args = (sizes, moved, source) + scale
            want = jax.jit(loop)(*args)
            t = per_call(loop, args)
            caller = "moe_combine" if scale else "moe_dispatch_grad"
            print(f"  {caller}: loop {t:8.1f} us, {t / used:.4f} us a row",
                  flush=True)
            lanes = width // 128
            for blocks in (b for b in range(1, 4) if lanes % b == 0):
                trial = (width // blocks,) + plan[1:]

                def call(s, m, src, *scale):
                    return moe._token_sum_call(m, src, k, n, s, m.dtype,
                                               trial, *scale)
                same = bool(jnp.array_equal(jax.jit(call)(*args), want))
                t = per_call(call, args)
                print(f"    {blocks} column block(s) of {trial[0]}: "
                      f"{t:8.1f} us, {t / used:.4f} us a row, "
                      f"bitwise {same}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or list(CELLS))
