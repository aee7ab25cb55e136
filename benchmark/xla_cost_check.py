#!/usr/bin/env python3
"""A one-off cross-check, not part of any run: XLA's `cost_analysis` FLOPs
of a one-chip cell's compiled step beside the count from shapes (`flops/`).

    python3 benchmark/xla_cost_check.py --workload <cell> [--batch N]

They need not agree: the compiler counts what it emitted (recomputation,
the optimizer, elementwise and reduction work, the full square of a causal
attention it does not skip; a Mosaic custom call counts as nothing), the
shape count what the model requires. The metrics use the shape count only.
Reaches the compiled step through the executor's private cache, as
`tools/_common.py::compile_main_step` does; nothing else here may.
"""

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    from system import System, make_pool

    def load(*parts):
        with open(os.path.join(HERE, *parts)) as f:
            return json.load(f)

    cell = load("workloads", args.workload + ".json")
    config = load("configs", cell["config"] + ".json")
    traffic = load("traffic", cell["traffic"] + ".json")
    batch = args.batch or traffic["batch"]
    system = System(config, cell, traffic, jax.devices()[:1], batch)
    feed = system.place(make_pool(system.feeds, config["feed_ranges"], batch,
                                  1, 0)[0])
    system.step(feed).block_until_ready()
    compiled = max(system.exe._cache.values(),
                   key=lambda c: len(c.program.global_block().ops))
    mut = {n: system.scope.find_var(n) for n in compiled.mut_names}
    const = {n: system.scope.find_var(n) for n in compiled.const_names}
    exe = compiled._step.lower({k: feed[k] for k in sorted(feed)}, mut, const,
                               np.uint32(0)).compile()
    cost = exe.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    shapes = importlib.import_module("flops." + config["flops"]) \
        .flops_per_example(**system.build_args)["forward_backward"] * batch
    text = exe.as_text()
    print(f"xla_cost_check {args.workload} batch {batch} on "
          f"{jax.devices()[0].device_kind}: XLA cost_analysis flops "
          f"{cost.get('flops', float('nan')):.6e}, bytes accessed "
          f"{cost.get('bytes accessed', float('nan')):.6e}; from shapes "
          f"{shapes:.6e} flops a step; ratio XLA / shapes "
          f"{cost.get('flops', float('nan')) / shapes:.4f}; "
          f"{text.count('tpu_custom_call')} tpu_custom_call mentions")
    mem = exe.memory_analysis()
    print(f"xla_cost_check memory_analysis: {mem}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
