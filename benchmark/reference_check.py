#!/usr/bin/env python3
"""The system against the configuration's plain reference, at published
widths on the chip (or `--tiny` on the CPU): more than one loss.

    python3 benchmark/reference_check.py --config olmoe_1b_7b [--seed N]
        [--workload CELL] [--steps N] [--tiny]

One training step of the system (the configuration's program under its
optimizer and AMP setting, built as `run.py` builds it, at the lengths of
the configuration's cell; `--workload` says which if several use it) on one
seeded batch, and the reference (`references/<reference.module>.py`:
float32, every product at "highest") on the same weights and batch. With
`--steps N` the system first trains N steps over the cell's pool of batches,
as a run of the cell does, and the comparison is made on those weights:
where a run's own check (`generators/train_loop_checked.py`) decides
`correct`. Compared, each under a tolerance written in the configuration's
`reference.check` with its reason:

  * logits on the last `last_positions` positions, which the reference
    computes against the whole context, given the system's routing;
  * the three loss parts and their sum;
  * the gradients of the parameters `reference.check.gradients` names, in
    the Frobenius norm. A lower precision flips near-ties in the router, and
    a flipped token moves whole rows of two experts' gradients: so the
    gradients are compared with the reference GIVEN the system's routing
    ("what the experts compute"), and the routing itself is compared apart
    ("which experts": the share of tokens whose expert set differs).

Then the reference once more with everything, router and losses included,
in bfloat16, held to the same limits against the float32 reference: every
reading is printed with what its limit says of it, and at least one has to
be refused, or the check would accept a lower precision than the
configuration states. After `--steps` the system's loss is also held to the
traffic file's in-run limit, the bfloat16 reference's distance is read
against it, and the comparisons of logits and gradients are readings only:
their limits are stated for the initial weights. Exits non-zero on any
miss. The system's arrays are released before the reference's backward pass
runs: both do not fit a chip.
"""

import argparse
import functools
import importlib
import inspect
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def cell_of(config_name, workload):
    """The cell `workload`, or the one cell that uses the configuration."""
    if workload:
        return load_json("workloads", workload + ".json")
    cells = [c for c in (load_json("workloads", n) for n in
                         sorted(os.listdir(os.path.join(HERE, "workloads"))))
             if c["config"] == config_name]
    if len(cells) != 1:
        sys.exit(f"reference_check: {[c['name'] for c in cells]} use "
                 f"{config_name}: name one with --workload")
    return cells[0]


def fro(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--workload")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    config = load_json("configs", args.config + ".json")
    cell = cell_of(args.config, args.workload)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    check = config["reference"]["check"]
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        check = {**check, **config["tiny"]["reference"].get("check", {})}
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from system import System, make_pool

    devices = jax.devices()[:1]
    print(f"reference_check: {args.config} seed {args.seed} on "
          f"{devices[0].platform} {devices[0].device_kind}", flush=True)
    if not args.tiny and devices[0].platform != "tpu":
        sys.exit("reference_check: published widths need the TPU "
                 "(--tiny rehearses on the CPU)")
    ref = importlib.import_module("references." + check["module"])
    batch = config["tiny"]["batch"] if args.tiny else traffic["batch"]
    system = System(config, cell, traffic, devices, batch, tiny=args.tiny)
    ranges = dict(config["feed_ranges"])
    if args.tiny:
        ranges.update(config["tiny"].get("feed_ranges", {}))
    pool = make_pool(system.feeds, ranges, batch, traffic["pool_batches"],
                     args.seed)
    for i in range(args.steps):                 # as a run of the cell does
        loss = system.step(system.place(pool[i % len(pool)]))
    if args.steps:
        print(f"reference_check: {args.steps} steps over {len(pool)} batches "
              f"first, loss now {float(np.asarray(loss).reshape(-1)[0]):.6f}",
              flush=True)
    host = pool[args.steps % len(pool)]
    from generators.train_loop_checked import reference_args
    params, kw = reference_args(system, ref)
    params = {n: np.asarray(v) for n, v in params.items()}   # off the chip
    fwd_kw = {k: v for k, v in kw.items()
              if k in inspect.signature(ref.forward).parameters}

    # -- the system's step ------------------------------------------------------
    block = system.main.global_block()
    index_vars = [op.outputs["TopKIndex"][0] for op in block.ops
                  if op.type == "moe_router"]
    part_names = ["loss", "ce", "load_balance", "z_loss"]
    fetch = fetch_names(config, system.build_args, part_names + ["logits"])
    grad_names = list(check["gradients"])
    got = iter(np.asarray(x) for x in system.exe.run(
        system.main, feed=host,
        fetch_list=[fetch[n] for n in part_names] + [fetch["logits"]]
        + index_vars + [n + "@GRAD" for n in grad_names], scope=system.scope))
    parts = {n: float(next(got).reshape(-1)[0]) for n in part_names}
    logits = next(got).astype(np.float32)
    routing = [next(got) for _ in index_vars]
    grads = {n: next(got) for n in grad_names}
    del got
    system.close()
    del system                      # the weights, moments and executables
    jax.clear_caches()

    # -- the reference ------------------------------------------------------------
    tokens, labels = jnp.asarray(host["tokens"]), jnp.asarray(host["labels"])
    dev = {n: jnp.asarray(v) for n, v in params.items()}
    last = min(check["last_positions"], tokens.shape[1])

    def scalars(out):
        return {n: out[n] for n in part_names}

    # forward passes op by op, not under one jit: the loop over experts
    # repeats a few small programs; the unrolled pass as one program takes
    # minutes to compile
    own = ref.loss_parts(dev, tokens, labels, **kw)
    own_parts = {n: float(v) for n, v in scalars(own).items()}
    own_index = [np.asarray(i) for i in own["index"]]
    del own
    given = [jnp.asarray(r.reshape(i.shape)) for r, i in zip(routing, own_index)]
    want_logits, _ = ref.forward(dev, tokens, last=last, routing=given,
                                 **fwd_kw)
    want_logits = np.asarray(want_logits)
    wrt = {n: dev[n] for n in grad_names}

    def loss_of(sub, rest, routing, dtype=jnp.float32):
        out = ref.loss_parts({**rest, **sub}, tokens, labels,
                             routing=routing, dtype=dtype, **kw)
        return out["loss"], scalars(out)

    # the backward pass as one program: op by op every expert's residuals
    # stay alive at once, 15 GB at published widths; XLA schedules them
    (_, given_parts), want_grads = jax.jit(jax.value_and_grad(
        loss_of, has_aux=True))(wrt, dev, given)
    given_parts = {n: float(v) for n, v in given_parts.items()}
    want_grads = {n: np.asarray(v) for n, v in want_grads.items()}

    # the nearest precision below: everything in bfloat16
    low = scalars(ref.loss_parts(dev, tokens, labels, dtype=jnp.bfloat16,
                                 **kw))
    low = {n: float(v) for n, v in low.items()}
    low_logits, _ = ref.forward(dev, tokens, last=last, routing=given,
                                dtype=jnp.bfloat16, **fwd_kw)
    low_logits = np.asarray(low_logits, np.float32)
    _, low_grads = jax.jit(jax.value_and_grad(
        functools.partial(loss_of, dtype=jnp.bfloat16), has_aux=True))(
            wrt, dev, given)
    low_grads = {n: np.asarray(v, np.float32) for n, v in low_grads.items()}

    # -- the comparison -------------------------------------------------------------
    failures = []

    def verdict(what, value, limit, must_fail=False, decides=True):
        ok = (value > limit) if must_fail else (value <= limit)
        mark = ("ok  " if ok else "FAIL") if decides else \
            ("read (holds)" if ok else "read (does not hold)")
        print(f"reference_check: {mark} {what}: {value:.6g} "
              f"{'>' if must_fail else '<='} {limit}", flush=True)
        if decides and not ok:
            failures.append(what)

    # the limits on logits and gradients are stated for the initial weights:
    # on weights that have memorised their pool the gradients are small
    # differences of large terms and their relative error is not bounded
    at_start = args.steps == 0

    unlike = [np.sort(a.reshape(b.shape), -1) != np.sort(b, -1)
              for a, b in zip(routing, own_index)]
    differ = [np.mean(np.any(u, axis=-1)) for u in unlike]
    moved = [np.mean(u) for u in unlike]
    print(f"reference_check: routing: share of tokens whose expert set "
          f"differs between the system and the float32 reference, per layer "
          f"{[round(float(x), 5) for x in differ]}; share of assignments "
          f"{[round(float(x), 5) for x in moved]}", flush=True)
    verdict("share of tokens whose expert set differs (worst layer)",
            float(max(differ)), check["routing_differs_max"])
    err = np.abs(logits[:, -last:] - want_logits)
    print(f"reference_check: logits on the last {last} positions against the "
          f"whole context, reference given the system's routing: reference "
          f"std {float(np.std(want_logits)):.4f}, |difference| "
          f"mean {float(err.mean()):.5f} max {float(err.max()):.5f}",
          flush=True)
    verdict("logits, largest |difference|", float(err.max()),
            check["logits_atol"], decides=at_start)
    for n in part_names:
        print(f"reference_check: {n}: system {parts[n]:.6f}, reference "
              f"{own_parts[n]:.6f} (own routing), {given_parts[n]:.6f} "
              f"(system's routing), bfloat16 reference {low[n]:.6f}",
              flush=True)
        verdict(f"{n} against the reference, own routing",
                abs(parts[n] - own_parts[n]), check["loss_atol"][n])
    for n in grad_names:
        verdict(f"gradient of {n}, Frobenius, reference given the system's "
                f"routing", fro(grads[n], want_grads[n]),
                check["gradient_rel"], decides=at_start)
    # the nearest precision below, under the same limits: it has to come out
    # as not correct, so at least one of its comparisons has to fail
    low_err = np.abs(low_logits - want_logits)
    low_rel = {n: fro(low_grads[n], want_grads[n]) for n in grad_names}
    low_reads = [(n, abs(low[n] - own_parts[n]), check["loss_atol"][n])
                 for n in part_names]
    low_reads.append(("logits, same routing, largest |difference|",
                      float(low_err.max()), check["logits_atol"]))
    low_reads += [(f"gradient of {n}", low_rel[n], check["gradient_rel"])
                  for n in grad_names]
    for what, value, limit in low_reads:
        print(f"reference_check: the bfloat16 reference's {what}: "
              f"{value:.6g}, {'refused' if value > limit else 'accepted'} "
              f"by {limit}", flush=True)
    refused = sum(value > limit for _, value, limit in low_reads)
    verdict(f"the bfloat16 reference must NOT be judged correct: its "
            f"comparisons refused ({refused} of {len(low_reads)})",
            refused, 0, must_fail=True)
    if args.steps:
        # where a run of the cell decides `correct`: its own limit, and what
        # that limit says of the lower precision on these weights
        in_run = traffic["reference_check"]["loss_atol"]
        verdict(f"loss after {args.steps} steps under the in-run limit",
                abs(parts["loss"] - own_parts["loss"]), in_run)
        verdict(f"the bfloat16 reference's loss after {args.steps} steps is "
                f"refused by the in-run limit",
                abs(low["loss"] - own_parts["loss"]), in_run, must_fail=True,
                decides=False)
    if failures:
        sys.exit(f"reference_check: FAIL {failures}")
    print("reference_check: PASS", flush=True)
    return 0


def fetch_names(config, build_args, wanted):
    """The program's variable names for the builder's fetches `wanted`.
    `System` keeps only the loss; the builder run again in a scratch program
    under a fresh unique-name guard gives every variable the same name."""
    import paddle_tpu as fluid
    module, _, attr = config["builder"].partition(":")
    builder = getattr(importlib.import_module(module), attr)
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard():
        _, fetches = builder(**build_args)
    return {n: fetches[n].name for n in wanted}


if __name__ == "__main__":
    sys.exit(main())
