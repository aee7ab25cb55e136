#!/usr/bin/env python3
"""The looped LM against its plain reference, at published widths on the chip
(or `--tiny` on the CPU): more than one loss. `reference_check.py` cannot
take this configuration unedited (it reads OLMoE's loss parts and router ops
by name), so this is the same check for a reference whose `loss_parts`
returns `loss`, `expected_ce`, `entropy`, `exit_probs` and per-pass logits.

    python3 benchmark/reference_check_ouro.py [--config ouro_2_6b] [--seed N]
        [--workload CELL] [--steps N] [--tiny]

One training step of the system (the configuration's program under its
optimizer and AMP setting, built as `run.py` builds it, at the lengths of
the configuration's cell) on one seeded batch, and the reference
(`references/<reference.check.module>.py`: float32, every product at
"highest") on the same weights and batch. With `--steps N` the system first
trains N steps over the cell's pool of batches, as a run of the cell does.
Compared, each under a tolerance written in the configuration's
`reference.check` with its reason:

  * the logits of EVERY pass on the last `last_positions` positions (the
    head is applied once a pass; a pass that starts from the wrong state, or
    a head that is not shared, shows in its pass and the later ones);
  * `loss`, `expected_ce`, `entropy`, and the mean exit distribution;
  * the gradients of the parameters `reference.check.gradients` names, in
    the Frobenius norm: every layer weight, the final norm and the head get
    a contribution from each pass, the gate from all but the last.

The reference is computed as `reference.check.reference_args` says (a block
of queries at a time) and its gradient with `remat` (`jax.checkpoint` per
layer application and head): that is its memory beside a chip's 16 GB, not
its mathematics. Then the reference once more with everything, the gate, the
exit distribution and the losses included, in bfloat16, held to the same
limits against the float32 reference: every reading is printed with what its
limit says of it, and at least one has to be refused, or the check would
accept a lower precision than the configuration states. After `--steps` the
system's loss is also held to the traffic file's in-run limit, the bfloat16
reference's distance is read against it, and the comparisons of logits and
gradients are readings only: their limits are stated for the initial
weights. Exits non-zero on any miss. The system's arrays are released before
the reference's gradient is computed: both do not fit a chip.
"""

import argparse
import functools
import importlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from reference_check import cell_of, fetch_names, fro, load_json  # noqa: E402

SCALARS = ("loss", "expected_ce", "entropy")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="ouro_2_6b")
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
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as fluid
    from generators.train_loop_checked import reference_args
    from system import System, make_pool

    devices = jax.devices()[:1]
    print(f"reference_check_ouro: {args.config} seed {args.seed} on "
          f"{devices[0].platform} {devices[0].device_kind}", flush=True)
    if not args.tiny and devices[0].platform != "tpu":
        sys.exit("reference_check_ouro: published widths need the TPU "
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
        print(f"reference_check_ouro: {args.steps} steps over {len(pool)} "
              f"batches first, loss now "
              f"{float(np.asarray(loss).reshape(-1)[0]):.6f}", flush=True)
    host = pool[args.steps % len(pool)]
    params, kw = reference_args(system, ref)
    params = {n: np.asarray(v) for n, v in params.items()}   # off the chip
    kw.update(check.get("reference_args", {}))

    # -- the system's step ----------------------------------------------------
    # every pass's logits are what the products with head.w write; only
    # their last positions are fetched (a slice appended to the program: a
    # whole pass's logits are 0.4 GB, and four of them kept to the end of
    # the step would not fit beside it)
    block = system.main.global_block()
    heads = [block.var(op.output("Out")[0]) for op in block.ops
             if op.type == "mul" and "head.w" in op.input_arg_names]
    seq_len = heads[0].shape[1]
    last = min(check["last_positions"], seq_len)
    with fluid.program_guard(system.main, system.startup):
        tails = [fluid.layers.slice(h, axes=[1], starts=[seq_len - last],
                                    ends=[seq_len]) for h in heads]
    fetch = fetch_names(config, system.build_args,
                        list(SCALARS) + ["exit_probs"])
    grad_names = list(check["gradients"])
    got = iter(np.asarray(x) for x in system.exe.run(
        system.main, feed=host,
        fetch_list=[fetch[n] for n in SCALARS] + [fetch["exit_probs"]]
        + tails + [n + "@GRAD" for n in grad_names], scope=system.scope))
    parts = {n: float(next(got).reshape(-1)[0]) for n in SCALARS}
    exit_probs = next(got).astype(np.float64)
    logits = np.stack([next(got).astype(np.float32) for _ in tails])
    grads = {n: next(got) for n in grad_names}
    del got
    system.close()
    del system                      # the weights, moments and executables
    jax.clear_caches()

    # -- the reference ------------------------------------------------------------
    tokens, labels = jnp.asarray(host["tokens"]), jnp.asarray(host["labels"])
    dev = {n: jnp.asarray(v) for n, v in params.items()}

    def small(out):
        return {n: np.asarray(out[n], np.float64) for n in
                SCALARS + ("exit_probs", "ce")}

    def loss_of(sub, rest, dtype=jnp.float32):
        out = ref.loss_parts({**rest, **sub}, tokens, labels, dtype=dtype,
                             remat=True, **kw)
        return out["loss"]

    def run_reference(dtype):
        """(small parts, logits [R, B, last, V] float32, gradients): the
        forward pass op by op (the unrolled passes repeat a few small
        programs), the gradient as one program, which XLA schedules."""
        out = ref.loss_parts(dev, tokens, labels, last=last, dtype=dtype,
                             **kw)
        own, own_logits = small(out), np.asarray(out["logits"], np.float32)
        del out
        wrt = {n: dev[n] for n in grad_names}
        own_grads = jax.jit(jax.grad(functools.partial(loss_of, dtype=dtype)))(
            wrt, dev)
        return own, own_logits, {n: np.asarray(v, np.float32)
                                 for n, v in own_grads.items()}

    want, want_logits, want_grads = run_reference(jnp.float32)
    low, low_logits, low_grads = run_reference(jnp.bfloat16)

    # -- the comparison -------------------------------------------------------------
    failures = []

    def verdict(what, value, limit, must_fail=False, decides=True):
        ok = (value > limit) if must_fail else (value <= limit)
        mark = ("ok  " if ok else "FAIL") if decides else \
            ("read (holds)" if ok else "read (does not hold)")
        print(f"reference_check_ouro: {mark} {what}: {value:.6g} "
              f"{'>' if must_fail else '<='} {limit}", flush=True)
        if decides and not ok:
            failures.append(what)

    # the limits on logits and gradients are stated for the initial weights
    at_start = args.steps == 0
    print(f"reference_check_ouro: per pass, mean cross-entropy "
          f"{np.round(want['ce'], 5).tolist()} (bfloat16 "
          f"{np.round(low['ce'], 5).tolist()}); mean exit distribution "
          f"system {np.round(exit_probs, 6).tolist()}, reference "
          f"{np.round(want['exit_probs'], 6).tolist()}, bfloat16 "
          f"{np.round(low['exit_probs'], 6).tolist()}", flush=True)
    low_reads = []
    for t in range(logits.shape[0]):
        err = np.abs(logits[t] - want_logits[t])
        print(f"reference_check_ouro: pass {t + 1} logits on the last {last} "
              f"positions: reference std {float(np.std(want_logits[t])):.4f}, "
              f"|difference| mean {float(err.mean()):.5f} max "
              f"{float(err.max()):.5f}", flush=True)
        verdict(f"pass {t + 1} logits, largest |difference|",
                float(err.max()), check["logits_atol"], decides=at_start)
        low_reads.append((f"pass {t + 1} logits, largest |difference|",
                          float(np.abs(low_logits[t] - want_logits[t]).max()),
                          check["logits_atol"]))
    for n in SCALARS:
        print(f"reference_check_ouro: {n}: system {parts[n]:.6f}, reference "
              f"{float(want[n]):.6f}, bfloat16 reference {float(low[n]):.6f}",
              flush=True)
        verdict(f"{n} against the reference", abs(parts[n] - float(want[n])),
                check["loss_atol"][n])
        low_reads.append((n, abs(float(low[n]) - float(want[n])),
                          check["loss_atol"][n]))
    verdict("mean exit distribution, largest |difference|",
            float(np.abs(exit_probs - want["exit_probs"]).max()),
            check["exit_probs_atol"])
    low_reads.append(("mean exit distribution, largest |difference|",
                      float(np.abs(low["exit_probs"]
                                   - want["exit_probs"]).max()),
                      check["exit_probs_atol"]))
    for n in grad_names:
        verdict(f"gradient of {n}, Frobenius", fro(grads[n], want_grads[n]),
                check["gradient_rel"], decides=at_start)
        low_reads.append((f"gradient of {n}", fro(low_grads[n], want_grads[n]),
                          check["gradient_rel"]))
    # the nearest precision below, under the same limits: it has to come out
    # as not correct, so at least one of its comparisons has to fail
    for what, value, limit in low_reads:
        print(f"reference_check_ouro: the bfloat16 reference's {what}: "
              f"{value:.6g}, {'refused' if value > limit else 'accepted'} "
              f"by {limit}", flush=True)
    refused = sum(value > limit for _, value, limit in low_reads)
    verdict(f"the bfloat16 reference must NOT be judged correct: its "
            f"comparisons refused ({refused} of {len(low_reads)})",
            refused, 0, must_fail=True)
    if args.steps:
        in_run = traffic["reference_check"]["loss_atol"]
        verdict(f"loss after {args.steps} steps under the in-run limit",
                abs(parts["loss"] - float(want["loss"])), in_run)
        verdict(f"the bfloat16 reference's loss after {args.steps} steps is "
                f"refused by the in-run limit",
                abs(float(low["loss"]) - float(want["loss"])), in_run,
                must_fail=True, decides=False)
    if failures:
        sys.exit(f"reference_check_ouro: FAIL {failures}")
    print("reference_check_ouro: PASS", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
