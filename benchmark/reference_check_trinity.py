#!/usr/bin/env python3
"""The gated sliding-window / full-attention sparse-expert LM (Trinity-Mini)
against its plain reference, at published widths on the chip (or `--tiny` on
the CPU). `reference_check_kanana2.py` reads the same loss parts and biases,
`reference_check_mellum2.py` plants faults and probes the mask; this check
does both, for a model whose attention kinds differ in whether they turn.

    python3 benchmark/reference_check_trinity.py [--config trinity_mini_26b_a3b]
        [--seed N] [--workload CELL] [--steps N] [--control-batches N]
        [--tiny]

One training step of the system (the configuration's program under its
optimizer and AMP setting, built as `run.py` builds it, at the lengths of the
configuration's cell) on one seeded batch, and the reference
(`references/<reference.check.module>.py`: float32, every product at
"highest", attention as a masked softmax whose mask is the two inequalities,
rotary on the sliding layers alone, key and value heads by `jnp.repeat`, the
gate on the head-merged context, four norms a layer, a loop over the held
experts, the same share and vocabulary slice) on the same weights, the same
router biases and the same batch. With `--steps N` the system first trains N
steps over the cell's pool of batches, as a run of the cell does, so that the
biases are no longer 0, and prints how the held experts' load and the biases
moved. Compared, each under a tolerance written in the configuration's
`reference.check` with its reason:

  * the logits on the last `last_positions` positions;
  * `loss` and `ce`;
  * every expert layer's bias after the step against `next_bias` on the
    system's own counts: exactly;
  * the assignments to each of the routed experts, per layer (a reading: a
    near-tie flips on bf16 inputs; the share of assignments that differ);
  * the gradients of the parameters `reference.check.gradients` names, in
    the Frobenius norm, each under `gradient_rel`, or under its own entry of
    `gradient_rel_by_name` where it has one.

Then the reference once more with everything, the router, the softmax and the
loss included, in bfloat16, held to the same limits against the float32
reference: every reading is printed with what its limit says of it, and at
least one has to be refused. Then the reference with each fault of
`reference.check.faults` planted (`references/trinity_reference.py::FAULTS`),
its forward pass under the limits on the logits and the loss, and its
gradients too where the forward pass lets it through: each has to be refused
by at least one comparison. `bias_in_weights` is no fault while the biases
are 0, so it is planted on biases of `reference.check.planted_bias.std` drawn
from the seed (what some hundreds of steps at 0.001 a step reach), the
reference with and without the fault on the same planted biases, forward and
gradients. A
window one key too long moves a row's output by one key's weight in 2048,
which no limit that leaves bf16 room can see in a logit or a loss; the mask
probe is what refuses it (`reference_check_mellum2.py::mask_probe`:
`layers.fused_attention(window=...)` alone through the Executor, under AMP,
on scores of std `mask_probe.score_std`, against `masked_attention` with and
without each window fault), at this cell's window and length.

After `--steps` the system's loss is also held to the traffic file's in-run
limit, and so is the step's update of the parameters that the in-run
comparison names (`generators/train_loop_reference_update.py`: the change of
each against Adam on the reference's gradient from the system's own moments;
at the initial weights the moments are 0 and Adam is a sign function, so the
update is a reading there), with the update that a bfloat16 state makes of the
bfloat16 reference's gradient beside it: it has to be refused. The
comparisons of logits and gradients are readings only after the steps, and
the faults and the probe are not run again. `--control-batches N` then reads
the bfloat16 reference's loss against the float32 one on N more batches of
the pool, on the same weights (a forward pass each): how often the in-run
limit on the loss refuses that precision, which is a draw (the bfloat16 loss
lies on a grid), where the limit on the update refuses it every time. Exits
non-zero on any miss. The system's arrays are released before the
reference's gradient is computed: both do not fit a chip.
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
from reference_check_mellum2 import mask_probe  # noqa: E402

SCALARS = ("loss", "ce")
# the faults of the mask alone, which the mask probe judges
PROBED = ("window_off_by_one", "no_window")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="trinity_mini_26b_a3b")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--workload")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--control-batches", type=int, default=0)
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
    from generators.train_loop_reference_update import (
        optimizer_state, reference_delta, update_gap)
    from system import System, make_pool

    me = "reference_check_trinity"
    devices = jax.devices()[:1]
    print(f"{me}: {args.config} seed {args.seed} on {devices[0].platform} "
          f"{devices[0].device_kind}", flush=True)
    if not args.tiny and devices[0].platform != "tpu":
        sys.exit(f"{me}: published widths need the TPU (--tiny rehearses on "
                 f"the CPU)")
    ref = importlib.import_module("references." + check["module"])
    batch = config["tiny"]["batch"] if args.tiny else traffic["batch"]
    system = System(config, cell, traffic, devices, batch, tiny=args.tiny)
    ranges = dict(config["feed_ranges"])
    if args.tiny:
        ranges.update(config["tiny"].get("feed_ranges", {}))
    pool = make_pool(system.feeds, ranges, batch, traffic["pool_batches"],
                     args.seed)
    fetch = fetch_names(config, system.build_args,
                        list(SCALARS) + ["logits", "tokens_per_expert"])
    first = system.build_args.get("first_expert", 0)
    held = system.build_args.get("experts_held")

    def load(counts):
        """Assignments to the held experts, and the rows their groups take
        in whole 128-row tiles, per layer."""
        mine = np.asarray(counts)[:, first:first + held]
        return mine.sum(1).tolist(), (-(-mine // 128) * 128).sum(1).tolist()

    biases = sorted(p.name for p in
                    system.main.global_block().all_parameters()
                    if not p.trainable)
    gamma = system.build_args["bias_update_rate"]

    def read_biases():
        return {n: np.asarray(system.scope.find_var(n)) for n in biases}

    for i in range(args.steps):                 # as a run of the cell does
        loss, counts = system.exe.run(
            system.main, feed=system.place(pool[i % len(pool)]),
            fetch_list=[system.loss, fetch["tokens_per_expert"]],
            return_numpy=False, scope=system.scope)
        if held and (i % 25 == 0 or i == args.steps - 1):
            now = read_biases()
            print(f"{me}: step {i} loss "
                  f"{float(np.asarray(loss).reshape(-1)[0]):.4f}; held "
                  f"assignments and padded rows per layer {load(counts)}; "
                  f"largest |b| per layer "
                  f"{[round(float(np.abs(now[n]).max()), 6) for n in biases]}"
                  f"; largest and smallest count of an expert "
                  f"{int(np.max(counts))}, {int(np.min(counts))}",
                  flush=True)
    host = pool[args.steps % len(pool)]
    params, kw = reference_args(system, ref)
    params = {n: np.asarray(v) for n, v in params.items()}   # off the chip
    kw.update(check.get("reference_args", {}))

    # -- the system's step ----------------------------------------------------
    block = system.main.global_block()
    logits_var = block.var(fetch["logits"])
    seq_len = logits_var.shape[1]
    last = min(check["last_positions"], seq_len)
    with fluid.program_guard(system.main, system.startup):
        tail = fluid.layers.slice(logits_var, axes=[1],
                                  starts=[seq_len - last], ends=[seq_len])
    update = traffic["reference_check"].get("update", {"parameters": []})
    grad_names = list(check["gradients"])
    grad_names += [n for n in update["parameters"] if n not in grad_names]
    before = optimizer_state(system, update["parameters"])
    got = iter(np.asarray(x) for x in system.exe.run(
        system.main, feed=host,
        fetch_list=[fetch[n] for n in SCALARS]
        + [fetch["tokens_per_expert"], tail]
        + [n + "@GRAD" for n in grad_names], scope=system.scope))
    parts = {n: float(next(got).reshape(-1)[0]) for n in SCALARS}
    counts = next(got).astype(np.int64)
    logits = next(got).astype(np.float32)
    grads = {n: next(got) for n in grad_names}
    del got
    after = read_biases()
    stepped = {n: np.asarray(system.scope.find_var(n)) - state["Param"]
             for n, (_, state) in before.items()}
    system_args = dict(system.build_args)
    system.close()
    del system                      # the weights, moments and executables
    jax.clear_caches()

    # -- the reference ------------------------------------------------------------
    tokens, labels = jnp.asarray(host["tokens"]), jnp.asarray(host["labels"])
    dev = {n: jnp.asarray(v) for n, v in params.items()}

    def loss_of(sub, rest, dtype=jnp.float32, fault=None):
        out = ref.loss_parts({**rest, **sub}, tokens, labels, dtype=dtype,
                             remat=True, fault=fault, **kw)
        return out["loss"]

    def run_reference(dtype, fault=None, on=None):
        on = dev if on is None else on      # the weights it is computed on
        out = ref.loss_parts(on, tokens, labels, last=last, dtype=dtype,
                             fault=fault, **kw)
        own = {n: float(out[n]) for n in SCALARS}
        own_counts = np.asarray(out["tokens_per_expert"], np.int64)
        own_logits = np.asarray(out["logits"], np.float32)
        del out
        wrt = {n: on[n] for n in grad_names}
        own_grads = jax.jit(jax.grad(functools.partial(
            loss_of, dtype=dtype, fault=fault)))(wrt, on)
        return own, own_counts, own_logits, {
            n: np.asarray(v, np.float32) for n, v in own_grads.items()}

    want, want_counts, want_logits, want_grads = run_reference(jnp.float32)
    low, low_counts, low_logits, low_grads = run_reference(jnp.bfloat16)

    # -- the comparison -------------------------------------------------------------
    failures = []

    def verdict(what, value, limit, must_fail=False, decides=True):
        ok = (value > limit) if must_fail else (value <= limit)
        mark = ("ok  " if ok else "FAIL") if decides else \
            ("read (holds)" if ok else "read (does not hold)")
        print(f"{me}: {mark} {what}: {value:.6g} "
              f"{'>' if must_fail else '<='} {limit}", flush=True)
        if decides and not ok:
            failures.append(what)

    def grad_limit(name):
        return check.get("gradient_rel_by_name", {}).get(
            name, check["gradient_rel"])

    at_start = args.steps == 0      # limits on logits and gradients: there
    for layer, n in enumerate(biases):
        want_bias = np.asarray(ref.next_bias(params[n], counts[layer], gamma))
        wrong = int(np.sum(after[n] != want_bias))
        print(f"{me}: {n}: largest |b| before {np.abs(params[n]).max():.6f}, "
              f"after {np.abs(after[n]).max():.6f}; up "
              f"{int(np.sum(after[n] > params[n]))}, down "
              f"{int(np.sum(after[n] < params[n]))} of {after[n].size}",
              flush=True)
        verdict(f"{n} after the step is next_bias(b, the system's counts, "
                f"{gamma}): values that differ", wrong, 0)
    if held:
        print(f"{me}: held assignments and padded rows per layer: system "
              f"{load(counts)}, reference {load(want_counts)}; even routing "
              f"gives {counts[0].sum() * held // counts.shape[1]} a layer",
              flush=True)
    moved = np.abs(counts - want_counts).sum() / 2 / counts.sum()
    low_moved = np.abs(low_counts - want_counts).sum() / 2 / counts.sum()
    print(f"{me}: share of assignments on another expert than the "
          f"reference's: system {moved:.5f}, bfloat16 reference "
          f"{low_moved:.5f} (a reading)", flush=True)
    err = np.abs(logits - want_logits)
    print(f"{me}: logits on the last {last} positions: reference std "
          f"{float(np.std(want_logits)):.4f}, |difference| mean "
          f"{float(err.mean()):.5f} max {float(err.max()):.5f}", flush=True)
    verdict("logits, largest |difference|", float(err.max()),
            check["logits_atol"], decides=at_start)
    low_reads = [("logits, largest |difference|",
                  float(np.abs(low_logits - want_logits).max()),
                  check["logits_atol"])]
    for n in SCALARS:
        print(f"{me}: {n}: system {parts[n]:.6f}, reference {want[n]:.6f}, "
              f"bfloat16 reference {low[n]:.6f}", flush=True)
        verdict(f"{n} against the reference", abs(parts[n] - want[n]),
                check["loss_atol"][n])
        low_reads.append((n, abs(low[n] - want[n]), check["loss_atol"][n]))
    for n in grad_names:
        print(f"{me}: gradient of {n}: reference norm "
              f"{float(np.linalg.norm(want_grads[n])):.4g}", flush=True)
        verdict(f"gradient of {n}, Frobenius", fro(grads[n], want_grads[n]),
                grad_limit(n), decides=at_start)
        low_reads.append((f"gradient of {n}", fro(low_grads[n], want_grads[n]),
                          grad_limit(n)))
    # the in-run comparison's second number: the step's update. It decides
    # where the in-run comparison is made, on moments that some steps built
    for n, (attrs, state) in before.items():
        step = reference_delta(attrs, state, want_grads[n])
        verdict(f"update of {n} against Adam on the reference's gradient, "
                f"|difference| / |reference's|", update_gap(stepped[n], step),
                update["rel_atol"], decides=not at_start)
        low_step = update_gap(reference_delta(
            attrs, state, low_grads[n], dtype="bfloat16"), step)
        if at_start:
            print(f"{me}: the bfloat16 reference's update of {n} in a "
                  f"bfloat16 state: {low_step:.6g} (a reading)", flush=True)
        else:
            low_reads.append((f"update of {n}", low_step,
                              update["rel_atol"]))
    # the nearest precision below, under the same limits: it has to come out
    # as not correct, so at least one of its comparisons has to fail
    for what, value, limit in low_reads:
        print(f"{me}: the bfloat16 reference's {what}: {value:.6g}, "
              f"{'refused' if value > limit else 'accepted'} by {limit}",
              flush=True)
    refused = sum(value > limit for _, value, limit in low_reads)
    verdict(f"the bfloat16 reference must NOT be judged correct: its "
            f"comparisons refused ({refused} of {len(low_reads)})",
            refused, 0, must_fail=True)

    # -- planted faults: each has to be refused -----------------------------------
    faults = check.get("faults", []) if at_start else []
    probe = check.get("mask_probe") if at_start else None
    planted = check.get("planted_bias", {"faults": []})
    refused_by = {f: [] for f in faults}

    def refusals(bad, base):
        """The comparisons of (scalars, counts, logits, gradients) that
        refuse `bad` against `base`, the forward pass's first."""
        reads = [("logits", float(np.abs(bad[2] - base[2]).max()),
                  check["logits_atol"])]
        reads += [(n, abs(bad[0][n] - base[0][n]), check["loss_atol"][n])
                  for n in SCALARS]
        reads += [(f"gradient of {n}", fro(bad[3][n], base[3][n]),
                   grad_limit(n)) for n in grad_names]
        return reads

    for fault in faults:
        if fault in planted["faults"]:
            # no fault at b = 0: the reference with and without it on the
            # same planted biases
            rng = np.random.RandomState(args.seed % (2 ** 32))
            biased = {**dev, **{n: jnp.asarray(
                rng.randn(*params[n].shape).astype(np.float32)
                * planted["std"]) for n in biases}}
            reads = refusals(run_reference(jnp.float32, fault, biased),
                             run_reference(jnp.float32, None, biased))
            del biased
        else:                       # the forward pass alone: seconds each
            bad = ref.loss_parts(dev, tokens, labels, last=last, fault=fault,
                                 **kw)
            reads = [("logits", float(np.abs(
                np.asarray(bad["logits"], np.float32) - want_logits).max()),
                check["logits_atol"])]
            reads += [(n, abs(float(bad[n]) - want[n]),
                       check["loss_atol"][n]) for n in SCALARS]
            del bad
        refused_by[fault] = [w for w, value, limit in reads if value > limit]
        print(f"{me}: fault {fault} ({ref.FAULTS[fault]}): "
              + ", ".join(f"{w} {value:.6g} ("
                          f"{'refused' if value > limit else 'accepted'} by "
                          f"{limit})" for w, value, limit in reads),
              flush=True)
        if not refused_by[fault] and fault not in PROBED \
                and fault not in planted["faults"]:
            # the forward pass let it through: its gradients have to show it
            _, _, _, bad_grads = run_reference(jnp.float32, fault)
            reads = [(f"gradient of {n}", fro(bad_grads[n], want_grads[n]),
                      grad_limit(n)) for n in grad_names]
            refused_by[fault] = [w for w, value, limit in reads
                                 if value > limit]
            worst = max(reads, key=lambda r: r[1] / r[2])
            print(f"{me}: fault {fault}: its gradients: refused by "
                  f"{len(refused_by[fault])} of {len(reads)}; furthest past "
                  f"its limit: {worst[0]} {worst[1]:.6g} against {worst[2]}",
                  flush=True)
    if probe:
        del dev
        jax.clear_caches()
        _, probes = mask_probe(fluid, jax, jnp, np, ref, probe, config,
                               system_args, args.seed)
        for what, value in probes:
            if what == "window_on_full":    # Mellum2's: no such fault here
                continue
            if what not in refused_by:
                verdict(f"mask probe, {what}: largest |difference|", value,
                        probe["atol"])
                continue
            print(f"{me}: mask probe, fault {what}: largest |difference| "
                  f"{value:.6g}, "
                  f"{'refused' if value > probe['atol'] else 'accepted'} by "
                  f"{probe['atol']}", flush=True)
            if value > probe["atol"]:
                refused_by[what].append("mask probe")
    for fault in faults:
        verdict(f"fault {fault} must NOT be judged correct: comparisons "
                f"that refuse it", len(refused_by[fault]), 0, must_fail=True)
    if args.steps:
        in_run = traffic["reference_check"]["loss_atol"]
        for i in range(1, args.control_batches + 1):
            other = pool[(args.steps + i) % len(pool)]
            sides = [float(ref.loss_parts(
                dev, jnp.asarray(other["tokens"]),
                jnp.asarray(other["labels"]), dtype=dtype, **kw)["loss"])
                for dtype in (jnp.float32, jnp.bfloat16)]
            verdict(f"the bfloat16 reference's loss on pool batch "
                    f"{(args.steps + i) % len(pool)} ({sides[1]:.6f} "
                    f"against {sides[0]:.6f}) is refused by the in-run limit",
                    abs(sides[1] - sides[0]), in_run, must_fail=True,
                    decides=False)
        verdict(f"loss after {args.steps} steps under the in-run limit",
                abs(parts["loss"] - want["loss"]), in_run)
        verdict(f"the bfloat16 reference's loss after {args.steps} steps is "
                f"refused by the in-run limit",
                abs(low["loss"] - want["loss"]), in_run, must_fail=True,
                decides=False)
    if failures:
        sys.exit(f"{me}: FAIL {failures}")
    print(f"{me}: PASS", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
