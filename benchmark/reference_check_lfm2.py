#!/usr/bin/env python3
"""LFM2-8B-A1B's layers 1-5 (four gated short convolutions of three taps with
no activation and one QK-normed rotary 32/8 attention layer, a dense gated MLP
and then 8 of 32 routed experts of 1792 at top-4 under a sigmoid router with a
selection bias, no shared expert, one table as embedding and head) against
their plain reference, at published widths on the chip (or `--tiny` on the
CPU). `reference_check_granite4.py` is the same check for another reference;
this one also reads the router's counts and holds the biases' update, as
`reference_check_nemotron_h.py` does.

    python3 benchmark/reference_check_lfm2.py
        [--config lfm2_8b_a1b] [--seed N] [--workload CELL]
        [--steps N] [--tiny]

One training step of the system (the configuration's program under its
optimizer and AMP setting, built as `run.py` builds it, at the lengths of the
configuration's cell) on one seeded batch, and the reference
(`references/<reference.check.module>.py`: float32, every product at
"highest", the convolution as shifted products, the gates as written, a masked
softmax with `jnp.repeat`, a loop over the held experts, the table used twice)
on the same weights, the same router biases and the same batch. Before either,
unless `--steps` trains first, `reference.check.planted` is written into the
scope (the reference is handed the same values), since at the initial values
several faults are none:

  * `router_bias_std`: every router bias drawn normal at that std from the
    seed (b starts at 0, where `bias_in_weights` is exactly no fault);
  * `tap_ramp`: every convolution's taps times these factors, oldest first
    (the initial taps are exchangeable in distribution: their order reversed
    is statistically the same convolution);
  * `uniform`: the named norm weights drawn uniform in the range (at 1 every
    pair of a head's dims turns alike under the norm, and which dims rotary
    pairs up changes little), then `scale`: the named ones times a factor
    (the query norm's: a sharper softmax, so that positions and the key-value
    heads' order move something);
  * `head_ramp`: the columns of the named projections by a factor a head,
    from the first number to the second over the third number of heads (on
    equal heads the wrong key-value head is as good as the right one, and a
    norm over all heads is the norm over one).

With `--steps N` the system first trains N steps over the cell's pool of
batches, as a run of the cell does, and prints how the held experts' load and
the biases moved. Compared, each under a tolerance written in the
configuration's `reference.check` with its reason:

  * the logits on the last `last_positions` positions;
  * `loss` and `ce`;
  * the assignments to each of the routed experts, per layer (a reading: a
    near-tie flips on bf16 inputs; the share of assignments that differ);
  * every layer's bias after the compared step against `next_bias` applied
    to the bias before it and the system's own counts: exactly;
  * the gradients of the parameters `reference.check.gradients` names (the
    tied table, a conv operator's three weights in the first and `W_in` in
    the last layer, the dense MLP's three, the attention's `W_q`, `W_k` and
    query norm, an expert layer's router and three stacks, the final norm),
    in the Frobenius norm, each under `gradient_rel`, or under its own entry
    of `gradient_rel_by_name` where it has one.

The reference is computed as `reference.check.reference_args` says (queries a
block at a time) and its gradient with `remat`: that is its memory beside a
chip's 16 GB, not its mathematics. Then the reference once more with
everything in bfloat16, held to the same limits against the float32
reference: at least one has to refuse it. Then the reference with each fault
of `reference.check.faults` planted (`references/lfm2_moe_reference.py::
FAULTS`): each has to be refused by at least one comparison (the forward pass
first; its gradients where the forward pass lets it through, as `untied_head`
always does). After `--steps` the system's loss is also held to the traffic
file's in-run limit and the comparisons of logits and gradients are readings
only. Exits non-zero on any miss. The system's arrays are released before the
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

SCALARS = ("loss", "ce")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="lfm2_8b_a1b")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--workload")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--dump-calls", metavar="PATH", help="write the compiled "
                    "step's custom-call instructions there, and those the "
                    "convolution, the conv operators' gates and slices and "
                    "the tied table's ops own (the texts a metric's pattern "
                    "is tested on)")
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

    me = "reference_check_lfm2"
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
    held = system.build_args["experts_held"]
    biases = sorted(p.name for p in
                    system.main.global_block().all_parameters()
                    if not p.trainable)
    gamma = system.build_args["bias_update_rate"]

    def load(counts):
        """Assignments to the held experts, and the rows their groups take
        in whole 128-row tiles, per layer."""
        mine = np.asarray(counts)[:, first:first + held]
        return mine.sum(1).tolist(), (-(-mine // 128) * 128).sum(1).tolist()

    def read_biases():
        return {n: np.asarray(system.scope.find_var(n)) for n in biases}

    for i in range(args.steps):                 # as a run of the cell does
        loss, counts = system.exe.run(
            system.main, feed=system.place(pool[i % len(pool)]),
            fetch_list=[system.loss, fetch["tokens_per_expert"]],
            return_numpy=False, scope=system.scope)
        if i % 25 == 0 or i == args.steps - 1:
            now = read_biases()
            print(f"{me}: step {i} loss "
                  f"{float(np.asarray(loss).reshape(-1)[0]):.4f}; held "
                  f"assignments and padded rows per layer {load(counts)}; "
                  f"largest |b| per layer "
                  f"{[round(float(np.abs(now[n]).max()), 6) for n in biases]}",
                  flush=True)
    host = pool[args.steps % len(pool)]
    planted = check.get("planted") if not args.steps else None
    if planted:         # values at which every planted fault is a fault
        def rewrite(name, change):
            value = change(np.asarray(system.scope.find_var(name)))
            system.scope.set_var(name, jnp.asarray(value.astype(np.float32)))

        rng = np.random.RandomState(args.seed % (2 ** 32))
        names = [p.name for p in system.main.global_block().all_parameters()]
        for name in biases:
            rewrite(name, lambda v: rng.randn(*v.shape)
                    * planted["router_bias_std"])
        for name in sorted(n for n in names if n.endswith(".conv.conv.w")):
            rewrite(name, lambda v: v * np.asarray(planted["tap_ramp"]))
        for name, (low, high) in sorted(planted["uniform"].items()):
            rewrite(name, lambda v: rng.uniform(low, high, v.shape))
        for name, factor in sorted(planted["scale"].items()):
            rewrite(name, lambda v: v * factor)
        for name, (low, high, heads) in sorted(planted["head_ramp"].items()):
            ramp = np.geomspace(low, high, heads)       # a factor a head
            rewrite(name, lambda v: (
                v.reshape(v.shape[0], heads, -1) * ramp[None, :, None])
                .reshape(v.shape))
        print(f"{me}: planted on the initial values: router biases of std "
              f"{planted['router_bias_std']}, taps times "
              f"{planted['tap_ramp']}, uniform {planted['uniform']}, factors "
              f"{planted['scale']} and, head by head, "
              f"{planted['head_ramp']}", flush=True)
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
    grad_names = list(check["gradients"])
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
    if args.dump_calls:
        from paddle_tpu import observe
        texts = [e.compiled_text() for e in observe.observatory().events()
                 if e.program_uid == system.main._uid
                 and hasattr(e, "compiled_text")]
        wanted = (" custom-call(", "/l1.conv/", "/core/", "[16384,2048]")
        with open(args.dump_calls, "w") as f:
            f.write("\n".join(line.strip()[:1500] for line in
                              (texts[-1] or "").splitlines()
                              if any(w in line for w in wanted)))
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

    def run_reference(dtype, fault=None):
        out = ref.loss_parts(dev, tokens, labels, last=last, dtype=dtype,
                             fault=fault, **kw)
        own = {n: float(out[n]) for n in SCALARS}
        own_counts = np.asarray(out["tokens_per_expert"], np.int64)
        own_logits = np.asarray(out["logits"], np.float32)
        del out
        wrt = {n: dev[n] for n in grad_names}
        own_grads = jax.jit(jax.grad(functools.partial(
            loss_of, dtype=dtype, fault=fault)))(wrt, dev)
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
    print(f"{me}: the bfloat16 reference's logits: |difference| mean "
          f"{float(np.abs(low_logits - want_logits).mean()):.5f} (a reading)",
          flush=True)
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
    for fault in (check.get("faults", []) if at_start else []):
        bad = ref.loss_parts(dev, tokens, labels, last=last, fault=fault,
                             **kw)      # the forward pass alone: seconds
        off = np.abs(np.asarray(bad["logits"], np.float32) - want_logits)
        print(f"{me}: fault {fault}: logits |difference| mean "
              f"{float(off.mean()):.5f} (a reading)", flush=True)
        reads = [("logits", float(off.max()), check["logits_atol"])]
        reads += [(n, abs(float(bad[n]) - want[n]), check["loss_atol"][n])
                  for n in SCALARS]
        del bad
        if all(value <= limit for _, value, limit in reads):
            # the forward pass let it through: its gradients have to show it
            bad_grads = run_reference(jnp.float32, fault)[3]
            reads += [(f"gradient of {n}", fro(bad_grads[n], want_grads[n]),
                       grad_limit(n)) for n in grad_names]
        print(f"{me}: fault {fault} ({ref.FAULTS[fault]}): "
              + ", ".join(f"{w} {value:.6g} ("
                          f"{'accepted' if value <= limit else 'refused'} by "
                          f"{limit})" for w, value, limit in reads),
              flush=True)
        verdict(f"fault {fault} must NOT be judged correct: comparisons that "
                f"refuse it", sum(not value <= limit
                                  for _, value, limit in reads), 0,
                must_fail=True)
    if args.steps:
        in_run = traffic["reference_check"]["loss_atol"]
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
