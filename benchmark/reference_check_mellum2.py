#!/usr/bin/env python3
"""The sliding-window / full-attention sparse-expert LM (Mellum2) against its
plain reference, at published widths on the chip (or `--tiny` on the CPU).
`reference_check_qwen3_next.py` reads the same loss parts; what this check
adds is what a window brings: faults planted in the reference that the
comparison has to refuse, and a probe of the attention op alone on scores
sharp enough that one key more or less in a row is seen.

    python3 benchmark/reference_check_mellum2.py [--config mellum2_12b_a2_5b]
        [--seed N] [--workload CELL] [--steps N] [--tiny]

One training step of the system (the configuration's program under its
optimizer and AMP setting, built as `run.py` builds it, at the lengths of the
configuration's cell) on one seeded batch, and the reference
(`references/<reference.check.module>.py`: float32, every product at
"highest", attention as a masked softmax whose mask is the two inequalities,
key and value heads by `jnp.repeat`, YaRN's frequencies from the formulas, a
loop over the held experts, the same share and vocabulary slice) on the same
weights and batch. With `--steps N` the system first trains N steps over the
cell's pool of batches, as a run of the cell does, and prints how the held
experts' load moved. Compared, each under a tolerance written in the
configuration's `reference.check` with its reason:

  * the logits on the last `last_positions` positions;
  * `loss`, `ce` and `load_balance`;
  * the assignments to each of the routed experts, per layer (a reading: a
    near-tie flips on bf16 inputs; the share of assignments that differ);
  * the gradients of the parameters `reference.check.gradients` names, in
    the Frobenius norm, each under `gradient_rel`, or under its own entry of
    `gradient_rel_by_name` where it has one.

Then the reference once more with everything, the router, the softmax and the
losses included, in bfloat16, held to the same limits against the float32
reference: every reading is printed with what its limit says of it, and at
least one has to be refused, or the check would accept a lower precision than
the configuration states. Then the reference with each fault of
`reference.check.faults` planted (`references/mellum2_reference.py::FAULTS`:
the window one key too long, no window, a window on the full layer too, YaRN
left off, YaRN on the sliding layers, a key-value head serving the wrong
query heads), its forward pass under the limits on the logits and the loss
parts, and its gradients too where the forward pass lets a fault of the
rotary tables or the grouping through: each has to be refused by at least one
comparison.
A window one key too long moves a row's output by one key's weight in 1024,
which no limit that leaves bf16 room can see in a logit or a loss; the mask
probe is what refuses it: `layers.fused_attention(window=...)`
alone through the Executor, under AMP, on queries scaled so that the scores
have a standard deviation of `mask_probe.score_std` (a row's largest weights
are tenths, so a key more or less moves the row's output by its own size
where it is the largest), against `masked_attention` with and without each
window fault; the limit `mask_probe.atol` on the largest difference lies
between the system's reading and the faults'.

After `--steps` the system's loss is also held to the traffic file's in-run
limit, the comparisons of logits and gradients are readings only, and the
faults and the probe are not run again. Exits non-zero on any miss. The
system's arrays are released before the reference's gradient is computed:
both do not fit a chip.
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

SCALARS = ("loss", "ce", "load_balance")
# the faults of the mask alone, which the mask probe judges
PROBED = ("window_off_by_one", "no_window", "window_on_full")


def mask_probe(fluid, jax, jnp, np, ref, probe, config, build_args, seed):
    """`layers.fused_attention` alone, windowed and full, through the
    Executor under the configuration's AMP setting, on seeded q, k, v at the
    cell's heads and length with q scaled so that the scores' standard
    deviation is `score_std`; against `masked_attention` in float32. Returns
    (the system's outputs, [(what, largest |difference|)]): the windowed op
    and the full op against their masks, and the windowed op against each
    window fault of the reference."""
    heads, dim = build_args["n_head"], build_args["head_dim"]
    t, window = build_args["seq_len"], build_args["sliding_window"]
    rng = np.random.RandomState(seed % (2 ** 32))
    q, k, v = (rng.randn(1, heads, t, dim).astype(np.float32)
               for _ in range(3))
    q *= probe["score_std"]
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup), fluid.unique_name.guard():
        data = [fluid.layers.data(name=n, shape=[1, heads, t, dim],
                                  dtype="float32", append_batch_size=False)
                for n in "qkv"]
        outs = [fluid.layers.fused_attention(*data, causal=True,
                                             sm_scale=dim ** -0.5, window=w)
                for w in (window, None)]
    exe = fluid.Executor(fluid.TPUPlace(0), amp=config["amp"])
    got = [np.asarray(x, np.float32) for x in exe.run(
        main_p, feed=dict(zip("qkv", (q, k, v))), fetch_list=outs)]
    exe.close()

    def want(w, fault=None):
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.masked_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                scale=dim ** -0.5, window=w, q_block=probe.get("q_block"),
                fault=fault))

    reads = [("the windowed op against 0 <= i - j < window",
              float(np.abs(got[0] - want(window)).max())),
             ("the full op against j <= i",
              float(np.abs(got[1] - want(None)).max())),
             ("window_off_by_one",
              float(np.abs(got[0] - want(window, "window_off_by_one")).max())),
             ("no_window", float(np.abs(got[0] - want(None)).max())),
             ("window_on_full", float(np.abs(got[1] - want(window)).max()))]
    return got, reads


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="mellum2_12b_a2_5b")
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

    me = "reference_check_mellum2"
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

    for i in range(args.steps):                 # as a run of the cell does
        loss, counts = system.exe.run(
            system.main, feed=system.place(pool[i % len(pool)]),
            fetch_list=[system.loss, fetch["tokens_per_expert"]],
            return_numpy=False, scope=system.scope)
        if held and (i % 25 == 0 or i == args.steps - 1):
            print(f"{me}: step {i} loss "
                  f"{float(np.asarray(loss).reshape(-1)[0]):.4f}; held "
                  f"assignments and padded rows per layer {load(counts)}",
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
    refused_by = {f: [] for f in faults}
    for fault in faults:            # the forward pass alone: seconds each
        bad = ref.loss_parts(dev, tokens, labels, last=last, fault=fault,
                             **kw)
        reads = [("logits", float(np.abs(
            np.asarray(bad["logits"], np.float32) - want_logits).max()),
            check["logits_atol"])]
        reads += [(n, abs(float(bad[n]) - want[n]), check["loss_atol"][n])
                  for n in SCALARS]
        refused_by[fault] = [w for w, value, limit in reads if value > limit]
        print(f"{me}: fault {fault} ({ref.FAULTS[fault]}): "
              + ", ".join(f"{w} {value:.6g} ("
                          f"{'refused' if value > limit else 'accepted'} by "
                          f"{limit})" for w, value, limit in reads),
              flush=True)
        if not refused_by[fault] and fault not in PROBED:
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
        got_probe, probes = mask_probe(
            fluid, jax, jnp, np, ref, probe, config, system_args, args.seed)
        for what, value in probes:
            fault = what if what in refused_by else None
            if fault is None:
                verdict(f"mask probe, {what}: largest |difference|", value,
                        probe["atol"])
            else:
                print(f"{me}: mask probe, fault {fault}: largest "
                      f"|difference| {value:.6g}, "
                      f"{'refused' if value > probe['atol'] else 'accepted'} "
                      f"by {probe['atol']}", flush=True)
                if value > probe["atol"]:
                    refused_by[fault].append("mask probe")
    for fault in faults:
        verdict(f"fault {fault} must NOT be judged correct: comparisons "
                f"that refuse it", len(refused_by[fault]), 0, must_fail=True)
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
