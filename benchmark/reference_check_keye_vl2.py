#!/usr/bin/env python3
"""Keye-VL-2.0's language model (a learned selection of 2048 keys a query
through the flash kernels, a share of a top-8 expert layer) against its plain
reference, at published widths on the chip (or `--tiny` on the CPU).

    python3 benchmark/reference_check_keye_vl2.py [--config keye_vl_2_30b_a3b]
        [--seed N] [--workload CELL] [--steps N] [--tiny]

One training step of the system (the configuration's program under its
optimizer and AMP setting, built as `run.py` builds it, at the lengths of the
configuration's cell) on one seeded batch, and the reference
(`references/keye_vl2_reference.py`: float32, every product at "highest", the
index scores as an einsum, `jax.lax.top_k`, a softmax under the mask, a loop
over the held experts) on the same weights and the same batch. With `--steps
N` the system first trains N steps over the cell's pool. Two comparisons,
because a bf16 index score flips the keys that lie at a row's threshold:

  (a) the kept sets, layer by layer: every row of the system's keeps exactly
      min(t + 1, topk) keys and none above the diagonal (exactly), and the
      share of the kept pairs of either side that both sides keep is at least
      `kept_agreement_min`;
  (b) with the reference HANDED the system's kept sets: the logits on the
      last `last_positions` positions, `loss`, `ce` and `load_balance`, the
      assignments to each routed expert (a reading), and the gradients of the
      parameters `reference.check.gradients` names, in the Frobenius norm,
      each under `gradient_rel` or its own entry of `gradient_rel_by_name`;
      the parameters `reference.check.frozen` names (the indexer's) have no
      gradient variable in the program, are bitwise what they were after the
      step, and get a zero gradient from the reference.

Then the reference once more with everything in bfloat16, its own selection
against the float32 one's (a) and, handed the system's kept sets, under the
same limits as the system (b): at least one comparison has to refuse it. Then
the reference with each fault of `reference.check.faults` planted
(`references/keye_vl2_reference.py::FAULTS`): its own selection against the
system's (a), its forward pass under the system's kept sets (b), and its
gradients where both let it through (a gradient that reaches the indexer
refuses it): each has to be refused by at least one comparison.

After `--steps` the system's loss against the reference's OWN selection (what
a run of the cell compares) is held to the traffic file's in-run limit, and
so is the update of the parameters the in-run comparison names, with what a
bfloat16 state makes of the bfloat16 reference's gradient beside it, which has
to be refused; logits and gradients are readings there and the faults are not
run again. Exits non-zero on any miss. The system's arrays are released
before the reference's gradient is computed: both do not fit a chip.
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
ME = "reference_check_keye_vl2"


def agreement(a, b):
    """The share of the kept pairs of either side that both sides keep."""
    import numpy as np
    a, b = np.asarray(a) != 0, np.asarray(b) != 0
    if a.shape != b.shape:          # one set a head is no set a token
        return 0.0
    return float((a & b).sum() / max((a | b).sum(), 1))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="keye_vl_2_30b_a3b")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--workload")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--dump-calls", metavar="PATH", help="write the compiled "
                    "step's custom-call instructions there (the texts a "
                    "metric's pattern is tested on)")
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

    devices = jax.devices()[:1]
    print(f"{ME}: {args.config} seed {args.seed} on {devices[0].platform} "
          f"{devices[0].device_kind}", flush=True)
    if not args.tiny and devices[0].platform != "tpu":
        sys.exit(f"{ME}: published widths need the TPU (--tiny rehearses on "
                 f"the CPU)")
    ref = importlib.import_module("references." + check["module"])
    batch = config["tiny"]["batch"] if args.tiny else traffic["batch"]
    system = System(config, cell, traffic, devices, batch, tiny=args.tiny)
    ranges = dict(config["feed_ranges"])
    if args.tiny:
        ranges.update(config["tiny"].get("feed_ranges", {}))
    pool = make_pool(system.feeds, ranges, batch, traffic["pool_batches"],
                     args.seed)
    n_layer, topk = system.build_args["n_layer"], system.build_args["topk"]
    kept_names = [f"l{i}.kept" for i in range(n_layer)]
    fetch = fetch_names(config, system.build_args, list(SCALARS) + [
        "logits", "tokens_per_expert"] + kept_names)
    for i in range(args.steps):                 # as a run of the cell does
        loss = system.step(system.place(pool[i % len(pool)]))
        if i % 25 == 0 or i == args.steps - 1:
            print(f"{ME}: step {i} loss "
                  f"{float(np.asarray(loss).reshape(-1)[0]):.4f}", flush=True)
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
    update = traffic["reference_check"]["update"]
    grad_names = list(check["gradients"])
    grad_names += [n for n in update["parameters"] if n not in grad_names]
    frozen = list(check["frozen"])
    before = optimizer_state(system, update["parameters"])
    got = iter(np.asarray(x) for x in system.exe.run(
        system.main, feed=host,
        fetch_list=[fetch[n] for n in SCALARS]
        + [fetch["tokens_per_expert"], tail]
        + [fetch[n] for n in kept_names]
        + [n + "@GRAD" for n in grad_names], scope=system.scope))
    parts = {n: float(next(got).reshape(-1)[0]) for n in SCALARS}
    counts = next(got).astype(np.int64)
    logits = next(got).astype(np.float32)
    kept = [next(got) for _ in kept_names]
    grads = {n: next(got) for n in grad_names}
    del got
    stepped = {n: np.asarray(system.scope.find_var(n)) - state["Param"]
               for n, (_, state) in before.items()}
    untouched = {n: bool(np.array_equal(
        params[n], np.asarray(system.scope.find_var(n)))) for n in frozen}
    no_grad_var = {n: not block.has_var(n + "@GRAD") for n in frozen}
    if args.dump_calls:
        from paddle_tpu import observe
        texts = [e.compiled_text() for e in observe.observatory().events()
                 if e.program_uid == system.main._uid
                 and hasattr(e, "compiled_text")]
        with open(args.dump_calls, "w") as f:
            f.write("\n".join(line.strip() for line in
                              (texts[-1] or "").splitlines()
                              if " custom-call(" in line))
    system.close()
    del system                      # the weights, moments and executables
    jax.clear_caches()

    failures = []

    def verdict(what, value, limit, must_fail=False, decides=True,
                at_least=False):
        ok = (value >= limit) if at_least else (value <= limit)
        if must_fail:
            ok = value > limit
        mark = ("ok  " if ok else "FAIL") if decides else \
            ("read (holds)" if ok else "read (does not hold)")
        sign = ">" if must_fail else (">=" if at_least else "<=")
        print(f"{ME}: {mark} {what}: {value:.6g} {sign} {limit}", flush=True)
        if decides and not ok:
            failures.append(what)

    # -- (a) the system's kept sets, exactly ---------------------------------
    rows_keep = np.minimum(np.arange(seq_len) + 1, topk)
    below = np.tril(np.ones((seq_len, seq_len), bool))
    for name, mine in zip(kept_names, kept):
        wrong_rows = int(np.sum(mine.sum(-1, dtype=np.int64) != rows_keep))
        verdict(f"{name}: rows that do not keep min(t + 1, {topk})",
                wrong_rows, 0)
        verdict(f"{name}: keys kept above the diagonal",
                int(np.count_nonzero(mine[:, ~below])), 0)
    for n in frozen:
        verdict(f"{n} has a gradient variable or moved in the step",
                int(not (no_grad_var[n] and untouched[n])), 0)

    # -- the reference ------------------------------------------------------------
    tokens, labels = jnp.asarray(host["tokens"]), jnp.asarray(host["labels"])
    dev = {n: jnp.asarray(v) for n, v in params.items()}
    handed = [jnp.asarray(k) for k in kept]

    def own_selection(dtype=jnp.float32, fault=None):
        out = ref.loss_parts(dev, tokens, labels, dtype=dtype, fault=fault,
                             return_kept=True, **kw)
        return float(out["loss"]), [np.asarray(k) for k in out["kept"]]

    def loss_of(sub, rest, dtype=jnp.float32, fault=None):
        return ref.loss_parts({**rest, **sub}, tokens, labels, dtype=dtype,
                              remat=True, fault=fault, kept=handed,
                              **kw)["loss"]

    def under_the_systems_sets(dtype=jnp.float32, fault=None, wrt=None):
        out = ref.loss_parts(dev, tokens, labels, last=last, dtype=dtype,
                             fault=fault, kept=handed, **kw)
        own = {n: float(out[n]) for n in SCALARS}
        own_counts = np.asarray(out["tokens_per_expert"], np.int64)
        own_logits = np.asarray(out["logits"], np.float32)
        del out
        if wrt is None:
            return own, own_counts, own_logits, None
        own_grads = jax.jit(jax.grad(functools.partial(
            loss_of, dtype=dtype, fault=fault)))({n: dev[n] for n in wrt}, dev)
        return own, own_counts, own_logits, {
            n: np.asarray(v, np.float32) for n, v in own_grads.items()}

    own_loss, own_kept = own_selection()
    low_loss, low_kept = own_selection(jnp.bfloat16)
    floor = check["kept_agreement_min"]
    shares = [agreement(a, b) for a, b in zip(kept, own_kept)]
    low_shares = [agreement(a, b) for a, b in zip(low_kept, own_kept)]
    for name, share, low_share in zip(kept_names, shares, low_shares):
        verdict(f"{name}: share of kept pairs the system and the reference "
                f"both keep", share, floor, at_least=True)
        print(f"{ME}: {name}: the bfloat16 reference's own selection "
              f"against the float32 one's: {low_share:.6f}", flush=True)
    print(f"{ME}: loss under the reference's own selection {own_loss:.6f} "
          f"(bfloat16 reference {low_loss:.6f}), system {parts['loss']:.6f}",
          flush=True)

    want, want_counts, want_logits, want_grads = under_the_systems_sets(
        wrt=grad_names + frozen)
    low, low_counts, low_logits, low_grads = under_the_systems_sets(
        jnp.bfloat16, wrt=grad_names)

    def grad_limit(name):
        return check.get("gradient_rel_by_name", {}).get(
            name, check["gradient_rel"])

    at_start = args.steps == 0      # limits on logits and gradients: there
    moved = np.abs(counts - want_counts).sum() / 2 / counts.sum()
    low_moved = np.abs(low_counts - want_counts).sum() / 2 / counts.sum()
    print(f"{ME}: share of assignments on another expert than the "
          f"reference's: system {moved:.5f}, bfloat16 reference "
          f"{low_moved:.5f} (a reading)", flush=True)
    err = np.abs(logits - want_logits)
    print(f"{ME}: logits on the last {last} positions: reference std "
          f"{float(np.std(want_logits)):.4f}, |difference| mean "
          f"{float(err.mean()):.5f} max {float(err.max()):.5f}", flush=True)
    verdict("logits, largest |difference|", float(err.max()),
            check["logits_atol"], decides=at_start)
    low_reads = [("logits, largest |difference|",
                  float(np.abs(low_logits - want_logits).max()),
                  check["logits_atol"])]
    for n in SCALARS:
        print(f"{ME}: {n}: system {parts[n]:.6f}, reference {want[n]:.6f}, "
              f"bfloat16 reference {low[n]:.6f}", flush=True)
        verdict(f"{n} against the reference", abs(parts[n] - want[n]),
                check["loss_atol"][n])
        low_reads.append((n, abs(low[n] - want[n]), check["loss_atol"][n]))
    for n in grad_names:
        print(f"{ME}: gradient of {n}: reference norm "
              f"{float(np.linalg.norm(want_grads[n])):.4g}", flush=True)
        verdict(f"gradient of {n}, Frobenius", fro(grads[n], want_grads[n]),
                grad_limit(n), decides=at_start)
        low_reads.append((f"gradient of {n}", fro(low_grads[n], want_grads[n]),
                          grad_limit(n)))
    for n in frozen:
        verdict(f"the reference's gradient of {n}, largest |value|",
                float(np.abs(want_grads[n]).max()), 0)
    for n, (attrs, state) in before.items():
        step = reference_delta(attrs, state, want_grads[n])
        verdict(f"update of {n} against Adam on the reference's gradient, "
                f"|difference| / |reference's|", update_gap(stepped[n], step),
                update["rel_atol"], decides=not at_start)
        low_step = update_gap(reference_delta(
            attrs, state, low_grads[n], dtype="bfloat16"), step)
        if at_start:
            print(f"{ME}: the bfloat16 reference's update of {n} in a "
                  f"bfloat16 state: {low_step:.6g} (a reading)", flush=True)
        else:
            low_reads.append((f"update of {n}", low_step,
                              update["rel_atol"]))
    if not at_start:
        verdict("loss against the reference under ITS OWN selection (the "
                "in-run comparison)", abs(parts["loss"] - own_loss),
                traffic["reference_check"]["loss_atol"])
        print(f"{ME}: the bfloat16 reference's loss under its own selection "
              f"against the float32 one's: {abs(low_loss - own_loss):.6g} "
              f"(in-run limit {traffic['reference_check']['loss_atol']})",
              flush=True)
    for what, value, limit in low_reads:
        print(f"{ME}: the bfloat16 reference's {what}: {value:.6g}, "
              f"{'refused' if value > limit else 'accepted'} by {limit}",
              flush=True)
    refused = sum(value > limit for _, value, limit in low_reads)
    verdict(f"the bfloat16 reference must NOT be judged correct: its "
            f"comparisons refused ({refused} of {len(low_reads)})",
            refused, 0, must_fail=True)

    # -- planted faults: each has to be refused -----------------------------------
    for fault in (check.get("faults", []) if at_start else []):
        _, bad_kept = own_selection(fault=fault)
        share = min(agreement(a, b) for a, b in zip(kept, bad_kept))
        bad = under_the_systems_sets(fault=fault)
        reads = [("logits", float(np.abs(bad[2] - want_logits).max()),
                  check["logits_atol"])]
        reads += [(n, abs(bad[0][n] - want[n]), check["loss_atol"][n])
                  for n in SCALARS]
        by = [w for w, value, limit in reads if value > limit]
        # the exact comparisons of (a), on the fault's own selection
        counted = [k for k in bad_kept if k.shape == kept[0].shape]
        if any(np.any(k.sum(-1, dtype=np.int64) != rows_keep)
               for k in counted):
            by.insert(0, "rows that do not keep min(t + 1, topk)")
        if any(np.count_nonzero(k[:, ~below]) for k in counted):
            by.insert(0, "keys kept above the diagonal")
        if share < floor:
            by.insert(0, "kept sets")
        print(f"{ME}: fault {fault} ({ref.FAULTS[fault]}): its own selection "
              f"agrees with the system's on {share:.6f} "
              f"({'refused' if share < floor else 'accepted'} by {floor}; "
              f"refused so far by {by or 'nothing'}); under the system's "
              f"sets: "
              + ", ".join(f"{w} {value:.6g} ("
                          f"{'refused' if value > limit else 'accepted'} by "
                          f"{limit})" for w, value, limit in reads),
              flush=True)
        if not by:      # both let it through: its gradients have to show it
            bad_grads = under_the_systems_sets(
                fault=fault, wrt=grad_names + frozen)[3]
            reads = [(f"gradient of {n}", fro(bad_grads[n], want_grads[n]),
                      grad_limit(n)) for n in grad_names]
            reads += [(f"gradient that reaches {n}",
                       float(np.abs(bad_grads[n]).max()), 0) for n in frozen]
            by = [w for w, value, limit in reads if value > limit]
            worst = max(reads, key=lambda r: r[1] / (r[2] or 1e-30))
            print(f"{ME}: fault {fault}: its gradients: refused by "
                  f"{len(by)} of {len(reads)}; furthest past its limit: "
                  f"{worst[0]} {worst[1]:.6g} against {worst[2]}", flush=True)
        verdict(f"fault {fault} must NOT be judged correct: comparisons "
                f"that refuse it", len(by), 0, must_fail=True)

    if failures:
        print(f"{ME}: FAILED: " + "; ".join(failures), flush=True)
        return 1
    print(f"{ME}: PASS" if not args.tiny else f"{ME}: REHEARSAL passed",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
