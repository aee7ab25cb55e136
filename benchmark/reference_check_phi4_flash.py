#!/usr/bin/env python3
"""Phi-4-mini-flash-reasoning's own layers 14-19 (two Mamba-1 mixers through
`selective_scan`, differential attention under the window of 512 and full, a
gated memory unit on layer 16's scan output, cross attention on layer 17's
keys and values, LayerNorm, a gated MLP in every layer, one table as
embedding and head) against their plain reference, at published widths on
the chip (or `--tiny` on the CPU). `reference_check_granite4.py` is the same
check for another reference.

    python3 benchmark/reference_check_phi4_flash.py
        [--config phi_4_mini_flash_reasoning] [--seed N] [--workload CELL]
        [--steps N] [--tiny]

One training step of the system (the configuration's program under its
optimizer and AMP setting, built as `run.py` builds it, at the lengths of the
configuration's cell) on one seeded batch, and the reference
(`references/<reference.check.module>.py`: float32, every product at
"highest", the recurrence token by token with a decay per channel and state,
the convolution as shifted products plus its bias, each softmax map a masked
softmax over the whole row, the table used twice) on the same weights and
the same batch. Before either, unless `--steps` trains first,
`reference.check.planted` is written into the scope (the reference is handed
the same weights): every convolution's bias drawn at `conv_bias_std` and
every q, k, v and o bias at `attention_bias_std` from the seed (all start at
0, where a dropped bias is no fault), the lambda vectors of every
differential layer set so that `lq1 . lk1` and `lq2 . lk2` are
`lambda_dots` (at their initial draw both are ~0 and lam hardly leaves
lam0), the parameters under `scale` multiplied by their factors (the query
projections: at the initial values every softmax is nearly uniform, both
maps give the mean of v, and the faults in the pairing, the key-value pairs'
order and the window would move nothing), and the columns of those under
`head_ramp` by a factor a head, from the first number to the second over the
third number of heads (on equal heads a wrong pairing is as good as the
right one). With `--steps N` the system first trains N steps over the cell's
pool of batches, as a run of the cell does. Compared, each under a tolerance
written in the configuration's `reference.check` with its reason:

  * the logits on the last `last_positions` positions;
  * `loss` and `ce`;
  * the gradients of the parameters `reference.check.gradients` names (the
    tied table and one parameter of every kind of every mixer), in the
    Frobenius norm, each under `gradient_rel`, or under its own entry of
    `gradient_rel_by_name` where it has one.

The reference is computed as `reference.check.reference_args` says (queries a
block at a time, the recurrence a block of tokens at a time) and its gradient
with `remat`: that is its memory beside a chip's 16 GB, not its mathematics.
Then the reference once more with everything in bfloat16, held to the same
limits against the float32 reference: at least one has to refuse it. Then the
reference with each fault of `reference.check.faults` planted
(`references/phi4_flash_reference.py::FAULTS`): each has to be refused by
at least one comparison (the forward pass first; its gradients where the
forward pass lets it through, as `untied_head` always does; `window_off_by_one`
by the mask probe: `layers.fused_attention(window=...)` alone on one map's
20 heads of 64 / 128 with scores of standard deviation `mask_probe.score_std`
against `softmax_map`, where one key more in a row of 512 moves the result by
far more than bf16 does). After `--steps`
the system's loss is also held to the traffic file's in-run limit and the
comparisons of logits and gradients are readings only. Exits non-zero on any
miss. The system's arrays are released before the reference's gradient is
computed: both do not fit a chip.
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
# the fault of the mask alone, which the mask probe judges
PROBED = ("window_off_by_one",)


def mask_probe(fluid, jax, jnp, np, ref, probe, config, build_args, seed):
    """One softmax map of a differential layer alone (`layers.fused_attention`
    on 20 heads with q, k at 64 and v at 128), windowed and full, through the
    Executor under the configuration's AMP setting, on seeded q, k, v at the
    cell's length with q scaled so that the scores' standard deviation is
    `score_std`; against `softmax_map` in float32. Returns [(what, largest
    |difference|)]: the windowed op and the full op against their masks, the
    windowed op against the reference's window one key too long, against no
    window, and the full op against the window."""
    heads, dim = build_args["n_head"] // 2, build_args["head_dim"]
    t, window = build_args["seq_len"], build_args["window"]
    rng = np.random.RandomState(seed % (2 ** 32))
    q, k = (rng.randn(1, heads, t, dim).astype(np.float32) for _ in "qk")
    v = rng.randn(1, heads, t, 2 * dim).astype(np.float32)
    q *= probe["score_std"]
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup), fluid.unique_name.guard():
        data = [fluid.layers.data(name=n, shape=list(a.shape),
                                  dtype="float32", append_batch_size=False)
                for n, a in zip("qkv", (q, k, v))]
        outs = [fluid.layers.fused_attention(*data, causal=True,
                                             sm_scale=dim ** -0.5, window=w)
                for w in (window, None)]
    exe = fluid.Executor(fluid.TPUPlace(0), amp=config["amp"])
    got = [np.asarray(x, np.float32) for x in exe.run(
        main_p, feed=dict(zip("qkv", (q, k, v))), fetch_list=outs)]
    exe.close()

    def want(w, fault=None):
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.softmax_map(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                scale=dim ** -0.5, window=w, q_block=probe.get("q_block"),
                fault=fault))

    return [("the windowed op against 0 <= t - s < window",
             float(np.abs(got[0] - want(window)).max())),
            ("the full op against s <= t",
             float(np.abs(got[1] - want(None)).max())),
            ("window_off_by_one",
             float(np.abs(got[0] - want(window, "window_off_by_one")).max())),
            ("no window at all", float(np.abs(got[0] - want(None)).max())),
            ("the window on the full op",
             float(np.abs(got[1] - want(window)).max()))]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="phi_4_mini_flash_reasoning")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--workload")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--dump-calls", metavar="PATH", help="write the compiled "
                    "step's custom-call instructions there, and those the "
                    "scan, the convolution, the flash calls' scopes and the "
                    "tied table's ops own (the texts a metric's pattern is "
                    "tested on)")
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

    me = "reference_check_phi4_flash"
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
                        list(SCALARS) + ["logits"])
    for i in range(args.steps):                 # as a run of the cell does
        loss = system.step(system.place(pool[i % len(pool)]))
        if i % 25 == 0 or i == args.steps - 1:
            print(f"{me}: step {i} loss "
                  f"{float(np.asarray(loss).reshape(-1)[0]):.4f}", flush=True)
    host = pool[args.steps % len(pool)]
    planted = check.get("planted") if not args.steps else None
    if planted:         # values at which every planted fault is a fault
        def rewrite(name, change):
            value = change(np.asarray(system.scope.find_var(name)))
            system.scope.set_var(name, jnp.asarray(value.astype(np.float32)))

        rng = np.random.RandomState(args.seed % (2 ** 32))
        names = [p.name for p in system.main.global_block().all_parameters()]
        for name in sorted(n for n in names if n.endswith(".conv.b")):
            rewrite(name, lambda v: rng.randn(*v.shape)
                    * planted["conv_bias_std"])
        for name in sorted(n for n in names if n.endswith(
                (".q.b", ".k.b", ".v.b", ".o.b"))):
            rewrite(name, lambda v: rng.randn(*v.shape)
                    * planted["attention_bias_std"])
        for prefix in sorted({n.rsplit(".", 1)[0] for n in names
                              if n.endswith(".lq1")}):
            for pair, dot in zip("12", planted["lambda_dots"]):
                # lq = lk = sqrt(|dot| / Dh) a channel, lk's sign the dot's
                rewrite(f"{prefix}.lq{pair}", lambda v: np.full(
                    v.shape, np.sqrt(abs(dot) / v.size)))
                rewrite(f"{prefix}.lk{pair}", lambda v: np.full(
                    v.shape, np.sign(dot) * np.sqrt(abs(dot) / v.size)))
        for name, factor in sorted(planted["scale"].items()):
            rewrite(name, lambda v: v * factor)
        for name, (low, high, heads) in sorted(planted["head_ramp"].items()):
            ramp = np.geomspace(low, high, heads)       # a factor a head
            rewrite(name, lambda v: (
                v.reshape(v.shape[0], heads, -1) * ramp[None, :, None])
                .reshape(v.shape))
        print(f"{me}: planted on the initial values: convolution biases of "
              f"std {planted['conv_bias_std']}, attention biases of std "
              f"{planted['attention_bias_std']}, lambda dots "
              f"{planted['lambda_dots']}, factors {planted['scale']} and, "
              f"head by head, {planted['head_ramp']}", flush=True)
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
        fetch_list=[fetch[n] for n in SCALARS] + [tail]
        + [n + "@GRAD" for n in grad_names], scope=system.scope))
    parts = {n: float(next(got).reshape(-1)[0]) for n in SCALARS}
    logits = next(got).astype(np.float32)
    grads = {n: next(got) for n in grad_names}
    del got
    if args.dump_calls:
        from paddle_tpu import observe
        texts = [e.compiled_text() for e in observe.observatory().events()
                 if e.program_uid == system.main._uid
                 and hasattr(e, "compiled_text")]
        wanted = (" custom-call(", "/selective_scan", "/causal_conv1d",
                  ".attn/", ".cross/", ".gmu/", "[25008,2560]")
        with open(args.dump_calls, "w") as f:
            f.write("\n".join(line.strip()[:1500] for line in
                              (texts[-1] or "").splitlines()
                              if any(w in line for w in wanted)))
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
        own_logits = np.asarray(out["logits"], np.float32)
        del out
        wrt = {n: dev[n] for n in grad_names}
        own_grads = jax.jit(jax.grad(functools.partial(
            loss_of, dtype=dtype, fault=fault)))(wrt, dev)
        return own, own_logits, {
            n: np.asarray(v, np.float32) for n, v in own_grads.items()}

    want, want_logits, want_grads = run_reference(jnp.float32)
    low, low_logits, low_grads = run_reference(jnp.bfloat16)

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
    faults = check.get("faults", []) if at_start else []
    probe = check.get("mask_probe") if at_start else None
    refused_by = {f: [] for f in faults}
    for fault in faults:
        bad = ref.loss_parts(dev, tokens, labels, last=last, fault=fault,
                             **kw)      # the forward pass alone: seconds
        off = np.abs(np.asarray(bad["logits"], np.float32) - want_logits)
        print(f"{me}: fault {fault}: logits |difference| mean "
              f"{float(off.mean()):.5f} (a reading)", flush=True)
        reads = [("logits", float(off.max()), check["logits_atol"])]
        reads += [(n, abs(float(bad[n]) - want[n]), check["loss_atol"][n])
                  for n in SCALARS]
        del bad
        if all(value <= limit for _, value, limit in reads) \
                and not (probe and fault in PROBED):
            # the forward pass let it through: its gradients have to show it
            bad_grads = run_reference(jnp.float32, fault)[2]
            reads += [(f"gradient of {n}", fro(bad_grads[n], want_grads[n]),
                       grad_limit(n)) for n in grad_names]
        refused_by[fault] = [w for w, value, limit in reads
                             if not value <= limit]
        print(f"{me}: fault {fault} ({ref.FAULTS[fault]}): "
              + ", ".join(f"{w} {value:.6g} ("
                          f"{'accepted' if value <= limit else 'refused'} by "
                          f"{limit})" for w, value, limit in reads),
              flush=True)
    if probe:
        del dev
        jax.clear_caches()
        for what, value in mask_probe(fluid, jax, jnp, np, ref, probe, config,
                                      system_args, args.seed):
            if what in refused_by:
                print(f"{me}: mask probe, fault {what}: largest |difference| "
                      f"{value:.6g}, "
                      f"{'refused' if value > probe['atol'] else 'accepted'} "
                      f"by {probe['atol']}", flush=True)
                if value > probe["atol"]:
                    refused_by[what].append("mask probe")
            else:
                verdict(f"mask probe, {what}: largest |difference|", value,
                        probe["atol"], must_fail="against" not in what)
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
