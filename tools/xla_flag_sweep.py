"""Sweep XLA TPU compiler options on the transformer-base train step.

Round-5 task (VERDICT #1): the hand-written yardstick demonstrates 50.3%
MFU on this chip while the framework records 46.4–47.5%; the ~3.7 ms
residue is XLA fusion *grouping*, and every structural (program-level)
attack measured ~0. This tool attacks the one untried axis: the
compiler's own knobs, passed per-executable via
`lowered.compile(compiler_options=...)` — no env mutation, no effect on
any other compile.

Method (per docs/PERF.md + memory): AOT-compile the SAME lowered step
once per flag set, then two-point-slope time each executable with donated
state threaded through, all in one process so drift cancels in
the ratios. Baseline is re-measured every few configs; the winner is
confirmed with a strict interleaved A/B at the end.

Usage:
    python tools/xla_flag_sweep.py [--model framework|yardstick|both]
                                   [--steps 15] [--json out.json]
"""

from __future__ import annotations

import gc
import json
import sys
import os
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from tools._common import parse_flag, slope_step_time

# Flag sets to try. The names were accepted as per-executable
# compiler_options on the installation the sweep last ran on (2026-07);
# an unknown name fails the compile loudly. Values chosen around the knobs that govern
# fusion grouping / scheduling on TPU:
#   - scoped_vmem_limit_kib: VMEM budget the fusion merger may assume;
#     more lets bigger fusions form (fewer HBM round-trips between them).
#   - experimental_fusion_cost_model / bundle_aware_cost_model: alternate
#     profitability models for the same merge decisions.
#   - multi_level_{input,output}_dot_dot_fusion, dot_dot_fusion_duplicated:
#     let producer/consumer dots fuse through elementwise chains.
#   - rwb_fusion: reduce+broadcast grouping (softmax/LN shape).
#   - vector_{load,store}_fusion_window: instruction-window the vectorizer
#     scans when folding loads/stores into fusions.
#   - licm_size_inflation_ratio: loop-invariant code motion threshold.
#   - aggressive_broadcast_priority_update: scheduler priority tweak.
SWEEPS = [
    ("baseline", {}),
    ("vmem32M", {"xla_tpu_scoped_vmem_limit_kib": "32768"}),
    ("vmem64M", {"xla_tpu_scoped_vmem_limit_kib": "65536"}),
    ("vmem96M", {"xla_tpu_scoped_vmem_limit_kib": "98304"}),
    ("fusion_cost_model",
     {"xla_tpu_enable_experimental_fusion_cost_model": "true"}),
    ("bundle_cost_model",
     {"xla_tpu_use_bundle_aware_cost_model_for_fusions": "true"}),
    ("dot_dot_ml",
     {"xla_tpu_enable_multi_level_input_dot_dot_fusion": "true",
      "xla_tpu_enable_multi_level_output_dot_dot_fusion": "true"}),
    ("dot_dot_dup", {"xla_tpu_dot_dot_fusion_duplicated": "true"}),
    ("no_dot_dot", {"xla_tpu_dot_dot_fusion": "false"}),
    ("no_rwb", {"xla_tpu_rwb_fusion": "false"}),
    ("no_dot_strength", {"xla_tpu_enable_dot_strength_reduction": "false"}),
    ("licm2", {"xla_tpu_licm_size_inflation_ratio": "2.0"}),
    ("bcast_prio",
     {"xla_tpu_enable_aggressive_broadcast_priority_update": "true"}),
    ("vload2048", {"xla_tpu_vector_load_fusion_window": "2048"}),
    ("vstore1024", {"xla_tpu_vector_store_fusion_window": "1024"}),
    ("lhs", {"xla_tpu_enable_latency_hiding_scheduler": "true"}),
    ("order_dot_layout", {"xla_tpu_order_dot_after_layout": "true"}),
]

# Phase 2 (--phase 2): refine around the phase-1 winner
# (xla_tpu_scoped_vmem_limit_kib=32768, x0.87) and try combos with the
# runner-ups (bcast_prio x0.94, bundle_cost_model x0.93).
PHASE2 = [
    ("baseline", {}),
    ("vmem24M", {"xla_tpu_scoped_vmem_limit_kib": "24576"}),
    ("vmem28M", {"xla_tpu_scoped_vmem_limit_kib": "28672"}),
    ("vmem32M", {"xla_tpu_scoped_vmem_limit_kib": "32768"}),
    ("vmem40M", {"xla_tpu_scoped_vmem_limit_kib": "40960"}),
    ("vmem48M", {"xla_tpu_scoped_vmem_limit_kib": "49152"}),
    ("vmem32M+bcast",
     {"xla_tpu_scoped_vmem_limit_kib": "32768",
      "xla_tpu_enable_aggressive_broadcast_priority_update": "true"}),
    ("vmem32M+bundle",
     {"xla_tpu_scoped_vmem_limit_kib": "32768",
      "xla_tpu_use_bundle_aware_cost_model_for_fusions": "true"}),
    ("vmem32M+no_rwb",
     {"xla_tpu_scoped_vmem_limit_kib": "32768",
      "xla_tpu_rwb_fusion": "false"}),
    ("vmem32M", {"xla_tpu_scoped_vmem_limit_kib": "32768"}),  # repeat: drift check
]

# Phase 3 (--phase 3): the shipped default vs baseline, interleaved twice —
# the confirmation A/B (also used on the yardstick for the honest
# framework-vs-yardstick comparison under identical flags).
PHASE3 = [
    ("baseline", {}),
    ("vmem32M", {"xla_tpu_scoped_vmem_limit_kib": "32768"}),
    ("baseline", {}),
    ("vmem32M", {"xla_tpu_scoped_vmem_limit_kib": "32768"}),
]

# Phase R (--model resnet --phase r): conv-program knobs. ResNet-50 is
# HBM-roofline-bound (docs/PERF.md) and the transformer's vmem winner
# HURTS it (-7%), so this sweep asks whether any conv-targeted option
# helps instead.
PHASER = [
    ("baseline", {}),
    ("conv_in_fusion", {"xla_jf_conv_input_fusion": "true"}),
    ("conv_out_fusion", {"xla_jf_conv_output_fusion": "true"}),
    ("conv_in+out", {"xla_jf_conv_input_fusion": "true",
                     "xla_jf_conv_output_fusion": "true"}),
    ("vmem8M", {"xla_tpu_scoped_vmem_limit_kib": "8192"}),
    ("vmem24M", {"xla_tpu_scoped_vmem_limit_kib": "24576"}),
    ("copy_bw2", {"xla_tpu_async_copy_bandwidth_scaling_factor": "2.0"}),
    ("nd_chunks", {"xla_tpu_nd_short_transfer_max_chunks": "4096"}),
    ("bundle_cost_model",
     {"xla_tpu_use_bundle_aware_cost_model_for_fusions": "true"}),
    # distinct label: a second "baseline" entry would re-anchor base_dt
    # BEFORE its ratio prints (always x1.000); this one reports the
    # actual drift vs the opening anchor
    ("baseline_drift_check", {}),
]

# The recorded phase-1 outcome (docs/PERF.md round-5 table): ms/step
# ratio vs the nearest baseline anchor for every config. This is the
# ground truth `--simulate-recorded` replays to evaluate a probe ORDER
# without a chip: how many probes until the order has visited a config
# within 1% of the sweep winner (vmem32M, x0.87).
RECORDED_PHASE1_RATIO = {
    "baseline": 1.00,
    "vmem32M": 0.87, "vmem64M": 0.90, "vmem96M": 0.98,
    "fusion_cost_model": 0.93, "bundle_cost_model": 0.93,
    "dot_dot_ml": 0.94, "bcast_prio": 0.94, "no_dot_dot": 0.95,
    "no_rwb": 0.96, "vstore1024": 0.96, "no_dot_strength": 0.97,
    "order_dot_layout": 0.97, "dot_dot_dup": 1.00, "licm2": 1.00,
    "vload2048": 1.00, "lhs": 1.00,
}


def flag_family(opts: dict) -> str:
    """Map one config's option keys onto the planner's flag FAMILIES
    (the granularity the cost-profile priors score)."""
    if not opts:
        return "baseline"
    keys = " ".join(opts)
    if "scoped_vmem" in keys:
        return "vmem_budget"
    if "conv" in keys or "async_copy" in keys or "nd_short" in keys:
        return "conv_dma"
    if "cost_model" in keys:
        return "fusion_cost"
    if "dot" in keys:
        return "dot_fusion"
    if "rwb" in keys:
        return "reduce_bcast"
    if "vector_" in keys:
        return "vectorizer"
    if "licm" in keys:
        return "licm"
    return "scheduler"


def rank_sweeps(sweeps, model="framework"):
    """fluid-planner probe ordering: score each config's flag family by
    the target program's cost profile (`planner.flag_family_priors`)
    and sort high-prior families first. The baseline anchor stays at
    position 0 (every ratio needs it); within a family the hand-written
    order is preserved. Returns (ranked sweeps, priors)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.analysis import planner

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        if model == "resnet":
            _, fetches = models.resnet.build(class_dim=1000, depth=50,
                                             data_format="NHWC")
            fluid.optimizer.Momentum(learning_rate=0.1,
                                     momentum=0.9).minimize(fetches["loss"])
            feed_shapes = {"image": (128, 224, 224, 3), "label": (128, 1)}
        else:
            _, fetches = models.transformer.build(seq_len=256,
                                                  fused_attention=False)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(
                fetches["loss"])
            feed_shapes = {k: (64, 256)
                           for k in ("src_word", "trg_word", "lbl_word")}
    from paddle_tpu.analysis.cost_model import estimate_cost
    priors = planner.flag_family_priors(
        estimate_cost(main, feed_shapes))
    head = list(sweeps[:1]) if sweeps and sweeps[0][0] == "baseline" \
        else []
    rest = list(sweeps[len(head):])
    order = sorted(range(len(rest)),
                   key=lambda i: (-priors.get(flag_family(rest[i][1]),
                                              0.0), i))
    return head + [rest[i] for i in order], priors


def probes_to_winner(order, ratios, within=0.01):
    """1-based probe index at which `order` first visits a config whose
    recorded ratio is within `within` of the sweep's global best; None
    if it never does."""
    known = [ratios[lab] for lab, _ in order if lab in ratios]
    if not known:
        return None
    best = min(min(known), min(ratios.values()))
    for i, (lab, _) in enumerate(order, 1):
        if ratios.get(lab, float("inf")) <= best * (1.0 + within):
            return i
    return None


def simulate_recorded(sweeps, model="framework"):
    """Replay the recorded phase-1 ratios under both probe orders — the
    chip-free evaluation of the planner ranking (and the acceptance
    record: ranked must reach within 1% of the winner in <= half the
    probes of the full sweep)."""
    ranked, priors = rank_sweeps(sweeps, model)
    ratios = RECORDED_PHASE1_RATIO
    return {
        "mode": "simulate-recorded",
        "model": model,
        "recorded_ratios": ratios,
        "winner": min(ratios, key=ratios.get),
        "n_probes": len(sweeps),
        "original_order": [lab for lab, _ in sweeps],
        "ranked_order": [lab for lab, _ in ranked],
        "original_probes_to_winner": probes_to_winner(sweeps, ratios),
        "ranked_probes_to_winner": probes_to_winner(ranked, ratios),
        "priors": {k: round(v, 4) for k, v in priors.items()},
    }


_V32 = {"xla_tpu_scoped_vmem_limit_kib": "32768"}
# Phase 4 (--phase 4): the remaining phase-1 mild winners stacked ON TOP
# of the shipped vmem32M, plus a finer vmem grid around 32 MiB — chasing
# the last ~4.5% to the yardstick's best build.
PHASE4 = [
    ("baseline", {}),
    ("vmem32M", dict(_V32)),
    ("vmem30M", {"xla_tpu_scoped_vmem_limit_kib": "30720"}),
    ("vmem34M", {"xla_tpu_scoped_vmem_limit_kib": "34816"}),
    ("v32+vstore1024", {**_V32, "xla_tpu_vector_store_fusion_window": "1024"}),
    ("v32+order_dot", {**_V32, "xla_tpu_order_dot_after_layout": "true"}),
    ("v32+fusion_cost", {**_V32,
                         "xla_tpu_enable_experimental_fusion_cost_model": "true"}),
    ("v32+dot_dot_ml", {**_V32,
                        "xla_tpu_enable_multi_level_input_dot_dot_fusion": "true",
                        "xla_tpu_enable_multi_level_output_dot_dot_fusion": "true"}),
    ("v32+no_dot_strength", {**_V32,
                             "xla_tpu_enable_dot_strength_reduction": "false"}),
    ("vmem32M", dict(_V32)),   # repeat: drift check
]


def build_framework_runner(seq_len=256, batch_size=64, fused=False):
    """Build the bench transformer program; return (lowered, caller) where
    caller(compiled) -> window function threading donated state."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import models

    # the executor's own default ("auto") would bake the shipped winner
    # into jax.jit(compiler_options=...), and jit-level options MERGE into
    # every per-call lowered.compile(...) — contaminating the baseline.
    # The sweep must start from compiler defaults.
    fluid.flags.set_flag("xla_compiler_options", "none")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = models.transformer.build(seq_len=seq_len,
                                                  fused_attention=fused)
        loss = fetches["loss"]
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    batch = {k: jax.device_put(rng.randint(1, 30000, (batch_size, seq_len))
                               .astype(np.int32))
             for k in ("src_word", "trg_word", "lbl_word")}
    out = exe.run(main, feed=batch, fetch_list=[loss], return_numpy=False,
                  scope=scope)
    np.asarray(out[0])

    return _make_lowered_runner(exe, scope, batch)


def _make_lowered_runner(exe, scope, batch):
    """Shared tail of every framework-style runner: pick the largest
    compiled step in the executor cache, lower it once, and return a
    window factory that threads the DONATED mut state through every
    config — re-starting a config from the initial state would pass
    deleted arrays (each call invalidates the buffers it was handed)."""
    compiled = max(exe._cache.values(),
                   key=lambda c: len(c.program.global_block().ops))
    mut0, const = compiled.gather_state(scope)
    feeds = {k: batch[k] for k in sorted(batch)}
    # the step as the executor runs it: with its AMP shadows, donated too
    state = {"mut": dict(mut0), "shadows": compiled.make_shadows(mut0)}
    lowered = compiled._step.lower(feeds, mut0, const, np.uint32(0),
                                   state["shadows"])

    def make_window(c):
        def window(n):
            mut, shadows = state["mut"], state["shadows"]
            t0 = time.perf_counter()
            for _ in range(n):
                fetches, new_state, _, shadows = c(feeds, mut, const,
                                                   np.uint32(0), shadows)
                mut = {k: new_state[k] for k in mut}
            np.asarray(fetches[0])
            dt = time.perf_counter() - t0
            state["mut"], state["shadows"] = mut, shadows
            return dt

        return window

    return lowered, make_window


def build_resnet_runner(batch_size=128):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.flags.set_flag("xla_compiler_options", "none")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = models.resnet.build(class_dim=1000, depth=50,
                                             data_format="NHWC")
        loss = fetches["loss"]
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    batch = {
        "image": jax.device_put(rng.rand(batch_size, 224, 224, 3)
                                .astype(np.float32)),
        "label": jax.device_put(rng.randint(0, 1000, (batch_size, 1))
                                .astype(np.int32)),
    }
    out = exe.run(main, feed=batch, fetch_list=[loss], return_numpy=False,
                  scope=scope)
    np.asarray(out[0])
    return _make_lowered_runner(exe, scope, batch)


def build_yardstick_runner(seq_len=256, batch_size=64):
    import jax
    from tools import yardstick_transformer as y

    params = y.init_params(0)
    opt = y.adam_init(params)
    batch = y.make_batch(batch_size, seq_len)
    key = jax.random.key(0)
    lowered = y.train_step.lower(params, opt, batch, key)

    state = {"p": params, "o": opt}      # shared across configs (donation)

    def make_window(c):
        def window(n):
            p, o = state["p"], state["o"]
            t0 = time.perf_counter()
            for _ in range(n):
                p, o, loss = c(p, o, batch, key)
            np.asarray(loss)
            dt = time.perf_counter() - t0
            state["p"], state["o"] = p, o
            return dt

        return window

    return lowered, make_window


def time_config(lowered, make_window, options, steps, warmup=3):
    t0 = time.perf_counter()
    c = lowered.compile(compiler_options=options) if options \
        else lowered.compile()
    compile_s = time.perf_counter() - t0
    w = make_window(c)
    w(warmup)
    dt = slope_step_time(w, steps)
    del c, w
    gc.collect()
    return dt, compile_s


def main():
    argv = sys.argv[1:]
    model = parse_flag(argv, "--model", "framework")
    steps = int(parse_flag(argv, "--steps", "15"))
    out_json = parse_flag(argv, "--json", "")
    phase = parse_flag(argv, "--phase", "1")
    sweeps = {"2": PHASE2, "3": PHASE3, "4": PHASE4,
              "r": PHASER}.get(phase, SWEEPS)

    if "--simulate-recorded" in argv:
        # chip-free: replay the recorded phase-1 ratios under the
        # planner-ranked probe order vs the hand-written one
        sim = simulate_recorded(SWEEPS, model)
        print(f"winner {sim['winner']!r}: ranked order reaches within 1% "
              f"in {sim['ranked_probes_to_winner']} probe(s) vs "
              f"{sim['original_probes_to_winner']} hand-ordered, of "
              f"{sim['n_probes']} total")
        print("ranked:", ", ".join(sim["ranked_order"]))
        if out_json:
            with open(out_json, "w") as f:
                json.dump(sim, f, indent=1)
            print(f"wrote {out_json}")
        return

    rank_info = None
    if "--ranked" in argv:
        sweeps, priors = rank_sweeps(
            sweeps, "resnet" if model == "resnet" else "framework")
        rank_info = {
            "priors": {k: round(v, 4) for k, v in priors.items()},
            "order": [lab for lab, _ in sweeps],
            "families": {lab: flag_family(opts) for lab, opts in sweeps},
        }
        print("planner-ranked probe order:",
              ", ".join(lab for lab, _ in sweeps), flush=True)
    # per-model work-items per step, for the printed rate
    units = {"framework": (64 * 256, "tok"), "yardstick": (64 * 256, "tok"),
             "resnet": (128, "img")}

    targets = []
    if model in ("framework", "both"):
        targets.append(("framework", build_framework_runner()))
    if model in ("yardstick", "both"):
        targets.append(("yardstick", build_yardstick_runner()))
    if model == "resnet":
        targets.append(("resnet", build_resnet_runner()))

    results = {}
    for name, (lowered, make_window) in targets:
        rows = []
        base_dt = None
        for i, (label, opts) in enumerate(sweeps):
            try:
                dt, comp_s = time_config(lowered, make_window, opts, steps)
            except Exception as e:
                print(f"{name:10s} {label:20s} FAILED: {e!r:.120}",
                      flush=True)
                rows.append({"label": label, "opts": opts, "error": str(e)})
                continue
            if label == "baseline":
                base_dt = dt
            ratio = dt / base_dt if base_dt else float("nan")
            rows.append({"label": label, "opts": opts, "ms": dt * 1e3,
                         "vs_baseline": ratio, "compile_s": comp_s})
            n_items, unit = units.get(name, (1, "step"))
            print(f"{name:10s} {label:20s} {dt * 1e3:7.2f} ms/step "
                  f"({n_items / dt:9.1f} {unit}/s) "
                  f"x{ratio:.3f} vs base  [compile {comp_s:.0f}s]",
                  flush=True)
            # re-anchor the baseline every 6 configs: clock drift.
            # tolerate a flaky compile here like everywhere else — a
            # failed recheck keeps the previous anchor instead of
            # aborting the sweep
            if i and i % 6 == 0:
                try:
                    dt_b, _ = time_config(lowered, make_window, {}, steps)
                except Exception as e:
                    print(f"{name:10s} {'baseline(recheck)':20s} "
                          f"FAILED: {e!r:.120}", flush=True)
                else:
                    print(f"{name:10s} {'baseline(recheck)':20s} "
                          f"{dt_b * 1e3:7.2f} ms/step", flush=True)
                    base_dt = dt_b
        results[name] = rows
        if rank_info is not None:
            # the ranked order + how quickly its running best converged,
            # recorded next to the measurements (acceptance evidence)
            valid = [r for r in rows if "ms" in r]
            best_ms = min((r["ms"] for r in valid), default=None)
            conv = None
            if best_ms is not None:
                for i, r in enumerate(valid, 1):
                    if r["ms"] <= best_ms * 1.01:
                        conv = i
                        break
            results[name + "_rank"] = dict(rank_info,
                                           probes_to_winner=conv)

    if out_json:
        with open(out_json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {out_json}")


if __name__ == "__main__":
    main()
