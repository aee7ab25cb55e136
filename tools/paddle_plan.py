#!/usr/bin/env python
"""paddle_plan: the fluid-planner CLI — ranked mesh plans for a model.

Prints the cost-model-driven `PlanReport` for a book model at a given
chip count: every dp×mp×sp factorization with predicted step time, MFU,
per-device peak HBM (OOM candidates rejected with the reason) and
bytes-on-the-wire, fastest first. The same search backs
`parallel.mesh.auto_mesh`; this tool is the human/CI view of it.

    python tools/paddle_plan.py --model transformer --devices 8
    python tools/paddle_plan.py --model resnet --devices 4 --json
    python tools/paddle_plan.py --model transformer --devices 1 \
        --full-size --peak-tflops 191.5      # bench calibration run

Exit status is the CI gate: nonzero when NO candidate fits the device
memory budget (i.e. the top candidate's predicted peak HBM exceeds it)
— a program that cannot be placed should fail the pipeline before it
fails on the chip. `--hw cpu` forces the virtual-device rehearsal
profile, `--hbm-gb`/`--peak-tflops` override single knobs for what-if
runs (knobs documented in docs/PLANNER.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="ranked dp*mp*sp mesh plans from the per-op cost model")
    ap.add_argument("--model", choices=("mlp", "transformer", "resnet"),
                    default="transformer")
    ap.add_argument("--devices", type=int, default=8,
                    help="chip count to factorize (default 8)")
    ap.add_argument("--batch", type=int, default=8,
                    help="global batch the feeds are sized at (default 8)")
    ap.add_argument("--full-size", action="store_true",
                    help="transformer: the real base config (bench shape, "
                         "batch 64 x seq 256 unless overridden)")
    ap.add_argument("--topk", type=int, default=12)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--hw", choices=("auto", "tpu", "cpu"), default="auto",
                    help="hardware profile (default: detect from backend)")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="override the profile's peak (e.g. the bench's "
                         "freshly measured value)")
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="override the per-device memory budget")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers, models
    from paddle_tpu.analysis import planner
    from tools.op_profile import build_mlp, build_resnet

    batch = args.batch
    if args.model == "transformer" and args.full_size and args.batch == 8:
        batch = 64   # the bench shape, so plan vs bench MFU is like-for-like

    def build_transformer_train(fluid_, layers_, batch_):
        # a TRAIN step (op_profile's is inference-only): fused attention
        # with dropout 0 — the dryrun/mesh configuration, so sp
        # candidates are plannable — and Adam like the bench
        kw = {} if args.full_size else dict(
            src_vocab_size=128, trg_vocab_size=128, seq_len=16, n_layer=2,
            n_head=4, d_model=64, d_inner=128)
        _, fetches = models.transformer.build(dropout_rate=0.0,
                                              fused_attention=True, **kw)
        fluid_.optimizer.Adam(learning_rate=1e-3).minimize(
            fetches["loss"])
        seq = 256 if args.full_size else 16
        feed = {k: np.zeros((batch_, seq), np.int64)
                for k in ("src_word", "trg_word", "lbl_word")}
        return fetches["loss"], feed

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup), fluid.unique_name.guard():
        _, feed = {
            "mlp": build_mlp,
            "transformer": build_transformer_train,
            "resnet": build_resnet,
        }[args.model](fluid, layers, batch)
    feed_shapes = {k: tuple(v.shape) for k, v in feed.items()}

    hw = {"tpu": lambda: planner.TPU_CHIP,
          "cpu": lambda: planner.CPU_REHEARSAL,
          "auto": planner.detect_hardware}[args.hw]()
    if args.peak_tflops is not None:
        hw = hw.replace(peak_flops=args.peak_tflops * 1e12)
    if args.hbm_gb is not None:
        hw = hw.replace(hbm_bytes=args.hbm_gb * 1e9)

    report = planner.plan_meshes(main_p, feed_shapes, args.devices, hw=hw)
    best = report.best

    if args.json:
        out = report.as_dict(args.topk)
        out["model"] = args.model
        out["batch"] = batch
        out["feed_shapes"] = {k: list(v) for k, v in feed_shapes.items()}
        print(json.dumps(out, sort_keys=True))
    else:
        print(f"model={args.model} batch={batch} "
              f"devices={args.devices} hw={hw.name}")
        print(report.table(args.topk))
        if best is not None:
            print(f"PLAN: {best.label()} — predicted "
                  f"{best.t_step_s * 1e3:.3f} ms/step, "
                  f"MFU {best.mfu:.1%}, peak HBM "
                  f"{best.peak_hbm_bytes / 1e9:.2f} GB of "
                  f"{hw.hbm_bytes / 1e9:.2f} GB")

    if best is None:
        top = report.candidates[0] if report.candidates else None
        print(f"FAIL: no feasible mesh — top candidate "
              f"{top.label() if top else '?'}: "
              f"{top.reason if top else 'no candidates'}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
