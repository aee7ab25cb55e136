#!/usr/bin/env python
"""Headline benchmarks on one TPU chip, printed as ONE JSON line.

Primary metric: ResNet-50 ImageNet training throughput (NHWC, bf16 AMP).
Baseline: the best ResNet-50 training number published in the reference repo —
84.08 images/sec (CPU MKL-DNN bs256, reference
benchmark/IntelOptimizedPaddle.md:41-45; no GPU ResNet-50 number is published
in-tree, see BASELINE.md).

MFU is computed honestly: model FLOPs come from XLA's own cost analysis of
the compiled train step, and the peak is MEASURED on this chip at bench time
(chained 4096^3 bf16 matmuls), not taken from a datasheet.

`extra` carries the second BASELINE.json metric (Transformer-base WMT
tokens/sec) as a like-for-like fused/unfused pair at seq 256, and the
long-context pair at seq 2048 where the Pallas flash path wins.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

BASELINE_IMG_PER_SEC = 84.08

# Published claim ranges — the README "Performance" section and
# docs/PERF.md tables are generated from these, and these are derived
# ONLY from driver-recorded BENCH_r*.json values plus the current build's
# measured envelope (round-5 claim-hygiene contract: a published range
# must contain what the driver records). When a fresh measurement falls
# outside its range, bench prints a CLAIM-DRIFT warning (fail-soft) so
# the drift is visible in the recorded tail instead of silently shipping.
CLAIMS = {
    "transformer_base_wmt_tokens_per_sec": (210_000, 275_000),
    "transformer_mfu": (0.42, 0.56),
    "resnet50_mfu": (0.27, 0.32),
    "transformer_seq2048_flash_tokens_per_sec": (71_000, 105_000),
    # narrowed in round 5 BECAUSE the unfused side got faster (the
    # scoped-VMEM flag applies to it too): observed 1.37-1.52 on this
    # build vs r4's recorded 1.51 on a slower unfused baseline
    "flash_vs_unfused_seq4096": (1.30, 1.75),
    "stacked_lstm_examples_per_sec": (3_500, 15_000),
    "feeder_overlap_speedup_cpu_demo": (1.3, 2.3),
    # round 12 (fluid-wire): int8 per-chunk codec on the dense sync-PS
    # push path — 4x data minus per-chunk scale overhead; the acceptance
    # floor is 2.0 (bf16 territory), the ceiling is the int8 theoretical
    "wire_compression_x": (2.0, 4.05),
    # round 6: host dispatch overhead, prepared vs the pre-round-6 run()
    # path (tools/step_overhead_bench.py, CPU subprocess — host-side
    # python, backend-independent). The floor of 2.0 is the acceptance
    # criterion; the ceiling is generous because the measured ratio
    # divides two µs-scale medians on a shared 1-core box
    "step_overhead_reduction_x": (2.0, 500.0),
}


def check_claims(extra, out=sys.stderr):
    drift = []
    for k, (lo, hi) in CLAIMS.items():
        v = extra.get(k)
        if not isinstance(v, (int, float)):
            continue
        if v <= 0:
            # failure sentinel (a sub-bench crashed/timed out and recorded
            # 0.0) — that is a broken measurement, not a claim problem
            print(f"MEASUREMENT-FAILED: {k}={v} (sub-bench failure "
                  f"sentinel; not counted as claim drift)", file=out)
            continue
        if not (lo <= v <= hi):
            drift.append(k)
            print(f"CLAIM-DRIFT: {k}={v} outside the published range "
                  f"[{lo}, {hi}] — re-derive README/docs/PERF.md ranges "
                  f"from the recorded BENCH_r*.json values", file=out)
    return drift


def measure_peak_tflops(jax):
    """Measured bf16 matmul peak for THIS chip: chained 4096^3 matmuls.
    Two-point (reps) slope cancels the constant dispatch+sync overhead of
    a window; the median of 3 slope measurements tames run-to-run
    variance.
    Operands carry mixed-sign varied data with a per-step renorm so no
    value pattern (identity, zeros) can flatter the kernel."""
    import jax.numpy as jnp
    from jax import lax

    N_MM = 512   # ~350 ms of device time per call — amortizes all jitter

    @jax.jit
    def chain(x, w):
        def body(c, _):
            c = c @ w
            c = c * lax.rsqrt(jnp.float32(jnp.mean(
                jnp.square(c.astype(jnp.float32))) + 1e-6)).astype(c.dtype)
            return c, ()
        out, _ = lax.scan(body, x, None, length=N_MM)
        return out.sum()

    i = jnp.arange(4096, dtype=jnp.float32)
    x = (jnp.sin(i)[:, None] * jnp.cos(i)[None, :]).astype(jnp.bfloat16)
    w = (jnp.cos(2 * i)[:, None] * jnp.sin(3 * i)[None, :] * 0.02) \
        .astype(jnp.bfloat16)
    chain(x, w).block_until_ready()

    def run(reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = chain(x, w)
        out.block_until_ready()
        return time.perf_counter() - t0

    slopes = []
    for _ in range(3):
        t_lo, t_hi = run(1), run(3)
        slopes.append((t_hi - t_lo) / 2)
    per_call = sorted(slopes)[1]
    return N_MM * 2 * 4096 ** 3 / per_call / 1e12


def _step_flops(exe, scope, feed_arrays):
    """XLA cost-analysis FLOPs of the largest compiled step in the cache.
    A failure here raises: an MFU computed from a stand-in would read as
    a measurement."""
    from tools._common import compile_main_step

    ca = compile_main_step(exe, scope, feed_arrays).cost_analysis()
    return float(ca["flops"])


def bench_resnet(fluid, models, jax, want_flops=False):
    batch_size = int(os.environ.get("BENCH_BATCH", "128"))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = models.resnet.build(class_dim=1000, depth=50,
                                             data_format="NHWC")
        loss = fetches["loss"]
        opt = fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        opt.minimize(loss)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0),
                         amp=os.environ.get("BENCH_AMP", "1") == "1")
    exe.run(startup, scope=scope)

    # Pre-stage batches on device and cycle them — the AsyncFeeder
    # double-buffer pattern: the step time measured is the device's, not
    # the host-to-device copy's.
    rng = np.random.RandomState(0)
    batches = []
    for _ in range(4):
        batches.append({
            "image": jax.device_put(rng.rand(batch_size, 224, 224, 3)
                                    .astype(np.float32)),
            "label": jax.device_put(rng.randint(0, 1000, (batch_size, 1))
                                    .astype(np.int32)),
        })

    for i in range(warmup):
        out = exe.run(main, feed=batches[i % 4], fetch_list=[loss],
                      return_numpy=False, scope=scope)
    out[0].block_until_ready()

    def window(n):
        t0 = time.perf_counter()
        for i in range(n):
            out = exe.run(main, feed=batches[i % 4], fetch_list=[loss],
                          return_numpy=False, scope=scope)
        out[0].block_until_ready()
        return time.perf_counter() - t0

    # two-point window slope, median of 3: cancels the fixed sync each
    # window pays
    from tools._common import slope_step_time
    dt = slope_step_time(window, steps)
    ips = batch_size / dt
    flops = _step_flops(exe, scope, batches[0]) if want_flops else 0.0
    return ips, flops / dt


def bench_transformer(fluid, models, jax, seq_len, batch_size, fused,
                      steps=15, warmup=4, want_flops=False):
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
    for _ in range(warmup):
        out = exe.run(main, feed=batch, fetch_list=[loss],
                      return_numpy=False, scope=scope)
    out[0].block_until_ready()

    def window(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = exe.run(main, feed=batch, fetch_list=[loss],
                          return_numpy=False, scope=scope)
        out[0].block_until_ready()
        return time.perf_counter() - t0

    from tools._common import slope_step_time
    dt = slope_step_time(window, steps)
    tok_s = batch_size * seq_len / dt
    flops = _step_flops(exe, scope, batch) if want_flops else 0.0
    return tok_s, flops / dt


def bench_stacked_lstm(fluid, models, jax, batch_size=64, seq_len=100,
                       steps=64, warmup=3):
    """Variable-length RNN path (BASELINE config "Stacked dynamic LSTM
    LM"): 3x512 masked-scan LSTMs with peepholes over padded batches +
    lengths, IMDB-shaped (seq 100, dict 30k — the reference's RNN
    benchmark config, benchmark/README.md:111).

    steps=64: the LSTM step is ~1-3 ms of device time, so a short
    window's slope is dispatch noise; a 48-step delta puts >100 ms of
    device time behind the measurement."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, outs = models.stacked_dynamic_lstm.build()
        loss = outs["loss"]
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    words = rng.randint(1, 30000, (batch_size, seq_len, 1)).astype(np.int64)
    lens = rng.randint(seq_len // 2, seq_len + 1,
                       (batch_size,)).astype(np.int32)
    feed = {"words": (words, lens),
            "label": rng.randint(0, 2, (batch_size, 1)).astype(np.int64)}
    for _ in range(warmup):
        out = exe.run(main, feed=feed, fetch_list=[loss],
                      return_numpy=False, scope=scope)
    out[0].block_until_ready()

    def window(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = exe.run(main, feed=feed, fetch_list=[loss],
                          return_numpy=False, scope=scope)
        out[0].block_until_ready()
        return time.perf_counter() - t0

    from tools._common import slope_step_time
    dt = slope_step_time(window, steps)
    return batch_size * seq_len / dt, batch_size / dt


def _tool_json(script, label, args=(), timeout=600):
    """Shared CPU-subprocess segment runner: every sub-bench that owns no
    TPU state runs as `python tools/<script>` in a subprocess and prints
    its record as the last '{'-prefixed stdout line. This process holds
    the chip and a chip belongs to one process, so the child gets
    JAX_PLATFORMS=cpu in ITS environment and never probes the device.
    Returns (record, returncode); a child that leaves no record raises,
    which fails the segment."""
    import subprocess

    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools", script)] + list(args),
        capture_output=True, text=True, timeout=timeout,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    if not lines:
        raise RuntimeError(
            f"{label}: no JSON record on stdout (rc={out.returncode}); "
            f"stderr tail: {out.stderr[-400:]!r}")
    return json.loads(lines[-1]), out.returncode


# every segment label, in run order — the vocabulary for --segments /
# --skip-segments (prefix match, so `--segments transformer` selects the
# whole family and `--skip-segments quorum,elastic` drops two planes)
BENCH_SEGMENTS = (
    "peak_probe",
    "transformer256_unfused", "transformer256_flash",
    "resnet50",
    "transformer2048_unfused", "transformer2048_flash",
    "transformer4096_unfused", "transformer4096_flash",
    "feeder_overlap_subprocess",
    "stacked_lstm",
    "step_overhead_subprocess",
    "op_cost_subprocess",
    "serve_loadgen_subprocess",
    "decode_loadgen_subprocess",
    "fleet_subprocess",
    "torrent_subprocess",
    "wire_bench_subprocess",
    "haven_subprocess",
    "quorum_subprocess",
    "elastic_subprocess",
    "horizon_subprocess",
    "transformer256_remeasure",
    "resnet50_remeasure",
    "planner_subprocess",
)


def _parse_bench_args(argv=None):
    """Segment selection + the per-segment time budget (BENCH_r05: the
    driver's watchdog killed a whole run at rc=124 with nothing
    recorded — a bounded budget per segment and the ability to carve
    the run into driver-sized pieces are the fix). Flags default from
    the BENCH_* environment so existing drivers keep working unchanged."""
    import argparse
    ap = argparse.ArgumentParser(
        description="paddle_tpu benchmark driver (one JSON line on "
                    "stdout; deselected segments record sentinels)")
    ap.add_argument("--segments",
                    default=os.environ.get("BENCH_SEGMENTS", ""),
                    help="comma-separated label prefixes to RUN "
                         "(empty = all); see --list-segments")
    ap.add_argument("--skip-segments",
                    default=os.environ.get("BENCH_SKIP_SEGMENTS", ""),
                    help="comma-separated label prefixes to skip")
    ap.add_argument("--segment-budget-s", type=float,
                    default=float(os.environ.get(
                        "BENCH_SEGMENT_BUDGET_S", 600)),
                    help="per-segment wall budget; a segment past it "
                         "records its sentinel and the run moves on")
    ap.add_argument("--list-segments", action="store_true",
                    help="print the segment labels in run order and exit")
    return ap.parse_args(argv)


def _segment_filter(args):
    want = [s.strip() for s in args.segments.split(",") if s.strip()]
    skip = [s.strip() for s in args.skip_segments.split(",") if s.strip()]

    def selected(label):
        if want and not any(label.startswith(w) for w in want):
            return False
        return not any(label.startswith(s) for s in skip)

    return selected


def feeder_overlap_subprocess():
    """AsyncFeeder proof on the CPU backend: the demo measures the
    overlap property itself (I/O-bound producer hidden under
    per-step-synced compute) with in-process timing."""
    rec, _ = _tool_json("feeder_overlap_demo.py", "feeder overlap demo")
    return rec


def step_overhead_subprocess():
    """Host dispatch µs/step, prepared vs unprepared
    (tools/step_overhead_bench.py — host dispatch is backend-independent
    python)."""
    rec, _ = _tool_json("step_overhead_bench.py", "step overhead bench")
    return rec


def op_cost_subprocess():
    """fluid-xray cost model: the per-op cost table of the (scaled-down)
    book transformer, cross-checked against XLA's own cost_analysis.
    The compact summary lands in the recorded JSON so every bench round
    carries the cost-attribution story the fluid-planner work will
    consume."""
    rec, _ = _tool_json("op_profile.py", "op cost profile",
                        args=("--model", "transformer", "--json"))
    top = rec.get("top") or [{}]
    return {
        "op_cost_total_gflops": round(
            rec.get("total_flops", 0.0) / 1e9, 4),
        "op_cost_xla_agreement": rec.get("xla_agreement", 0.0),
        "op_cost_arithmetic_intensity": round(
            rec.get("arithmetic_intensity", 0.0), 2),
        "op_cost_top_op": (f"{top[0].get('type')}:{top[0].get('out')}"
                           f"={top[0].get('flops_share', 0.0):.0%}"
                           if top[0] else ""),
    }


def wire_bench_subprocess():
    """fluid-wire numbers (tools/wire_bench.py — the pserver wire is host
    TCP + numpy): the sync-PS dense push A/B — bytes/step raw vs on-wire,
    the compression ratio (acceptance: >= 2.0), step-time both modes,
    the sparse-row compression, and the quantized-vs-raw loss delta."""
    rec, _ = _tool_json("wire_bench.py", "wire bench")
    return rec


def serve_loadgen_subprocess():
    """fluid-serve numbers (tools/serve_loadgen.py — serving host
    mechanics are backend-independent python around a prepared step).
    Nonzero exit = a steady-state recompile or a failed request; the
    sentinel keeps that visible in the JSON."""
    rec, rc = _tool_json("serve_loadgen.py", "serve loadgen",
                         args=("--duration", "6"))
    if rc != 0:
        rec["serve_loadgen_rc"] = rc
    return rec


def horizon_subprocess():
    """fluid-horizon trace-context overhead: ONE oneshot serve loadgen
    with observe ON throughout, alternating the `trace` flag off (no
    span ids, no recording, legacy wire frames) and on across paired
    open-loop phases. Both halves pay for the metrics/pulse plane, so
    the delta prices trace context ALONE. Acceptance: median paired
    open-loop p50 delta within 2% of the trace-off p50.

    PAIRED IN ONE PROCESS (`--trace-ab`): two separate loadgen
    subprocesses differ by tens of microseconds from allocator layout
    and CPU frequency alone — more than the tracing effect under test —
    so the loadgen alternates the flag across open-loop phases of ONE
    warmed process and the gate reads the median paired p50 delta.
    Phases are grouped into ABBA blocks (off,on,on,off — mirrored every
    other block): the latency floor also wanders WITHIN a run by more
    than the effect, and a fixed phase order turns that drift into
    systematic bias, while ABBA cancels linear drift inside each block.

    Single in-process client (`--threads 1`): the loadgen's default 4
    in-process client threads all contend for this 1-core container's
    GIL, and that client-side contention amplifies any server-side work
    severalfold — a rig artifact (real serving clients are remote
    processes; their scheduling doesn't tax the server's interpreter).
    One client still exercises the full submit -> batch -> record path,
    so the delta prices the server-side trace cost the gate is about."""
    res, rc = _tool_json(
        "serve_loadgen.py", "horizon trace A/B (paired)",
        args=("--trace-ab", "8", "--duration", "64", "--threads", "1",
              "--no-swap"))
    p50_off = res.get("serve_p50_us_trace_off", 0.0)
    p50_on = res.get("serve_p50_us_trace_on", 0.0)
    delta = res.get("trace_p50_delta_us", 0.0)
    overhead = res.get("trace_overhead_pct", -1.0)
    return {
        "horizon_trace_overhead_pct": overhead,
        "horizon_overhead_ok": bool(0 <= overhead <= 2.0 or delta <= 0),
        "horizon_p50_us_trace_off": p50_off,
        "horizon_p50_us_trace_on": p50_on,
        "horizon_p50_delta_us": delta,
        "horizon_ab_rounds": res.get("trace_ab_rounds", 0),
        "horizon_ab_rc": rc,
    }


def decode_loadgen_subprocess():
    """fluid-decode numbers (tools/serve_loadgen.py --workload generate —
    paged-KV continuous batching over a tiny LM; host mechanics are
    backend-independent python around two prepared steps). Runs the
    continuous/drain A/B at saturating offered load: tokens/s, TTFT
    p50/p99, and the continuous-over-drain speedup (acceptance >= 1.3x).
    The drill itself gates on zero steady-state recompiles AND exact
    solo-parity of under-load generations; rc != 0 keeps that visible."""
    # qps 800 offers ~2.9x the drain-mode capacity measured on the CPU
    # rehearsal box — deep-queue saturation, where slot occupancy (not
    # admission rate) is what bounds throughput and the A/B is honest.
    # TTFT at that point is queueing delay, not serving latency, so the
    # headline ttft_p50/p99 come from a separate moderate-load run.
    cont, rc_c = _tool_json(
        "serve_loadgen.py", "decode loadgen (continuous)",
        args=("--workload", "generate", "--duration", "8",
              "--qps", "800", "--no-swap"))
    drain, rc_d = _tool_json(
        "serve_loadgen.py", "decode loadgen (drain)",
        args=("--workload", "generate", "--duration", "8",
              "--qps", "800", "--admission", "drain", "--no-swap"))
    lat, rc_l = _tool_json(
        "serve_loadgen.py", "decode loadgen (latency)",
        args=("--workload", "generate", "--duration", "6",
              "--qps", "120", "--no-swap"))
    d = drain.get("decode_tokens_per_s", 0.0)
    out = {
        "decode_tokens_per_s": cont.get("decode_tokens_per_s", 0.0),
        "decode_recompiles": cont.get("decode_recompiles", -1),
        "decode_avg_occupancy": cont.get("decode_avg_occupancy", 0.0),
        "decode_generations": cont.get("decode_generations", 0),
        "ttft_p50_us": lat.get("ttft_p50_us", 0.0),
        "ttft_p99_us": lat.get("ttft_p99_us", 0.0),
        "ttft_p50_us_saturated": cont.get("ttft_p50_us", 0.0),
        "decode_tokens_per_s_drain": d,
        "ttft_p50_us_drain": drain.get("ttft_p50_us", 0.0),
    }
    out["decode_continuous_speedup_x"] = round(
        out["decode_tokens_per_s"] / d, 2) if d else 0.0
    if rc_c:
        out["decode_loadgen_rc"] = rc_c
    if rc_l:
        out["decode_loadgen_latency_rc"] = rc_l
    if rc_d:
        out["decode_loadgen_drain_rc"] = rc_d
    return out


def fleet_subprocess():
    """fluid-fleet numbers (tools/serve_loadgen.py --replicas N + the
    replica_kill chaos drill; replicas are SUBPROCESSES, the router is
    in-process host python): the 1-vs-3 replica QPS scaling curve
    (acceptance: >= 2.5x at N=3), the skew-free coordinated swap under
    load, p99 across a mid-run replica SIGKILL with ZERO failed
    requests, and the end-to-end DeepFM drill whose embedding tables
    live only in pserver shards.

    Rehearsal-rig honesty: on a real fleet each replica's step runs on
    its own TPU chip, so host CPU is not what a replica count scales.
    This container is 1-core, so each replica SIMULATES its device time
    (--device-ms, serialized per replica, recorded in the JSON as
    fleet_device_ms_simulated) and the segment measures what the fleet
    tier actually adds: router dispatch, RPC, membership and failover
    overhead — the part that could destroy linear chip scaling."""
    DEV_MS = "6"
    common = ("--duration", "6", "--qps", "600", "--threads", "24",
              "--device-ms", DEV_MS, "--no-swap")
    one, rc1 = _tool_json("serve_loadgen.py", "fleet loadgen (1 replica)",
                          args=("--replicas", "1") + common, timeout=300)
    three, rc3 = _tool_json("serve_loadgen.py",
                            "fleet loadgen (3 replicas + swap)",
                            args=("--replicas", "3", "--duration", "6",
                                  "--qps", "600", "--threads", "24",
                                  "--device-ms", DEV_MS), timeout=300)
    dfm, rc_d = _tool_json("serve_loadgen.py",
                           "fleet loadgen (deepfm sparse)",
                           args=("--replicas", "2", "--duration", "5",
                                 "--qps", "60", "--threads", "6",
                                 "--fleet-model", "deepfm-sparse",
                                 "--sparse-quant", "int8"), timeout=300)
    q1 = one.get("fleet_qps", 0.0)
    q3 = three.get("fleet_qps", 0.0)
    out = {
        "fleet_qps_1": q1,
        "fleet_qps_3": q3,
        "fleet_qps_scaling_x": round(q3 / q1, 2) if q1 else 0.0,
        "fleet_p99_us_3": three.get("fleet_p99_us", 0.0),
        "fleet_swap_skew_violations": three.get(
            "fleet_skew_violations", -1),
        "fleet_swap_ok": three.get("fleet_swap_ok", False),
        "fleet_recompiles": (one.get("fleet_recompiles", 0)
                             + three.get("fleet_recompiles", 0)),
        "fleet_device_ms_simulated": float(DEV_MS),
    }
    if rc1 or rc3:
        out["fleet_loadgen_rc"] = rc1 or rc3
    out["fleet_deepfm_qps"] = dfm.get("fleet_qps", 0.0)
    out["fleet_deepfm_failed"] = dfm.get("fleet_failed", -1)
    sp = next(iter((dfm.get("fleet_sparse") or {}).values()), {})
    m = next(iter(sp.values()), {}) if sp else {}
    out["fleet_deepfm_cache_hits"] = m.get("cache_hits", 0)
    out["fleet_deepfm_cache_misses"] = m.get("cache_misses", 0)
    if rc_d:
        out["fleet_deepfm_rc"] = rc_d
    # the replica-kill drill: p99 pre/post SIGKILL, zero failed gate
    kill, rc_k = _tool_json("chaos_drill.py", "replica_kill drill",
                            args=("--scenario", "replica_kill"),
                            timeout=300)
    out["fleet_p99_under_kill_us"] = kill.get("fleet_p99_post_kill_us", 0.0)
    out["fleet_p99_pre_kill_us"] = kill.get("fleet_p99_pre_kill_us", 0.0)
    out["fleet_kill_failed_requests"] = kill.get("fleet_kill_failed", -1)
    if rc_k:
        out["fleet_kill_drill_rc"] = rc_k
    return out


def torrent_subprocess():
    """fluid-torrent numbers (tools/torrent_bench.py + the decode_kill
    chaos drill): the disaggregated serving plane (1 prefill + 2 decode
    replicas, int8 KV residency, wire-streamed KV) vs the pre-torrent
    co-located fp32 baseline at a FIXED fleet size and a FIXED per-chip
    KV byte budget. Acceptance: the torrent arm wins BOTH lower TTFT
    p99 AND higher tokens/s/chip (gains > 1.0) with zero failed and
    zero token-divergent generations and the KV transfer bytes metered,
    and the decode_kill drill loses zero completed tokens across a
    mid-generation decode-replica SIGKILL (re-prefill failover).

    Device-cost honesty as in fleet_subprocess: replicas simulate the
    two TPU cost shapes (compute-bound prefill us/token, memory-bound
    decode us/STEP — the decode batch rides one HBM sweep) so a 1-core
    rig prices what disaggregation actually moves: which chip pays the
    prefill stall and how many resident sequences amortize each decode
    sweep."""
    res, rc = _tool_json("torrent_bench.py", "torrent bench",
                         args=("--duration", "6", "--clients", "12"),
                         timeout=480)
    out = dict(res)
    out["torrent_bench_ok"] = (
        rc == 0 and res.get("torrent_throughput_gain_x", 0.0) > 1.0
        and res.get("torrent_ttft_p99_gain_x", 0.0) > 1.0)
    # the decode_kill drill: SIGKILL a decode replica mid-generation;
    # session-affinity failover must re-prefill onto a survivor with
    # zero failed generations and zero token divergence
    kill, rc_k = _tool_json("chaos_drill.py", "decode_kill drill",
                            args=("--scenario", "decode_kill"), timeout=300)
    out["torrent_decode_kill_failed"] = kill.get("decode_kill_failed", -1)
    out["torrent_decode_kill_divergent"] = kill.get(
        "decode_kill_divergent", -1)
    out["torrent_decode_kill_failovers"] = kill.get(
        "decode_kill_failovers", -1)
    if rc_k:
        out["torrent_decode_kill_rc"] = rc_k
    return out


def haven_subprocess():
    """fluid-haven numbers (tools/haven_bench.py — the replicated PS
    plane is host TCP + numpy): steady-state sync-PS step-time overhead
    of primary/backup replication with the int8 wire codec on
    (acceptance: <= 10%, measured under the fleet segment's simulated-
    device-time convention — the backup's apply CPU belongs to another
    host on a real deployment), and the failover blip — the wall-time
    gap in trainer step completions across a primary SIGKILL, which
    must land under lease time + one retry/resolve budget."""
    rec, rc = _tool_json("haven_bench.py", "haven bench", timeout=420)
    if rc:
        rec["haven_bench_rc"] = rc
    return rec


def quorum_subprocess():
    """fluid-quorum numbers (tools/quorum_bench.py — the arbiter plane
    is host TCP + json): lease-renewal overhead on the sync-PS step of
    a quorum-armed haven pair vs the PR 12 haven baseline, interleaved
    min-of-medians (acceptance: <= 2% — the renewal is one tiny
    majority fan-out per lease/3 on its own thread), and the
    asymmetric-partition failover blip — the wall-time gap in trainer
    step completions while the primary fences, steps down, and the
    majority-side backup wins the election — which must land inside
    the 2-lease + retry/resolve budget (quorum_failover_ok)."""
    rec, rc = _tool_json("quorum_bench.py", "quorum bench", timeout=420)
    if rc:
        rec["quorum_bench_rc"] = rc
    return rec


def elastic_subprocess():
    """fluid-elastic numbers (tools/elastic_bench.py — the HA data
    plane is host TCP + json): `master_failover_blip_ms` — the largest
    consumer-visible stall streaming task leases across a SIGKILL'd
    primary master (lease expiry + quorum election + client
    re-resolution, gated against the 2-lease + retry/resolve
    `master_failover_budget_ms`) — and `elastic_scaleup_admission_s`,
    the first-heartbeat-to-counted-world latency of a NEW trainer id
    joining a running sync-PS world (barrier-epoch admission)."""
    rec, rc = _tool_json("elastic_bench.py", "elastic bench", timeout=420)
    if rc:
        rec["elastic_bench_rc"] = rc
    return rec


def planner_subprocess(peak, tf_fps):
    """fluid-planner agreement segment (tools/paddle_plan.py, CPU
    subprocess — the plan is a static walk, no device work): predicted
    MFU of the bench transformer from the roofline cost model, against
    the MFU this very run measured. plan_agreement = predicted/measured
    is the health gate on the planner's calibration — the mesh search
    and HBM gate rank with the same model."""
    if not peak or not tf_fps:
        raise RuntimeError(
            "planner_subprocess compares against this run's measured peak "
            "and transformer FLOP/s: select peak_probe and "
            "transformer256_unfused with it")
    measured_mfu = tf_fps / peak
    rec, rc = _tool_json(
        "paddle_plan.py", "planner plan",
        args=("--model", "transformer", "--full-size", "--devices", "1",
              "--hw", "tpu", "--peak-tflops", f"{peak / 1e12:.1f}",
              "--json"))
    best = rec["best"]
    return {
        "plan_predicted_mfu": round(best["mfu"], 3),
        "plan_measured_mfu": round(measured_mfu, 3),
        "plan_agreement": round(best["mfu"] / measured_mfu, 3),
        "plan_predicted_step_us": best.get("step_time_us", 0.0),
        "plan_predicted_peak_hbm_gb": round(
            best.get("peak_hbm_bytes", 0) / 1e9, 2),
        "plan_rc": rc,
    }


def _release(jax):
    """Drop compiled executables + dead buffers between benches: the
    long-context configs need most of the chip's 15.75 GB HBM and OOM if
    earlier benches' donated buffers / cached executables linger."""
    import gc

    gc.collect()
    jax.clear_caches()
    gc.collect()


# Progressive result record: every derived metric lands here as soon as
# it is measured, so the hang watchdog can emit a PARTIAL-but-valid JSON
# line if the process wedges inside a native call later on.
_PARTIAL = {"value": 0.0, "extra": {}}
_DONE = None  # threading.Event, set when main() prints normally


_EMIT_ONCE = threading.Lock()


def _emit_partial_and_exit(reason=None):
    """Emit a WELL-FORMED (partial) JSON record and hard-exit: the driver
    must never be left with only a raw log tail (BENCH_r05 recorded
    rc=124 with no JSON at all). `failure_stage` names the segment that
    was running when the run died; `segment_wall_s` has the per-segment
    wall timings measured so far.

    Exactly-once: SIGTERM can reach both the Python-level handler (main
    thread) and the wakeup-fd watcher thread — only the first caller
    emits, later callers park until its os._exit tears the process down
    (two interleaved JSON lines would be worse than none)."""
    if not _EMIT_ONCE.acquire(blocking=False):
        while True:
            time.sleep(60)
    # everything below runs under try/finally: whatever goes wrong, the
    # process MUST still exit promptly (a dead emitter holding the lock
    # would recreate the lingering-process failure this code fixes)
    try:
        _PARTIAL["extra"]["bench_failure"] = reason or (
            "global watchdog fired: a segment hung in a native call; "
            "metrics below were measured before the hang, the rest are "
            "absent")
        # flight recorder (fluid-xray): the black box — last N step
        # records, RPC outcomes, compile events, the failing stage —
        # lands next to the partial JSON so an abnormal exit leaves a
        # postmortem artifact, not just a log tail
        try:
            from paddle_tpu.observe import flight as _flight
            _flight.set_stage(str(_PARTIAL["extra"].get("failure_stage")))
            fp = _flight.dump(
                os.environ.get("BENCH_FLIGHT_PATH")
                or _flight.default_dump_path(),
                reason=str(_PARTIAL["extra"]["bench_failure"])[:200])
            if fp:
                _PARTIAL["extra"]["flight_recorder"] = fp
        except Exception:
            pass
        # the main thread may still be mutating _PARTIAL["extra"]
        # (note(), per-segment bookkeeping) while this thread serializes
        # it — retry the dump (any error: concurrent-mutation
        # RuntimeError, a non-JSON value, ...), then degrade to the
        # failure reason alone rather than emit NOTHING
        line = None
        for attempt in range(5):
            try:
                line = json.dumps({
                    "metric": "resnet50_train_images_per_sec_per_chip",
                    "value": float(_PARTIAL["value"]),
                    "unit": "images/sec",
                    "vs_baseline": round(
                        float(_PARTIAL["value"]) / BASELINE_IMG_PER_SEC,
                        2),
                    "extra": _PARTIAL["extra"],
                }, default=str)
                break
            except Exception:
                time.sleep(0.05)
        if line is None:
            line = json.dumps({
                "metric": "resnet50_train_images_per_sec_per_chip",
                "value": 0.0,
                "unit": "images/sec",
                "vs_baseline": 0.0,
                "extra": {
                    "bench_failure": str(_PARTIAL["extra"].get(
                        "bench_failure")),
                    "failure_stage": str(_PARTIAL["extra"].get(
                        "failure_stage"))},
            })
        print(line)
        sys.stdout.flush()
        sys.stderr.flush()
    finally:
        os._exit(1)


def main(argv=None):
    bench_args = _parse_bench_args(argv)
    if bench_args.list_segments:
        for label in BENCH_SEGMENTS:
            print(label)
        return 0
    _selected = _segment_filter(bench_args)
    budget_s = max(1.0, bench_args.segment_budget_s)

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import models

    import signal
    import threading

    # SIGALRM breaks Python-level hangs per segment; it CANNOT interrupt
    # a thread blocked inside a native PJRT/compile call, so a global
    # watchdog thread guarantees the driver still receives a (partial)
    # JSON line: after 80 minutes it prints everything measured so far
    # and hard-exits.
    global _DONE
    _DONE = threading.Event()

    # watchdog > the normal full-run time (~45 min) with real headroom;
    # under PATHOLOGICAL degradation (every segment crawling to its own
    # 600 s breaker) the run cannot finish inside any sane budget, and
    # the watchdog's partial line — everything measured so far — is the
    # intended outcome, not a failure of the per-segment guarantee
    watchdog_s = float(os.environ.get("BENCH_WATCHDOG_S", 100 * 60))

    def _watchdog():
        if not _DONE.wait(watchdog_s):
            _emit_partial_and_exit()

    threading.Thread(target=_watchdog, daemon=True,
                     name="bench-watchdog").start()

    # a driver-side `timeout` sends SIGTERM before SIGKILL: emit the
    # partial record NOW instead of dying with only a log tail
    # (BENCH_r05 rc=124 was exactly this, undiagnosable from the JSON)
    def _term_reason():
        return (f"terminated by SIGTERM (driver timeout?) during stage "
                f"{_PARTIAL['extra'].get('failure_stage')!r}; metrics "
                f"below were measured before the kill")

    def _on_term(signum, frame):
        _emit_partial_and_exit(_term_reason())

    signal.signal(signal.SIGTERM, _on_term)
    # Python-level handlers only run on the MAIN thread between bytecodes
    # — a main thread wedged inside a native PJRT/compile call (the
    # rc=124 case) never executes them. set_wakeup_fd delivers the signal
    # byte from the C handler regardless, so a watcher thread can emit
    # the partial JSON even during a native hang.
    _sig_r, _sig_w = os.pipe()
    os.set_blocking(_sig_w, False)
    signal.set_wakeup_fd(_sig_w, warn_on_full_buffer=False)

    def _term_watcher():
        while True:
            try:
                data = os.read(_sig_r, 1)
            except OSError:
                return
            if not data:
                return
            # SIGALRM bytes from the per-segment hang-breakers drain
            # through here too — only TERM triggers the emission
            if data[0] == signal.SIGTERM:
                _emit_partial_and_exit(_term_reason())

    threading.Thread(target=_term_watcher, daemon=True,
                     name="bench-sigterm-watcher").start()

    # fluid-scope telemetry for the whole run: per-segment step-phase
    # breakdowns + recompile counts land next to each headline number
    # (the per-step overhead is nanoseconds against ms-scale steps)
    import paddle_tpu.observe as _obs
    fluid.set_flag("observe", True)
    # fluid-pulse: a live health plane for the whole bench run — the
    # driver (or a human) can scrape /status /healthz /metrics while a
    # segment is hung instead of waiting for the postmortem artifacts
    try:
        pulse_port = _obs.start_pulse(
            int(os.environ.get("BENCH_PULSE_PORT", "0")))
        _PARTIAL["extra"]["pulse_port"] = pulse_port
    except Exception as e:
        print(f"WARNING: pulse endpoint failed to start ({e!r})",
              file=sys.stderr)

    def _recompile_counts():
        """Per-cause compile counts from the CUMULATIVE metrics counter
        (the observatory's event ring is bounded at 256 — counts derived
        from it would go backwards once old events fall off)."""
        c = _obs.default_registry().get("executor_recompiles_total")
        out = {}
        if c is not None:
            for labels, v in c.items():
                cause = labels.get("cause", "unknown")
                out[cause] = out.get(cause, 0) + v
        return out

    def note(**kv):
        _PARTIAL["extra"].update(kv)

    def seg(label, fn, default, timeout_s=None):
        """Fault isolation per sub-bench: one failing segment must not
        cost the others their place in the recorded JSON line, so its
        exception is caught HERE, the segment is listed under
        failed_stages, and the run goes on — and then exits non-zero
        (main's return value): a record with a failed stage never
        passes for a clean one. Each segment also runs under a SIGALRM
        hang-breaker (Python-level hangs; native hangs fall to the
        global watchdog). A deselected segment (--segments /
        --skip-segments) returns its default without running and is
        listed under skipped_segments — a skip must read as "not
        measured", never as a zero measurement."""
        if timeout_s is None:
            timeout_s = int(budget_s)
        if not _selected(label):
            _PARTIAL["extra"].setdefault("skipped_segments",
                                         []).append(label)
            return default

        def _alarm(signum, frame):
            raise TimeoutError(f"segment exceeded {timeout_s}s")

        # failure_stage: whatever stage is current when the process dies
        # (watchdog/SIGTERM emission) or fails softly is named in the
        # recorded JSON — the rc=124 diagnosability fix. The flight
        # recorder mirrors it so a black-box dump names the stage too.
        _PARTIAL["extra"]["failure_stage"] = label
        _obs.flight.set_stage(label)
        t_seg = time.perf_counter()
        prev = signal.signal(signal.SIGALRM, _alarm)
        signal.alarm(timeout_s)
        try:
            return fn()
        except Exception as e:
            print(f"FAILED: bench segment {label!r} ({e!r})",
                  file=sys.stderr)
            _PARTIAL["extra"].setdefault("failed_stages", []).append(label)
            return default
        finally:
            _PARTIAL["extra"].setdefault("segment_wall_s", {})[label] = \
                round(time.perf_counter() - t_seg, 2)
            # per-segment telemetry: step-phase breakdown + recompile
            # deltas from fluid-scope (reset per segment so each headline
            # number carries ITS phase profile and compile count)
            try:
                ph = _obs.get_steplog().phase_summary(reset=True)
                if ph.get("steps"):
                    _PARTIAL["extra"].setdefault("step_phases_us", {})[
                        label] = dict(ph["phase_us"],
                                      steps=ph["steps"],
                                      mean_step_us=ph["mean_step_us"])
                counts = _recompile_counts()
                prevc = seg._recompiles_seen
                delta = {c: n - prevc.get(c, 0) for c, n in counts.items()
                         if n - prevc.get(c, 0) > 0}
                seg._recompiles_seen = counts
                if delta:
                    _PARTIAL["extra"].setdefault("recompiles", {})[
                        label] = delta
                # fluid-pulse memory observatory: the segment's peak HBM
                # ESTIMATE (max over the programs it compiled), plus live
                # device bytes whenever a real backend reports them (the
                # CPU rehearsal degrades to estimate-only silently)
                mem_obs = _obs.memory.get_observatory()
                mem_peak = mem_obs.segment_peak(reset=True)
                if mem_peak:
                    _PARTIAL["extra"].setdefault(
                        "mem_peak_est_bytes", {})[label] = int(mem_peak)
                live = mem_obs.live_device_stats()
                if live:
                    _PARTIAL["extra"].setdefault(
                        "mem_live_bytes", {})[label] = {
                            "bytes_in_use": sum(
                                d.get("bytes_in_use", 0) for d in live),
                            "peak_bytes_in_use": sum(
                                d.get("peak_bytes_in_use", 0)
                                for d in live)}
            except Exception:
                pass
            # re-arm a short breaker over the cleanup too: _release talks
            # to the device
            signal.alarm(120)
            try:
                _release(jax)
            except Exception:
                pass
            signal.alarm(0)
            signal.signal(signal.SIGALRM, prev)

    seg._recompiles_seen = {}

    # MFU's denominator is measured on this chip in this run or not at
    # all: a failed probe abandons the run (no recorded constant stands
    # in), and with the probe deselected every MFU field reads None.
    peak = None
    if _selected("peak_probe"):
        _PARTIAL["extra"]["failure_stage"] = "peak_probe"
        _obs.flight.set_stage("peak_probe")
        try:
            peak = measure_peak_tflops(jax) * 1e12
        except Exception as e:
            _emit_partial_and_exit(
                f"peak_probe failed ({e!r}); MFU has no denominator, "
                f"run abandoned")
        note(measured_peak_tflops_bf16=round(peak / 1e12, 1))
    else:
        _PARTIAL["extra"].setdefault("skipped_segments",
                                     []).append("peak_probe")

    def mfu(flops_per_s):
        return round(flops_per_s / peak, 3) if peak else None

    # headline (transformer-base unfused) runs FIRST: measured rates in
    # this process drop a few % once the ResNet/flash benches have run
    # (allocator/compile-cache residue), and the headline is the number
    # the north star is judged on
    tok_unf, tf_fps = seg(
        "transformer256_unfused",
        lambda: bench_transformer(fluid, models, jax, seq_len=256,
                                  batch_size=64, fused=False,
                                  want_flops=True), (0.0, 0.0))
    note(transformer_base_wmt_tokens_per_sec=round(tok_unf, 0),
         transformer_mfu=mfu(tf_fps))
    tok_fus, _ = seg(
        "transformer256_flash",
        lambda: bench_transformer(fluid, models, jax, seq_len=256,
                                  batch_size=64, fused=True), (0.0, 0.0))
    note(transformer_base_wmt_tokens_per_sec_flash=round(tok_fus, 0))

    ips, rn_fps = seg(
        "resnet50",
        lambda: bench_resnet(fluid, models, jax, want_flops=True),
        (0.0, 0.0))
    _PARTIAL["value"] = round(ips, 2)
    note(resnet50_mfu=mfu(rn_fps))
    # like-for-like pair at long context (flash attention territory).
    # MFU for the flash configs reuses the UNFUSED program's XLA-counted
    # FLOPs-per-token: the Pallas kernel is a custom call whose FLOPs XLA
    # cannot see, but the model math per token is identical.
    # steps=12 (not 8): the 2048 pair is the recorded bench's noisiest
    # number (r4 recorded 1.26x where same-process measurement gives
    # ~1.4x) — longer windows put more device time behind each slope
    tok_long_unf, tf2k_fps = seg(
        "transformer2048_unfused",
        lambda: bench_transformer(fluid, models, jax, seq_len=2048,
                                  batch_size=8, fused=False, steps=12,
                                  warmup=3, want_flops=True), (0.0, 0.0))
    tok_long_fus, _ = seg(
        "transformer2048_flash",
        lambda: bench_transformer(fluid, models, jax, seq_len=2048,
                                  batch_size=8, fused=True, steps=12,
                                  warmup=3), (0.0, 0.0))
    flops_per_tok_2k = tf2k_fps / tok_long_unf if tok_long_unf else 0.0
    fus2k_fps = flops_per_tok_2k * tok_long_fus
    note(transformer_seq2048_flash_tokens_per_sec=round(tok_long_fus, 0),
         transformer_seq2048_unfused_tokens_per_sec=round(tok_long_unf, 0))
    # seq-4096 pair: flash territory (the 8192 point is not benched here —
    # the unfused side cannot compile at all: its O(T^2) score tensors
    # need ~37.5 GB vs the chip's 15.75 GB; see docs/PERF.md)
    # batch 2: the unfused side's O(T^2) score+mask tensors barely fit
    # the 15.75 GB chip at batch 4 in a fresh process and not at all after
    # the earlier benches' residue (tools/flash_longctx_bench.py measures
    # the bs4 pair standalone)
    tok_4k_unf, _ = seg(
        "transformer4096_unfused",
        lambda: bench_transformer(fluid, models, jax, seq_len=4096,
                                  batch_size=2, fused=False, steps=8,
                                  warmup=3), (0.0, 0.0))
    tok_4k_fus, _ = seg(
        "transformer4096_flash",
        lambda: bench_transformer(fluid, models, jax, seq_len=4096,
                                  batch_size=2, fused=True, steps=8,
                                  warmup=3), (0.0, 0.0))
    note(transformer_seq4096_flash_tokens_per_sec=round(tok_4k_fus, 0),
         transformer_seq4096_unfused_tokens_per_sec=round(tok_4k_unf, 0))
    feeder = seg("feeder_overlap_subprocess", feeder_overlap_subprocess,
                 {})
    lstm_tok, lstm_ex = seg(
        "stacked_lstm",
        lambda: bench_stacked_lstm(fluid, models, jax), (0.0, 0.0))
    note(stacked_lstm_examples_per_sec=round(lstm_ex, 1))
    overhead = seg("step_overhead_subprocess", step_overhead_subprocess,
                   {})
    note(step_overhead_us=overhead.get("step_overhead_us", 0.0),
         step_overhead_us_unprepared=overhead.get(
             "step_overhead_us_unprepared", 0.0),
         step_overhead_reduction_x=overhead.get(
             "step_overhead_reduction_x", 0.0))
    # fluid-serve: p50/p99/qps + the zero-steady-state-recompiles gate
    # (recompiles: 0 = observatory-verified clean run; -1 = the loadgen
    # itself failed to produce numbers)
    opcost = seg("op_cost_subprocess", op_cost_subprocess, {})
    note(**opcost)
    srv = seg("serve_loadgen_subprocess", serve_loadgen_subprocess, {})
    note(serve_p50_us=srv.get("serve_p50_us", 0.0),
         serve_p99_us=srv.get("serve_p99_us", 0.0),
         serve_qps=srv.get("serve_qps", 0.0),
         serve_recompiles=srv.get("serve_recompiles", -1))
    # fluid-decode: paged-KV continuous batching — decode tokens/s, TTFT
    # p50/p99, and the continuous-vs-drain A/B (acceptance >= 1.3x)
    dec = seg("decode_loadgen_subprocess", decode_loadgen_subprocess, {})
    note(**dec)
    # fluid-fleet: multi-replica QPS scaling (subprocess replicas behind
    # the router), skew-free coordinated swap, p99 across a replica
    # SIGKILL with zero failed requests, DeepFM-from-pserver-shards
    fleet_rec = seg("fleet_subprocess", fleet_subprocess, {})
    note(**fleet_rec)
    # fluid-torrent: disaggregated (1 prefill + 2 decode, int8 KV) vs
    # co-located fp32 at fixed fleet size + fixed per-chip KV budget
    # (acceptance: wins BOTH TTFT p99 and tokens/s/chip) + decode_kill
    torrent_rec = seg("torrent_subprocess", torrent_subprocess, {})
    note(**torrent_rec)
    # fluid-wire: quantized PS wire A/B (bytes/step raw vs encoded, sync-PS
    # step time both modes, sparse-row compression, loss-delta neutrality)
    wirebench = seg("wire_bench_subprocess", wire_bench_subprocess, {})
    note(**wirebench)
    # fluid-haven: replicated-PS steady-state overhead + failover blip
    havenrec = seg("haven_subprocess", haven_subprocess, {})
    note(**havenrec)
    # fluid-quorum: lease-renewal overhead on the sync-PS step (<=2%
    # acceptance vs the haven baseline) + the asymmetric-partition
    # failover blip vs the lease+retry budget (quorum_failover_ok)
    quorumrec = seg("quorum_subprocess", quorum_subprocess, {})
    note(**quorumrec)
    # fluid-elastic: master-failover blip vs its lease+retry budget +
    # the scale-up admission latency of a new trainer joining mid-job
    elasticrec = seg("elastic_subprocess", elastic_subprocess, {})
    note(**elasticrec)
    # fluid-horizon: trace-context overhead gate — serve loadgen A/B
    # with the observe plane off vs on (acceptance: p50 within 2%)
    horizonrec = seg("horizon_subprocess", horizon_subprocess, {})
    note(**horizonrec)
    # the headline pair drifts within a run, and the noise is ONE-SIDED:
    # a host stall can only lower a reading below the true device rate,
    # never raise it (the device cannot run faster than device-busy).
    # Re-measure minutes after the first pass and
    # keep the max — the less-biased estimator under one-sided noise
    # (recorded spread without this: 229.8-249.7k tok/s across runs of
    # one build). BOTH readings are preserved as *_first/_remeasure
    # extras so the published JSON keeps the spread behind the
    # keep-the-max headline (advisor r5).
    tok_unf_first, tf_fps_first = tok_unf, tf_fps
    tok_unf2, tf_fps2 = seg(
        "transformer256_remeasure",
        lambda: bench_transformer(fluid, models, jax, seq_len=256,
                                  batch_size=64, fused=False,
                                  want_flops=True), (0.0, 0.0))
    if tf_fps2 > 0 and tf_fps <= 0 and tok_unf2 > 0:
        # first FLOPs probe failed but the second succeeded: FLOPs/token
        # is rate-independent, so rescale to the kept token rate
        tf_fps = tf_fps2 * (tok_unf / tok_unf2)
    if tok_unf2 > tok_unf and tf_fps2 > 0:   # never adopt a failed probe
        tok_unf, tf_fps = tok_unf2, tf_fps2
    note(transformer_base_wmt_tokens_per_sec=round(tok_unf, 0),
         transformer_mfu=mfu(tf_fps))
    # ResNet gets the same one-sided-noise treatment (it is the file's
    # primary metric and now runs after the transformer pair)
    ips_first, rn_fps_first = ips, rn_fps
    ips2, rn_fps2 = seg(
        "resnet50_remeasure",
        lambda: bench_resnet(fluid, models, jax, want_flops=True),
        (0.0, 0.0))
    if rn_fps2 > 0 and rn_fps <= 0 and ips2 > 0:
        rn_fps = rn_fps2 * (ips / ips2)
    if ips2 > ips and rn_fps2 > 0:
        ips, rn_fps = ips2, rn_fps2
    _PARTIAL["value"] = round(ips, 2)   # keep the partial record adopted
    note(resnet50_mfu=mfu(rn_fps))
    # fluid-planner: predicted-vs-measured MFU on the headline model,
    # with THIS run's measured peak and the final (keep-the-max) MFU —
    # plan_agreement ~1.0 means the mesh/HBM/flag rankings upstream of
    # auto_mesh are computed from an honest time model
    plan = seg("planner_subprocess",
               lambda: planner_subprocess(peak, tf_fps), {})
    note(**plan)

    extra = {
        "vs_baseline_note": "reference best is CPU MKL-DNN bs256; "
                            "judge MFU fields, not this ratio",
        "measured_peak_tflops_bf16": round(peak / 1e12, 1) if peak
        else None,
        "transformer_mfu": mfu(tf_fps),
        "resnet50_mfu": mfu(rn_fps),
        "transformer_base_wmt_tokens_per_sec": round(tok_unf, 0),
        "transformer_base_wmt_tokens_per_sec_flash": round(tok_fus, 0),
        "transformer_seq2048_flash_tokens_per_sec": round(tok_long_fus, 0),
        "transformer_seq2048_unfused_tokens_per_sec": round(tok_long_unf, 0),
        "transformer_seq2048_mfu": mfu(fus2k_fps),
        "transformer_seq4096_flash_tokens_per_sec": round(tok_4k_fus, 0),
        "transformer_seq4096_unfused_tokens_per_sec": round(tok_4k_unf, 0),
        "flash_vs_unfused_seq4096": round(tok_4k_fus / tok_4k_unf, 2)
            if tok_4k_unf else 0.0,
        "feeder_overlap_speedup_cpu_demo":
            feeder.get("feeder_overlap_speedup_cpu_demo", 0.0),
        "stacked_lstm_tokens_per_sec": round(lstm_tok, 0),
        "stacked_lstm_examples_per_sec": round(lstm_ex, 1),
        # host dispatch per step (CPU subprocess, device time subtracted):
        # prepared handle vs the pre-round-6 run() dispatch
        "step_overhead_us": overhead.get("step_overhead_us", 0.0),
        "step_overhead_us_unprepared": overhead.get(
            "step_overhead_us_unprepared", 0.0),
        "step_overhead_reduction_x": overhead.get(
            "step_overhead_reduction_x", 0.0),
        # fluid-serve (CPU subprocess loadgen: mixed-shape open loop,
        # >=2 buckets, 4 client threads, mid-run hot swap)
        "serve_p50_us": srv.get("serve_p50_us", 0.0),
        "serve_p99_us": srv.get("serve_p99_us", 0.0),
        "serve_qps": srv.get("serve_qps", 0.0),
        "serve_recompiles": srv.get("serve_recompiles", -1),
        "serve_occupancy": srv.get("serve_occupancy", 0.0),
        "serve_padding_waste": srv.get("serve_padding_waste", 0.0),
        "serve_hot_swap_ok": srv.get("serve_hot_swap_ok", False),
        "serve_failed": srv.get("serve_failed", -1),
        # fluid-xray per-op cost model (CPU subprocess, scaled-down book
        # transformer): static total vs XLA cost_analysis agreement is
        # the health gate — 1.0 means the planner-facing table is honest
        "op_cost_total_gflops": opcost.get("op_cost_total_gflops", 0.0),
        "op_cost_xla_agreement": opcost.get("op_cost_xla_agreement", 0.0),
        "op_cost_arithmetic_intensity": opcost.get(
            "op_cost_arithmetic_intensity", 0.0),
        "op_cost_top_op": opcost.get("op_cost_top_op", ""),
        # fluid-wire (CPU subprocess, sync-PS dense push A/B + sparse leg):
        # bytes/step down >= 2x at a negligible loss delta is the headline
        "wire_bytes_per_step_raw": wirebench.get(
            "wire_bytes_per_step_raw", 0.0),
        "wire_bytes_per_step_encoded": wirebench.get(
            "wire_bytes_per_step_encoded", 0.0),
        "wire_compression_x": wirebench.get("wire_compression_x", 0.0),
        "wire_sync_ps_step_ms_raw": wirebench.get(
            "wire_sync_ps_step_ms_raw", 0.0),
        "wire_sync_ps_step_ms_quant": wirebench.get(
            "wire_sync_ps_step_ms_quant", 0.0),
        "wire_sparse_compression_x": wirebench.get(
            "wire_sparse_compression_x", 0.0),
        "wire_quant_loss_delta": wirebench.get(
            "wire_quant_loss_delta", -1.0),
        # fluid-haven (CPU subprocess, replicated sync-PS pair): steady-
        # state replication overhead (acceptance <= 10% with codecs on)
        # and the trainer-observed failover blip vs its lease+retry
        # budget across a primary SIGKILL
        "haven_repl_overhead_pct": havenrec.get(
            "haven_repl_overhead_pct", -1.0),
        "haven_step_ms_single": havenrec.get("haven_step_ms_single", 0.0),
        "haven_step_ms_replicated": havenrec.get(
            "haven_step_ms_replicated", 0.0),
        "haven_device_ms_simulated": havenrec.get(
            "haven_device_ms_simulated", 0.0),
        "ps_failover_blip_ms": havenrec.get("ps_failover_blip_ms", 0.0),
        "ps_failover_budget_ms": havenrec.get(
            "ps_failover_budget_ms", 0.0),
        "ps_failover_ok": havenrec.get("ps_failover_ok", False),
        # both readings behind the keep-the-max headline metrics, so the
        # recorded JSON preserves the spread (advisor r5)
        "transformer_base_wmt_tokens_per_sec_first": round(tok_unf_first, 0),
        "transformer_base_wmt_tokens_per_sec_remeasure": round(tok_unf2, 0),
        "transformer_mfu_first": mfu(tf_fps_first),
        "transformer_mfu_remeasure": mfu(tf_fps2),
        "resnet50_images_per_sec_first": round(ips_first, 2),
        "resnet50_images_per_sec_remeasure": round(ips2, 2),
        "resnet50_mfu_first": mfu(rn_fps_first),
        "resnet50_mfu_remeasure": mfu(rn_fps2),
        # fluid-planner (CPU subprocess): the roofline model's predicted
        # MFU for the headline transformer vs what this run measured
        "plan_predicted_mfu": plan.get("plan_predicted_mfu", 0.0),
        "plan_measured_mfu": plan.get("plan_measured_mfu", 0.0),
        "plan_agreement": plan.get("plan_agreement", 0.0),
    }
    # normal completion: no stage is "failing"; soft failures (sentinel
    # segments) stay listed in failed_stages. Carry over the per-segment
    # telemetry accumulated in _PARTIAL plus the whole-run compile story.
    extra["failure_stage"] = (_PARTIAL["extra"].get("failed_stages")
                              or [None])[0]
    # every note()'d key rides along — segment records whose metrics are
    # NOT mirrored in the literal above (fleet/quorum/elastic/horizon/
    # decode) used to be silently dropped on a SUCCESSFUL run and only
    # survived in watchdog partials; explicit entries keep precedence
    for k, v in _PARTIAL["extra"].items():
        extra.setdefault(k, v)
    extra["recompile_causes_total"] = _recompile_counts()
    drift = check_claims(extra)
    if drift:
        extra["claim_drift"] = drift
    _DONE.set()   # normal completion: the watchdog stands down
    print(json.dumps({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/sec",
        # ratio vs the reference's best PUBLISHED ResNet-50 number, which
        # is CPU MKL-DNN (no GPU number exists in-tree) — flattering by
        # construction; the honest chip-efficiency headline is the MFU
        # fields below
        "vs_baseline": round(ips / BASELINE_IMG_PER_SEC, 2),
        "extra": extra,
    }))
    failed = extra.get("failed_stages")
    if failed:
        print(f"FAILED stages: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
