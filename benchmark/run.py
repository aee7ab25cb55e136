#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name (see README.md):
`workloads/<cell>.json` names its configuration (`configs/<config>.json`) and
its traffic mix (`traffic/<traffic>.json`: the batch and lengths of a step
and how steps are issued, and the generator under `generators/` that reads
them); `../BENCHMARK.json` lists the metrics and the cells each is
reported in, and `metrics/<metric>.json` names each metric's reader under
`readers/` with its arguments. This file knows no cell, configuration or
metric by name.

With --trace 0 the last line holds the cell's end-to-end metrics, with
--trace 1 its per-layer metrics (a short profiled window after the same
warm-up). Earlier lines are for a reader of the log. A measurement needs
TPUs, as many as the cell asks for; anything else exits non-zero before any
number.

    --tiny      the harness's own CPU rehearsal: the configuration's `tiny`
                sizes, 4 virtual CPU devices, prints REHEARSAL, and every
                value in the last line is null
    --batch N   trials only: overrides the traffic mix's batch
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")


def load_json(*parts):
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        sys.exit(f"benchmark: no file {path}")
    with open(path) as f:
        return json.load(f)


def metrics_for(cell_name, kind):
    """The `end_to_end` or `per_layer` metrics of BENCHMARK.json reported in
    this cell, each with its reader and arguments from `metrics/`."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if not any(w["name"] == cell_name for w in bench["workloads"]):
        print(f"benchmark: cell {cell_name!r} is not in BENCHMARK.json "
              f"(a trial)", file=sys.stderr)
    out = []
    for m in bench[kind]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        spec = load_json("metrics", m["name"] + ".json")
        out.append((m["name"], m["unit"], spec["reader"],
                    spec.get("args", {})))
    return out


def compared_numbers(obs, ref, mesh=None):
    """Every number that `correct` rests on, beside its limit:
    `{name: [value, limit]}`, a miss where the value is over its limit or is
    not a number. `ref` is the configuration's `reference`; `mesh`, on
    several chips, what was found of the collectives and the devices'
    bytes. A generator that compares more (a loss against the plain
    reference's after the window) hands its numbers over in
    `obs["compared"]`, in the same form."""
    losses = obs["losses"]
    first = obs["first_loss"]
    out = {
        "compilations_in_window": [obs["compiles_window"], 0],
        "losses_not_finite": [
            sum(1 for x in obs["all_losses"] if not math.isfinite(x)), 0],
        "first_loss_gap": [abs(first - math.log(ref["classes"])),
                           ref["first_loss_atol"]],
        "last_ten_excess": [max(losses[-10:]) - first,
                            ref["last_losses_slack"]],
    }
    out.update(mesh or {})
    out.update(obs.get("compared", {}))
    return out


def misses(compared):
    """The names of `compared` whose value is not within its limit."""
    return [name for name, (value, limit) in compared.items()
            if not value <= limit]


def print_compared(compared, missed, file):
    for name, (value, limit) in compared.items():
        print(f"benchmark: compared {name} {value:.6g} limit {limit} "
              f"{'MISS' if name in missed else 'ok'}", file=file, flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int)
    args = ap.parse_args()

    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    chips = cell["chips"]
    wanted = metrics_for(args.workload,
                         "per_layer" if args.trace else "end_to_end")

    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        sys.exit(f"benchmark: no program to measure: {ROOT}/paddle_tpu is "
                 f"missing")
    sys.path.insert(0, ROOT)        # the program: paddle_tpu

    t_import = time.perf_counter()
    import jax
    # the compile cache at a fixed path inside the checkout, unless the
    # machine names one; the program's own rule (paddle_tpu/__init__.py)
    # yields to a directory that is already set
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_compile_cache"))
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"benchmark: cell {args.workload} seed {args.seed} seconds "
          f"{args.seconds} trace {args.trace}; jax {jax.__version__} on "
          f"{device}; compile cache {jax.config.jax_compilation_cache_dir}",
          flush=True)
    if not args.tiny:
        if any(d.platform != "tpu" for d in devices) or len(devices) < chips:
            sys.exit(f"benchmark: cell {args.workload} needs {chips} TPU "
                     f"chip(s); jax found {devices}. Nothing was measured. "
                     f"(--tiny rehearses on the CPU.)")
        from peaks import peaks_for
        peaks = peaks_for(device["kind"])      # unknown kind: an error
    else:
        peaks = None
    devices = devices[:chips]

    import trace_reduce as tr
    from compile_counter import CompileCounter
    from readers import step_rate
    from system import System, make_pool
    counter = CompileCounter()
    batch = args.batch or (config["tiny"]["batch"] * chips if args.tiny
                           else traffic["batch"])
    t0 = time.perf_counter()
    system = System(config, cell, traffic, devices, batch, tiny=args.tiny)
    flops = importlib.import_module("flops." + config["flops"]) \
        .flops_per_example(**system.build_args)
    ranges = dict(config["feed_ranges"])
    if args.tiny:
        ranges.update(config["tiny"].get("feed_ranges", {}))
    pool = make_pool(system.feeds, ranges, batch, traffic["pool_batches"],
                     args.seed)
    shapes = {k: (v.shape, str(v.dtype)) for k, v in pool[0].items()}
    print(f"benchmark: {t0 - t_import:.2f} s to import jax and reach the "
          f"device(s), {t_import - T_PROCESS_START:.2f} s before that; program "
          f"built in {system.build_s:.2f} s, startup program run in "
          f"{system.startup_s:.2f} s ({counter.n} compilations, "
          f"{counter.cache_misses} missed the cache; weights from the startup "
          f"program, fixed seed {system.startup.random_seed}); pool of "
          f"{len(pool)} distinct batches from RandomState(seed), {shapes}, "
          f"feed={traffic['feed']}", flush=True)

    trace_dir = None
    if args.trace:
        # a rehearsal's trace goes to a directory of its own and is removed:
        # two tests that rehearse one cell at once would empty each other's
        trace_dir = os.path.join(TRACE_ROOT, args.workload + (
            f".tiny{os.getpid()}" if args.tiny else ""))
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    generator = importlib.import_module("generators." + traffic["generator"])
    obs = generator.run(system, pool, traffic, args.seconds, trace_dir,
                        T_PROCESS_START, counter)

    # -- the log: no arithmetic left to the reader --------------------------
    stamps, losses = obs["stamps"], obs["losses"]
    steps = len(stamps) - 1
    window_s = stamps[-1] - stamps[0]
    rate = step_rate.read({"obs": obs})
    gaps_ms = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    q = max(1, len(gaps_ms) // 4)       # does a step slow through a run?
    print(f"benchmark: set-up {obs['setup_s']:.2f} s (first step "
          f"{obs['first_step_s']:.2f} s, {obs['warmup_steps']} warm-up steps, "
          f"{obs['compiles_setup']} compilations of which "
          f"{obs['cache_misses_setup']} missed the cache)", flush=True)
    print(f"benchmark: window {window_s:.3f} s, {steps} steps of {batch} "
          f"examples: {rate:.2f} examples/s"
          + (f" = {rate * flops['positions_per_example']:.0f} tokens/s"
             if "positions_per_example" in flops else " (images/s)")
          + f"; step interval ms median {statistics.median(gaps_ms):.3f} "
          f"(first quarter of the window {statistics.median(gaps_ms[:q]):.3f}"
          f", last quarter {statistics.median(gaps_ms[-q:]):.3f}) "
          f"min {min(gaps_ms):.3f} max {max(gaps_ms):.3f}; dispatch us median "
          f"{statistics.median(obs['dispatch_s']) * 1e6:.0f}; "
          f"{obs['compiles_window']} compilations in the window", flush=True)
    peak = max(losses)
    print(f"benchmark: loss first step {obs['first_loss']:.4f}, window start "
          f"{losses[0]:.4f}, window end {losses[-1]:.4f} "
          f"(last ten max {max(losses[-10:]):.4f}; window max {peak:.4f} at "
          f"step {obs['warmup_steps'] + losses.index(peak) + 1} of the run)",
          flush=True)
    if len(losses) <= 64:       # a traced run's clocked window: every loss
        print("benchmark: the window's losses, steps "
              f"{obs['warmup_steps'] + 1}-{obs['warmup_steps'] + len(losses)}"
              " of the run: " + " ".join(f"{x:.4f}" for x in losses),
              flush=True)

    # -- correct -------------------------------------------------------------
    ref = dict(config["reference"])
    if args.tiny:
        ref.update(config["tiny"]["reference"])
    failed = sum(1 for x in losses if not math.isfinite(x))

    @functools.lru_cache(maxsize=None)
    def get_compiled_text():
        return system.compiled_text(system.place(pool[0]))

    mesh = {}
    if chips > 1:
        from readers.compiled_text import inventory
        inv = inventory(get_compiled_text())
        print(f"benchmark: collectives in the compiled step: {inv}",
              flush=True)
        mesh["all_reduce_missing"] = [int(not inv.get("all-reduce", 0)), 0]
        if not args.tiny:       # CPU devices report no memory
            used = [d.memory_stats()["bytes_in_use"] for d in devices]
            print(f"benchmark: bytes in use on each device: {used}",
                  flush=True)
            mesh["devices_without_bytes"] = [sum(1 for u in used if not u), 0]
    compared = compared_numbers(obs, ref, mesh)
    missed = misses(compared)
    print(f"benchmark: first loss {obs['first_loss']:.4f} against "
          f"ln({ref['classes']}) = {math.log(ref['classes']):.4f}; last ten "
          f"max {max(losses[-10:]):.4f} against the first + "
          f"{ref['last_losses_slack']}", flush=True)
    print_compared(compared, missed, sys.stdout)
    correct = not missed

    # -- metrics -------------------------------------------------------------
    @functools.lru_cache(maxsize=None)
    def get_trace():
        """The profiled window reduced once: per-device summary, the busiest
        device, the steps in the window. None without a profile."""
        if not obs["profile"]:
            return None
        events = tr.load_xplane(
            obs["profile"]["dir"],
            keep_line=lambda plane, line:
                not tr.DEVICE_PLANE.match(plane) or line == tr.OPS_LINE)
        summary = tr.device_summary(events)
        return {"summary": summary, "device": tr.busiest(summary),
                "steps": obs["profile"]["steps"],
                "spans": tr.host_spans(events, "bench:")}

    ctx = {"obs": obs, "system": system, "config": config, "cell": cell,
           "traffic": traffic, "peaks": peaks, "flops": flops, "chips": chips,
           "trace": get_trace, "compiled_text": get_compiled_text}
    metrics = {}
    for name, unit, reader, reader_args in wanted:
        value = importlib.import_module("readers." + reader).read(
            ctx, **reader_args)
        if value is None:
            print(f"benchmark: {name}: nothing to read, left out", flush=True)
            continue
        metrics[name] = {"value": value, "unit": unit}

    from readers.memory import peak_bytes
    print(f"benchmark: memory_stats of device 0: {devices[0].memory_stats()}",
          flush=True)
    device["memory_peak_bytes"] = peak_bytes(devices)
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    trace = get_trace()
    if trace and trace["device"] is not None:
        summary = trace["summary"]
        device["busy_s"] = statistics.mean(
            s["busy_ns"] for s in summary.values()) / 1e9
        device["window_s"] = summary[trace["device"]]["window_ns"] / 1e9
        s = summary[trace["device"]]
        top = tr.top_groups(s["by_name"], 10)
        longest = sorted(s["gaps"], key=lambda g: g[0] - g[1])[:10]
        gaps = tr.attribute_gaps(longest, trace["spans"])
        result["breakdown"] = {
            "device_ops": [[n, ns / 1e9] for n, ns in top],
            "idle_gaps": [[n, ns / 1e9] for n, ns in gaps]}
        print(f"benchmark: profiled window {device['window_s']:.4f} s on "
              f"device {trace['device']}, busy {s['busy_ns'] / 1e9:.4f} s, "
              f"{trace['steps']} steps, {s['n_ops']} ops, {len(s['gaps'])} "
              f"gaps summing {sum(b - a for a, b in s['gaps']) / 1e9:.4f} s",
              flush=True)
    system.close()

    if args.tiny:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"REHEARSAL on {device['platform']}: metrics computed "
              f"{sorted(metrics)}; nothing here is a device number",
              flush=True)
        for m in metrics.values():
            m["value"] = None
        result["rehearsal"] = True
    # each number compared beside its limit: the result's last key and the
    # last lines on standard error (what the driver's record keeps of a run
    # that is not correct)
    result["compared"] = {
        name: {"value": value if math.isfinite(value) else None,
               "limit": limit}
        for name, (value, limit) in compared.items()}
    print(json.dumps(result), flush=True)
    print_compared(compared, missed, sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
