"""Host dispatch overhead per step: prepared vs unprepared (round 6).

The device side is near its ceiling (docs/PERF.md round 5), so this tool
measures the HOST side: the pure-python work `Executor.run()` does around
the jitted call each step. It is CPU-runnable (tiny MLP, in-process CPU
backend: host dispatch is backend-independent python, and what it
hides or exposes on the chip is a device-idle share only a chip trace
can give).

Three dispatch paths over the SAME compiled entry, device time subtracted:

  legacy   : a faithful re-implementation of the pre-round-6 Executor.run
             body — per-step listen_and_serv op scan, flag-registry reads,
             compiler-option resolution, sorted cache-key rebuild, and a
             full O(state) scope gather (kept here as the measurement
             baseline; the shipped run() no longer does this)
  run      : the shipped Executor.run() — thin wrapper over a memoized
             PreparedProgram
  prepared : a held Executor.prepare() handle — feed conversion, cached
             state gather, jitted call, write-back only

  floor    : the bare jitted `_step` call with pre-gathered state — the
             irreducible jax dispatch + device time both paths pay

host overhead(path) = per-step wall(path) - floor;
the headline `step_overhead_reduction_x` = legacy overhead / prepared
overhead (acceptance: >= 2x). Prints ONE JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_program(fluid):
    """Tiny on purpose: host dispatch overhead is the quantity under
    measurement, so device time per step must be small against it (a
    16-wide 3-layer MLP + Adam still has ~20 state vars, so the O(state)
    scope gather the legacy path pays per step is realistic)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        h = fluid.layers.fc(input=h, size=16, act="relu")
        pred = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def legacy_run(exe, cache, counters, order, program, feed, fetch_list, scope,
               np, jax, ir_mod, exec_mod):
    """The pre-round-6 Executor.run body, reproduced op for op as the
    'unprepared' measurement baseline (see module docstring)."""
    from paddle_tpu import flags as _flags

    ls = [op for op in program.global_block().ops
          if op.type == "listen_and_serv"]
    assert not ls
    fetch_names = [f.name if isinstance(f, ir_mod.Variable) else str(f)
                   for f in fetch_list]
    block = program.global_block()
    feed_arrays = exec_mod._convert_feed_dict(block, feed)
    copts = exec_mod.resolve_compiler_options(
        exe.place.jax_device().platform, program)
    cache_key = (program._uid, program._version,
                 tuple(sorted(feed_arrays)), tuple(fetch_names),
                 scope._uid, exe.amp, exe.check_nan_inf,
                 _flags.get_flag("dropout_impl"),
                 tuple(sorted(copts.items())) if copts else None,
                 program.random_seed)
    order.setdefault(program._uid, len(order))
    compiled = cache[cache_key]   # always warm in this bench
    counter = np.uint32(counters.get(program._uid, 0))
    counters[program._uid] = int(counter) + 1
    with jax.default_device(exe.place.jax_device()):
        return compiled.run(scope, feed_arrays, counter)


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    # synchronous dispatch: with async CPU dispatch the host work of step
    # N overlaps (or blocks on) step N-1's execution depending on where
    # buffer releases land, which smears µs-scale host costs across
    # steps; synchronous calls make wall = host + device exactly, and the
    # shared floor subtraction removes the device part from every path
    jax.config.update("jax_cpu_enable_async_dispatch", False)

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core import executor as exec_mod
    from paddle_tpu.core import ir as ir_mod

    steps = int(os.environ.get("STEP_OVERHEAD_STEPS", "200"))
    n_rounds = int(os.environ.get("STEP_OVERHEAD_ROUNDS", "24"))
    warmup = 50

    main_p, startup, loss = build_program(fluid)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)

    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(32, 16).astype(np.float32),
            "y": rng.randint(0, 4, (32, 1)).astype(np.int64)}

    # bind + compile once through the public path; every timed path below
    # dispatches this same entry
    exe.run(main_p, feed=feed, fetch_list=[loss], scope=scope,
            return_numpy=False)
    entry = next(c for c in exe._cache.values()
                 if c.program is main_p)

    # seed the legacy path's cache with the same entry under the key the
    # legacy body computes, so it measures dispatch, not compilation
    from paddle_tpu import flags as _flags
    feed_arrays = exec_mod._convert_feed_dict(main_p.global_block(), feed)
    copts = exec_mod.resolve_compiler_options(
        exe.place.jax_device().platform, main_p)
    legacy_key = (main_p._uid, main_p._version,
                  tuple(sorted(feed_arrays)), (loss.name,),
                  scope._uid, exe.amp, exe.check_nan_inf,
                  _flags.get_flag("dropout_impl"),
                  tuple(sorted(copts.items())) if copts else None,
                  main_p.random_seed)
    legacy_cache = {legacy_key: entry}
    legacy_counters = dict(exe._run_counts)
    legacy_order = {}

    prepared = exe.prepare(main_p, fetch_list=[loss], scope=scope)

    warmed = set()

    def time_path(fn, n):
        if fn not in warmed:
            warmed.add(fn)
            for _ in range(warmup):
                out = fn()
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        # one sync at the end: dispatch is synchronous (config above), so
        # per-step wall time already contains device time; the shared
        # floor subtraction removes it from every path identically
        np.asarray(out[0])
        return (time.perf_counter() - t0) / n * 1e6  # us/step

    def run_legacy():
        return legacy_run(exe, legacy_cache, legacy_counters, legacy_order,
                          main_p, feed, [loss], scope, np, jax, ir_mod,
                          exec_mod)

    def run_public():
        return exe.run(main_p, feed=feed, fetch_list=[loss], scope=scope,
                       return_numpy=False)

    def run_prepared():
        return prepared.run(feed, return_numpy=False)

    # floor: the bare jitted call. mut state is donated, so each call
    # refreshes its mut dict from the step's outputs — the minimal python
    # any dispatch path must do. The floor bypasses scope write-back, so
    # each floor window gathers fresh state first and restores the final
    # values to the scope after, keeping the other paths' reads live.
    state = {"mut": None, "const": None}

    def run_floor():
        fetches, new_state, _ = entry._step(feed_arrays, state["mut"],
                                            state["const"], np.uint32(0))
        state["mut"] = {n: new_state[n] for n in entry.mut_names}
        return fetches

    def floor_window(n):
        state["mut"], state["const"] = entry.gather_state(scope)
        us = time_path(run_floor, n)
        for k, v in state["mut"].items():
            scope.set_var(k, v)
        return us

    # many SHORT interleaved windows, per-path MINIMUM over rounds: this
    # box suffers multi-second interference bursts (shared core) that
    # inflate whole windows, and the noise is one-sided — interference
    # only ever ADDS time — so each path's minimum over many interleaved
    # windows is the clean per-step cost (the same argument bench.py
    # makes for its keep-the-max headline; timeit uses min likewise).
    rounds = {"legacy": [], "run": [], "prepared": [], "floor": []}
    for _ in range(n_rounds):
        rounds["floor"].append(floor_window(steps))
        rounds["prepared"].append(time_path(run_prepared, steps))
        rounds["run"].append(time_path(run_public, steps))
        rounds["legacy"].append(time_path(run_legacy, steps))
    med = {k: min(v) for k, v in rounds.items()}

    # the irreducible floor is BY DEFINITION <= every path's minimum; a
    # path window reading below the floor windows only proves the floor
    # estimate was inflated by drift, so take the min across all of them
    floor = min(med.values())
    over_legacy = max(med["legacy"] - floor, 0.0)
    over_run = max(med["run"] - floor, 0.0)
    over_prepared = max(med["prepared"] - floor, 0.0)
    # denominator clamped at ~the resolution of this measurement (2µs):
    # the prepared path's overhead routinely lands inside window noise,
    # and a literal zero would turn a best-case result into a 0.0 ratio
    # that reads as a failed measurement. The clamp makes the reported
    # reduction CONSERVATIVE (never inflated by a tiny denominator).
    reduction = over_legacy / max(over_prepared, 2.0)
    result = {
        "steps_per_window": steps,
        "floor_us": round(floor, 2),
        "legacy_us": round(med["legacy"], 2),
        "run_us": round(med["run"], 2),
        "prepared_us": round(med["prepared"], 2),
        "step_overhead_us_unprepared": round(over_legacy, 2),
        "step_overhead_us_run": round(over_run, 2),
        "step_overhead_us": round(over_prepared, 2),
        "step_overhead_reduction_x": round(reduction, 2),
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
