"""Shared helper for the perf tools: compile a framework program's main
training step and return a jax `Compiled` for cost analysis / HLO dumps.

Centralizes the private-API dance (pick the largest cached step, collect
mut/const state, lower+compile) so a change to Executor internals breaks
one place, not three."""

from __future__ import annotations


def compile_main_step(exe, scope, feed):
    """exe must have run the program at least once with `feed`."""
    import numpy as np

    compiled = max(exe._cache.values(),
                   key=lambda c: len(c.program.global_block().ops))
    mut = {n: scope.find_var(n) for n in compiled.mut_names}
    const = {n: scope.find_var(n) for n in compiled.const_names}
    feeds = {k: feed[k] for k in sorted(feed)}
    return (compiled._step.lower(feeds, mut, const, np.uint32(0))
            .compile())


def parse_flag(argv, name, default):
    """`--name value` or `--name=value`."""
    for i, a in enumerate(argv):
        if a == name and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith(name + "="):
            return a.split("=", 1)[1]
    return default


def slope_step_time(window, steps, lo=None, rounds=3, retries=2):
    """Two-point-slope per-step time, median of `rounds`: a window pays
    one fixed sync regardless of length, so dividing a single window by
    its step count inflates per-step time; the slope is what a
    steady-state training loop sees.

    A host stall landing in the LONG window of 2 of 3 rounds can push
    the median slope to zero or below; since callers divide by the
    result, a non-positive median is re-measured and ultimately an error,
    never a recorded throughput (round-4 advisor)."""
    lo = lo or max(2, steps // 4)
    med = None
    for _ in range(retries + 1):
        slopes = []
        for _ in range(rounds):
            t_lo, t_hi = window(lo), window(steps)
            slopes.append((t_hi - t_lo) / (steps - lo))
        med = sorted(slopes)[len(slopes) // 2]
        if med > 0:
            return med
    raise RuntimeError(
        f"slope_step_time: non-positive median slope {med!r} persisted "
        f"across {retries + 1} attempts — refusing to record a "
        f"negative/inf throughput")
