"""Shared helper for the perf tools: compile a framework program's main
training step and return a jax `Compiled` for cost analysis / HLO dumps.

`Executor.compiled_step` is the public accessor; this keeps the tools'
call shape."""

from __future__ import annotations


def compile_main_step(exe, scope, program=None):
    """exe must have run `program` (the default main program if None) at
    least once against `scope`. No feed is needed: the executor noted the
    feeds' signature when it bound the step."""
    return exe.compiled_step(program, scope=scope)


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
