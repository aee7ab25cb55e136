"""The one generator of training traffic. A traffic file under `traffic/`
gives its parameters; a new mix is a new data file, not new code.

Parameters (all from the traffic file):
  pool_batches   distinct batches made from the seed
  feed           "device": the pool is put on the device during set-up
                 (synthetic-data mode); "host": numpy batches go through
                 `run(feed=...)` every step (the input path is measured)
  in_flight      steps queued behind the one that runs (1: the device always
                 has the next step waiting)
  warmup         the cell's own shape only, until two consecutive intervals
                 agree within `agree_within`, between min and max steps
  traced         with --trace 1: `clock_steps` un-profiled steps for the host
                 clocks and the rate, then `profile_steps` under the profiler

How a step is timed: dispatch step i (the call returns before the device has
finished), then `block_until_ready` on step i - in_flight and take a
timestamp. Every step has a completion time; the window runs from one
completion to the first completion at least `seconds` later, and everything
in between counts.
"""

import collections
import contextlib
import time


def _span(name, on):
    """A host span in the profiler's own trace, on its clock; nothing when
    no profile is being taken."""
    if not on:
        return contextlib.nullcontext()
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


class _Pipe:
    """Dispatch / wait with `in_flight` steps queued; records per step the
    seconds inside the dispatching call and the completion timestamp."""

    def __init__(self, system, feeds, in_flight):
        self.system, self.feeds, self.depth = system, feeds, in_flight
        self.queue = collections.deque()
        self.i = 0
        self.losses = []       # device arrays, read after the window
        self.dispatch_s = []
        self.stamps = []

    def advance(self, annotate=False):
        """Dispatch one step, then wait for the oldest beyond the depth.
        Returns its completion time, or None while the queue fills."""
        feed = self.feeds[self.i % len(self.feeds)]
        self.i += 1
        t0 = time.perf_counter()
        with _span("bench:dispatch inside exe.run", annotate):
            loss = self.system.step(feed)
        self.dispatch_s.append(time.perf_counter() - t0)
        self.queue.append(loss)
        if len(self.queue) <= self.depth:
            return None
        return self._wait(annotate)

    def _wait(self, annotate=False):
        done = self.queue.popleft()
        with _span("bench:wait in block_until_ready", annotate):
            done.block_until_ready()
        now = time.perf_counter()
        self.losses.append(done)
        self.stamps.append(now)
        return now

    def drain(self):
        while self.queue:
            self._wait()

    def mark(self):
        return len(self.stamps), len(self.dispatch_s)


def run(system, host_pool, traffic, seconds, trace_dir, t_process_start,
        counter):
    """Set-up, warm-up, the measured window and (if `trace_dir`) the profiled
    window. Returns the observations the readers take their metrics from."""
    import jax
    import numpy as np

    feeds = host_pool if traffic["feed"] == "host" else \
        [system.place(b) for b in host_pool]
    pipe = _Pipe(system, feeds, traffic["in_flight"])

    # warm-up: the first step compiles (or loads from the cache)
    w = traffic["warmup"]
    t0 = time.perf_counter()
    while pipe.advance() is None:
        pass
    first_step_s = time.perf_counter() - t0
    while True:
        pipe.advance()
        n = len(pipe.stamps)
        if n >= w["max_steps"]:
            break
        if n >= max(w["min_steps"], 3):
            a = pipe.stamps[-1] - pipe.stamps[-2]
            b = pipe.stamps[-2] - pipe.stamps[-3]
            if abs(a - b) <= w["agree_within"] * max(a, b):
                break
    warm_steps, warm_calls = pipe.mark()
    t_warm = pipe.stamps[-1]
    setup_s = t_warm - t_process_start
    compiles_setup, misses_setup = counter.n, counter.cache_misses

    # the window: from the last warm-up completion, the queue still full
    traced = traffic["traced"] if trace_dir else None
    while True:
        now = pipe.advance()
        if now - t_warm >= seconds or (
                traced and len(pipe.stamps) - warm_steps
                >= traced["clock_steps"]):
            break
    end_steps, end_calls = pipe.mark()
    compiles_window = counter.n - compiles_setup

    obs = {
        "setup_s": setup_s,
        "first_step_s": first_step_s,
        "warmup_steps": warm_steps,
        "compiles_setup": compiles_setup,
        "cache_misses_setup": misses_setup,
        "compiles_window": compiles_window,
        "batch": system.batch,
        "stamps": pipe.stamps[warm_steps - 1:end_steps],
        "dispatch_s": pipe.dispatch_s[warm_calls:end_calls],
        "profile": None,
    }

    if traced:
        # the profiler takes a second or more to start, and the device runs
        # dry meanwhile: the profiled window is read from the device's own
        # first op to its last, not from the host's start and stop
        pipe.drain()
        before_profile = len(pipe.stamps)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # no per-call Python events
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t_prof = time.perf_counter()
        try:
            for _ in range(traced["profile_steps"] + traffic["in_flight"]):
                pipe.advance(annotate=True)
            pipe.drain()
        finally:
            host_window = time.perf_counter() - t_prof
            jax.profiler.stop_trace()
        obs["profile"] = {"dir": trace_dir, "host_window_s": host_window,
                          "steps": len(pipe.stamps) - before_profile}
        obs["compiles_window"] = counter.n - compiles_setup
    else:
        pipe.drain()

    losses = [float(np.asarray(x).reshape(-1)[0]) for x in pipe.losses]
    obs["first_loss"] = losses[0]
    obs["losses"] = losses[warm_steps:end_steps]
    obs["all_losses"] = losses
    return obs
