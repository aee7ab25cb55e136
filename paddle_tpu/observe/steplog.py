"""Per-step phase accounting, the recompilation observatory and the
set-up store.

Three runtime questions dominate TPU cost and were previously invisible:

1. *Where does a step's host time go?* `RunSpans` opens one
   `paddle_tpu:run` span per `PreparedProgram.run` (both executors' one
   run loop) and one child span per host phase around the jitted call — feed
   conversion, bind, state gather, the jitted call, state write-back,
   fetch transfer — as `jax.profiler.TraceAnnotation`s: on at default
   flags, on the profiler's clock, beside the device track of any
   capture, and free when no capture runs. With the `observe` flag on the
   same boundaries also fill a `StepStats` in the bounded `StepLog`. One
   clock serves both and the set-up store: `time.perf_counter()` read once
   at each boundary into the run's own slots.

   The same slots keep the pace of an entry's steps (`Pace`), and every
   step interval that runs long (`is_long`) leaves a record of where the
   host was and what the process did meanwhile (`stall_record`,
   `RecompilationObservatory.stalls()`): no profile covers the untraced
   run in which a stall of seconds falls. A steady step pays for it one
   more clock read at the run's end, a compare and a write into a ring of
   floats, and every eighth one `getrusage(RUSAGE_THREAD)` and one
   `process_time()` (two system calls, 6 us each on the chip's host: chip
   run, PR 67); it takes no lock, writes to no store and logs nothing.

2. *Why did XLA recompile?* The static lint (analysis/, PR 2) can only
   WARN about feed-shape recompile hazards; the observatory closes the
   loop by recording every actual jit cache miss with its attributed
   cause:

   - ``first_call``       first compile of this program (expected)
   - ``feed_shape``       same feed names, new shapes/dtypes — the
                          hazard the lint warns about, now caught live
   - ``program_version``  the program was mutated after compilation
   - ``copts_change``     xla_compiler_options changed between runs
   - ``feed_names``       a different set of feed variables was bound
   - ``fetch_set``        a different fetch list forced a new executable
   - ``new_scope``        the same program bound against a different
                          Scope (train/test scopes, per-request scopes)
   - ``options_change``   an executor-setting flip re-keyed the compile
                          cache (amp / check_nan_inf / random_seed)
   - ``uncached``         use_program_cache=False (tests probing
                          recompilation; never attributed further)
   - ``warmup``           an ahead-of-time compile the serving layer
                          (serve/) deliberately provoked while warming a
                          bucket ladder — expected, like ``first_call``
   - ``padding_bucket``   a shape miss on a ``serving``-source handle:
                          the request's padded shape was NOT in the
                          warmed bucket ladder. Same mechanism as
                          ``feed_shape`` but attributed separately so
                          `--assert-no-recompiles` distinguishes a
                          mis-sized ladder from a genuine cache bug

   Compile events are recorded regardless of the `observe` flag — a
   compile costs seconds, the record costs microseconds, and the
   observatory is the whole point of `tools/telemetry_dump.py
   --assert-no-recompiles`. Only the per-step shape *tracking* that
   detects `feed_shape` misses is flag-gated (it is on the hot path).

   Each event also carries what the compile cost, `stages_s`: the seconds
   jax itself reports (`jax.monitoring`) for tracing the Program through
   `BlockLowerer` (`trace`), lowering to MLIR (`lower`), the XLA compile
   or the load from the persistent cache (`backend`) and, of that, the
   cache read (`cache_retrieval`), from the event's creation until the
   run() that caused it returns. When jax compiles again inside the
   jitted call of a later run() and the executor recorded no cause for
   it (what its keys cannot see: with `observe` off a new feed shape; on
   the TPU the SECOND call of every step, whose state the startup program
   left uncommitted and the first call committed — chip run, PR 25), the
   durations go to the event of the entry that run() called, whose
   `backend_compiles` then reads 2. No cause is invented for it. A cache
   miss reads `cache_retrieval` 0 s: nothing was read.

3. *Where does a set-up go?* A set-up is never under a profile, so its
   phases are kept as `Phase` records in the observatory, on
   `time.perf_counter()`, the clock of the stage intervals above: the body
   of a `program_guard` (`paddle_tpu:program_build`, with the seconds
   inside `registry.infer_op_shapes`), `Optimizer.minimize` inside it, and
   every run() that binds or during which jax reports a compile, with its
   phases (`RunSpans.keep`). A compile jax reports inside a run() but
   outside its jitted call, or inside a set-up phase with no run open, is
   counted there (`EagerCompiles`), not dropped and not given a cause. On
   at default flags, as the compile events are. A steady step keeps
   nothing here: it pays the clock reads and the samples of question 1, no
   lock, no write to any store.
"""

from __future__ import annotations

import functools
import gc
import itertools
import logging
import statistics
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax.monitoring
import jax.profiler

from .. import flags as _flags
from . import flight as _flight
from . import metrics as _metrics
from . import tracer as _tracer

# (span in the profiler's trace, StepStats key, the RunSpans slot that keeps
# its start) of each host phase of a run(); constants, so a step builds no
# string. `device_compute` is the host wall of the jitted call: dispatch only
# under async dispatch (the device runs on after it returns), trace + lower +
# compile on a first call.
FEED_CONVERT = ("paddle_tpu:feed_convert", "feed_convert", "_t_feed_convert")
BIND = ("paddle_tpu:bind", "bind", "_t_bind")
STATE_GATHER = ("paddle_tpu:state_gather", "state_gather", "_t_state_gather")
JIT_CALL = ("paddle_tpu:jit_call", "device_compute", "_t_jit_call")
WRITE_BACK = ("paddle_tpu:write_back", "write_back", "_t_write_back")
FETCH = ("paddle_tpu:fetch", "fetch", "_t_fetch")
# in the order a run() passes through them
RUN_PHASES = (FEED_CONVERT, BIND, STATE_GATHER, JIT_CALL, WRITE_BACK, FETCH)
RUN = "paddle_tpu:run"
# the set-up phases outside a run(): the body of `program_guard`, and
# `Optimizer.minimize` inside it
PROGRAM_BUILD = "paddle_tpu:program_build"
MINIMIZE = "paddle_tpu:minimize"
# the two places a host-fed loop waits outside the phases above
READER_POP = "paddle_tpu:reader_pop"    # py_reader.next_feed()
FEEDER_PUT = "paddle_tpu:feeder_put"    # AsyncFeeder's device transfer

PHASES = tuple(w[1] for w in (FEED_CONVERT, STATE_GATHER, JIT_CALL,
                              WRITE_BACK, FETCH, BIND))

span = jax.profiler.TraceAnnotation
_now = time.perf_counter     # the one clock of phases, stages and runs
_process_cpu = time.process_time    # CPU seconds of all threads, so far
logger = logging.getLogger("paddle_tpu.observe")


def _thread_usage():
    """`getrusage(RUSAGE_THREAD)` as a call without arguments, or None where
    the platform has no `resource` module or no per-thread usage: a stall's
    record then holds None where this thread's numbers would be."""
    try:
        import resource
        return functools.partial(resource.getrusage, resource.RUSAGE_THREAD)
    except (ImportError, AttributeError):
        return None


_usage = _thread_usage()


def _sample(t: float) -> tuple:
    """(t, this thread, its usage or None, the process's CPU seconds)"""
    return (t, threading.get_ident(),
            None if _usage is None else _usage(), _process_cpu())


class StepStats:
    """Host-side phase wall times (seconds) of one run()."""

    __slots__ = ("program_uid", "source", "ts", "phases", "total")

    def __init__(self, program_uid: int, source: str, ts: float,
                 phases: Dict[str, float]):
        self.program_uid = program_uid
        self.source = source          # "executor" | "parallel" | "serving"
        self.ts = ts
        self.phases = phases
        self.total = sum(phases.values())

    def as_dict(self) -> dict:
        return {"program_uid": self.program_uid, "source": self.source,
                "ts": self.ts, "total_us": round(self.total * 1e6, 2),
                "phases_us": {k: round(v * 1e6, 2)
                              for k, v in self.phases.items()}}


class StepLog:
    """Bounded record of recent StepStats + running per-phase totals."""

    def __init__(self, capacity: int = 1024):
        self._steps: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._totals = {p: 0.0 for p in PHASES}
        self._count = 0
        # (registry generation, counter, histogram): resolved once per
        # registry generation instead of two get-or-create registry-lock
        # round trips on every observed step
        self._mcache = None

    def _metric_handles(self):
        reg = _metrics.default_registry()
        gen = reg.generation()
        mc = self._mcache
        if mc is None or mc[0] != gen:
            mc = self._mcache = (
                gen,
                reg.counter("executor_steps_total",
                            "run() calls instrumented by the steplog"),
                reg.histogram("executor_step_phase_us",
                              "host wall time per step phase "
                              "(microseconds)"))
        return mc[1], mc[2]

    def record(self, stats: StepStats, emit_metrics: bool = True,
               emit_trace: bool = True):
        with self._lock:
            self._steps.append(stats)
            for p, v in stats.phases.items():
                self._totals[p] = self._totals.get(p, 0.0) + v
            self._count += 1
        if emit_metrics:
            c, h = self._metric_handles()
            c.inc(source=stats.source)
            for p, v in stats.phases.items():
                h.observe(v * 1e6, phase=p, source=stats.source)
        if emit_trace:
            _tracer.get_tracer().record(
                "step", stats.ts, stats.total, cat="step",
                **{f"{k}_us": round(v * 1e6, 2)
                   for k, v in stats.phases.items()})
        # flight recorder: callers only invoke record() when observing,
        # so this rides the same gate as the metric writes
        _flight.note("step", program_uid=stats.program_uid,
                     source=stats.source,
                     total_us=round(stats.total * 1e6, 2))

    def recent(self, n: int = 16) -> List[StepStats]:
        with self._lock:
            return list(self._steps)[-n:]

    def phase_summary(self, reset: bool = False) -> dict:
        """Aggregated per-phase totals (µs) since the last reset."""
        with self._lock:
            out = {"steps": self._count,
                   "phase_us": {p: round(v * 1e6, 2)
                                for p, v in self._totals.items() if v},
                   "mean_step_us": round(
                       sum(self._totals.values()) * 1e6
                       / max(self._count, 1), 2)}
            if reset:
                self._totals = {p: 0.0 for p in PHASES}
                self._count = 0
        return out

    def clear(self):
        with self._lock:
            self._steps.clear()
            self._totals = {p: 0.0 for p in PHASES}
            self._count = 0


class _Building(threading.local):
    """What is open on this thread. `event`: the compile event last recorded
    here; it takes the stage durations jax reports until the run() that built
    it returns (RunSpans) or the thread records another. `run`: the RunSpans
    in progress, if any. `phase`: the innermost open `Phase`. `run_s`: the
    seconds this thread has spent inside run()s so far, of any program: what
    grew between two runs of one entry, less the first run itself, was
    inside other programs' runs."""
    event = run = phase = None
    run_s = 0.0


_building = _Building()


class EagerCompiles:
    """The compiles jax reported on this thread that built no step: inside a
    run() but outside its jitted call (eager `jnp` calls in the executor's
    own host code, or in a reader's), or inside a set-up phase with no run
    open (a layer that computes a table with `jnp` while the Program is
    built). Counted where they fell (`where`: the run's phase, or the
    set-up phase's name) under the name jax gives the compiled function
    (`names`), never given a cause. It takes what a `RecompileEvent` takes
    from the listeners."""

    __slots__ = ("compiles", "compile_s", "cache_hits", "cache_misses",
                 "where", "names", "at")

    def __init__(self):
        self.compiles = self.cache_hits = self.cache_misses = 0
        self.compile_s = 0.0
        self.where: Dict[str, int] = {}
        self.names: Dict[str, int] = {}
        self.at = None

    def add_stage(self, stage: str, seconds: float, name=None):
        if stage == "backend":
            self.compiles += 1
            self.compile_s += seconds
            self.where[self.at] = self.where.get(self.at, 0) + 1
            self.names[name] = self.names.get(name, 0) + 1

    def as_dict(self) -> dict:
        return {"eager_compiles": self.compiles,
                "eager_compile_s": round(self.compile_s, 6),
                "eager_cache_hits": self.cache_hits,
                "eager_cache_misses": self.cache_misses,
                "eager_where": dict(self.where),
                "eager_names": dict(self.names)}


class Phase:
    """One interval of a set-up on `time.perf_counter()`: the clock of
    `RecompileEvent.add_stage`'s intervals, so phases, compile stages and a
    caller's own stamps lie on one line.

        with Phase(PROGRAM_BUILD, main._uid) as phase:
            ...
            phase.detail["ops"] = ...

    opens a `TraceAnnotation` of that name (a profile taken over a whole
    script shows the phase beside the device track) and, at exit, leaves the
    record in the observatory: `name`, `program_uid` (the spans of one
    Program share it), `parent` (the `id` of the phase open on this thread
    when it started), `start`, `end`, `detail`. A recorded run() and its
    phases are `Phase`s too, made by `RunSpans` from the times it kept; a
    run's `event` is the compile event of the entry it called."""

    __slots__ = ("id", "name", "program_uid", "parent", "start", "end",
                 "detail", "event", "eager", "_outer", "_annotation")
    _ids = itertools.count(1)

    def __init__(self, name: str, program_uid: int, parent=None, start=None,
                 end=None, detail=None, event=None, eager=None):
        self.id = next(Phase._ids)
        self.name, self.program_uid, self.parent = name, program_uid, parent
        self.start, self.end = start, end
        self.detail = {} if detail is None else detail
        self.event, self.eager = event, eager

    def __enter__(self):
        self._outer = _building.phase
        if self._outer is not None:
            self.parent = self._outer.id
        _building.phase = self
        self._annotation = span(self.name, program=self.program_uid)
        self.start = _now()
        return self

    def __exit__(self, *exc):
        self.end = _now()
        self._annotation.__exit__(None, None, None)
        _building.phase = self._outer
        _observatory.note_phases((self,))
        return False

    def add(self, key: str, seconds: float):
        """`seconds` more, and one call more, of `key` on this phase and on
        every phase it is inside: `<key>_s` and `<key>_calls` of `detail`."""
        phase = self
        while phase is not None:
            d = phase.detail
            d[key + "_s"] = d.get(key + "_s", 0.0) + seconds
            d[key + "_calls"] = d.get(key + "_calls", 0) + 1
            phase = phase._outer

    def as_dict(self) -> dict:
        detail = dict(self.detail)
        if self.eager is not None:
            detail.update(self.eager.as_dict())
        return {"id": self.id, "name": self.name,
                "program_uid": self.program_uid, "parent": self.parent,
                "start": self.start, "end": self.end, "detail": detail}

    def __repr__(self):
        return (f"Phase({self.name!r}, uid={self.program_uid}, "
                f"{(self.end or 0) - (self.start or 0):.6f} s)")


def open_phase() -> Optional[Phase]:
    """The innermost set-up phase open on this thread, if any."""
    return _building.phase


class _Process:
    """What the whole process did so far that can hold a step up, as two
    tuples that are replaced, never changed, so that a run samples each with
    one read: `gc` = collections by generation, then seconds inside them by
    generation (from a `gc.callbacks` hook: nothing runs between
    collections, and a collection stops every thread); `compiles` = backend
    compiles, persistent-cache hits, misses that jax reported on any thread
    (the listeners below). Collections never overlap, so `gc` needs no lock;
    two threads may compile at once."""

    __slots__ = ("gc", "compiles", "_gc_start", "_lock")

    def __init__(self):
        self.gc = (0, 0, 0, 0.0, 0.0, 0.0)
        self.compiles = (0, 0, 0)
        self._gc_start = None
        self._lock = threading.Lock()

    def on_gc(self, phase, info):
        if phase == "start":
            self._gc_start = _now()
        elif self._gc_start is not None:    # hooked in mid-collection: skip
            tally, gen = list(self.gc), info["generation"]
            tally[gen] += 1
            tally[3 + gen] += _now() - self._gc_start
            self.gc = tuple(tally)

    def count_compile(self, which: int):
        with self._lock:
            tally = list(self.compiles)
            tally[which] += 1
            self.compiles = tuple(tally)


_process = _Process()
gc.callbacks.append(_process.on_gc)

# a kept interval's parts beside the phases of the run it starts with (each
# of those under its span's name less the prefix): run()'s start to its first
# phase, where a py_reader program waits for its next batch; the seconds
# inside other programs' runs on this thread; and the rest, the caller's: its
# wait for a result, a reader, a feeder
RUN_ENTRY = "run_entry"
OTHER_RUNS = "other_runs"
OUTSIDE_RUN = "outside_run"
PACE_RING = 32
# runs of an entry between two samples of the thread's and the process's
# usage: the two system calls are 12.3 of the 14.1 us that a run's spans cost
# on the chip's host (815 us a run in the cell of the shortest one), so a
# stall's usage is differenced over the interval and up to seven steady
# steps before it (`usage_over_s`)
USAGE_EVERY = 8
STALLS_KEPT = 64
STALL_LINES = 16               # lines on the logger, in a process


def is_long(interval: float, median: float) -> bool:
    """The one rule for a step interval that is kept, in seconds: it
    exceeds the entry's median by more than a quarter of it and by more
    than 5 ms. Steady intervals of one entry agree within 0.2% (PERF.md
    section 2), so a quarter keeps far fewer than one in a thousand of a
    window without stalls, and still keeps the smallest stall the records
    hold, 39.5 ms on a step of 60.9 ms, and the ~100 ms late returns on the
    longest step, 231 ms. Under 5 ms a step of microseconds (a small
    program on the CPU) would be kept for the scheduler's jitter."""
    excess = interval - median
    return excess > 0.005 and excess > 0.25 * median


class Pace:
    """The pace of one compiled entry's steps, held by the entry and handed
    to each `RunSpans` of it: `prev`, the last run if it may start an
    interval (it ran to its end, on a steady step), a ring of the last
    `PACE_RING` intervals and their `median`, None until the ring has been
    full once and recomputed whenever it wraps, so that a pace that changes
    for good is the new median within two rings. An interval is start of
    run k to start of run k + 1 on one thread, less what lay inside other
    programs' runs between them. `usage`: the last `_sample`, taken at the
    start of every `USAGE_EVERY`-th run (`until` counts down to it)."""

    __slots__ = ("prev", "ring", "at", "median", "usage", "until")

    def __init__(self):
        self.prev = self.median = self.usage = None
        self.ring = [0.0] * PACE_RING
        self.at = self.until = 0


def stall_record(prev: "RunSpans", cur: "RunSpans", median: float,
                 before: tuple, after: tuple) -> dict:
    """What is kept of the interval from the start of `prev` to the start of
    `cur`, two consecutive runs of one entry on one thread: its parts, which
    sum to it (`parts_s`, the largest named in `where`), the collections and
    compiles inside it, and what the thread and the process used up from
    `before`, the entry's last `_sample` (at the start of `prev` or of one
    of the seven runs before it), to `after`, one taken now: `usage_over_s`
    of wall, the interval and those steady steps. Wall far above
    `thread_cpu_s` with involuntary switches: the thread was taken off the
    CPU; with voluntary ones: it was blocked (a lock, a file, the runtime's
    queue); CPU near wall: the process was working, and a collection or a
    compile says so itself; major faults: paging; `process_cpu_s` far above
    the thread's: another thread of ours was busy. `step` is what the
    `paddle_tpu:run` span of `prev` carries in a trace; `start` and `end`
    are on `time.perf_counter()`."""
    interval = cur._t_run - prev._t_run
    run_s = prev._t_end - prev._t_run
    others = cur._in_runs - prev._in_runs - run_s
    phases = prev._intervals(prev._t_end)
    parts = {RUN_ENTRY:
             (phases[0][1] if phases else prev._t_end) - prev._t_run}
    parts.update((which[0].split(":")[1], e - s) for which, s, e in phases)
    parts[OTHER_RUNS] = others
    parts[OUTSIDE_RUN] = interval - run_s - others
    a, b = before[2], after[2]
    if a is None or b is None or before[1] != after[1]:
        thread = dict.fromkeys(("thread_cpu_s", "voluntary_switches",
                                "involuntary_switches", "major_faults",
                                "minor_faults"))
    else:
        thread = {
            "thread_cpu_s": ((b.ru_utime - a.ru_utime)
                             + (b.ru_stime - a.ru_stime)),
            "voluntary_switches": b.ru_nvcsw - a.ru_nvcsw,
            "involuntary_switches": b.ru_nivcsw - a.ru_nivcsw,
            "major_faults": b.ru_majflt - a.ru_majflt,
            "minor_faults": b.ru_minflt - a.ru_minflt}
    collected = [y - x for x, y in zip(prev._gc, cur._gc)]
    compiles, hits, misses = (
        y - x for x, y in zip(prev._compiles, cur._compiles))
    return {"program_uid": prev.program_uid, "source": prev.source,
            "step": prev.step, "start": prev._t_run, "end": cur._t_run,
            "interval_s": interval, "median_s": median,
            "where": max(parts, key=parts.get), "parts_s": parts,
            "usage_over_s": after[0] - before[0], **thread,
            "process_cpu_s": after[3] - before[3],
            "gc_collections": collected[:3], "gc_s": collected[3:],
            "compiles": compiles, "cache_hits": hits,
            "cache_misses": misses}


def stall_line(record: dict) -> str:
    """A stall's record on one line, every number in it, milliseconds, the
    parts from the largest down to 0.05 ms: the logger's,
    `tools/telemetry_dump.py`'s and the benchmark's."""
    def ms(seconds):
        return "n/a" if seconds is None else f"{seconds * 1e3:.1f}"

    def n(count, sign=""):
        return "n/a" if count is None else f"{sign}{count}"

    said = {OUTSIDE_RUN: "{} outside run()",
            OTHER_RUNS: "{} inside other programs' runs"}
    parts = sorted(((name, seconds)
                    for name, seconds in record["parts_s"].items()
                    if seconds >= 5e-5), key=lambda kv: -kv[1])
    return (
        f"paddle_tpu: step interval {ms(record['interval_s'])} ms at run "
        f"{record['step']} of program {record['program_uid']} (median "
        f"{ms(record['median_s'])}): "
        + ", ".join(said.get(name, name + " {}").format(ms(seconds))
                    for name, seconds in parts)
        + f"; over {ms(record['usage_over_s'])} ms thread CPU "
        f"{ms(record['thread_cpu_s'])} ms, process CPU "
        f"{ms(record['process_cpu_s'])} ms; switches "
        f"{n(record['voluntary_switches'], '+')} voluntary "
        f"{n(record['involuntary_switches'], '+')} involuntary; faults "
        f"{n(record['major_faults'])} major {n(record['minor_faults'])} "
        f"minor; gc {'+'.join(map(str, record['gc_collections']))} by "
        f"generation ({ms(sum(record['gc_s']))} ms); compiles "
        f"{record['compiles']}, cache hits {record['cache_hits']} misses "
        f"{record['cache_misses']}")


class RunSpans:
    """The host spans of one `PreparedProgram.run` (both executors' one).

        with RunSpans(program_uid, source, step) as spans:
            spans.phase(FEED_CONVERT); ...
            spans.phase(STATE_GATHER); ...

    `paddle_tpu:run` covers the `with` block and carries `step` (the
    per-program run counter: what all spans of one step share, and by order
    the k-th module execution on the device's track), `program` and
    `source`. `phase()` ends the open child span and starts the next, so
    the children are leaves that tile the run. At default flags a steady
    step does that, notes itself as this thread's current run and, in
    `event`, the compile event of the entry it calls (for a compile that
    jax reports inside it, `_taker`), and reads `time.perf_counter()` once
    at each boundary, and at its end, into a slot of its own. A
    `TraceAnnotation` costs one atomic check while no profile is taken.

    Given the `pace` of the entry it is about to call (`Pace`; None where the
    handle has bound none yet), it also reads `_process`'s tallies at its
    start and closes the interval that the entry's last run opened: into
    the ring and, if `is_long`, into the observatory (`stall_record`); every
    `USAGE_EVERY`-th run of the entry then samples this thread's usage and
    the process's CPU seconds (`_sample`). A steady step keeps nothing but
    that float.

    A run that binds, or during which jax reports a compile, is a first run
    (`keep`): at exit the same times go to the observatory as `Phase`s, the
    run and its phases, and it opens no interval. With the `observe` flag
    on they also fill a `StepStats`. Both after the run span has closed,
    unless the body raised."""

    __slots__ = ("observing", "program_uid", "source", "step", "which",
                 "event", "keep", "eager", "pace", "_run", "_child",
                 "_t_run", "_t_end", "_thread", "_gc", "_compiles",
                 "_in_runs") + tuple(w[2] for w in RUN_PHASES)

    def __init__(self, program_uid: int, source: str, step: int, pace=None):
        self.observing = _flags.get_flag("observe")
        self.program_uid, self.source, self.step = program_uid, source, step
        self.which = self._child = self.event = self.eager = None
        self.keep = False
        self.pace = pace
        self._t_run = _now()
        if pace is not None:
            self._thread = threading.get_ident()
            self._gc, self._compiles = _process.gc, _process.compiles
            self._in_runs = _building.run_s
            prev, pace.prev = pace.prev, None
            if prev is not None and prev._thread == self._thread:
                self._close_interval(pace, prev)
            if pace.until:
                pace.until -= 1
            else:
                pace.until = USAGE_EVERY - 1
                pace.usage = _sample(self._t_run)
        self._run = span(RUN, step=step, program=program_uid, source=source)
        _building.run = self

    def _close_interval(self, pace, prev):
        """The interval `prev` opened ends at this run's start."""
        own = self._t_run - prev._t_run - (
            self._in_runs - prev._in_runs - (prev._t_end - prev._t_run))
        median = pace.median
        if median is not None and is_long(own, median):
            after = _sample(self._t_run)
            _observatory.note_stall(stall_record(
                prev, self, median, pace.usage, after))
            pace.usage, pace.until = after, USAGE_EVERY
        at = pace.at
        pace.ring[at] = own
        if at + 1 == PACE_RING:
            pace.at = 0
            pace.median = statistics.median(pace.ring)
        else:
            pace.at = at + 1

    def __enter__(self):
        return self

    def phase(self, which):
        if self._child is not None:
            self._child.__exit__(None, None, None)
        self.which = which
        if which is BIND:
            self.keep = True
        setattr(self, which[2], _now())
        self._child = span(which[0])

    def _intervals(self, end):
        """[(phase constant, start, end)] of the phases this run opened:
        each ends where the next began, the last with the run."""
        out = []
        for which in reversed(RUN_PHASES):
            start = getattr(self, which[2], None)
            if start is not None:
                out.append((which, start, end))
                end = start
        return out[::-1]

    def __exit__(self, exc_type, exc, tb):
        if self._child is not None:
            self._child.__exit__(None, None, None)
        self._run.__exit__(None, None, None)
        # the compile event this run built stops taking stage durations
        _building.run = _building.event = None
        end = self._t_end = _now()
        _building.run_s += end - self._t_run
        if self.pace is not None and not self.keep and exc_type is None:
            self.pace.prev = self
        if (self.keep or self.observing) and exc_type is None:
            phases = self._intervals(end)
            if self.keep:
                _observatory.note_run(self, phases, end)
            if self.observing:
                _steplog.record(StepStats(
                    self.program_uid, self.source, time.time(),
                    {which[1]: e - s for which, s, e in phases}))
        return False


# jax.monitoring duration events -> the keys of RecompileEvent.stages_s
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}


class RecompileEvent:
    __slots__ = ("ts", "program_uid", "cause", "source", "detail",
                 "cache_hits", "cache_misses", "_stage_spans", "_text_fn",
                 "_op_map")

    def __init__(self, ts, program_uid, cause, source, detail):
        self.ts = ts
        self.program_uid = program_uid
        self.cause = cause
        self.source = source
        self.detail = detail
        self.cache_hits = self.cache_misses = 0   # persistent compile cache
        self._stage_spans: Dict[str, list] = {}
        self._text_fn = self._op_map = None
        _building.event = self

    def offer_text(self, fn):
        """The executor that built this event's step says how to get its
        compiled text: `fn()` lowers and compiles when asked, not before."""
        self._text_fn = fn

    def compiled_text(self) -> Optional[str]:
        """Optimized HLO of the step this event built, as it runs in steady
        state; None where no executor offered one (a shape miss) or its scope
        is gone. Made at every ask and not kept (a step's
        text runs to tens of MB; `op_map` keeps what is read from it): once
        the step has run with the signature asked for, jax answers the
        trace, the lowering and the executable from what it cached for the
        running step, and the ask costs the printing of the text; before
        that it is a lowering and a compile."""
        return self._text_fn() if self._text_fn is not None else None

    def op_map(self, build):
        """`build(compiled_text())`, made once (`profiler.op_map` passes its
        builder); None where there is no text."""
        if self._op_map is None:
            text = self.compiled_text()
            if text is not None:
                self._op_map = build(text)
        return self._op_map

    def add_stage(self, stage: str, seconds: float, name=None):
        """jax reports a duration when it ends, and a traced function that
        calls jitted ones reports theirs inside its own: keep the union
        (durations arrive in order of their ends). `name`, jax's of the
        function, is not kept: the event is one step's."""
        end = _now()
        start = end - seconds
        merged = self._stage_spans.setdefault(stage, [])
        while merged and merged[-1][0] >= start:
            merged.pop()
        if merged and merged[-1][1] > start:
            merged[-1][1] = end
        else:
            merged.append([start, end])

    @property
    def stages_s(self) -> Dict[str, float]:
        """Seconds by compile stage (`_STAGES`); empty if nothing compiled
        while the event was being built."""
        return {stage: sum(e - s for s, e in merged)
                for stage, merged in self._stage_spans.items()}

    def stage_intervals(self) -> Dict[str, List[Tuple[float, float]]]:
        """The merged `(start, end)` of each stage, on `perf_counter()`."""
        return {stage: [(s, e) for s, e in merged]
                for stage, merged in self._stage_spans.items()}

    def as_dict(self) -> dict:
        return {"ts": self.ts, "program_uid": self.program_uid,
                "cause": self.cause, "source": self.source,
                "detail": self.detail,
                "stages_s": {k: round(v, 6)
                             for k, v in self.stages_s.items()},
                "stage_intervals": {
                    stage: [[round(s, 6), round(e, 6)] for s, e in spans]
                    for stage, spans in self.stage_intervals().items()},
                # disjoint backend intervals: 2 = the step was built twice
                "backend_compiles": len(self._stage_spans.get("backend", ())),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def __repr__(self):
        return (f"RecompileEvent(uid={self.program_uid}, "
                f"cause={self.cause!r}, source={self.source!r})")


# causes that are expected on a healthy steady-state run and therefore
# ignored by --assert-no-recompiles (the first compile of each program
# has to happen, and a serving warmup compiles its bucket ladder ahead
# of traffic on purpose; everything else is a recompile someone should
# explain)
EXPECTED_CAUSES = ("first_call", "warmup")


# The cause of a compile-cache miss, by the first field of the step's record
# (`core.executor.StepKey`), in this order of priority, that holds a value
# this program was never built with. Every field after `program_uid` has an
# entry (tests/test_prepared_executor.py fails for one without).
CAUSE_OF_FIELD = {
    "program_version": "program_version",
    "copts": "copts_change",
    "feeds": "feed_names",
    "fetches": "fetch_set",
    "scope_uid": "new_scope",
    "amp": "options_change",
    "check_nan_inf": "options_change",
    "seed": "options_change",
    "mesh": "options_change",
}


class RecompilationObservatory:
    """Records every executor-level compile with an attributed cause.

    Attribution compares the miss's record, field by field in
    `CAUSE_OF_FIELD`'s order, against what this process has already
    compiled for the same program uid: new version → ``program_version``;
    new compiler options → ``copts_change``; new feed-name set →
    ``feed_names``; new fetch list → ``fetch_set``; new scope →
    ``new_scope``; a new amp / check_nan_inf / random_seed / mesh →
    ``options_change``, which is also the name where every value was seen
    before (another executor built the same step, or a new combination).
    Run-time shape tracking (flag-gated, see note in the module
    docstring) reports jax-level retraces of an already-bound entry as
    ``feed_shape``."""

    def __init__(self, capacity: int = 256, phase_capacity: int = 1024):
        self._events: deque = deque(maxlen=capacity)
        # the set-up store: `Phase`s of program builds and of first runs
        self._phases: deque = deque(maxlen=phase_capacity)
        # the step intervals that ran long (`stall_record`), newest kept,
        # and how many there were: the first `STALL_LINES` are logged
        self._stalls: deque = deque(maxlen=STALLS_KEPT)
        self._stalls_noted = 0
        self._lock = threading.Lock()
        # program uid -> {field of CAUSE_OF_FIELD: the values built with}
        self._seen: Dict[int, Dict[str, set]] = {}

    def note_entry_build(self, key, source: str,
                         detail: dict) -> "RecompileEvent":
        """Called on every executor compile-cache miss (a new
        _CompiledProgram is about to be built for `key`, its `StepKey`).
        Returns the event, whose cause it found. `detail` is kept as it
        is, not copied: the builder (`core.executor.PreparedProgram._build_entry`) puts the
        version, the feed and fetch names and `census.program_detail`
        (the sharing counters and the census of mixer and expert layers)
        there and hands the same dict to the step's lowerer, so what a
        rule notes under the trace lands on this event."""
        with self._lock:
            seen = self._seen.get(key.program_uid)
            if seen is None:
                cause = "first_call"
                seen = self._seen[key.program_uid] = {
                    f: set() for f in CAUSE_OF_FIELD}
            else:
                cause = next((c for f, c in CAUSE_OF_FIELD.items()
                              if getattr(key, f) not in seen[f]),
                             "options_change")
            for f, values in seen.items():
                values.add(getattr(key, f))
            event = RecompileEvent(time.time(), key.program_uid, cause,
                                   source, detail)
            self._events.append(event)
        self._emit_metric(cause, source)
        return event

    def note_shape_miss(self, program_uid: int, shape_sig, source: str,
                        cause: str = "feed_shape"):
        """A bound entry saw a NEW feed shape/dtype signature: jax.jit
        will retrace and XLA will recompile. This is the live counterpart
        of the lint's feed-shape recompile hazard. On a ``serving``-source
        handle the caller attributes it ``padding_bucket`` instead — the
        bucket planner should have padded the request onto a warmed rung,
        so a miss means the ladder is mis-sized, not that the jit cache
        misbehaved."""
        self.record(program_uid, cause, source,
                    {"shapes": {n: list(shp) for n, shp, _ in shape_sig}})

    def record(self, program_uid: int, cause: str, source: str,
               detail=None) -> "RecompileEvent":
        """Direct record without attribution (e.g. `uncached` runs)."""
        event = RecompileEvent(time.time(), program_uid, cause, source,
                               detail)
        with self._lock:
            self._events.append(event)
        self._emit_metric(cause, source)
        return event

    def note_phases(self, phases):
        """`Phase`s that have closed (an inner one before its outer)."""
        with self._lock:
            self._phases.extend(phases)

    def note_run(self, spans: "RunSpans", intervals, end: float):
        """A first run (`RunSpans.keep`): its `paddle_tpu:run` interval and,
        under it, `intervals` (`RunSpans._intervals`), with the compile
        event it fed and the compiles that fell outside its jitted call."""
        outer = _building.phase
        run = Phase(RUN, spans.program_uid,
                    parent=None if outer is None else outer.id,
                    start=spans._t_run, end=end,
                    detail={"source": spans.source, "step": spans.step},
                    event=spans.event, eager=spans.eager)
        self.note_phases([run] + [
            Phase(which[0], spans.program_uid, parent=run.id, start=s, end=e)
            for which, s, e in intervals])

    def phases(self) -> List["Phase"]:
        """The set-up store: program builds, first runs and their phases."""
        with self._lock:
            return list(self._phases)

    def note_stall(self, record: dict):
        """A step interval that ran long: kept here, in the flight ring (the
        dump of a process that is killed holds it) and, the first
        `STALL_LINES` of a process, as a line on the logger."""
        with self._lock:
            self._stalls.append(record)
            self._stalls_noted += 1
            noted = self._stalls_noted
        _flight.note("stall", **record)
        if noted <= STALL_LINES:
            logger.warning(stall_line(record))

    def stalls(self) -> List[dict]:
        """The last `STALLS_KEPT` step intervals that ran long."""
        with self._lock:
            return list(self._stalls)

    def latest(self, program_uid: int) -> Optional[RecompileEvent]:
        """The program's most recent compile event, if the ring holds one."""
        with self._lock:
            for event in reversed(self._events):
                if event.program_uid == program_uid:
                    return event
        return None

    @staticmethod
    def _emit_metric(cause: str, source: str):
        _metrics.counter(
            "executor_recompiles_total",
            "executor compile events by attributed cause").inc(
                cause=cause, source=source)
        # compile events are never hot — they go to the black box
        # unconditionally, like the metric above
        _flight.note("compile", cause=cause, source=source)

    def events(self) -> List[RecompileEvent]:
        with self._lock:
            return list(self._events)

    def counts(self) -> Dict[str, int]:
        """Per-cause counts over the BOUNDED event ring — right for short
        runs and detail inspection. For cumulative whole-run counts read
        the `executor_recompiles_total` metrics counter instead (events
        older than the ring capacity fall out of this tally)."""
        out: Dict[str, int] = {}
        for e in self.events():
            out[e.cause] = out.get(e.cause, 0) + 1
        return out

    def unexpected(self) -> List[RecompileEvent]:
        """Events whose cause is not in EXPECTED_CAUSES — the set
        --assert-no-recompiles fails on."""
        return [e for e in self.events() if e.cause not in EXPECTED_CAUSES]

    def as_dict(self) -> dict:
        """What `observe.summary()` / `/status` carry under `recompiles`."""
        return {"counts": self.counts(),
                "events": [e.as_dict() for e in self.events()],
                "phases": [p.as_dict() for p in self.phases()],
                "stalls": self.stalls()}

    def clear(self):
        with self._lock:
            self._events.clear()
            self._phases.clear()
            self._stalls.clear()
            self._stalls_noted = 0
            self._seen.clear()


_steplog = StepLog()
_observatory = RecompilationObservatory()


def _eager(holder, at) -> EagerCompiles:
    """The `EagerCompiles` of a run or an open phase, made at its first
    compile; `at` is where the next one falls."""
    if holder.eager is None:
        holder.eager = EagerCompiles()
    holder.eager.at = at
    return holder.eager


def _taker():
    """What takes the compile jax reports on this thread now. Inside the
    jitted call of a run() it is a step being built: the event the thread
    last recorded, or, where the executor recorded no cause, the event of
    the entry that run() called, for the rest of the run. Elsewhere inside a
    run() it is host code compiling on its own: the run's `EagerCompiles`.
    With no run open, the event being built (a serving warm-up) or the open
    set-up phase's `EagerCompiles`; else nothing (a user's own jnp code)."""
    run = _building.run
    if run is not None:
        run.keep = True
        if run.which is not JIT_CALL:
            return _eager(run, run.which[1] if run.which else "run")
        if _building.event is None:
            _building.event = run.event
        return _building.event
    if _building.event is not None:
        return _building.event
    phase = _building.phase
    return None if phase is None else _eager(phase, phase.name)


def _on_duration(event, seconds, fun_name=None, **_):
    stage = _STAGES.get(event)
    if stage is not None:
        if stage == "backend":
            _process.count_compile(0)
        taker = _taker()
        if taker is not None:
            taker.add_stage(stage, seconds, fun_name)


def _on_event(event, **_):
    attr = _CACHE_EVENTS.get(event)
    if attr is not None:
        _process.count_compile(1 if attr == "cache_hits" else 2)
        taker = _taker()
        if taker is not None:
            setattr(taker, attr, getattr(taker, attr) + 1)
            if attr == "cache_misses":
                # nothing was read from the cache: 0 s beside the stages
                taker.add_stage("cache_retrieval", 0.0)


# they fire only when something compiles, never in a steady step
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def get_steplog() -> StepLog:
    return _steplog


def observatory() -> RecompilationObservatory:
    return _observatory


def shape_sig(feed_arrays: Dict) -> Tuple:
    """Canonical (name, shape, dtype) signature of a feed dict — the part
    of the jax.jit cache key the executor can observe cheaply."""
    return tuple(sorted(
        (n, tuple(getattr(v, "shape", ())), str(getattr(v, "dtype", "")))
        for n, v in feed_arrays.items()))


def track_shapes(entry, program_uid: int, feed_arrays: Dict,
                 source: str = "executor"):
    """Flag-gated per-step shape tracking: detect jax-level retraces of a
    bound entry. The first signature an entry ever runs is covered by its
    build event; every NEW signature after that is a `feed_shape` miss —
    or, on a serving handle (where the bucket planner guarantees every
    steady-state shape was warmed ahead of time), a `padding_bucket`
    miss."""
    sig = shape_sig(feed_arrays)
    seen = entry.shape_sigs
    if sig not in seen:
        if seen:
            cause = "padding_bucket" if source == "serving" else "feed_shape"
            observatory().note_shape_miss(program_uid, sig, source, cause)
        seen.add(sig)


def preseed_shapes(entry, feed_arrays: Dict):
    """Register a feed signature as already-seen on a bound entry WITHOUT
    recording a shape-miss event. The serving warmup uses this: it runs
    each bucket shape once ahead of traffic (recording those compiles as
    the expected `warmup` cause via the observatory), and pre-seeding
    keeps the tracker from re-flagging the warmed shapes as misses —
    including when warmup ran with the `observe` flag off and the flag is
    flipped on later."""
    entry.shape_sigs.add(shape_sig(feed_arrays))
