"""fluid-scope: unified runtime telemetry for paddle_tpu.

Three cooperating pieces (see docs/OBSERVABILITY.md):

- `observe.metrics`  — process-wide registry of counters / gauges /
  histograms (thread-safe, labeled, snapshot/JSON/Prometheus export)
- `observe.tracer`   — structured spans in a bounded ring buffer with
  chrome://tracing export; absorbs the profiler's host-event table
- `observe.steplog`  — the spans inside run() (`RunSpans`), per-run()
  StepStats phase timings + the recompilation observatory (every jit
  cache miss, with attributed cause and the seconds each stage cost, and
  the set-up store: `Phase`s of program builds and of first runs)
- `observe.xray`     — W3C trace contexts across processes (round 11)
- `observe.flight`   — the crash flight recorder (round 11)
- `observe.pulse`    — per-process HTTP health endpoint: /metrics,
  /healthz, /readyz, /status, /flight (round 13, `start_pulse(port=0)`)
- `observe.health`   — metric time-series + anomaly detectors firing
  structured Alerts into the registry AND the flight ring (round 13)
- `observe.memory`   — the HBM observatory: per-program peak estimates
  vs live device memory stats (round 13)
- `observe.stitch`   — causal cross-process trace assembly: flow
  events + clock-skew correction over merged chrome traces (round 21)
- `observe.scrape`   — the fluid-horizon observatory: a scraper over
  every pulse /metrics into one queryable time-series store (round 21)

Emission from hot paths (Executor/PreparedProgram/ParallelExecutor steps,
AsyncFeeder, pserver RPC) is gated on the `observe` flag:

    fluid.set_flag("observe", True)        # or PADDLE_TPU_OBSERVE=1

With the flag off, the prepared-program fast path performs ZERO registry
writes per step: one flag read and branch, and five reads of
`time.perf_counter()` into the run's own slots (one at its start, one at
each phase), which a steady step drops with the run — no allocation, no
lock, no write to any store. Compile-time recompile events are recorded
regardless — they are never hot and they are what
`tools/telemetry_dump.py --assert-no-recompiles` audits in CI.

Two things are on at DEFAULT flags: the set-up store and the host spans of
a run. The store (`observatory().phases()`) keeps, on `perf_counter()`,
the body of every `program_guard` (`paddle_tpu:program_build`, two clock
reads an op for the seconds inside shape inference), `minimize` inside it,
and every run() that binds or compiles, with its phases and the compiles
that fell outside its jitted call: a few records a program, bounded. Every
`PreparedProgram.run` (an `Executor`'s steps and a `ParallelExecutor`'s
alike) opens `paddle_tpu:run` and,
inside it, `paddle_tpu:feed_convert`, `:bind` (a step that binds),
`:state_gather`, `:jit_call`, `:write_back`, `:fetch` (with
`return_numpy=True`) as `jax.profiler.TraceAnnotation`s; `py_reader` and
`AsyncFeeder` add `paddle_tpu:reader_pop` and `paddle_tpu:feeder_put`.
They cost one atomic check each while no profile is taken, and in any
`profiler.profiler(...)` / TensorBoard capture they sit next to the
device track, on its clock. The `observe` flag adds the `StepStats` ring
on the same boundaries; its `device_compute` key is the host wall of the
jitted call — dispatch, not device time, under async dispatch.
"""

from __future__ import annotations

from .. import flags as _flags
from . import flight, health, memory, metrics, pulse  # noqa: F401
from . import scrape, steplog, stitch, tracer, xray  # noqa: F401
from .flight import get_flight  # noqa: F401
from .health import get_engine  # noqa: F401
from .metrics import counter, default_registry, gauge, histogram  # noqa: F401
from .pulse import start_pulse, stop_pulse  # noqa: F401
from .scrape import Scraper, TimeSeriesStore  # noqa: F401
from .steplog import (StepStats, get_steplog, observatory,  # noqa: F401
                      preseed_shapes, track_shapes)
from .stitch import stitch_traces, trace_tree  # noqa: F401
from .tracer import get_tracer, merge_chrome_traces  # noqa: F401

# fluid-pulse: every flight-recorder dump carries the memory observatory
# (an OOM/SIGTERM death must be attributable to who held the bytes)
get_flight().add_section("memory", memory.get_observatory().flight_section)


def enabled() -> bool:
    """The hot-path gate: one flag-registry read."""
    return _flags.get_flag("observe")


def enable():
    _flags.set_flag("observe", True)


def disable():
    _flags.set_flag("observe", False)


def summary() -> dict:
    """One dict with everything a run left behind — what
    tools/telemetry_dump.py prints. Derived from
    pulse.status_document() (the live `/status` body) minus process
    identity, so the dead- and live-process shapes CANNOT diverge —
    one source of truth for the one-tool-reads-both contract."""
    doc = pulse.status_document()
    for k in ("pid", "process", "ts"):
        doc.pop(k, None)
    return doc


def reset():
    """Clear every telemetry store (tests)."""
    default_registry().reset()
    get_tracer().clear()
    get_steplog().clear()
    observatory().clear()


def reset_all():
    """`reset()` plus the fluid-xray stores (flight-recorder ring +
    stage, this thread's ambient trace context) and the fluid-pulse
    plane (the HTTP server thread is STOPPED, the health engine and
    memory observatory cleared). The tier-1 autouse fixture calls this
    so tests stop sharing process-global telemetry state — and can
    never leak a pulse thread."""
    reset()
    get_flight().clear()
    xray.reset()
    pulse.stop_pulse()
    health.reset()
    memory.reset()
