"""Host microseconds per step inside the program's own spans, from the
profiled window: the `paddle_tpu:` `TraceAnnotation`s the executor opens at
default flags, on the profiler's clock (`paddle_tpu/observe/steplog.py`,
`RunSpans`). Every step is one `paddle_tpu:run` span, and its phases are
leaf spans inside it, on the same thread.

`spans` names what is summed within each step (`paddle_tpu:run` itself, or
one or more of its phases); the value is the median over the steps of the
window. A program that opens no such span (one from before they existed)
gives nothing, and the metric is left out.
"""

import functools
import statistics

import trace_reduce

PREFIX = "paddle_tpu:"
RUN = "paddle_tpu:run"


@functools.lru_cache(maxsize=2)
def load(trace_dir):
    """[(start, end, name)] of the program's spans in the trace, sorted."""
    events = trace_reduce.load_xplane(
        trace_dir, keep_line=lambda plane, line:
            not trace_reduce.DEVICE_PLANE.match(plane))
    return trace_reduce.host_spans(events, PREFIX)


def window_spans(ctx):
    profile = ctx["obs"].get("profile")
    return load(profile["dir"]) if profile else []


def per_run_ns(spans, names):
    """For each `paddle_tpu:run` span, the ns of the spans named in `names`
    that lie inside it (the run's own length where `names` holds RUN)."""
    runs = [s for s in spans if s[2] == RUN]
    out = []
    for r0, r1, _ in runs:
        out.append(sum(s1 - s0 for s0, s1, name in spans
                       if name in names and r0 <= s0 and s1 <= r1))
    return out


def read(ctx, spans, scale=1e-3):
    values = per_run_ns(window_spans(ctx), set(spans))
    if not values:
        return None
    return statistics.median(values) * scale
