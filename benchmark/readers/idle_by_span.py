"""Whose idle time is it? The share, in %, of the busiest device's idle
nanoseconds (the gaps between its ops in the profiled window) that fall
inside a `paddle_tpu:run` span: idle while the program's own host path runs
(feed conversion, state gather, dispatch, write-back), as against idle while
the caller waits for a result or prepares data. Nothing where the program
opens no such span, or the device has no gap.

Also prints the ten longest gaps, each named after the program phase that
covers most of it (else the benchmark's own span), for the reader of the
log; `run.py`'s `breakdown.idle_gaps` reads the benchmark's spans only.
"""

import trace_reduce
from readers import program_spans


def inside_ns(gaps, covers):
    """ns of `gaps` covered by the union of the `covers` intervals."""
    merged, _ = trace_reduce.busy_union(covers)
    return sum(max(0, min(g1, c1) - max(g0, c0))
               for g0, g1 in gaps for c0, c1 in merged)


def name_gaps(gaps, spans, bench_spans):
    """[(name, ns)]: each gap named after the span that covers most of it,
    among the program's leaf spans (its phases) and those of the
    benchmark's spans that hold no `paddle_tpu:run` (one that does only
    repeats, less precisely, what the phases inside it say)."""
    runs = [s for s in spans if s[2] == program_spans.RUN]
    leaves = [s for s in spans if s[2] != program_spans.RUN]
    outside = [b for b in bench_spans
               if not any(b[0] <= r[0] and r[1] <= b[1] for r in runs)]
    return trace_reduce.attribute_gaps(gaps, leaves + outside)


def read(ctx):
    trace = ctx["trace"]()
    spans = program_spans.window_spans(ctx)
    runs = [s for s in spans if s[2] == program_spans.RUN]
    if trace is None or trace["device"] is None or not runs:
        return None
    gaps = trace["summary"][trace["device"]]["gaps"]
    idle = sum(g1 - g0 for g0, g1 in gaps)
    if not idle:
        return None
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    named = name_gaps(longest, spans, trace["spans"])
    print("benchmark: ten longest idle gaps by program phase: "
          + ", ".join(f"{name} {ns / 1e3:.1f} us" for name, ns in named),
          flush=True)
    return 100.0 * inside_ns(gaps, runs) / idle
