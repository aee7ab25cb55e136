"""Device milliseconds per step of the ops whose name matches `pattern`
(a regular expression), on the busiest device of the profiled window.
Returns nothing where no op of the trace matches, so that a metric the
trace cannot name is left out instead of reported as 0."""

import trace_reduce


def read(ctx, pattern):
    trace = ctx["trace"]()
    if trace is None or trace["device"] is None:
        return None
    by_name = trace["summary"][trace["device"]]["by_name"]
    ns, names = trace_reduce.sum_matching(by_name, pattern)
    if not names:
        return None
    return ns / 1e6 / trace["steps"]
