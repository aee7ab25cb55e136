"""How many distinct ops whose name matches `pattern` (a regular
expression) ran on the busiest device in the profiled window. An op's event
name is its whole HLO line, result name included, so every call site in the
compiled step counts once however many steps the window holds. Nothing
where no op matches."""

import trace_reduce


def read(ctx, pattern):
    trace = ctx["trace"]()
    if trace is None or trace["device"] is None:
        return None
    by_name = trace["summary"][trace["device"]]["by_name"]
    _, names = trace_reduce.sum_matching(by_name, pattern)
    return float(len(names)) if names else None
