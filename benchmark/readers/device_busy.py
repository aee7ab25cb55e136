"""Device busy time from the profiled window's "XLA Ops" track.

quantity "step_ms": the union of busy intervals on the busiest device,
divided by the steps in the window. "idle_pct": 100 * (1 - busy / window),
the window running from that device's first op to its last.
"""


def read(ctx, quantity):
    trace = ctx["trace"]()
    if trace is None or trace["device"] is None:
        return None
    s = trace["summary"][trace["device"]]
    if quantity == "step_ms":
        return s["busy_ns"] / 1e6 / trace["steps"]
    if quantity == "idle_pct":
        return 100.0 * (1.0 - s["busy_ns"] / s["window_ns"])
    raise ValueError(f"device_busy: unknown quantity {quantity!r}")
