"""The median of one of the benchmark's own host-clock series, scaled.

`series` names a list of seconds among the generator's observations, such
as `dispatch_s`: the time inside each `exe.run` / `pe.run` call that
returns without waiting for the device.
"""

import statistics


def read(ctx, series, scale=1.0):
    values = ctx["obs"].get(series)
    if not values:
        return None
    return statistics.median(values) * scale
