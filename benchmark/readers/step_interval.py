"""A percentile of the interval between consecutive step completions, ms.

The percentile is refused (nothing is returned) unless `samples_beyond`
intervals lie beyond it: a 95th percentile over too few steps is a maximum.
"""

import math


def read(ctx, percentile, samples_beyond=10):
    stamps = ctx["obs"]["stamps"]
    gaps = sorted(b - a for a, b in zip(stamps, stamps[1:]))
    n = len(gaps)
    rank = math.ceil(percentile / 100.0 * n)        # 1-based, nearest rank
    if n == 0 or n - rank < samples_beyond:
        return None
    return gaps[rank - 1] * 1e3
