"""The one table of published per-chip peaks, keyed by jax's `device_kind`."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind):
    """Published peaks of one chip. A device that is not in the table is an
    error, never a default: a utilization over a guessed peak means nothing."""
    with open(_PATH) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in {_PATH}; add its published "
            f"peaks with their source before measuring on it")
    return table[device_kind]
