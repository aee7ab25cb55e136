"""A kernel's share of its roofline, %: the least time the chip could take
for the kernel's work of one step, over the device time the trace shows for
the ops whose name matches `pattern` (a regular expression), busiest device.

The least time is the larger of operations / peak FLOP/s and bytes / peak
HBM bytes/s (`peaks.json`). Operations and bytes per example come from the
configuration's FLOP count (`flops/<name>.py`), under the keys `flops_key`
and `bytes_key` name; a step holds `batch` examples. Nothing where the trace has no
matching op, the count lacks a key, or there is no peak (a rehearsal). A
share above 100% means the count is too high or the pattern misses part of
the work: it is reported as it comes out, never clipped."""

import trace_reduce


def share(ops, nbytes, device_s, peaks):
    """(share in %, which bound: "compute" or "memory")."""
    compute_s = ops / peaks["bf16_flops_per_s"]
    memory_s = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if compute_s >= memory_s else "memory"
    return 100.0 * max(compute_s, memory_s) / device_s, bound


def read(ctx, pattern, flops_key, bytes_key):
    trace = ctx["trace"]()
    counts = ctx["flops"]
    if trace is None or trace["device"] is None or ctx["peaks"] is None \
            or flops_key not in counts or bytes_key not in counts:
        return None
    by_name = trace["summary"][trace["device"]]["by_name"]
    ns, names = trace_reduce.sum_matching(by_name, pattern)
    if not names or ns <= 0:
        return None
    batch = ctx["obs"]["batch"]
    value, bound = share(counts[flops_key] * batch,
                         counts[bytes_key] * batch,
                         ns / 1e9 / trace["steps"], ctx["peaks"])
    print(f"benchmark: roofline of {len(names)} op(s) matching {pattern!r}: "
          f"{value:.2f}% ({bound}-bound)", flush=True)
    return value
