"""A Fluid op's share of its roofline, %: `roofline.py`'s share over the
device time `trace_scopes.py` sums for the instructions the ops matching
`op` own (forward and grad ops alike, whatever implements them: Pallas calls
or XLA fusions), busiest device. `roofline.py` itself finds instructions by
their names, which an op lowered to XLA fusions does not have.

Operations and bytes per example come from the configuration's FLOP count
under `flops_key` and `bytes_key`; a step holds `batch` examples. Nothing
where the trace has no instruction of such an op (a program older than the
op), the count lacks a key, or there is no peak (a rehearsal). Reported as it
comes out, never clipped."""

from readers import roofline, trace_scopes


def read(ctx, op, flops_key, bytes_key):
    counts = ctx["flops"]
    if ctx["peaks"] is None or flops_key not in counts \
            or bytes_key not in counts:
        return None
    ms = trace_scopes.read(ctx, op=op)
    if not ms:
        return None
    batch = ctx["obs"]["batch"]
    value, bound = roofline.share(counts[flops_key] * batch,
                                  counts[bytes_key] * batch, ms / 1e3,
                                  ctx["peaks"])
    print(f"benchmark: roofline of the ops matching {op!r}: {value:.2f}% "
          f"({bound}-bound) over {ms:.3f} ms a step", flush=True)
    return value
