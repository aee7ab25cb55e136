"""The share of its roofline of what was built under a name scope, %:
`roofline_by_op.py` with a `scope` beside `op`. An operator that is built
from ops other layers have too (LFM2's gated short convolution: an
`rms_norm`, two `mul`s, three `slice`s, two `elementwise_mul`s, a
`causal_conv1d`, an `elementwise_add`, under `l<p>.conv`) cannot be selected
by op type, and XLA fuses its small ops into its large ones' fusions, so no
op type holds a part of it and nothing else: it is selected by the scope it
was built under, and by op type as well where a metric wants a part.
`roofline.py`'s share over the device time `trace_scopes.py` sums for the
instructions that the ops under the scopes matching `scope` own (of those
matching `op`, where given; forward and grad ops alike, whatever implements
them: Pallas calls or XLA fusions), busiest device.

Operations and bytes per example come from the configuration's FLOP count
under `flops_key` and `bytes_key`; a step holds `batch` examples. Nothing
where the trace has no instruction under such a scope (a program older than
the scope), the count lacks a key, or there is no peak (a rehearsal).
Reported as it comes out, never clipped."""

from readers import roofline, trace_scopes


def read(ctx, scope, flops_key, bytes_key, op=None):
    counts = ctx["flops"]
    if ctx["peaks"] is None or flops_key not in counts \
            or bytes_key not in counts:
        return None
    ms = trace_scopes.read(ctx, op=op, scope=scope)
    if not ms:
        return None
    batch = ctx["obs"]["batch"]
    value, bound = roofline.share(counts[flops_key] * batch,
                                  counts[bytes_key] * batch, ms / 1e3,
                                  ctx["peaks"])
    print(f"benchmark: roofline of the ops matching {op!r} under {scope!r}: "
          f"{value:.2f}% ({bound}-bound) over {ms:.3f} ms a step", flush=True)
    return value
