"""From a profiler trace to numbers: device events, busy union, idle gaps,
per-name sums. The one reduction every PR's per-layer metrics go through.

Everything works on a plain list of `Event`s, so it is checked on a
hand-made list (`tests/test_trace_reduce.py`); `load_xplane` turns the
`.xplane.pb` that `jax.profiler.trace` writes into that list with nothing
but jax (`jax.profiler.ProfileData`).

    python3 benchmark/trace_reduce.py <trace dir or .xplane.pb>    # a look by hand
"""

import collections
import glob
import os
import re
import sys

# plane: "/device:TPU:0", "/host:CPU", ...   line: "XLA Ops", a thread name, ...
Event = collections.namedtuple("Event", "plane line name start_ns dur_ns")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load_xplane(path, keep_line=None):
    """Every event of the trace as an `Event`. `keep_line(plane, line)`
    filters lines before their events are read (a trace holds millions)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    events = []
    for plane in data.planes:
        for line in plane.lines:
            if keep_line is not None and not keep_line(plane.name, line.name):
                continue
            for e in line.events:
                events.append(Event(plane.name, line.name, e.name,
                                    int(e.start_ns), int(e.duration_ns)))
    return events


def device_ops(events):
    """{device index: [(start, end, name), ...] sorted by start} from the
    "XLA Ops" line of each TPU plane: one event per executed HLO op."""
    per = collections.defaultdict(list)
    for e in events:
        m = DEVICE_PLANE.match(e.plane)
        if m and e.line == OPS_LINE and e.dur_ns > 0:
            per[int(m.group(1))].append(
                (e.start_ns, e.start_ns + e.dur_ns, e.name))
    return {d: sorted(v) for d, v in per.items()}


def busy_union(intervals):
    """Merged (start, end) list and its total length: overlapping events on
    one track (an op and the fusion it belongs to, async pairs) count once."""
    merged = []
    for start, end, *_ in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged, sum(e - s for s, e in merged)


def device_summary(events):
    """Per device: the window (first op start to last op end), busy ns (the
    union), the idle gaps inside the window, and ns summed by op name."""
    out = {}
    for dev, ops in device_ops(events).items():
        merged, busy = busy_union(ops)
        gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        by_name = collections.defaultdict(int)
        for start, end, name in ops:
            by_name[name] += end - start
        out[dev] = {"window_ns": merged[-1][1] - merged[0][0],
                    "start_ns": merged[0][0], "busy_ns": busy,
                    "gaps": gaps, "by_name": dict(by_name), "n_ops": len(ops)}
    return out


def busiest(summary):
    """The device whose busy time is largest: the one a step waits for."""
    return max(summary, key=lambda d: summary[d]["busy_ns"]) if summary \
        else None


def sum_matching(by_name, pattern):
    """ns over the op names that `pattern` (a regular expression) finds, and
    the names it found."""
    rx = re.compile(pattern)
    hit = {n: ns for n, ns in by_name.items() if rx.search(n)}
    return sum(hit.values()), sorted(hit)


_LHS = re.compile(r"^(%?[\w.\-]+?)(?:\.\d+)? = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(text):
    """An op's event name is its whole HLO line. The short form keeps the
    result's name without its number, the opcode and the first result shape:
    `%jvp__ custom-call bf16[768,256,64]`. Ops that differ only by number
    share it."""
    lhs = _LHS.match(text)
    if not lhs:
        return text[:80]
    rest = text[lhs.end():]
    opcode, shape = _OPCODE.search(rest), _SHAPE.search(rest)
    return " ".join(p for p in (lhs.group(1),
                                opcode.group(1) if opcode else "",
                                shape.group(0) if shape else "") if p)


def top_groups(by_name, n=10):
    """[(short name xCOUNT, ns)] of the op groups that took most time."""
    total, count = collections.defaultdict(int), collections.Counter()
    for name, ns in by_name.items():
        key = short_name(name)
        total[key] += ns
        count[key] += 1
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(f"{k} x{count[k]}" if count[k] > 1 else k, ns) for k, ns in top]


def host_spans(events, prefix):
    """[(start, end, name)] of the host events whose name starts with
    `prefix`: the benchmark's own `TraceAnnotation`s, on the trace's clock."""
    return sorted((e.start_ns, e.start_ns + e.dur_ns, e.name)
                  for e in events
                  if not DEVICE_PLANE.match(e.plane)
                  and e.name.startswith(prefix))


def attribute_gaps(gaps, spans, default="between calls"):
    """Each idle gap named after the host span that covers most of it."""
    out = []
    for g0, g1 in gaps:
        best, best_overlap = default, 0
        for s0, s1, name in spans:
            overlap = min(g1, s1) - max(g0, s0)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        out.append((best, g1 - g0))
    return out


def main(argv):
    events = load_xplane(argv[1])
    lines = collections.Counter((e.plane, e.line) for e in events)
    print("planes / lines / events:")
    for (plane, line), n in sorted(lines.items()):
        print(f"  {plane:28} {line:40} {n}")
    summary = device_summary(events)
    for dev, s in sorted(summary.items()):
        print(f"device {dev}: window {s['window_ns'] / 1e6:.3f} ms, busy "
              f"{s['busy_ns'] / 1e6:.3f} ms, {s['n_ops']} ops, "
              f"{len(s['gaps'])} gaps")
        for name, ns in top_groups(s["by_name"], 40):
            print(f"    {ns / 1e6:10.3f} ms  {name}")
        seen = set()
        for name in s["by_name"]:       # one whole line per opcode
            opcode = _OPCODE.search(name)
            if opcode and opcode.group(1) not in seen:
                seen.add(opcode.group(1))
                print(f"    e.g. {name[:1500]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
