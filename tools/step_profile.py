"""Capture a profiler trace of the framework transformer step and print
the top device ops by total self time (round-4 MFU hunt).

The framework's steps need no wrapper: every `exe.run` opens its own
`paddle_tpu:run` span and phase spans in the captured trace
(observe/steplog.py::RunSpans), and their table is printed next to the
device-op summary parsed from the same perfetto trace. The yardstick (plain
jax, no Program) is wrapped in `record_event`; either run leaves a host
timeline (`host_timeline.json`, chrome://tracing).

Usage: python tools/step_profile.py [--yardstick]
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def summarize(trace_dir, top=30):
    """Parse the perfetto trace.json.gz: sum durations per event name on
    the device tracks."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not paths:
        print("no trace.json.gz found under", trace_dir)
        return
    with gzip.open(sorted(paths)[-1], "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # the program's own host spans: one paddle_tpu:run per exe.run, its
    # phases inside it
    spans = {}
    for e in events:
        if e.get("ph") == "X" and e.get("name", "").startswith("paddle_tpu:"):
            spans.setdefault(e["name"], []).append(e.get("dur", 0))
    for name, durs in sorted(spans.items(), key=lambda kv: -sum(kv[1])):
        print(f"{name:60} {sum(durs) / 1e3:9.2f} {len(durs):5d} "
              f"(mean {sum(durs) / len(durs):.0f} us)")
    # the per-op device timeline is the thread named "XLA Ops" on the
    # /device:TPU process
    op_tracks = set()
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "thread_name"
                and e["args"].get("name") == "XLA Ops"):
            op_tracks.add((e["pid"], e["tid"]))
    total = {}
    count = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        if (e.get("pid"), e.get("tid")) not in op_tracks:
            continue
        name = e.get("name", "?")
        total[name] = total.get(name, 0.0) + e.get("dur", 0)
        count[name] = count.get(name, 0) + 1
    items = sorted(total.items(), key=lambda kv: -kv[1])
    grand = sum(total.values())
    print(f"{'op':60} {'total ms':>9} {'n':>5} {'%':>5}")
    for name, dur in items[:top]:
        print(f"{name[:60]:60} {dur / 1e3:9.2f} {count[name]:5d} "
              f"{100 * dur / grand:5.1f}")
    print(f"{'TOTAL (device events)':60} {grand / 1e3:9.2f}")


def main():
    import jax

    from paddle_tpu import profiler as prof

    trace_dir = tempfile.mkdtemp(prefix="stepprof_")
    prof.reset_profiler()
    if "--yardstick" in sys.argv:
        from tools import yardstick_transformer as y
        params = y.init_params(0)
        opt = y.adam_init(params)
        batch = y.make_batch()
        key = jax.random.key(0)
        params, opt, loss = y.train_step(params, opt, batch, key)
        np.asarray(loss)
        prof.start_profiler(profile_path=trace_dir)
        for i in range(3):
            with prof.record_event("train_step"):
                params, opt, loss = y.train_step(params, opt, batch,
                                                 jax.random.fold_in(key, i))
        with prof.record_event("fetch_sync"):
            np.asarray(loss)
        prof.stop_profiler()
    else:
        from tools.hlo_diff import framework_step
        _, run, out = framework_step()
        np.asarray(out[0])
        prof.start_profiler(profile_path=trace_dir)
        for _ in range(3):
            out = run()     # its paddle_tpu:run span is in the trace
        with prof.record_event("fetch_sync"):
            np.asarray(out[0])
        prof.stop_profiler()
    print("trace dir:", trace_dir)
    host_path = os.path.join(trace_dir, "host_timeline.json")
    prof.export_chrome_tracing(host_path)
    print("host timeline:", host_path)
    prof.print_host_events()
    summarize(trace_dir)


if __name__ == "__main__":
    main()
