"""The step intervals of the clocked window that ran long, from the program's
own record of them (`paddle_tpu.observe.observatory().stalls()`: every
interval from the start of one run() of a compiled entry to the start of the
next that exceeded the entry's median by more than a quarter of it and by more
than 5 ms, with the part of it the host was in and what the thread and the
process did meanwhile), on `time.perf_counter()`: the clock of this
benchmark's own stamps. Only the main program's records that lie whole
between the window's first stamp and its last are read: the drain after the
window and the profiler's start are seconds outside run() by design.

`what` names the reading:

  count               the intervals kept
  longest_excess_ms   the longest of them, in ms over its median; 0 where
                      none was kept

The first reading of a run prints every record of the window to the log, in
the form of the program's own log line. A program that keeps no such record
(one from before it existed) gives nothing, and the metrics are left out.
"""


def account(stalls, main_uid, t_first, t_last):
    """Both readings, and the records they rest on, from plain records:
    `stalls` as the program keeps them (`program_uid`, `start`, `end`,
    `interval_s`, `median_s`). Kept free of the program so that it can be
    checked on a hand-made record."""
    inside = [r for r in stalls if r["program_uid"] == main_uid
              and t_first <= r["start"] and r["end"] <= t_last]
    excess = [r["interval_s"] - r["median_s"] for r in inside]
    return {"count": float(len(inside)),
            "longest_excess_ms": 1e3 * max(excess, default=0.0),
            "records": inside}


def read(ctx, what):
    got = ctx.get("host_stalls")        # made and printed once a run
    if got is None:
        from paddle_tpu import observe
        store = observe.observatory()
        if not hasattr(store, "stalls"):
            return None
        from paddle_tpu.observe.steplog import stall_line
        stamps = ctx["obs"]["stamps"]
        got = ctx["host_stalls"] = account(
            store.stalls(), ctx["system"].main._uid, stamps[0], stamps[-1])
        print(f"benchmark: {got['count']:.0f} step interval(s) of the "
              f"clocked window ran long by the program's record, of "
              f"{len(store.stalls())} it keeps; the longest "
              f"{got['longest_excess_ms']:.3f} ms over its median",
              flush=True)
        for record in got["records"]:
            print(f"benchmark:   {record['start'] - stamps[0]:9.4f} s into "
                  f"the window: {stall_line(record)}", flush=True)
    return got[what]
