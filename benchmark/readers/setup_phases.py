"""Where a set-up goes, from the program's own record of it
(`paddle_tpu.observe.observatory()`): the `Phase`s it keeps of building a
Program (`paddle_tpu:program_build`, with the seconds inside shape
inference) and of every run() that bound or compiled (`paddle_tpu:run` and
its phases), beside its compile events, all on `time.perf_counter()`: the
clock of this benchmark's own stamps. Process start is `stamps[0] - setup_s`
and warm-up ends at `stamps[0]`; only what lies between is read, and a
record that straddles either end is an error, not a number.

`what` names the reading:

  program_build_s           the main program's build phase
  infer_shapes_s            of that, inside `registry.infer_op_shapes`
  startup_run_s             the startup program's recorded runs, summed
  startup_compiles          backend compiles jax reported inside those runs:
                            their events' `backend_compiles` and what fell
                            outside the jitted call (`eager_compiles`)
  cache_misses              persistent-cache misses on every compile event,
                            run and build of both programs: 0 = warm
  first_runs_outside_compile_s
                            the main program's recorded runs less the union
                            of their compile stages: bind, state gather,
                            load and dispatch, write-back
  in_program_pct            the union of the build and every recorded run of
                            both programs, over `setup_s`

The first reading of a run prints the timeline to the log. A program that
keeps no such record (one from before it existed) gives nothing, and the
metrics are left out.
"""

BUILD = "paddle_tpu:program_build"
RUN = "paddle_tpu:run"


def union_s(intervals):
    """Seconds covered by `[(start, end)]`, overlaps counted once."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def clip(intervals, windows):
    """The parts of `intervals` that lie inside one of `windows`."""
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            for w0, w1 in windows if max(s, w0) < min(e, w1)]


def window(obs):
    """(process start, end of warm-up) on `perf_counter()`."""
    return obs["stamps"][0] - obs["setup_s"], obs["stamps"][0]


def within(phases, t_start, t_warm):
    """The phases that began before the end of warm-up; every one of them
    has to lie between process start and there."""
    kept = [p for p in phases if p["start"] < t_warm]
    for p in kept:
        if not t_start <= p["start"] <= p["end"] <= t_warm:
            raise RuntimeError(
                f"benchmark: {p['name']} of program {p['program_uid']} "
                f"[{p['start']}, {p['end']}] does not lie between process "
                f"start {t_start} and the end of warm-up {t_warm}")
    return kept


def account(phases, events, main_uid, startup_uid, t_start, t_warm):
    """Every reading from plain records: `phases` as `Phase.as_dict()` gives
    them, `events` as `{"program_uid", "backend_compiles", "cache_misses",
    "stage_intervals"}`. Kept free of the program so that it can be checked
    on a hand-made record."""
    phases = within(phases, t_start, t_warm)
    both = (main_uid, startup_uid)
    builds = [p for p in phases
              if p["name"] == BUILD and p["program_uid"] == main_uid]
    outer = [p for p in builds
             if not any(q is not p and q["start"] <= p["start"]
                        and p["end"] <= q["end"] for q in builds)]
    runs = {uid: [p for p in phases
                  if p["name"] == RUN and p["program_uid"] == uid]
            for uid in both}
    spans = {uid: [(p["start"], p["end"]) for p in runs[uid]] for uid in both}
    mine = [e for e in events if e["program_uid"] in both]
    stages = [tuple(i) for e in mine if e["program_uid"] == main_uid
              for spans_ in e["stage_intervals"].values() for i in spans_]
    main_run_s = sum(e - s for s, e in spans[main_uid])
    in_program_s = union_s([(p["start"], p["end"]) for p in outer]
                           + spans[main_uid] + spans[startup_uid])
    return {
        "program_build_s": union_s([(p["start"], p["end"]) for p in outer]),
        "infer_shapes_s": sum(p["detail"].get("infer_shapes_s", 0.0)
                              for p in outer),
        "startup_run_s": sum(e - s for s, e in spans[startup_uid]),
        "startup_compiles": float(
            sum(e["backend_compiles"] for e in mine
                if e["program_uid"] == startup_uid)
            + sum(p["detail"].get("eager_compiles", 0)
                  for p in runs[startup_uid])),
        "cache_misses": float(
            sum(e["cache_misses"] for e in mine)
            + sum(p["detail"].get("eager_cache_misses", 0) for p in phases
                  if p["program_uid"] in both)),
        "main_run_s": main_run_s,
        "first_runs_outside_compile_s":
            main_run_s - union_s(clip(stages, spans[main_uid])),
        "in_program_s": in_program_s,
        "in_program_pct": 100.0 * in_program_s / (t_warm - t_start),
    }


def records(ctx):
    """(phases, events) as plain records, or None where the program keeps
    no set-up store."""
    from paddle_tpu import observe
    store = observe.observatory()
    if not hasattr(store, "phases"):
        return None
    return ([p.as_dict() for p in store.phases()],
            [e.as_dict() for e in store.events()])


def print_timeline(phases, events, got, t_start, t_warm):
    """The log's copy: every phase with its start and end from process
    start, a run's compile stages inside it, and the sums."""
    phases = sorted(within(phases, t_start, t_warm), key=lambda p: p["start"])
    depth = {}
    print(f"benchmark: set-up timeline from the program's record, seconds "
          f"from process start (warm-up ends at {t_warm - t_start:.3f}):",
          flush=True)
    for p in phases:
        d = depth[p["id"]] = depth.get(p["parent"], 0) + 1
        if d > 1 and p["end"] - p["start"] < 1e-3 and not p["detail"]:
            continue                    # a phase of microseconds
        print(f"benchmark: {'  ' * d}{p['start'] - t_start:8.3f} - "
              f"{p['end'] - t_start:8.3f}  {p['name']} program "
              f"{p['program_uid']}" + (f"  {p['detail']}" if p["detail"]
                                       else ""), flush=True)
        if p["name"] != RUN:
            continue
        here = [(p["start"], p["end"])]
        for e in events:
            if e["program_uid"] != p["program_uid"]:
                continue
            inside = {stage: clip([tuple(i) for i in spans], here)
                      for stage, spans in e["stage_intervals"].items()}
            if any(inside.values()):
                print(f"benchmark: {'  ' * (d + 1)}{e['cause']}: " + ", ".join(
                    f"{stage} {union_s(spans):.3f} s in "
                    f"{spans[0][0] - t_start:.3f} - "
                    f"{spans[-1][1] - t_start:.3f}"
                    for stage, spans in inside.items() if spans)
                    + f"; the event's cache hits {e['cache_hits']}, misses "
                    f"{e['cache_misses']}", flush=True)
    parts = (got["program_build_s"] + got["startup_run_s"]
             + got["main_run_s"])
    print(f"benchmark: set-up in the program: build "
          f"{got['program_build_s']:.3f} s (shape inference "
          f"{got['infer_shapes_s']:.3f} s) + startup runs "
          f"{got['startup_run_s']:.3f} s + main runs "
          f"{got['main_run_s']:.3f} s (outside compile stages "
          f"{got['first_runs_outside_compile_s']:.3f} s) = {parts:.3f} s; "
          f"their union {got['in_program_s']:.3f} s = "
          f"{got['in_program_pct']:.2f}% of {t_warm - t_start:.3f} s; "
          f"{got['startup_compiles']:.0f} startup compile(s), "
          f"{got['cache_misses']:.0f} cache miss(es)", flush=True)


def read(ctx, what):
    got = ctx.get("setup_account")      # made and printed once a run
    if got is None:
        found = records(ctx)
        if found is None:
            return None
        phases, events = found
        t_start, t_warm = window(ctx["obs"])
        system = ctx["system"]
        got = ctx["setup_account"] = account(
            phases, events, system.main._uid, system.startup._uid,
            t_start, t_warm)
        print_timeline(phases, events, got, t_start, t_warm)
    return got[what]
