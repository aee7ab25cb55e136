"""The eight set-up metrics: found by name with their files, computed by a
traced rehearsal of one cell, and the reader's arithmetic on a hand-made
record whose every expected number is worked out in the comments. With a
program that keeps no set-up store the reader gives nothing."""

import json
import os
import subprocess
import sys

import pytest

from readers import setup_phases

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
READS = {
    "setup_program_build_s.train": "program_build_s",
    "setup_infer_shapes_s.train": "infer_shapes_s",
    "setup_startup_run_s.train": "startup_run_s",
    "setup_startup_compiles.train": "startup_compiles",
    "setup_cache_misses.train": "cache_misses",
    "first_runs_outside_compile_s.train": "first_runs_outside_compile_s",
    "setup_in_program_pct.train": "in_program_pct",
}
NAMES = sorted(READS) + ["first_step_cache_retrieval_s.train"]


def test_the_eight_entries_and_their_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # appended behind the entries that were there, in the issue's order (a
    # later PR appends behind them: their place from the end is not held)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("setup_program_build_s.train")
    assert first > names.index("moe_token_sum_kernel_ms.train")
    assert names[first:first + 8] == [
        "setup_program_build_s.train", "setup_infer_shapes_s.train",
        "setup_startup_run_s.train", "setup_startup_compiles.train",
        "first_step_cache_retrieval_s.train", "setup_cache_misses.train",
        "first_runs_outside_compile_s.train", "setup_in_program_pct.train"]
    for name in NAMES:
        m = by_name[name]
        assert m["moves"] == "setup_s" and "workloads" not in m
        assert m["layer"] in ("executor (set-up)", "program IR (set-up)")
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        if name in READS:
            assert spec["reader"] == "setup_phases"
            assert spec["args"] == {"what": READS[name]}
        else:       # a data file over the reader that was there
            assert spec["reader"] == "compile_stages"
            assert spec["args"] == {"stage": "cache_retrieval"}


def test_a_traced_rehearsal_computes_all_eight():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "transformer_base.seq256", "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--tiny"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    computed = next(line for line in p.stdout.splitlines()
                    if line.startswith("REHEARSAL"))
    last = json.loads(p.stdout.strip().splitlines()[-1])
    for name in NAMES:
        # a cold rehearsal retrieves nothing: 0 s, still a reading
        assert repr(name) in computed, name
        assert last["metrics"][name]["value"] is None
    # the log carries the timeline once, and the sums with it
    assert p.stdout.count("benchmark: set-up timeline") == 1
    assert "paddle_tpu:program_build" in p.stdout
    assert p.stdout.count("benchmark: set-up in the program: build") == 1


def phase(i, name, uid, start, end, parent=None, **detail):
    return {"id": i, "name": name, "program_uid": uid, "parent": parent,
            "start": start, "end": end, "detail": detail}


def event(uid, cause, misses, **stage_intervals):
    return {"program_uid": uid, "cause": cause, "cache_hits": 0,
            "cache_misses": misses, "stage_intervals": stage_intervals,
            "backend_compiles": len(stage_intervals.get("backend", ()))}


# process start 100, warm-up ends at 140: setup_s = 40. Main program 1,
# startup program 2, a bystander 7.
PHASES = [
    # the main program's build [110, 114) with a nested guard of its own
    # [111, 112) (not counted twice) and minimize inside it
    phase(1, "paddle_tpu:program_build", 1, 110.0, 114.0,
          infer_shapes_s=3.5, infer_shapes_calls=200, eager_cache_misses=1),
    phase(2, "paddle_tpu:program_build", 1, 111.0, 112.0, parent=1,
          infer_shapes_s=0.5),
    phase(3, "paddle_tpu:minimize", 1, 113.0, 113.5, parent=1),
    # another program's build: nobody's
    phase(4, "paddle_tpu:program_build", 7, 114.0, 114.5,
          infer_shapes_s=0.25, eager_cache_misses=5),
    # the startup run [115, 117), two eager compiles in its bind
    phase(5, "paddle_tpu:run", 2, 115.0, 117.0, source="executor", step=0,
          eager_compiles=2, eager_cache_misses=1),
    phase(6, "paddle_tpu:jit_call", 2, 115.5, 117.0, parent=5),
    # the main program's first run [120, 130) and second [130, 133)
    phase(7, "paddle_tpu:run", 1, 120.0, 130.0, source="executor", step=0),
    phase(8, "paddle_tpu:jit_call", 1, 121.0, 129.5, parent=7),
    phase(9, "paddle_tpu:run", 1, 130.0, 133.0, source="executor", step=1),
    # after warm-up (a reference's program): not this set-up's
    phase(10, "paddle_tpu:program_build", 9, 150.0, 151.0, infer_shapes_s=1.0),
    phase(11, "paddle_tpu:run", 1, 160.0, 161.0, eager_cache_misses=3),
]
EVENTS = [
    event(2, "first_call", 1, trace=[[115.5, 115.75]],
          backend=[[116.0, 116.5], [116.5, 116.75]]),
    # the step: trace [121, 123), lower [122.5, 125) overlapping it, backend
    # [125, 129) holding its cache read; built again in the second run,
    # backend [131, 132)
    event(1, "first_call", 2, trace=[[121.0, 123.0]], lower=[[122.5, 125.0]],
          backend=[[125.0, 129.0], [131.0, 132.0]],
          cache_retrieval=[[125.5, 126.0]]),
    event(7, "first_call", 9, backend=[[114.0, 114.25]]),
]


def test_the_arithmetic_on_a_hand_made_record():
    got = setup_phases.account(PHASES, EVENTS, 1, 2, 100.0, 140.0)
    assert got["program_build_s"] == 4.0            # the outer guard alone
    assert got["infer_shapes_s"] == 3.5
    assert got["startup_run_s"] == 2.0
    # the event's two backend intervals and the run's two eager compiles
    assert got["startup_compiles"] == 4.0
    # events 1 + 2, the build's 1, the startup run's 1; not program 7's,
    # not the run after warm-up
    assert got["cache_misses"] == 5.0
    assert got["main_run_s"] == 13.0
    # stages cover [121, 129) and [131, 132) = 9 of the 13
    assert got["first_runs_outside_compile_s"] == 4.0
    assert got["in_program_s"] == 4.0 + 2.0 + 13.0
    assert got["in_program_pct"] == pytest.approx(100 * 19.0 / 40.0)
    # the three parts are disjoint: their sum is the union
    assert got["program_build_s"] + got["startup_run_s"] \
        + got["main_run_s"] == got["in_program_s"]


def test_union_and_clip():
    assert setup_phases.union_s([]) == 0.0
    assert setup_phases.union_s([(3, 5), (1, 2), (4, 7), (6, 6.5)]) == 5.0
    assert setup_phases.clip([(0, 10), (12, 13)], [(2, 4), (9, 12.5)]) == [
        (2, 4), (9, 10), (12, 12.5)]


def test_process_start_and_end_of_warm_up_come_from_the_stamps():
    obs = {"stamps": [140.0, 140.5, 141.0], "setup_s": 40.0}
    assert setup_phases.window(obs) == (100.0, 140.0)


@pytest.mark.parametrize("start,end", [(99.0, 101.0), (139.0, 141.0)])
def test_a_record_across_either_end_is_an_error(start, end):
    bad = PHASES + [phase(12, "paddle_tpu:run", 1, start, end)]
    with pytest.raises(RuntimeError, match="does not lie between"):
        setup_phases.account(bad, EVENTS, 1, 2, 100.0, 140.0)


def test_the_timeline_is_printed_from_process_start(capsys):
    got = setup_phases.account(PHASES, EVENTS, 1, 2, 100.0, 140.0)
    setup_phases.print_timeline(PHASES, EVENTS, got, 100.0, 140.0)
    out = capsys.readouterr().out
    assert "warm-up ends at 40.000" in out
    assert "  10.000 -   14.000  paddle_tpu:program_build program 1" in out
    # an event's stages, run by run
    assert "backend 4.000 s in 25.000 - 29.000" in out
    assert "backend 1.000 s in 31.000 - 32.000" in out
    assert "program 9" not in out       # after warm-up
    assert "= 19.000 s; their union 19.000 s = 47.50% of 40.000 s" in out


def test_a_program_without_the_store_gives_nothing(monkeypatch):
    from paddle_tpu import observe

    class Old:
        def events(self):
            return []

    monkeypatch.setattr(observe, "observatory", lambda: Old())
    ctx = {"obs": {"stamps": [140.0], "setup_s": 40.0}, "system": None}
    for what in READS.values():
        assert setup_phases.read(ctx, what) is None
