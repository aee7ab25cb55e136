"""The two metrics of the host's stalls, `host_stalls.train` and
`host_stall_longest_ms.train`: their files (in no list of BENCHMARK.json
until `per_layer` has room), the reader's arithmetic and the window's
clipping on a hand-made record, nothing from a program that keeps no such
record, and both computed by a traced rehearsal through a trial copy of
BENCHMARK.json that lists them."""

import json
import os
import subprocess
import sys
import types

from readers import host_stalls

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
READS = {"host_stalls.train": ("count", "intervals"),
         "host_stall_longest_ms.train": ("longest_excess_ms", "ms")}


def test_the_two_files_and_no_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    for name, (what, _) in READS.items():
        assert name not in listed
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "host_stalls"
        assert spec["args"] == {"what": what} and spec["what"]


def stall(uid, start, interval, median):
    return {"program_uid": uid, "start": start, "end": start + interval,
            "interval_s": interval, "median_s": median}


# the clocked window is [200, 236]; the main program is 1
STALLS = [
    stall(1, 190.0, 2.0, 0.06),         # warm-up's: before the window
    stall(1, 199.5, 1.0, 0.06),         # begins before the first stamp
    stall(1, 200.0, 0.125, 0.0625),     # from the first stamp on: read
    stall(7, 210.0, 5.0, 0.5),          # a bystander's (a reference's step)
    stall(1, 220.0, 2.25, 0.0625),      # read: the longest, 2187.5 ms over
    stall(1, 233.5, 2.5, 0.0625),       # ends with the last stamp: read
    stall(1, 235.0, 3.0, 0.0625),       # the drain: ends after the window
    stall(1, 240.0, 4.0, 0.0625),       # the profiler's start
]


def test_the_arithmetic_and_the_windows_clipping_on_a_hand_made_record():
    got = host_stalls.account(STALLS, 1, 200.0, 236.0)
    assert got["count"] == 3.0
    assert got["longest_excess_ms"] == 2437.5
    assert [r["start"] for r in got["records"]] == [200.0, 220.0, 233.5]
    # a window without one: 0 intervals, 0 ms
    none = host_stalls.account(STALLS, 1, 201.0, 219.0)
    assert none == {"count": 0.0, "longest_excess_ms": 0.0, "records": []}
    assert host_stalls.account([], 1, 200.0, 236.0)["count"] == 0.0


def _context(store):
    return {"obs": {"stamps": [200.0, 218.0, 236.0]},
            "system": types.SimpleNamespace(
                main=types.SimpleNamespace(_uid=1))}


def test_a_program_without_the_record_gives_nothing(monkeypatch):
    from paddle_tpu import observe
    monkeypatch.setattr(observe, "observatory", lambda: object())
    ctx = _context(None)
    for what, _ in READS.values():
        assert host_stalls.read(ctx, what) is None
    assert "host_stalls" not in ctx


def test_the_first_reading_prints_the_windows_records(monkeypatch, capsys):
    from paddle_tpu import observe
    from paddle_tpu.observe import steplog
    record = {
        **stall(1, 220.0, 2.25, 0.0625), "source": "executor", "step": 312,
        "where": "outside_run",
        "parts_s": {"run_entry": 0.0, "jit_call": 0.003, "other_runs": 0.0,
                    "outside_run": 2.247},
        "usage_over_s": 2.5, "thread_cpu_s": 0.004, "voluntary_switches": 2,
        "involuntary_switches": 0, "major_faults": 0, "minor_faults": 12,
        "process_cpu_s": 0.005, "gc_collections": [0, 0, 0],
        "gc_s": [0.0, 0.0, 0.0], "compiles": 0, "cache_hits": 0,
        "cache_misses": 0}
    store = types.SimpleNamespace(
        stalls=lambda: [stall(1, 190.0, 2.0, 0.06), record])
    monkeypatch.setattr(observe, "observatory", lambda: store)
    ctx = _context(store)
    assert host_stalls.read(ctx, "count") == 1.0
    assert host_stalls.read(ctx, "longest_excess_ms") == 2187.5
    out = capsys.readouterr().out
    assert out.count("ran long by the program's record, of 2 it keeps") == 1
    assert f"  20.0000 s into the window: {steplog.stall_line(record)}" in out
    assert "2247.0 outside run(), jit_call 3.0;" in out


def test_a_traced_rehearsal_computes_both_through_a_trial_copy(tmp_path):
    """The harness finds the metric files by name once a BENCHMARK.json
    lists them: a tree of links around a copy that does."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, (_, unit) in READS.items():
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "executor",
            "moves": "train_examples_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for part in ("benchmark", "paddle_tpu"):
        os.symlink(os.path.join(ROOT, part), tmp_path / part)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "transformer_base.seq256", "--seed", "2147483693",
         "--seconds", "2", "--trace", "1", "--tiny"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    computed = next(line for line in p.stdout.splitlines()
                    if line.startswith("REHEARSAL"))
    last = json.loads(p.stdout.strip().splitlines()[-1])
    for name, (_, unit) in READS.items():
        assert repr(name) in computed, name
        assert last["metrics"][name] == {"value": None, "unit": unit}
    assert p.stdout.count("ran long by the program's record") == 1
    # standard error's last lines stay the compared numbers
    assert p.stderr.strip().splitlines()[-1].startswith("benchmark: compared")
