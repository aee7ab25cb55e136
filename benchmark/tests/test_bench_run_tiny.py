"""`run.py --tiny` runs every cell file under `workloads/` end to end on the
CPU (4 virtual devices), both ways, and its last line has the contract's
keys. (A cell file that BENCHMARK.json does not list yet is a prepared cell
whose chip proof is still open; its harness path is rehearsed all the same.)
Also: the data files agree with each other, and a measurement run (no
--tiny) on a machine without a TPU exits non-zero with no result line."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = sorted(f[:-len(".json")]
               for f in os.listdir(os.path.join(BENCH, "workloads")))


def run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *argv], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_tiny_rehearsal(cell, trace):
    p = run("--workload", cell, "--seed", "2147483659", "--seconds", "2",
            "--trace", str(trace), "--tiny")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL" in p.stdout
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in BENCHMARK[kind]
               if cell in m.get("workloads", CELLS)}
    assert set(last["metrics"]) <= allowed
    # a rehearsal never carries a number under a device metric's name
    assert all(m["value"] is None for m in last["metrics"].values())
    if not trace:
        assert "setup_s" in last["metrics"]
        assert "train_examples_per_s" in last["metrics"]


def test_measurement_without_a_tpu_exits_nonzero_with_no_result():
    p = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode != 0
    assert "needs" in p.stderr and "TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_name_in_benchmark_json_has_its_files():
    for c in BENCHMARK["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in BENCHMARK["workloads"]:
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["batch"] > 0 and isinstance(traffic["build_args"], dict)
        assert os.path.isfile(os.path.join(
            BENCH, "generators", traffic["generator"] + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert len(set(pairs)) == len(pairs), "a pair of config and traffic twice"
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        assert m.get("moves", "train_examples_per_s") in e2e
