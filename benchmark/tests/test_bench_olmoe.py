"""The OLMoE configuration's own pieces of the yardstick: its FLOP count
against one worked out by hand, the roofline reader and the cell's trace
patterns on a hand-made event list, the reference kept identical to the
tests' copy, and `reference_check.py --tiny`. (`run.py --tiny` of the cell,
both ways, is `test_bench_run_tiny.py`'s, which runs every file under
`workloads/`.)"""

import filecmp
import importlib
import json
import os
import subprocess
import sys

import pytest

import trace_reduce as tr
from readers import roofline, trace_calls, trace_ops
from trace_reduce import Event

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def flops(seq_len=4096):
    c = load("configs", "olmoe_1b_7b.json")
    fn = importlib.import_module("flops." + c["flops"]).flops_per_example
    return fn(**dict(c["build_args"], seq_len=seq_len))


def test_olmoe_flops_by_hand():
    # multiply-adds a token, one layer: q, k, v, o 4 * 2048^2 = 16,777,216;
    # attention, causal half: scores and context T * d = 4096 * 2048 =
    # 8,388,608; eight experts of three 2048 x 1024 products 8 * 3 *
    # 2,097,152 = 50,331,648; router 2048 * 64 = 131,072
    layer = 16_777_216 + 8_388_608 + 50_331_648 + 131_072
    assert layer == 75_628_544
    head = 2048 * 50304
    assert head == 103_022_592
    forward = 2 * 4096 * (1 * layer + head)
    got = flops()
    assert got["forward"] == forward == 1_463_510_106_112
    assert got["forward_backward"] == 3 * forward       # 4.39 TFLOP a step
    assert got["forward_backward"] / 1e12 == pytest.approx(4.39, abs=5e-3)
    assert got["positions_per_example"] == 4096
    # what depth 1 does to the shares (16 layers in brackets)
    assert got["decoder_layers_share"] == pytest.approx(0.423, abs=1e-3)
    assert got["experts_share"] == pytest.approx(0.282, abs=1e-3)
    assert got["head_share"] == pytest.approx(0.577, abs=1e-3)
    full = 16 * layer + head
    assert 16 * layer / full == pytest.approx(0.92, abs=5e-3)
    assert 16 * 50_331_648 / full == pytest.approx(0.61, abs=5e-3)
    assert head / full == pytest.approx(0.08, abs=5e-3)
    # active, not resident: all 64 experts would be 8 times the experts
    assert got["multiply_adds_per_token_layer"]["experts"] == 50_331_648


def test_grouped_matmul_counts_by_hand():
    # nine products (3 projections x forward, input grad, weight grad), each
    # 32768 x 2048 x 1024 multiply-adds; each touches 32768*2048 +
    # 32768*1024 + 64*2048*1024 = 234,881,024 bf16 values once
    got = flops()
    assert got["expert_matmul_flops"] == 9 * 2 * 32768 * 2048 * 1024
    assert got["expert_matmul_bytes"] == 9 * 234_881_024 * 2
    # one product: 0.698 ms of compute against 0.574 ms of HBM traffic
    assert 2 * 32768 * 2048 * 1024 / 197e12 == pytest.approx(0.698e-3, rel=1e-3)
    assert 234_881_024 * 2 / 819e9 == pytest.approx(0.574e-3, rel=1e-3)


OPS = tr.OPS_LINE
D0 = "/device:TPU:0"
GROUPED = ("%{name} = bf16[{shape}]{{1,0:T(8,128)(2,1)}} "
           "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"")
NAMES = {
    "fwd": GROUPED.format(name="gmm.5", shape="32768,1024"),
    "dx": GROUPED.format(name="gmm.7", shape="32768,2048"),
    "dw": GROUPED.format(name="tgmm.2", shape="64,2048,1024"),
    "meta": "%fusion.32 = (s32[65]{0}, s32[127]{0}) fusion(%gs), kind=kLoop, "
            "calls=%fused_computation.32",
    "sort": "%sort.22 = (s32[32768]{0:T(1024)}, s32[32768]{0:T(1024)S(1)}) "
            "sort(%reshape.103, %iota.10), dimensions={0}, is_stable=true",
    "topk": "%sort = (f32[4096,64]{0,1}, s32[4096,64]{0,1}) sort(%p, %i)",
    "gather": "%fusion.3 = bf16[32768,2048]{1,0:T(8,128)(2,1)} "
              "fusion(%x, %order), kind=kCustom, calls=%fused_computation.3",
    "embed": "%fusion.2 = f32[4096,2048]{1,0:T(8,128)S(1)} fusion(%w, %ids),"
             " kind=kCustom, calls=%fused_computation.2",
    "scatter": "%fusion.7 = f32[50304,2048]{1,0:T(8,128)} fusion(%z, %ids, "
               "%g), kind=kCustom, calls=%fused_computation.7",
    "flash": "%jvp_flash_fwd_.1 = (bf16[16,4096,128]{2,1,0}, "
             "f32[16,1,4096]{2,1,0}) custom-call(%s, %q, %k, %v), "
             "custom_call_target=\"tpu_custom_call\"",
    "dq": "%jvp_flash_dq_.1 = bf16[16,4096,128]{2,1,0} custom-call(%s, %q), "
          "custom_call_target=\"tpu_custom_call\"",
}
# two steps; per step: forward 1.0 ms, input grad 2.0 ms, weight grad
# 1.5 ms, metadata 0.01 ms, sort 0.3 ms, top-k 0.2 ms, gather 0.7 ms,
# embedding gather 0.05 ms, scatter 0.4 ms, flash 3 ms + 2 ms
MS = {"fwd": 1.0, "dx": 2.0, "dw": 1.5, "meta": 0.01, "sort": 0.3,
      "topk": 0.2, "gather": 0.7, "embed": 0.05, "scatter": 0.4,
      "flash": 3.0, "dq": 2.0}


def ctx():
    events, t = [], 0
    for _ in range(2):
        for key, ms in MS.items():
            events.append(Event(D0, OPS, NAMES[key], t, int(ms * 1e6)))
            t += int(ms * 1e6)
    summary = tr.device_summary(events)
    trace = {"summary": summary, "device": tr.busiest(summary), "steps": 2}
    return {"trace": lambda: trace, "obs": {"batch": 1},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "flops": flops()}


def metric(name):
    spec = load("metrics", name + ".json")
    reader = {"trace_ops": trace_ops, "trace_calls": trace_calls,
              "roofline": roofline}[spec["reader"]]
    return reader.read(ctx(), **spec["args"])


def test_cell_patterns_on_a_hand_made_trace():
    assert metric("moe_expert_matmul_ms.train") == pytest.approx(4.5)
    # the sort of the assignments and the expert layer's gather; not the
    # router's top-k, the embedding's gather or its scatter-add
    assert metric("moe_dispatch_ms.train") == pytest.approx(1.0)
    assert metric("attention_kernels_ms.train") == pytest.approx(5.0)
    # %gmm: the forward and the input-gradient call site, not %tgmm
    assert metric("moe_fwd_matmul_calls.train") == 2.0


def test_dispatch_pattern_rows_are_the_cells_assignments():
    """`moe_dispatch_ms.train` tells the expert layer's gathers from the
    embedding's by their rows, written into the pattern: batch x seq_len x
    top_k where a gather reads the expert layer's layout, and that plus
    experts x the program's row tile where it writes it. Every cell that
    lists the metric has to have those, or the metric would read nothing
    (or another op) there."""
    from paddle_tpu.ops.moe import ROW_TILE
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric = next(m for m in bench["per_layer"]
                  if m["name"] == "moe_dispatch_ms.train")
    pattern = load("metrics", "moe_dispatch_ms.train.json")["args"]["pattern"]
    assert metric["workloads"]
    for name in metric["workloads"]:
        cell = load("workloads", name + ".json")
        traffic = load("traffic", cell["traffic"] + ".json")
        args = load("configs", cell["config"] + ".json")["build_args"]
        rows = traffic["batch"] * traffic["build_args"]["seq_len"] \
            * args["top_k"]
        padded = rows + args["n_expert"] * ROW_TILE
        assert f"\\[({rows}|{padded})(" in pattern, (name, rows, padded)


def test_roofline_reader_by_hand():
    # nine products need 9 * 0.6977 ms of compute (the larger bound) and
    # the trace shows 4.5 ms a step: 139.5% here, reported as it comes out
    # (a hand-made trace with three of nine products; the reader does not
    # clip, so a count that is too high shows)
    want = 100 * (9 * 2 * 32768 * 2048 * 1024 / 197e12) / 4.5e-3
    assert metric("moe_expert_matmul_roofline_pct.train") == \
        pytest.approx(want)
    assert want == pytest.approx(139.5, abs=0.1)
    value, bound = roofline.share(1e12, 1e9, 0.01, ctx()["peaks"])
    assert bound == "compute" and value == pytest.approx(50.76, abs=0.01)
    value, bound = roofline.share(1e9, 8.19e9, 0.02, ctx()["peaks"])
    assert bound == "memory" and value == pytest.approx(50.0)


def test_roofline_reader_leaves_the_metric_out_when_nothing_matches():
    c = ctx()
    assert roofline.read(c, "^nothing", "expert_matmul_flops",
                         "expert_matmul_bytes") is None
    assert roofline.read(c, "gmm", "no_such_count",
                         "expert_matmul_bytes") is None
    c["peaks"] = None                                  # a rehearsal
    assert roofline.read(c, "gmm", "expert_matmul_flops",
                         "expert_matmul_bytes") is None


def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "olmoe_reference.py"),
        os.path.join(ROOT, "tests", "olmoe_reference.py"), shallow=False)


def test_config_holds_the_catalog_numbers_and_lists_only_the_depth():
    c = load("configs", "olmoe_1b_7b.json")
    published = {"hidden_size": 2048, "intermediate_size": 1024,
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "vocab_size": 50304, "max_position_embeddings": 4096,
                 "rms_norm_eps": 1e-5, "rope_theta": 10000}
    for key, value in published.items():
        assert c[key] == value, key
    assert c["num_hidden_layers"] == 1          # published: 16; in `reduced`
    b = c["build_args"]
    assert (b["d_model"], b["n_head"], b["n_expert"], b["top_k"],
            b["d_expert"], b["vocab_size"], b["n_layer"]) == \
        (2048, 16, 64, 8, 1024, 50304, 1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [e for e in json.load(f)["configs"]
                  if e["name"] == "olmoe_1b_7b"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == c["source"]


def test_reference_check_tiny():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference_check.py"),
         "--config", "olmoe_1b_7b", "--tiny"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "reference_check: PASS" in p.stdout
    assert "the bfloat16 reference must NOT be judged correct" in p.stdout
    assert "the bfloat16 reference's logits" in p.stdout
    assert "the bfloat16 reference's gradient of l0.router.w" in p.stdout
