"""The Nemotron-3-Nano configuration's own pieces of the yardstick: its FLOP,
byte and share counts against numbers worked out by hand, each prepared
metric file's pattern against instruction texts recorded from the cell's
compiled step on the chip (PR 56) and against the other cells' recorded texts,
the scope metrics' expressions against owners, the counters' reader on a
hand-made observatory, the reference kept identical to the tests' copy, the
configuration against the catalog's numbers, the traffic and cell files found
by name, `run.py --tiny` over the new cell both ways and
`reference_check_nemotron_h.py --tiny`.

`BENCHMARK.json` lists the configuration and the cell. It does NOT list the
fifteen `ssm_*` metrics whose files are here: `per_layer` holds 128 of the 128
entries it may (ROADMAP D18), so they wait for a `benchmark` PR that makes
room; until then the readers are held to their files by this test and the
cell reports the metrics that carry no `workloads` list. Nothing here holds a
list to its present length."""

import filecmp
import importlib
import json
import os
import re
import subprocess
import sys
import types

import pytest

import trace_reduce as tr
from readers import compile_detail, roofline, trace_calls, trace_ops
from trace_reduce import Event

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "nemotron_3_nano_30b_a3b.s2048"
CONFIG = "nemotron_3_nano_30b_a3b"
TRAFFIC = "steady_b1_s2048_nemotron3"
KERNELS = ["ssm_scan_kernel_ms.train", "ssm_scan_kernel_calls.train",
           "ssm_scan_roofline_pct.train"]
SCOPES = ["ssm_scan_op_ms.train", "ssm_mixer_op_ms.train",
          "ssm_conv_op_ms.train", "ssm_gated_norm_op_ms.train",
          "ssm_moe_layout_op_ms.train", "ssm_router_op_ms.train"]
COUNTERS = {"ssm_layers.train": "state_space_layers",
            "ssm_grid_steps.train": "ssd_grid_steps",
            "ssm_router_bias_updates.train": "moe_router_bias_updates"}
SHARED = ["ssm_expert_matmul_ms.train",
          "ssm_expert_matmul_roofline_pct.train",
          "ssm_attention_kernels_ms.train"]
PREPARED = KERNELS + SCOPES + list(COUNTERS) + SHARED
D0, OPS = "/device:TPU:0", tr.OPS_LINE


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def flops(seq_len=2048, **over):
    c = load("configs", CONFIG + ".json")
    module = importlib.import_module("flops." + c["flops"])
    return module.flops_per_example(
        **dict(c["build_args"], seq_len=seq_len, **over))


# -- counts by hand ---------------------------------------------------------------

def test_nemotron_flops_by_hand():
    f = flops()
    per = f["multiply_adds_per_token"]
    assert per["mamba_projections"] == 2688 * 10304 + 4096 * 2688
    assert 2 * 4096 + 2 * 8 * 128 + 64 == 10304
    assert per["mamba_convolution"] == 6144 * 4
    # C B^T a group, the decayed tile times x, C S^T and the state's update
    assert per["mamba_scan"] == 8 * 128 * 128 + 64 * (128 * 64 + 2 * 128 * 64) \
        == 1_703_936
    assert per["attention_projections"] == 2 * 2688 * 4096 + 2 * 2688 * 256
    assert per["attention"] == 2048 * 32 * 128
    assert per["router"] == 2688 * 128
    assert per["shared_expert"] == 2 * 2688 * 3712
    assert per["routed_experts"] == 6 * 8 * 2 * 2688 * 1856 // 128
    assert f["multiply_adds_per_token_head"] == 2688 * 16384
    m_layer = 38_707_200 + 24_576 + 1_703_936
    total = 4 * m_layer + (23_396_352 + 8_388_608) \
        + 4 * (344_064 + 19_955_712 + 3_741_696) + 44_040_192
    assert f["forward"] == 2 * total * 2048
    assert f["forward_backward"] == 3 * f["forward"]
    # the issue's figures: 667 MFLOP a token forward, 4.1 TFLOP a step, the
    # state-space mixers 48% of it
    assert round(f["forward"] / 2048 / 1e6, 1) == 667.5
    assert round(f["forward_backward"] / 1e12, 2) == 4.10
    assert round(100 * f["mamba_layers_share"]) == 48
    assert f["layers"] == {"state_space": 4, "experts": 4,
                           "full_attention": 1}
    shares = [f[k] for k in ("mamba_layers_share", "attention_layers_share",
                             "experts_share", "head_share")]
    assert abs(sum(shares) - 1) < 1e-12


def test_scan_and_share_counts_by_hand():
    f = flops()
    # 3.41 MFLOP a token a layer forward, three times that with the backward
    assert f["ssd_flops"] == 4 * 3 * 2 * 2048 * 1_703_936
    # x, y 4096 and B, C 1024 each in bf16, dt and a 64 float32, the saved
    # state 64 x 64 x 128 float32 once a chunk of 128: 37.4 KB a token
    token = (2 * 4096 + 2 * 1024) * 2 + 2 * 64 * 4 + 64 * 64 * 128 * 4 // 128
    assert token == f["ssd_bytes_per_token_forward"] == 37_376
    assert 64 * 64 * 128 * 4 // 128 == 16_384
    assert f["ssd_bytes"] == 4 * 3 * 2048 * token
    # bytes bound it: 0.093 ms a layer forward at 819 GB/s, 1.12 ms a step
    assert round(2048 * token / 819e9 * 1e3, 3) == 0.093
    assert f["ssd_bytes"] / 819e9 > f["ssd_flops"] / 197e12
    assert f["share_expert_rows"] == 2048 * 6 * 8 // 128 == 768
    assert f["share_expert_flops"] == 6 * 4 * 2 * 768 * 2688 * 1856
    assert f["share_expert_bytes"] == 6 * 4 * 768 * (2688 + 1856) * 2
    assert round(f["share_expert_flops"] / 197e12 * 1e3, 2) == 0.93


def test_a_longer_sequence_and_a_whole_layer_scale_as_written():
    long = flops(seq_len=4096)
    assert long["ssd_flops"] == 2 * flops()["ssd_flops"]
    assert long["multiply_adds_per_token"]["attention"] == \
        2 * flops()["multiply_adds_per_token"]["attention"]
    whole = flops(experts_held=None)
    assert whole["multiply_adds_per_token"]["routed_experts"] == \
        6 * 2 * 2688 * 1856


# -- the patterns on recorded names -----------------------------------------------

with open(os.path.join(BENCH, "tests", "nemotron_h_trace_names.json")) as f:
    NAMES = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
with open(os.path.join(BENCH, "tests", "qwen3_next_trace_names.json")) as f:
    QWEN3 = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
SSD = ("ssd_fwd", "ssd_bwd")
MS = {"ssd_fwd": 2.0, "ssd_bwd": 6.0, "conv_fwd": 0.25, "conv_bwd": 0.5,
      "norm_fwd": 0.25, "norm_bwd": 0.5, "gmm": 0.75, "tgmm": 0.25,
      "flash_fwd": 1.0, "flash_bwd": 2.0, "token_sum": 0.5}


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


def metric(name, context=None):
    spec = load("metrics", name + ".json")
    reader = {"trace_ops": trace_ops, "trace_calls": trace_calls,
              "roofline": roofline,
              "compile_detail": compile_detail}[spec["reader"]]
    return reader.read(context or ctx(), **spec["args"])


def test_trace_names_are_the_cells():
    assert NAMES["ssd_fwd"].startswith("%ssd_fwd")
    assert NAMES["ssd_bwd"].startswith("%ssd_bwd")
    assert "f32[16,1,8,512,128]{" in NAMES["ssd_fwd"]      # the saved states
    assert "bf16[1,2048,4096]{" in NAMES["ssd_fwd"]
    assert "bf16[1,2048,1024]{" in NAMES["ssd_bwd"]        # dB and dC
    assert "f32[1,8,2048,8]{" in NAMES["ssd_bwd"]          # by columns
    assert "l0.mamba/ssd_scan" in NAMES["ssd_fwd"] or \
        re.search(r"l\d\.mamba/ssd_scan", NAMES["ssd_fwd"])
    assert NAMES["conv_fwd"].startswith("%causal_conv_fwd") \
        and "bf16[1,2048,6144]{" in NAMES["conv_fwd"]
    assert "f32[5,6144]{" in NAMES["conv_bwd"]             # dW and dBias
    assert NAMES["norm_fwd"].startswith("%gated_norm_fwd")
    assert NAMES["gmm"].startswith("%gmm") and \
        NAMES["tgmm"].startswith("%tgmm")
    assert NAMES["flash_fwd"].startswith("%flash_fwd") and \
        NAMES["flash_bwd"].startswith("%flash_dq_flash_dkv")


@pytest.mark.parametrize("name,found", [
    ("ssm_scan_kernel_ms.train", SSD),
    ("ssm_expert_matmul_ms.train", ("gmm", "tgmm")),
    ("ssm_attention_kernels_ms.train", ("flash_fwd", "flash_bwd"))])
def test_cell_pattern_finds_its_ops_and_no_others(name, found):
    pattern = re.compile(load("metrics", name + ".json")["args"]["pattern"])
    hit = {key for key, text in NAMES.items() if pattern.search(text)}
    assert hit == set(found)
    assert metric(name) == pytest.approx(sum(MS[k] for k in found))


def test_the_scan_pattern_finds_no_call_of_the_delta_rule_and_back():
    """Qwen3-Next's recorded `gdn_` kernels carry no `ssd_`, and the accepted
    delta-rule patterns find neither scan kernel: the two recurrences are
    told apart by name."""
    ssd = re.compile(load(
        "metrics", "ssm_scan_kernel_ms.train.json")["args"]["pattern"])
    assert not [k for k, text in QWEN3.items() if ssd.search(text)]
    for accepted in ("gdn_kernel_ms.train", "gdn_scan_ms.train",
                     "causal_conv_kernel_ms.train"):
        pattern = re.compile(load(
            "metrics", accepted + ".json")["args"]["pattern"])
        for key in SSD:
            assert not pattern.search(NAMES[key]), (accepted, key)
    for name in KERNELS[1:]:
        assert load("metrics", name + ".json")["args"]["pattern"] == \
            ssd.pattern
    for text, hit in [
            ("%ssd_fwd.3 = (f32[16,1,8,512,128]{4,3,2,1,0}) custom-call(", 1),
            ("%ssd_bwd = (bf16[1,2048,4096]{2,1,0}) custom-call(", 1),
            ("%jvp_ssd_fwd_.1 = (f32[2,1,1,512,128]{4,3,2,1,0}) "
             "custom-call(", 1),
            ("%gdn_fwd.1 = (f32[64,1,32,128,128]{4,3,2,1,0}) custom-call(",
             0),
            ("%ssd_fwd_fusion = f32[8]{0} fusion(", 0)]:
        assert bool(ssd.search(text)) is bool(hit), text


def test_call_counts_and_roofline_shares_on_a_hand_made_trace():
    assert metric("ssm_scan_kernel_calls.train") == 2.0
    f = flops()
    # bytes bound the scan: 1.12 ms a step; this trace gives it 8
    assert metric("ssm_scan_roofline_pct.train") == pytest.approx(
        100 * f["ssd_bytes"] / 819e9 / 8e-3, rel=1e-6)
    assert metric("ssm_scan_roofline_pct.train") < 100
    assert metric("ssm_expert_matmul_roofline_pct.train") == pytest.approx(
        100 * f["share_expert_flops"] / 197e12 / 1e-3, rel=1e-6)
    empty = ctx()
    empty["trace"] = lambda: None
    for name in KERNELS + SHARED:
        assert metric(name, empty) is None


# (name scope, op type) of owners a traced step of the cell shows
OWNERS = [("l0.mamba", "rms_norm"), ("l0.mamba", "mul"),
          ("l2.mamba", "mul_grad"), ("l0.mamba", "causal_conv1d"),
          ("l4.mamba", "causal_conv1d_grad"), ("l0.mamba", "ssd_gates"),
          ("l0.mamba", "ssd_scan"), ("l7.mamba", "ssd_scan_grad"),
          ("l2.mamba", "gated_rms_norm"), ("l2.mamba", "gated_rms_norm_grad"),
          ("l5.attn", "fused_attention"), ("l5.attn", "expand"),
          ("l1.moe", "moe_router"), ("l1.moe", "moe_router_grad"),
          ("l3.moe", "moe_dispatch"), ("l6.moe", "moe_combine_grad"),
          ("l6.moe", "grouped_matmul"), ("l8.moe", "relu2"),
          ("", "adam"), ("", "softmax_with_cross_entropy")]


def owned(name):
    spec = load("metrics", name + ".json")
    assert spec["reader"] == "trace_scopes"
    args = spec["args"]
    op = re.compile(args["op"]) if "op" in args else None
    scope = re.compile(args["scope"]) if "scope" in args else None
    return {(s, o) for s, o in OWNERS
            if (op is None or op.search(o))
            and (scope is None or scope.search(s))}


def test_scope_metrics_find_their_owners_and_no_others():
    mixer = {(s, o) for s, o in OWNERS if s.endswith(".mamba")}
    assert owned("ssm_mixer_op_ms.train") == mixer and len(mixer) == 10
    assert owned("ssm_scan_op_ms.train") == {
        ("l0.mamba", "ssd_scan"), ("l7.mamba", "ssd_scan_grad")}
    assert owned("ssm_conv_op_ms.train") == {
        ("l0.mamba", "causal_conv1d"), ("l4.mamba", "causal_conv1d_grad")}
    assert owned("ssm_gated_norm_op_ms.train") == {
        ("l2.mamba", "gated_rms_norm"), ("l2.mamba", "gated_rms_norm_grad")}
    assert owned("ssm_router_op_ms.train") == {
        ("l1.moe", "moe_router"), ("l1.moe", "moe_router_grad")}
    assert owned("ssm_moe_layout_op_ms.train") == {
        ("l3.moe", "moe_dispatch"), ("l6.moe", "moe_combine_grad")}
    # the same expressions as the accepted metrics of the other cells
    for mine, theirs in (("ssm_moe_layout_op_ms.train", "moe_layout_op_ms.train"),
                         ("ssm_conv_op_ms.train", "causal_conv_op_ms.train"),
                         ("ssm_gated_norm_op_ms.train", "gated_norm_op_ms.train")):
        assert load("metrics", mine + ".json")["args"] == \
            load("metrics", theirs + ".json")["args"], mine
    for scope in ("l0.swa", "l3.attn", "l0.mla", "l3.gdn", "l1.moe", ""):
        assert not re.search(load(
            "metrics", "ssm_mixer_op_ms.train.json")["args"]["scope"], scope)


def test_the_cell_and_the_configuration_are_listed_and_the_metrics_wait():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == load("workloads", CELL + ".json")
    assert len(cell["why"]) <= 200 and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                                "n_routed_experts", "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == load("configs", CONFIG + ".json")["source"]
    assert len(entry["why"]) <= 200
    tail, = [m for m in bench["end_to_end"]
             if m["name"] == "train_step_ms_p95"]
    assert CELL not in tail["workloads"]
    # every prepared metric has its file; an entry, where a later PR lists
    # one, names the cell and the metric the cell reports
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"] in PREPARED}
    for name in PREPARED:
        spec = load("metrics", name + ".json")
        assert spec["reader"] in ("trace_ops", "trace_calls", "roofline",
                                  "trace_scopes", "compile_detail"), name
        assert "PR 56" in spec["what"] or name in COUNTERS, name
    for m in listed.values():
        assert CELL in m["workloads"] and \
            m["moves"] == "train_examples_per_s", m["name"]
    # the cell reports what carries no list: at least one per-layer metric
    assert [m for m in bench["per_layer"] if "workloads" not in m
            and m["moves"] == "train_examples_per_s"]
    # no existing metric's list gained the cell
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])
                and m["name"] not in PREPARED]


def test_counter_readers_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "grad_fanin_max": 1,
                        "layer_kinds": {"state_space": 4,
                                        "full_attention": 1},
                        "state_space_layers": 4, "ssd_grid_steps": 1024,
                        "moe_router_bias_updates": 4, "ssd_plan": "kernel"})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    want = {"ssm_layers.train": 4.0, "ssm_grid_steps.train": 1024.0,
            "ssm_router_bias_updates.train": 4.0}
    for name, key in COUNTERS.items():
        spec = load("metrics", name + ".json")
        assert spec["args"] == {"key": key}
        assert compile_detail.read({"system": system}, **spec["args"]) \
            == want[name]
    older = types.SimpleNamespace(main=types.SimpleNamespace(_uid=3))
    for name in COUNTERS:                   # a program without the counter
        spec = load("metrics", name + ".json")
        assert compile_detail.read({"system": older}, **spec["args"]) is None


# -- the data files ---------------------------------------------------------------

def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "nemotron_h_reference.py"),
        os.path.join(ROOT, "tests", "nemotron_h_reference.py"), shallow=False)


CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True}


def test_config_holds_the_catalog_numbers_and_lists_its_three_cuts():
    c = load("configs", CONFIG + ".json")
    for key, value in CATALOG.items():
        assert c[key] == value, key
    assert (c["num_hidden_layers"], c["hybrid_override_pattern"],
            c["n_routed_experts"], c["vocab_size"]) == \
        (9, "MEMEM*EME", 8, 16384)
    published = c["hybrid_override_pattern_published"]
    assert (c["num_hidden_layers_published"], len(published),
            c["n_routed_experts_published"], c["vocab_size_published"]) == \
        (52, 52, 128, 131072)
    assert published.startswith("MEMEM*EME") and 131072 // 8 == 16384
    assert (published.count("M"), published.count("E"),
            published.count("*")) == (23, 23, 6)
    assert [i for i, k in enumerate(published) if k == "*"] == \
        [5, 12, 19, 26, 33, 42]
    assert [r.split(" ")[0] for r in c["reduced"]] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert "hybrid_override_pattern" in c["reduced"][0]
    assert c["source"] == ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-"
                           "Nano-30B-A3B-BF16/blob/main/config.json")
    args = c["build_args"]
    assert (args["d_model"], args["mamba_heads"], args["mamba_head_dim"],
            args["n_groups"], args["ssm_state"], args["conv_kernel"],
            args["chunk"], args["n_head"], args["n_kv_head"],
            args["head_dim"], args["d_expert"], args["d_shared"],
            args["n_expert"], args["top_k"], args["experts_held"],
            args["first_expert"], args["routed_scaling_factor"]) == (
        2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 128, 6, 8, 0,
        2.5)
    assert args["layer_pattern"] == "MEMEM*EME" and "seq_len" not in args
    assert "16 chips share each layer" in c["deployment"]
    for key in ("no positions in the attention layers",
                "the gate before the grouped norm",
                "the order of W_in's columns", "Mamba-2 initialisation",
                "initialisation", "bias update", "optimizer", "precision"):
        assert key in c["assumed"], key
    tiny = c["tiny"]["build_args"]
    assert (tiny["seq_len"], tiny["mamba_heads"], tiny["n_expert"],
            tiny["experts_held"], tiny["first_expert"]) == (256, 4, 16, 4, 4)
    assert "layer_pattern" not in tiny          # MEMEM*EME is kept
    # parameters: 38.74 M an M layer, 100.13 M an E layer, 23.40 M the
    # attention layer, 667.0 M in all = 8.00 GB of float32 state
    m = 2688 * 10304 + 4096 * 2688 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 2688
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    e = 2688 * 128 + 8 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688
    total = 4 * m + attn + 4 * e + 2 * 16384 * 2688 + 2688
    assert (round(m / 1e6, 2), round(attn / 1e6, 2), round(e / 1e6, 2)) == \
        (38.74, 23.40, 100.13)
    assert round(total / 1e6, 1) == 667.0
    assert round(total * 12 / 1e9, 2) == 8.00


def test_traffic_is_qwen3_nexts_but_for_the_length_the_pool_and_the_reference():
    mine = load("traffic", TRAFFIC + ".json")
    theirs = load("traffic", "steady_b1_s4096_qwen3_next.json")
    for key in ("generator", "batch", "feed", "in_flight", "warmup",
                "traced"):
        assert mine[key] == theirs[key], key
    assert mine["generator"] == "train_loop_reference"
    assert mine["build_args"] == {"seq_len": 2048}
    assert mine["pool_batches"] == 128
    check = mine["reference_check"]
    assert check["reference"] == "nemotron_h_reference"
    assert check["reference_args"] == {"q_block": 512, "token_block": 64}
    assert 0 < check["loss_atol"] < 0.02 and "PR 56" in check["loss_atol_why"]


@pytest.mark.parametrize("trace", [0, 1])
def test_run_tiny_over_the_new_cell(trace):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000007", "--seconds", "1", "--trace", str(trace),
         "--tiny"], capture_output=True, text=True, timeout=900, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert "reference_loss_gap" in line["compared"]
    assert line["metrics"]      # the metrics that carry no list


def test_reference_check_tiny():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference_check_nemotron_h.py"),
         "--tiny"], capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "reference_check_nemotron_h: PASS" in out.stdout
    faults = load("configs", CONFIG + ".json")["reference"]["check"]["faults"]
    assert len(faults) == 17
    for fault in faults:
        assert f"ok   fault {fault} must NOT be judged correct" in out.stdout
