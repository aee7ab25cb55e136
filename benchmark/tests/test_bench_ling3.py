"""The Ling-3.0-flash-VL configuration's own pieces of the yardstick: its FLOP,
byte, share and parameter counts against numbers worked out by hand, each
prepared metric file's pattern or expression against instruction texts and
owners recorded from the cell's compiled step on the chip (PR 60) and against
the other cells' recorded texts, the new reader `roofline_by_op` and the
counters' reader on hand-made inputs, the reference kept identical to the
tests' copy, the configuration against the catalog's numbers, the traffic and
cell files found by name, `run.py --tiny` over the new cell both ways and
`reference_check_ling3.py --tiny`.

`BENCHMARK.json` lists the configuration and the cell. It does NOT list the
ten `kda_*` metrics whose files are here: `per_layer` holds 128 of the 128
entries it may (ROADMAP D18), so they wait, as the fifteen `ssm_*` files do,
for a `benchmark` PR that makes room; until then the readers are held to
their files by this test and the cell reports the metrics that carry no
`workloads` list. Nothing here holds a list to its present length."""

import filecmp
import importlib
import json
import os
import re
import subprocess
import sys
import types

import pytest

from readers import compile_detail, roofline, roofline_by_op, trace_scopes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "ling_3_0_flash_vl.s2048"
CONFIG = "ling_3_0_flash_vl"
TRAFFIC = "steady_b1_s2048_ling3"
KERNELS = ["kda_scan_kernel_ms.train", "kda_scan_kernel_calls.train"]
SCOPES = ["kda_scan_op_ms.train", "kda_gates_op_ms.train",
          "kda_mixer_op_ms.train", "kda_mla_gate_op_ms.train",
          "kda_router_op_ms.train"]
COUNTERS = {"kda_layers.train": "kda_layers",
            "kda_grid_steps.train": "kda_grid_steps"}
PREPARED = KERNELS + SCOPES + list(COUNTERS) + ["kda_scan_roofline_pct.train"]


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def flops(seq_len=2048, **over):
    c = load("configs", CONFIG + ".json")
    module = importlib.import_module("flops." + c["flops"])
    return module.flops_per_example(
        **dict(c["build_args"], seq_len=seq_len, **over))


# -- counts by hand ---------------------------------------------------------------

def test_ling3_flops_by_hand():
    f = flops()
    per = f["multiply_adds_per_token"]
    assert per["kda_projections"] == 6 * 2560 * 4096 + 2560 * 32
    assert per["kda_convolution"] == 12288 * 4
    # two Gram tiles, the solve's two right-hand sides, the scores times v',
    # w S, q S and the state's update, a head; 32 heads
    assert per["kda_rule"] == 32 * (2 * 64 * 128 + 2 * 64 * 128 + 64 * 128
                                    + 3 * 128 * 128) == 2_883_584
    assert per["mla_projections"] == 2560 * 6144 + 2560 * 576 + 512 * 8192 \
        + 4096 * 2560 + 2560 * 32
    assert per["attention"] == 2048 * 32 * (192 + 128) // 2
    assert per["dense_mlp"] == 3 * 2560 * 6144
    assert per["router"] == 2560 * 512
    assert per["shared_expert"] == 3 * 2560 * 768
    assert per["routed_experts"] == 8 * 8 * 3 * 2560 * 768 // 512
    assert f["multiply_adds_per_token_head"] == 2560 * 19648
    kda = 62_996_480 + 49_152 + 2_883_584
    total = 5 * kda + (31_965_184 + 10_485_760) + 47_185_920 \
        + 5 * (1_310_720 + 5_898_240 + 737_280) + 50_298_880
    assert f["forward"] == 2 * total * 2048
    assert f["forward_backward"] == 3 * f["forward"]
    # 1019 MFLOP a token forward, 6.26 TFLOP a step, the KDA mixers 65%
    assert round(f["forward"] / 2048 / 1e6) == 1019
    assert round(f["forward_backward"] / 1e12, 2) == 6.26
    assert round(100 * f["kda_layers_share"]) == 65
    assert f["layers"] == {"kda": 5, "mla": 1, "dense": 1, "moe": 5}
    shares = [f[k] for k in ("kda_layers_share", "mla_layers_share",
                             "dense_mlp_share", "experts_share",
                             "head_share")]
    assert abs(sum(shares) - 1) < 1e-12


def test_rule_and_share_counts_by_hand():
    f = flops()
    assert f["kda_flops"] == 5 * 3 * 2 * 2048 * 2_883_584
    # q, k, v, o 4096 each in bf16, g 4096 float32 (16 KB), beta 32 float32,
    # the saved state 32 x 128 x 128 float32 once a chunk of 64 (32 KB)
    token = 4 * 4096 * 2 + 4096 * 4 + 32 * 4 + 32 * 128 * 128 * 4 // 64
    assert token == f["kda_bytes_per_token_forward"] == 82_048
    assert 32 * 128 * 128 * 4 // 64 == 32_768 and 4096 * 4 == 16_384
    assert f["kda_bytes"] == 5 * 3 * 2048 * token
    # bytes bound it: 0.205 ms a layer forward at 819 GB/s, 3.08 ms a step
    assert round(2048 * token / 819e9 * 1e3, 3) == 0.205
    assert f["kda_bytes"] / 819e9 > f["kda_flops"] / 197e12
    assert round(f["kda_bytes"] / 819e9 * 1e3, 2) == 3.08
    assert f["share_expert_rows"] == 2048 * 8 * 8 // 512 == 256
    assert f["share_expert_flops"] == 9 * 5 * 2 * 256 * 2560 * 768
    assert f["mla_attention_flops"] == 2 * 2048 * 2048 * 32 \
        * (4 * 192 + 3 * 128) // 2


def test_the_parameters_are_the_issues_767_million():
    c = load("configs", CONFIG + ".json")
    module = importlib.import_module("flops." + c["flops"])
    args = {k: v for k, v in c["build_args"].items()
            if k in module.parameters.__code__.co_varnames}
    total = module.parameters(**args)
    kda = 6 * 2560 * 4096 + 2560 * 32 + 12288 * 4 + 32 + 4096 + 128
    mla = 2560 * 6144 + 2560 * 576 + 512 + 512 * 8192 + 4096 * 2560 \
        + 2560 * 32
    moe = 2560 * 512 + 3 * 2560 * 768 + 8 * 3 * 2560 * 768
    assert (round(kda / 1e6, 2), round(mla / 1e6, 2), round(moe / 1e6, 2)) \
        == (63.05, 31.97, 54.39)
    assert total == 5 * kda + mla + 3 * 2560 * 6144 + 5 * moe \
        + 13 * 2560 + 2 * 19648 * 2560 == 767_006_496
    assert "767,006,496" in c["deployment"]
    assert round(total * 12 / 1e9, 2) == 9.20
    assert round(total * 16 / 1e9, 2) == 12.27
    # one more KDA + MoE layer: the driver's seven, over the room
    seven = total + kda + moe + 2 * 2560
    assert round(seven / 1e6, 1) == 884.5 and round(seven * 16 / 1e9, 2) \
        == 14.15


def test_a_run_from_layer_zero_and_a_whole_layer_scale_as_written():
    whole = flops(first_layer=0, n_layer=42, experts_held=None,
                  vocab_size=157184)
    assert whole["layers"] == {"kda": 35, "mla": 7, "dense": 2, "moe": 40}
    assert whole["multiply_adds_per_token"]["routed_experts"] == \
        8 * 3 * 2560 * 768
    long = flops(seq_len=4096)
    assert long["kda_flops"] == 2 * flops()["kda_flops"]
    assert long["multiply_adds_per_token"]["kda_rule"] == \
        flops()["multiply_adds_per_token"]["kda_rule"]


# -- the patterns and expressions on recorded names ---------------------------------

with open(os.path.join(BENCH, "tests", "ling3_trace_names.json")) as f:
    RECORDED = json.load(f)
NAMES = {k: v for k, v in RECORDED.items() if not k.startswith("_")}
with open(os.path.join(BENCH, "tests", "qwen3_next_trace_names.json")) as f:
    QWEN3 = {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def owner(text):
    """(name scope, op type) of a recorded instruction, from its op_name."""
    path = re.search(r'op_name="jit\(step\)/([^"]*)"', text).group(1)
    parts = path.split("/")
    scoped = re.fullmatch(r"l\d+\.\w+", parts[0]) is not None
    return (parts[0] if scoped else "", parts[1] if scoped else parts[0])


def test_trace_names_are_the_cells():
    assert owner(NAMES["rule_solve"]) == ("l0.kda", "kda_delta_rule")
    assert "f32[1,32,32,1,64,64]{" in NAMES["rule_solve"]   # XLA's solve
    assert owner(NAMES["rule_scan"])[1] == "kda_delta_rule"
    assert owner(NAMES["rule_grad"])[1] == "kda_delta_rule_grad"
    assert owner(NAMES["gates"])[1] == "kda_gates"
    assert owner(NAMES["router"])[1] == "moe_router"
    assert NAMES["conv_fwd"].startswith("%causal_conv_fwd") \
        and "bf16[1,2048,12288]{" in NAMES["conv_fwd"]
    assert NAMES["norm_fwd"].startswith("%gated_norm_fwd")
    assert NAMES["flash_fwd"].startswith("%flash_fwd")
    assert "bf16[32,2048,192]" in NAMES["flash_fwd"]        # MLA's q and k
    # the rule is XLA ops on the chip: no custom call of its own
    assert not [k for k, text in NAMES.items()
                if re.search(r"%kda_(fwd|bwd)", text)]


def test_the_kernel_pattern_waits_for_the_kernels():
    """`kda_scan_kernel_*` read nothing in PR 60's trace (the rule is XLA
    ops) and would read `kda_fwd` / `kda_bwd` custom calls; they find no
    other cell's kernels, and the accepted delta-rule patterns find none of
    this cell's instructions."""
    kda = re.compile(load(
        "metrics", "kda_scan_kernel_ms.train.json")["args"]["pattern"])
    assert load("metrics", KERNELS[1] + ".json")["args"]["pattern"] \
        == kda.pattern
    assert not [k for k, text in NAMES.items() if kda.search(text)]
    assert not [k for k, text in QWEN3.items() if kda.search(text)]
    for accepted in ("gdn_kernel_ms.train", "gdn_scan_ms.train"):
        pattern = re.compile(load(
            "metrics", accepted + ".json")["args"]["pattern"])
        assert not [k for k, text in NAMES.items() if pattern.search(text)]
    for text, hit in [
            ("%kda_fwd.3 = (f32[32,1,32,128,128]{4,3,2,1,0}) custom-call(", 1),
            ("%kda_bwd = (f32[1,2048,4096]{2,1,0}) custom-call(", 1),
            ("%gdn_fwd.1 = (f32[64,1,32,128,128]{4,3,2,1,0}) custom-call(",
             0),
            ("%kda_fwd_fusion = f32[8]{0} fusion(", 0)]:
        assert bool(kda.search(text)) is bool(hit), text


# (name scope, op type) of owners a traced step of the cell shows
OWNERS = sorted({owner(text) for text in NAMES.values()} | {
    ("l0.kda", "mul"), ("l2.kda", "mul_grad"), ("l0.kda", "concat"),
    ("l3.kda", "slice"), ("l0.kda", "causal_conv1d"),
    ("l5.kda", "causal_conv1d_grad"), ("l1.kda", "kda_gates_grad"),
    ("l2.kda", "gated_rms_norm"), ("l2.kda", "gated_rms_norm_grad"),
    ("l4.mla", "fused_attention"), ("l4.mla", "rotary_embedding"),
    ("l4.mla", "sigmoid"), ("l4.mla", "elementwise_mul"),
    ("l4.mla", "elementwise_mul_grad"), ("l4.mla", "sigmoid_grad"),
    ("l4.mla", "mul"), ("l4.mla", "transpose"), ("l0.mlp", "swiglu"),
    ("l1.moe", "moe_router_grad"), ("l3.moe", "moe_dispatch"),
    ("l1.moe", "grouped_matmul"), ("l1.moe", "elementwise_mul"),
    ("", "adam"), ("", "softmax_with_cross_entropy"), ("", "rms_norm")})


def owned(name):
    spec = load("metrics", name + ".json")
    assert spec["reader"] in ("trace_scopes", "roofline_by_op")
    args = spec["args"]
    op = re.compile(args["op"]) if "op" in args else None
    scope = re.compile(args["scope"]) if "scope" in args else None
    return {(s, o) for s, o in OWNERS
            if (op is None or op.search(o))
            and (scope is None or scope.search(s))}


def test_scope_metrics_find_their_owners_and_no_others():
    mixer = {(s, o) for s, o in OWNERS if s.endswith(".kda")}
    assert owned("kda_mixer_op_ms.train") == mixer and len(mixer) >= 12
    rule = {(s, o) for s, o in OWNERS
            if o in ("kda_delta_rule", "kda_delta_rule_grad")}
    assert owned("kda_scan_op_ms.train") == rule and len(rule) >= 2
    assert owned("kda_scan_roofline_pct.train") == rule     # the same work
    assert {o for _, o in owned("kda_gates_op_ms.train")} == {
        "kda_gates", "kda_gates_grad"}
    assert owned("kda_mla_gate_op_ms.train") == {
        ("l4.mla", "sigmoid"), ("l4.mla", "sigmoid_grad"),
        ("l4.mla", "elementwise_mul"), ("l4.mla", "elementwise_mul_grad")}
    assert {o for _, o in owned("kda_router_op_ms.train")} == {
        "moe_router", "moe_router_grad"}
    assert load("metrics", "kda_router_op_ms.train.json")["args"] == \
        load("metrics", "sigmoid_router_op_ms.train.json")["args"]
    for scope in ("l0.swa", "l3.attn", "l0.mla", "l3.gdn", "l1.moe",
                  "l0.mamba", ""):
        assert not re.search(load(
            "metrics", "kda_mixer_op_ms.train.json")["args"]["scope"], scope)


def _scopes_context(ms):
    """A context whose `trace_scopes.read` is a table lookup: what
    `roofline_by_op` adds to it is the arithmetic."""
    return {"obs": {"batch": 1}, "flops": flops(),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "_ms": ms}


def test_roofline_by_op_is_the_roofline_over_the_owners_time(monkeypatch):
    spec = load("metrics", "kda_scan_roofline_pct.train.json")
    assert spec["reader"] == "roofline_by_op"
    asked = []

    def table(ctx, op=None, scope=None, share=False):
        asked.append((op, scope))
        return ctx["_ms"]

    monkeypatch.setattr(trace_scopes, "read", table)
    f = flops()
    # bytes bound the rule: 3.08 ms a step; PR 60's trace gives it 68.9
    got = roofline_by_op.read(_scopes_context(68.904), **spec["args"])
    assert got == pytest.approx(100 * f["kda_bytes"] / 819e9 / 68.904e-3,
                                rel=1e-9)
    assert 4.4 < got < 4.5 and asked == [(spec["args"]["op"], None)]
    assert got == pytest.approx(roofline.share(
        f["kda_flops"], f["kda_bytes"], 68.904e-3,
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})[0])
    # a program without the op (the parent), a rehearsal, a count without
    # the keys: nothing, and nothing raised
    assert roofline_by_op.read(_scopes_context(None), **spec["args"]) is None
    no_peaks = {**_scopes_context(5.0), "peaks": None}
    assert roofline_by_op.read(no_peaks, **spec["args"]) is None
    older = {**_scopes_context(5.0), "flops": {"forward": 1}}
    assert roofline_by_op.read(older, **spec["args"]) is None


def test_the_cell_and_the_configuration_are_listed_and_the_metrics_wait():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == load("workloads", CELL + ".json")
    assert len(cell["why"]) <= 200 and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    assert bench["workloads"][-1] == cell       # appended, nothing moved
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert bench["configs"][-1] == entry
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == load("configs", CONFIG + ".json")["source"]
    assert len(entry["why"]) <= 200
    tail, = [m for m in bench["end_to_end"]
             if m["name"] == "train_step_ms_p95"]
    assert CELL not in tail["workloads"]
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"] in PREPARED}
    for name in PREPARED:
        spec = load("metrics", name + ".json")
        assert spec["reader"] in ("trace_ops", "trace_calls",
                                  "roofline_by_op", "trace_scopes",
                                  "compile_detail"), name
        assert "PR 60" in spec["what"] or name in COUNTERS, name
        assert "TO BE READ" not in spec["what"], name
    for m in listed.values():
        assert CELL in m["workloads"] and \
            m["moves"] == "train_examples_per_s", m["name"]
    assert [m for m in bench["per_layer"] if "workloads" not in m
            and m["moves"] == "train_examples_per_s"]
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])
                and m["name"] not in PREPARED]


def test_counter_readers_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "grad_fanin_max": 1,
                        "layer_kinds": {"kda": 5, "latent_attention": 1},
                        "kda_layers": 5, "kda_grid_steps": 10240,
                        "kda_plan": "xla", "moe_router_groups": 8})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    want = {"kda_layers.train": 5.0, "kda_grid_steps.train": 10240.0}
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
        os.path.join(BENCH, "references", "ling3_reference.py"),
        os.path.join(ROOT, "tests", "ling3_reference.py"), shallow=False)


CATALOG = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_key_value_heads": 32, "rope_theta": 6000000, "rms_norm_eps": 1e-06,
    "head_dim": 128, "partial_rotary_factor": 0.5,
    "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
    "n_group": 8, "topk_group": 4, "use_qk_norm": True,
    "score_function": "sigmoid", "moe_shared_expert_intermediate_size": 768,
    "layer_group_size": 6, "num_kv_heads_for_linear_attn": 0,
    "group_norm_size": 1, "linear_silu": True, "rotary_dim": 64,
    "use_mla_nope": False, "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}


def test_config_holds_the_catalog_numbers_and_lists_its_three_cuts():
    c = load("configs", CONFIG + ".json")
    for key, value in CATALOG.items():
        assert c[key] == value, key
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == \
        (6, 8, 19648)
    assert (c["num_hidden_layers_published"], c["num_experts_published"],
            c["vocab_size_published"]) == (42, 512, 157184)
    assert 157184 // 8 == 19648
    assert [r.split(" ")[0] for r in c["reduced"]] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert c["source"] == ("https://huggingface.co/inclusionAI/"
                           "Ling-3.0-flash-VL/blob/main/config.json")
    args = c["build_args"]
    assert "seq_len" not in args and args["first_layer"] == 1
    # the published layers the cut holds, by kind
    kinds = [("mla" if (p + 1) % 6 == 0 else "kda",
              "mlp" if p < 2 else "moe") for p in range(1, 7)]
    assert kinds == [("kda", "mlp")] + [("kda", "moe")] * 3 \
        + [("mla", "moe"), ("kda", "moe")]
    assert "64 chips share each layer" in c["deployment"]
    for key in ("the decay's form", "the decay's initial values",
                "W_f and W_g at full rank", "KDA's output gate and norm",
                "head_wise", "positions", "use_qk_norm", "router",
                "bias update", "head", "optimizer", "precision"):
        assert key in c["assumed"], key
    tiny = c["tiny"]["build_args"]
    assert (tiny["seq_len"], tiny["n_expert"], tiny["n_group"],
            tiny["topk_group"], tiny["experts_held"], tiny["first_expert"]) \
        == (256, 16, 4, 2, 4, 4)
    assert "n_layer" not in tiny and "first_layer" not in tiny     # kept
    assert "TO BE WRITTEN" not in json.dumps(c)


def test_traffic_is_nemotrons_but_for_the_reference():
    mine = load("traffic", TRAFFIC + ".json")
    theirs = load("traffic", "steady_b1_s2048_nemotron3.json")
    for key in ("generator", "batch", "build_args", "pool_batches", "feed",
                "in_flight", "warmup", "traced"):
        assert mine[key] == theirs[key], key
    assert mine["generator"] == "train_loop_reference"
    check = mine["reference_check"]
    assert check["reference"] == "ling3_reference"
    assert check["reference_args"] == {"q_block": 512, "token_block": 64}
    assert 0 < check["loss_atol"] < 0.02 and "PR 60" in check["loss_atol_why"]


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
        [sys.executable, os.path.join(BENCH, "reference_check_ling3.py"),
         "--tiny"], capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "reference_check_ling3: PASS" in out.stdout
    assert "reference_check_ling3: planted" in out.stdout
    faults = load("configs", CONFIG + ".json")["reference"]["check"]["faults"]
    assert len(faults) == 18
    for fault in faults:
        assert f"ok   fault {fault} must NOT be judged correct" in out.stdout
