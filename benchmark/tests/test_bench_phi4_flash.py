"""The Phi-4-mini-flash-reasoning configuration's own pieces of the yardstick:
its FLOP, byte, share and parameter counts against numbers worked out by
hand, each prepared metric file's expression against instruction texts
recorded from the cell's compiled step on the chip (PR 73) and against owners
a traced step shows, the roofline reader on hand-made inputs, the reference
kept identical to the tests' copy, the configuration against the catalog's
numbers and its two cuts, the traffic and cell files found by name,
`run.py --tiny` over the new cell both ways and
`reference_check_phi4_flash.py --tiny`.

`BENCHMARK.json` lists the configuration and the cell. It does NOT list the
fourteen metrics whose files are here: `per_layer` holds 128 of the 128
entries it may (ROADMAP D18), so they wait, as the files of five cells before
it do, for a `benchmark` PR that makes room; until then the readers are held
to their files by this test and the cell reports the metrics that carry no
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

from readers import compile_detail, roofline

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "phi_4_mini_flash_reasoning.s4096"
CONFIG = "phi_4_mini_flash_reasoning"
TRAFFIC = "steady_b1_s4096_phi4_flash"
PREPARED = ["sscan_op_ms.train", "sscan_kernel_calls.train",
            "sscan_roofline_pct.train", "mamba1_mixer_op_ms.train",
            "diff_attention_kernels_ms.train",
            "diff_attention_roofline_pct.train",
            "diff_window_kernels_ms.train", "diff_combine_op_ms.train",
            "gmu_op_ms.train", "cross_mixer_op_ms.train",
            "phi4_tied_table_op_ms.train",
            "selective_scan_layers.train", "selective_scan_grid_steps.train",
            "shared_kv_readers.train"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def flops(seq_len=4096, **over):
    c = load("configs", CONFIG + ".json")
    module = importlib.import_module("flops." + c["flops"])
    return module.flops_per_example(
        **dict(c["build_args"], seq_len=seq_len, **over))


# -- counts by hand ---------------------------------------------------------------

def test_phi4_flops_by_hand():
    f = flops()
    per = f["multiply_adds_per_token"]
    assert per["mamba_projections"] == 2560 * 10240 + 5120 * 192 \
        + 160 * 5120 + 5120 * 2560 == 41_123_840
    assert per["mamba_convolution"] == 5120 * 4
    assert per["mamba_scan"] == 3 * 5120 * 16
    assert per["attention_projections"] == 2560 * 5120 + 2560 * 2560 \
        == 19_660_800
    assert per["cross_projections"] == 2 * 2560 * 2560
    assert per["attention_full"] == 40 * 192 * 2048 == 15_728_640
    # a window of 512 over 4096 tokens: 512 x 513 / 2 + 3584 x 512 pairs
    assert per["attention_window"] == 7680 * (131_328 + 1_835_008) / 4096
    assert per["gated_memory"] == 2 * 2560 * 5120
    assert per["mlp"] == 3 * 2560 * 10240 == 78_643_200
    assert f["multiply_adds_per_token_head"] == 2560 * 25008
    assert f["layers"] == {"mamba": 2, "window": 1, "full": 1, "gmu": 1,
                           "cross": 1}
    total = 2 * (41_123_840 + 20_480 + 245_760) + 2 * 19_660_800 \
        + 13_107_200 + 2 * 15_728_640 + per["attention_window"] \
        + 26_214_400 + 6 * 78_643_200 + 64_020_480
    assert f["forward"] == 2 * total * 4096
    assert f["forward_backward"] == 3 * f["forward"]
    assert round(f["forward_backward"] / 1e12, 2) == 18.0
    shares = [f[k] for k in ("mamba_mixers_share", "mlp_share", "head_share",
                             "attention_projections_share",
                             "attention_scores_share", "gated_memory_share")]
    assert abs(sum(shares) - 1) < 1e-12
    assert round(f["mlp_share"], 3) == 0.644


def test_the_scans_and_the_maps_costs_by_hand():
    f = flops()
    # a token and layer forward: x and dt_raw bf16, y float32, B and C bf16,
    # a [5120, 16] float32 state every 128 tokens
    token = 5120 * 8 + 2 * 16 * 2 + 5120 * 16 * 4 // 128
    assert f["selective_scan_bytes_per_token_forward"] == token == 43_584
    assert f["selective_scan_bytes"] == 2 * 3 * 4096 * token
    assert f["selective_scan_flops"] == 2 * 3 * 2 * 3 * 5120 * 16 * 4096
    assert f["diff_attention_flops"] == 3 * 2 * 4096 * (
        2 * 15_728_640 + f["multiply_adds_per_token"]["attention_window"])
    assert f["diff_window_flops"] == 3 * 2 * 4096 \
        * f["multiply_adds_per_token"]["attention_window"]
    per_layer = 2 * (40 * 64 + 2 * 40 * 64 + 2 * 20 * 64)
    assert f["diff_attention_bytes"] == 3 * 4096 * per_layer * 3
    # the scan is bytes-bound and the maps compute-bound by the two peaks
    scan = roofline.share(f["selective_scan_flops"],
                          f["selective_scan_bytes"], 1.0, PEAKS)
    maps = roofline.share(f["diff_attention_flops"],
                          f["diff_attention_bytes"], 1.0, PEAKS)
    assert (scan[1], maps[1]) == ("memory", "compute")


def test_the_parameters_are_the_issues_697_million():
    f = flops()
    assert f["parameters"] == 697_094_272 \
        == load("configs", CONFIG + ".json")["parameters"]
    mamba = 26_214_400 + 5120 * 5 + 983_040 + 819_200 + 5120 + 81_920 \
        + 5120 + 13_107_200
    attention = 13_107_200 + 5120 + 6_553_600 + 2560 + 256 + 128
    cross = 2 * (6_553_600 + 2560) + 256 + 128
    assert (mamba, attention, cross) == (41_241_600, 19_668_864, 13_112_704)
    layers = 2 * mamba + 2 * attention + 26_214_400 + cross \
        + 6 * (78_643_200 + 10_240)
    assert layers == 633_068_672
    assert f["parameters"] == layers + 25008 * 2560 + 5120
    whole = flops(first_layer=0, layers_held=None, vocab_size=200064)
    assert whole["parameters"] == 3_852_562_944        # the published 3.8B


# -- the expressions on recorded names -------------------------------------------------

with open(os.path.join(BENCH, "tests", "phi4_trace_names.json")) as f:
    RECORDED = json.load(f)
NAMES = {k: v for k, v in RECORDED.items() if not k.startswith("_")}
with open(os.path.join(BENCH, "tests", "granite4_trace_names.json")) as f:
    GRANITE = {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def found(metric, names=None):
    spec = load("metrics", metric + ".json")
    pattern = re.compile(spec["args"]["pattern"])
    return {k for k, text in (names or NAMES).items() if pattern.search(text)}


def test_trace_names_are_the_cells():
    assert "(f32[32,1,16,5120]{" in NAMES["sscan_fwd"]      # 32 saved states
    assert NAMES["sscan_bwd"].count("f32[1,4096,16,128]{") >= 2
    assert "bf16[1,4096,5120]{" in NAMES["conv_fwd"]
    for k in ("flash_fwd_full", "flash_fwd_cross", "swa_fwd"):
        assert "(bf16[20,4096,128]{" in NAMES[k], k         # v at 128
    for k in ("flash_bwd_full", "flash_bwd_cross", "swa_bwd"):
        assert NAMES[k].count("bf16[20,4096,64]{") >= 2, k  # dq, dk at 64
    assert not [t for t in NAMES.values() if "[4096,5120,16]" in t]


def test_each_kernel_pattern_finds_its_kernels_and_no_others():
    scan = {"sscan_fwd", "sscan_bwd"}
    assert found("sscan_kernel_calls.train") == scan
    assert found("sscan_roofline_pct.train") == scan
    flash = {"flash_fwd_full", "flash_fwd_cross", "flash_bwd_full",
             "flash_bwd_cross", "swa_fwd", "swa_bwd"}
    assert found("diff_attention_kernels_ms.train") == flash
    assert found("diff_attention_roofline_pct.train") == flash
    assert found("diff_window_kernels_ms.train") == {"swa_fwd", "swa_bwd"}
    # the accepted scan patterns (Mamba-2's `ssd_`) read nothing here, and
    # this PR's read nothing in Granite's cell
    for accepted in ("ssm_scan_roofline_pct.train", "ssm_scan_kernel_ms.train",
                     "gdn_kernel_ms.train"):
        assert not found(accepted)
    assert not found("sscan_kernel_calls.train", GRANITE)
    # the accepted windowed and full patterns read this cell as they stand
    assert found("gated_swa_window_kernels_ms.train") == {"swa_fwd", "swa_bwd"}
    assert found("gated_swa_full_kernels_ms.train") == flash - {"swa_fwd",
                                                                "swa_bwd"}
    assert found("causal_conv_kernel_ms.train") == {"conv_fwd", "conv_bwd"}


def test_the_tied_tables_pattern_finds_its_ops_and_no_others():
    spec = load("metrics", "phi4_tied_table_op_ms.train.json")
    assert spec["reader"] == "trace_ops"
    pattern = re.compile(spec["args"]["pattern"])
    mine = {k for k, text in NAMES.items() if pattern.search(text)}
    assert mine == {k for k in NAMES if k.startswith("table_")}
    assert len(mine) >= 4
    for other in ("%fusion.0 = f32[2560,25008]{1,0} fusion(%p), kind=kLoop",
                  "%fusion.1 = f32[25008,2048]{1,0} fusion(%p), kind=kLoop",
                  "%fusion.2 = f32[2560,10240]{1,0} fusion(%p), "
                  "calls=%fused.25008,2560]"):
        assert not pattern.search(other), other
    assert not [k for k, text in GRANITE.items() if pattern.search(text)]


# (name scope, op type) of owners a traced step of the cell shows
OWNERS = sorted({
    ("l14.mamba", "layer_norm"), ("l14.mamba", "mul"),
    ("l16.mamba", "mul_grad"), ("l14.mamba", "slice"),
    ("l14.mamba", "causal_conv1d"), ("l16.mamba", "causal_conv1d_grad"),
    ("l14.mamba", "selective_scan"), ("l16.mamba", "selective_scan_grad"),
    ("l14.mamba", "swiglu"), ("l16.mamba", "swiglu_grad"),
    ("l14.mamba", "elementwise_add"),
    ("l15.attn", "layer_norm"), ("l15.attn", "mul"), ("l17.attn", "mul_grad"),
    ("l15.attn", "fused_attention"), ("l17.attn", "fused_attention_grad"),
    ("l15.attn", "expand"), ("l17.attn", "expand_grad"),
    ("l17.attn", "elementwise_sub"), ("l17.attn", "elementwise_sub_grad"),
    ("l17.attn", "elementwise_mul"), ("l15.attn", "elementwise_mul_grad"),
    ("l17.attn", "rms_norm"), ("l15.attn", "rms_norm_grad"),
    ("l17.attn", "scale"), ("l17.attn", "exp"), ("l17.attn", "reduce_sum"),
    ("l17.attn", "elementwise_add"),
    ("l18.gmu", "layer_norm"), ("l18.gmu", "mul"), ("l18.gmu", "mul_grad"),
    ("l18.gmu", "swiglu"), ("l18.gmu", "swiglu_grad"),
    ("l19.cross", "layer_norm_grad"), ("l19.cross", "mul"),
    ("l19.cross", "fused_attention"), ("l19.cross", "fused_attention_grad"),
    ("l19.cross", "elementwise_sub"), ("l19.cross", "rms_norm_grad"),
    ("l19.cross", "scale"),
    ("l14.mlp", "layer_norm"), ("l14.mlp", "mul"), ("l19.mlp", "mul_grad"),
    ("l15.mlp", "swiglu"), ("l16.mlp", "swiglu_grad"),
    ("", "adam"), ("", "layer_norm"), ("", "layer_norm_grad"),
    ("", "matmul"), ("", "matmul_grad"), ("", "lookup_table"),
    ("", "lookup_table_grad"), ("", "sum"),
    ("", "softmax_with_cross_entropy")})


def owned(name):
    spec = load("metrics", name + ".json")
    assert spec["reader"] == "trace_scopes"
    args = spec["args"]
    op = re.compile(args["op"]) if "op" in args else None
    scope = re.compile(args["scope"]) if "scope" in args else None
    return {(s, o) for s, o in OWNERS
            if (op is None or op.search(o))
            and (scope is None or scope.search(s))}


def test_the_scope_metrics_read_their_layers_and_nothing_else():
    for name, suffix, least in (("mamba1_mixer_op_ms.train", ".mamba", 10),
                                ("gmu_op_ms.train", ".gmu", 5),
                                ("cross_mixer_op_ms.train", ".cross", 7),
                                ("dense_mlp_op_ms.train", ".mlp", 5)):
        mine = {(s, o) for s, o in OWNERS if s.endswith(suffix)}
        assert owned(name) == mine and len(mine) >= least, name
    scan = {(s, o) for s, o in OWNERS
            if o in ("selective_scan", "selective_scan_grad")}
    assert owned("sscan_op_ms.train") == scan and len(scan) == 2
    combine = owned("diff_combine_op_ms.train")
    assert {o for _, o in combine} == {
        "elementwise_sub", "elementwise_sub_grad", "elementwise_mul",
        "elementwise_mul_grad", "rms_norm", "rms_norm_grad", "scale", "exp",
        "reduce_sum"}
    assert {s.split(".")[1] for s, _ in combine} == {"attn", "cross"}
    # the accepted files over the same scopes read this cell as they stand
    assert owned("ssm_mixer_op_ms.train") == owned("mamba1_mixer_op_ms.train")
    # and no file of this PR's is the twin of an accepted file that a test
    # of the benchmark's holds single (Olmo-Hybrid's `dense_mlp_op_ms`)
    twin = load("metrics", "dense_mlp_op_ms.train.json")
    for name in PREPARED:
        spec = load("metrics", name + ".json")
        assert (spec["reader"], spec["args"]) \
            != (twin["reader"], twin["args"]), name


def test_a_roofline_share_is_the_roofline_over_the_kernels_time():
    f = flops()
    for metric, ms, bound in (
            ("sscan_roofline_pct.train", RECORDED["_sscan_ms_a_step"],
             "memory"),
            ("diff_attention_roofline_pct.train",
             RECORDED["_flash_ms_a_step"], "compute")):
        args = load("metrics", metric + ".json")["args"]
        share, which = roofline.share(f[args["flops_key"]],
                                      f[args["bytes_key"]], ms / 1e3, PEAKS)
        assert which == bound and 0 < share < 100, metric
    # nothing where the count lacks the keys (an older program's cell)
    ctx = {"trace": lambda: {"device": 0, "steps": 1, "summary": {
        0: {"by_name": {}}}}, "flops": {"forward": 1}, "peaks": PEAKS,
        "obs": {"batch": 1}}
    args = load("metrics", "sscan_roofline_pct.train.json")["args"]
    assert roofline.read(ctx, **args) is None


def test_the_cell_and_the_configuration_are_listed_and_the_metrics_wait():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == load("workloads", CELL + ".json")
    assert len(cell["why"]) <= 200 and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    assert bench["workloads"][-1]["name"] == CELL           # at the end
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert bench["configs"][-1] == entry
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == load("configs", CONFIG + ".json")["source"]
    assert len(entry["why"]) <= 200
    for name in PREPARED:
        spec = load("metrics", name + ".json")
        assert spec["reader"] in ("trace_scopes", "trace_ops", "trace_calls",
                                  "roofline", "compile_detail"), name
        assert "not measured" not in spec["what"], name
        assert "PR 73" in spec["what"], name
    listed = [m for m in bench["per_layer"] if m["name"] in PREPARED]
    for m in listed:            # once a `benchmark` PR lists them
        assert CELL in m["workloads"] and \
            m["moves"] == "train_examples_per_s", m["name"]
    # no twin of another file
    mine = [json.dumps([load("metrics", n + ".json")["reader"],
                        load("metrics", n + ".json")["args"]],
                       sort_keys=True) for n in PREPARED]
    assert len(set(mine)) == len(PREPARED)


def test_the_counter_reader_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "grad_fanin_max": 2,
                        "selective_scan_layers": 2,
                        "selective_scan_state": 16,
                        "selective_scan_plan": "kernel",
                        "selective_scan_grid_steps": 1280,
                        "diff_attention_layers": 3, "shared_kv_readers": 1,
                        "memory_readers": 1,
                        "activation_grad_fanin_max": 4})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    for name, value in (("selective_scan_layers.train", 2.0),
                        ("selective_scan_grid_steps.train", 1280.0),
                        ("shared_kv_readers.train", 1.0),
                        ("grad_fanin_max.train", 2.0)):
        args = load("metrics", name + ".json")["args"]
        assert compile_detail.read({"system": system}, **args) == value
    older = types.SimpleNamespace(main=types.SimpleNamespace(_uid=3))
    args = load("metrics", "shared_kv_readers.train.json")["args"]
    assert compile_detail.read({"system": older}, **args) is None


# -- the data files ---------------------------------------------------------------

def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "phi4_flash_reference.py"),
        os.path.join(ROOT, "tests", "phi4_flash_reference.py"),
        shallow=False)


CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False}
CUT = {"num_hidden_layers": (6, 32), "vocab_size": (25008, 200064)}


def test_config_holds_the_catalog_numbers_and_lists_exactly_its_two_cuts():
    c = load("configs", CONFIG + ".json")
    for key, value in CATALOG.items():
        assert c[key] == value, key
    for key, (held, published) in CUT.items():
        assert (c[key], c[key + "_published"]) == (held, published), key
    assert 200064 // 8 == 25008
    assert [r.split(" ")[0] for r in c["reduced"]] == [
        "num_hidden_layers", "vocab_size"]
    assert "layers 14-19" in c["reduced"][0] \
        and "over-weighted" in c["reduced"][0]
    assert c["source"] == ("https://huggingface.co/microsoft/"
                           "Phi-4-mini-flash-reasoning/blob/main/config.json")
    args = c["build_args"]
    assert "seq_len" not in args
    assert (args["d_model"], args["d_ff"], args["n_head"], args["n_kv_head"],
            args["head_dim"], args["ssm_state"], args["conv_kernel"],
            args["expand"], args["dt_rank"], args["window"],
            args["norm_eps"]) == \
        (2560, 10240, 40, 20, 64, 16, 4, 2, 160, 512, 1e-5)  # no width cut
    assert (args["n_layer"], args["mb_per_layer"], args["first_layer"],
            args["layers_held"], args["vocab_size"]) == (32, 2, 14, 6, 25008)
    assert c["optimizer"] == {"type": "Adam",
                              "args": {"learning_rate": 1e-06}}
    assert c["amp"] is True
    assert "five pipeline stages" in c["deployment"] \
        and "eight chips" in c["deployment"]
    for key in ("the equations", "Mamba-1's sizes", "Mamba-1 initialisation",
                "biases", "the pairing", "lambda", "initialisation",
                "attention", "optimizer", "labels", "precision"):
        assert key in c["assumed"], key
    check = c["reference"]["check"]
    assert len(check["faults"]) == 21
    reference = importlib.import_module("references." + check["module"])
    assert sorted(check["faults"]) == sorted(reference.FAULTS)
    kinds = {n.split(".")[1] for n in check["gradients"] if n[0] == "l"}
    assert kinds == {"mamba", "attn", "gmu", "cross", "mlp", "norm"}
    planted = check["planted"]
    assert planted["conv_bias_std"] > 0 and planted["attention_bias_std"] > 0
    assert planted["lambda_dots"] == [0.5, -0.5]
    assert check["mask_probe"]["score_std"] == 6.0
    tiny = c["tiny"]["build_args"]
    assert (tiny["seq_len"], tiny["window"], tiny["d_model"]) == (128, 48, 64)
    assert "NOT MEASURED" not in json.dumps(c)


def test_traffic_is_granites_but_for_the_length_and_the_reference():
    mine = load("traffic", TRAFFIC + ".json")
    theirs = load("traffic", "steady_b1_s2048_granite4.json")
    for key in ("generator", "batch", "pool_batches", "feed", "in_flight",
                "warmup", "traced"):
        assert mine[key] == theirs[key], key
    assert mine["generator"] == "train_loop_reference"
    assert mine["build_args"] == {"seq_len": 4096} and mine["batch"] == 1
    check = mine["reference_check"]
    assert check["reference"] == "phi4_flash_reference"
    assert check["reference_args"] == {"q_block": 512, "token_block": 64}
    assert 0 < check["loss_atol"] < 0.02 and "PR 73" in check["loss_atol_why"]
    assert "NOT MEASURED" not in json.dumps(mine)


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
        [sys.executable,
         os.path.join(BENCH, "reference_check_phi4_flash.py"), "--tiny"],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "reference_check_phi4_flash: PASS" in out.stdout
    assert "reference_check_phi4_flash: planted" in out.stdout
    c = load("configs", CONFIG + ".json")
    for fault in c["reference"]["check"]["faults"]:
        assert f"ok   fault {fault} must NOT be judged correct" in out.stdout
