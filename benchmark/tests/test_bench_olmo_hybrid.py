"""The Olmo-Hybrid-7B configuration's own pieces of the yardstick: its FLOP,
byte, share and parameter counts against numbers worked out by hand, each
prepared metric file's expression against instruction texts and owners
recorded from the cell's compiled step on the chip (PR 63) and against the
sibling cell's recorded texts, the roofline reader on hand-made inputs, the
reference kept identical to the tests' copy, the configuration against the
catalog's numbers and its three cuts, the traffic and cell files found by
name, `run.py --tiny` over the new cell both ways and
`reference_check_olmo_hybrid.py --tiny`.

`BENCHMARK.json` lists the configuration and the cell. It does NOT list the
four metrics whose files are here: `per_layer` holds 128 of the 128 entries it
may (ROADMAP D18), so they wait, as the fifteen `ssm_*` and the ten `kda_*`
files do, for a `benchmark` PR that makes room; until then the readers are
held to their files by this test and the cell reports the metrics that carry
no `workloads` list. Nothing here holds a list to its present length."""

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
CELL = "olmo_hybrid_7b.s4096"
CONFIG = "olmo_hybrid_7b"
TRAFFIC = "steady_b1_s4096_olmo_hybrid"
SCOPES = ["gdn_mixer_op_ms.train", "dense_mlp_op_ms.train"]
PREPARED = SCOPES + ["gdn_rule_roofline_pct.train",
                     "attention_heads_held.train"]


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def flops(seq_len=4096, **over):
    c = load("configs", CONFIG + ".json")
    module = importlib.import_module("flops." + c["flops"])
    return module.flops_per_example(
        **dict(c["build_args"], seq_len=seq_len, **over))


# -- counts by hand ---------------------------------------------------------------

def test_olmo_hybrid_flops_by_hand():
    f = flops()
    per = f["multiply_adds_per_token"]
    assert per["gdn_projections"] == 3840 * (1440 + 1440 + 2880 + 2880) \
        + 2880 * 3840 + 2 * 3840 * 15 == 44_352_000
    # two Gram tiles, the solve's two right-hand sides, the scores times v',
    # w S, q S and the state's update, a head; 15 heads of 96 / 192
    assert per["gdn_rule"] == 15 * (3 * 96 * 192 + 64 * (2 * 96 + 192)
                                    + 32 * (96 + 192)) == 1_336_320
    assert per["attention_projections"] == 4 * 3840 * 1920
    assert per["attention"] == 4096 * 15 * 128
    assert per["mlp"] == 3 * 3840 * 11008 == 126_812_160
    assert f["multiply_adds_per_token_head"] == 3840 * 12544
    total = 3 * (44_352_000 + 1_336_320) + (29_491_200 + 7_864_320) \
        + 4 * 126_812_160 + 48_168_960
    assert f["forward"] == 2 * total * 4096
    assert f["forward_backward"] == 3 * f["forward"]
    # 1460 MFLOP a token forward, 17.9 TFLOP a step; the feed-forwards 69.5%
    assert round(f["forward"] / 4096 / 1e6) == 1460
    assert round(f["forward_backward"] / 1e12, 2) == 17.94
    assert round(100 * f["mlp_share"], 1) == 69.5
    assert round(100 * f["gdn_layers_share"], 1) == 18.8
    assert f["layers"] == {"linear_attention": 3, "full_attention": 1}
    shares = [f[k] for k in ("gdn_layers_share", "attention_layers_share",
                             "mlp_share", "head_share")]
    assert abs(sum(shares) - 1) < 1e-12


def test_the_whole_layers_shares_are_the_deployments():
    """At 30 heads (what the deployment's two chips compute together) the
    feed-forwards carry 56.1% and the delta-rule mixers 30.3%: what the
    configuration's `deployment` says the cut does to the step's shape."""
    whole = flops(heads_held=None)
    assert round(100 * whole["mlp_share"], 1) == 56.1
    assert round(100 * whole["gdn_layers_share"], 1) == 30.3
    assert whole["multiply_adds_per_token"]["gdn_rule"] == 2 * 1_336_320
    text = load("configs", CONFIG + ".json")["deployment"]
    assert "69.5%" in text and "56.1%" in text and "30.3%" in text


def test_rule_counts_by_hand():
    f = flops()
    assert f["gdn_flops"] == 3 * 3 * 2 * 4096 * 1_336_320
    # q, k 1440 each and v, o 2880 each in bf16, g and beta 15 float32 each,
    # the saved state 15 x 96 x 192 float32 once a chunk of 64
    token = 2 * (1440 + 2880) * 2 + 2 * 15 * 4 + 15 * 96 * 192 * 4 // 64
    assert token == f["gdn_bytes_per_token_forward"] == 34_680
    assert 15 * 96 * 192 * 4 // 64 == 17_280
    assert f["gdn_bytes"] == 3 * 3 * 4096 * token
    # bytes bound it: 0.173 ms a layer forward at 819 GB/s, 1.56 ms a step
    assert round(4096 * token / 819e9 * 1e3, 3) == 0.173
    assert f["gdn_bytes"] / 819e9 > f["gdn_flops"] / 197e12
    assert round(f["gdn_bytes"] / 819e9 * 1e3, 2) == 1.56
    long = flops(seq_len=8192)
    assert long["gdn_flops"] == 2 * f["gdn_flops"]


def test_the_parameters_are_the_issues_766_million():
    f = flops()
    gdn = 3840 * (1440 + 1440 + 2880 + 2880) + 2880 * 3840 + 2 * 3840 * 15 \
        + 5760 * 4 + 2 * 15 + 192
    attn = 4 * 3840 * 1920 + 2 * 1920
    mlp = 3 * 3840 * 11008
    assert (gdn, attn, mlp) == (44_375_262, 29_495_040, 126_812_160)
    total = 3 * gdn + attn + 4 * (mlp + 2 * 3840) + 3840 + 2 * 12544 * 3840
    assert f["parameters"] == total == 766_241_946
    text = load("configs", CONFIG + ".json")["deployment"]
    assert "766,241,946" in text and "44,375,262" in text
    assert round(total * 12 / 1e9, 3) == 9.195     # the compiled step's args
    assert round(total * 16 / 1e9, 2) == 12.26
    # whole layers: 30 heads of every mixer, the issue's 928.9 M, over the room
    whole = flops(heads_held=None)["parameters"]
    assert round(whole / 1e6, 1) == 928.9 and whole * 16 / 1e9 > 14.8


# -- the expressions on recorded names -------------------------------------------------

with open(os.path.join(BENCH, "tests", "olmo_hybrid_trace_names.json")) as f:
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
    assert owner(NAMES["rule_solve"]) == ("l0.gdn", "gated_delta_rule")
    assert "f32[1,15,64,1,64,64]{" in NAMES["rule_solve"]   # XLA's solve
    assert "f32[1,15,96,192]{" in NAMES["rule_scan"]        # the state
    assert owner(NAMES["rule_scan"])[1] == "gated_delta_rule"
    assert owner(NAMES["rule_grad"])[1] == "gated_delta_rule_grad"
    assert owner(NAMES["rule_grad_scan"])[1] == "gated_delta_rule_grad"
    assert owner(NAMES["gates"])[1] == "delta_rule_gates"
    assert owner(NAMES["norm"])[1] == "gated_rms_norm"      # XLA ops at 192
    assert owner(NAMES["norm_grad"])[1] == "gated_rms_norm_grad"
    assert NAMES["conv_fwd"].startswith("%causal_conv_fwd") \
        and "bf16[1,4096,5760]{" in NAMES["conv_fwd"]
    assert NAMES["conv_bwd"].startswith("%causal_conv_bwd")
    assert NAMES["flash_fwd"].startswith("%flash_fwd")
    assert "bf16[1,4096,1920]{" in NAMES["flash_fwd"]       # token-major
    assert "f32[15,1,4096]{" in NAMES["flash_fwd"]          # 15 heads' Lse
    assert owner(NAMES["flash_fwd"]) == ("l3.attn", "fused_attention")
    # off the lane tile the rule and its output norm are XLA ops on the
    # chip: no custom call of their own
    assert not [k for k, text in NAMES.items()
                if re.search(r"%(gdn_fwd|gdn_bwd|gated_norm_fwd)", text)]


def test_the_accepted_kernel_patterns_find_nothing_here_and_the_by_op_ones_do():
    """`gdn_kernel_ms` / `gdn_scan_ms` (by kernel name, by Qwen3-Next's
    shapes) read nothing in this cell; `gdn_op_ms`, `causal_conv_*` and
    `gated_norm_op_ms` (by owner, by the convolution's name) would read it as
    they stand once a list takes the cell."""
    for accepted in ("gdn_kernel_ms.train", "gdn_scan_ms.train"):
        pattern = re.compile(load(
            "metrics", accepted + ".json")["args"]["pattern"])
        assert not [k for k, text in NAMES.items() if pattern.search(text)]
    conv = re.compile(load(
        "metrics", "causal_conv_kernel_ms.train.json")["args"]["pattern"])
    assert {k for k, text in NAMES.items() if conv.search(text)} \
        == {"conv_fwd", "conv_bwd"}
    for by_op, found in (("gdn_op_ms.train", {"rule_solve", "rule_scan",
                                              "rule_grad", "rule_grad_scan"}),
                         ("gated_norm_op_ms.train", {"norm", "norm_grad"})):
        op = re.compile(load("metrics", by_op + ".json")["args"]["op"])
        assert {k for k, text in NAMES.items()
                if op.search(owner(text)[1])} == found
    rule = load("metrics", "gdn_rule_roofline_pct.train.json")["args"]
    assert rule["op"] == load("metrics", "gdn_op_ms.train.json")["args"]["op"]


# (name scope, op type) of owners a traced step of the cell shows
OWNERS = sorted({owner(text) for text in NAMES.values()} | {
    ("l0.gdn", "mul"), ("l2.gdn", "mul_grad"), ("l0.gdn", "concat"),
    ("l1.gdn", "slice"), ("l0.gdn", "causal_conv1d"),
    ("l2.gdn", "causal_conv1d_grad"), ("l1.gdn", "delta_rule_gates_grad"),
    ("l3.attn", "fused_attention_grad"), ("l3.attn", "rms_norm"),
    ("l3.attn", "rms_norm_grad"), ("l3.attn", "mul"), ("l3.attn", "mul_grad"),
    ("l0.mlp", "mul"), ("l3.mlp", "mul_grad"), ("l1.mlp", "swiglu"),
    ("l2.mlp", "swiglu_grad"), ("", "adam"), ("", "rms_norm"),
    ("", "rms_norm_grad"), ("", "elementwise_add"), ("", "lookup_table_grad"),
    ("", "softmax_with_cross_entropy")})


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
    for name, suffix, least in (("gdn_mixer_op_ms.train", ".gdn", 12),
                                ("dense_mlp_op_ms.train", ".mlp", 4)):
        mine = {(s, o) for s, o in OWNERS if s.endswith(suffix)}
        assert owned(name) == mine and len(mine) >= least, name
    # the attention layer's scope is the accepted `full_mixer_op_ms.train`'s:
    # a file of this PR's over `^l\d+\.attn` would be its twin, and is not here
    accepted = re.compile(load(
        "metrics", "full_mixer_op_ms.train.json")["args"]["scope"])
    assert {(s, o) for s, o in OWNERS if accepted.search(s)} \
        == {(s, o) for s, o in OWNERS if s.endswith(".attn")}
    rule = {(s, o) for s, o in OWNERS
            if o in ("gated_delta_rule", "gated_delta_rule_grad")}
    assert owned("gdn_rule_roofline_pct.train") == rule and len(rule) >= 2
    # no twin of an accepted file: the two scopes are this PR's own
    mine = {json.dumps(load("metrics", n + ".json")["args"], sort_keys=True)
            for n in PREPARED}
    for other in sorted(os.listdir(os.path.join(BENCH, "metrics"))):
        if other[:-5] not in PREPARED:
            spec = load("metrics", other)
            assert json.dumps(spec.get("args", {}), sort_keys=True) \
                not in mine or spec["reader"] == "roofline", other
    for scope in ("l0.swa", "l3.mla", "l3.kda", "l1.moe", "l0.mamba", ""):
        for name in SCOPES:
            assert not re.search(load(
                "metrics", name + ".json")["args"]["scope"], scope)


def _scopes_context(ms):
    """A context whose `trace_scopes.read` is a table lookup: what
    `roofline_by_op` adds to it is the arithmetic."""
    return {"obs": {"batch": 1}, "flops": flops(),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "_ms": ms}


def test_the_rules_roofline_is_the_roofline_over_the_owners_time(monkeypatch):
    spec = load("metrics", "gdn_rule_roofline_pct.train.json")
    assert spec["reader"] == "roofline_by_op"
    asked = []

    def table(ctx, op=None, scope=None, share=False):
        asked.append((op, scope))
        return ctx["_ms"]

    monkeypatch.setattr(trace_scopes, "read", table)
    f = flops()
    # bytes bound the rule: 1.56 ms a step; PR 63's trace gives it 27.593
    got = roofline_by_op.read(_scopes_context(27.593), **spec["args"])
    assert got == pytest.approx(100 * f["gdn_bytes"] / 819e9 / 27.593e-3,
                                rel=1e-9)
    assert 5.6 < got < 5.7 and asked == [(spec["args"]["op"], None)]
    assert got == pytest.approx(roofline.share(
        f["gdn_flops"], f["gdn_bytes"], 27.593e-3,
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
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "linear_num_key_heads", "linear_num_value_heads", "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == load("configs", CONFIG + ".json")["source"]
    assert len(entry["why"]) <= 200
    tail, = [m for m in bench["end_to_end"]
             if m["name"] == "train_step_ms_p95"]
    assert CELL not in tail["workloads"]
    for name in PREPARED:
        spec = load("metrics", name + ".json")
        assert spec["reader"] in ("roofline_by_op", "trace_scopes",
                                  "compile_detail"), name
        assert "TO BE READ" not in spec["what"], name
    listed = [m for m in bench["per_layer"] if m["name"] in PREPARED]
    for m in listed:            # once a `benchmark` PR lists them
        assert CELL in m["workloads"] and \
            m["moves"] == "train_examples_per_s", m["name"]
    assert [m for m in bench["per_layer"] if "workloads" not in m
            and m["moves"] == "train_examples_per_s"]


def test_the_counter_reader_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "grad_fanin_max": 1,
                        "layer_kinds": {"linear_attention": 3,
                                        "full_attention": 1},
                        "linear_attention_head_dims": [96, 192],
                        "delta_rule_beta_scale": 2.0,
                        "attention_heads_held": 15, "attention_heads": 30,
                        "residual_out_norms": 8, "gdn_plan": "xla"})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    spec = load("metrics", "attention_heads_held.train.json")
    assert spec["args"] == {"key": "attention_heads_held"}
    assert compile_detail.read({"system": system}, **spec["args"]) == 15.0
    accepted = load("metrics", "residual_out_norms.train.json")
    assert compile_detail.read({"system": system}, **accepted["args"]) == 8.0
    older = types.SimpleNamespace(main=types.SimpleNamespace(_uid=3))
    assert compile_detail.read({"system": older}, **spec["args"]) is None


# -- the data files ---------------------------------------------------------------

def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "olmo_hybrid_reference.py"),
        os.path.join(ROOT, "tests", "olmo_hybrid_reference.py"),
        shallow=False)


PERIOD = ["linear_attention"] * 3 + ["full_attention"]
CATALOG = {
    "model_type": "olmo_hybrid", "hidden_size": 3840,
    "intermediate_size": 11008, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": PERIOD * 8, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
CUT = {"num_hidden_layers": (4, 32), "num_attention_heads": (15, 30),
       "num_key_value_heads": (15, 30), "linear_num_key_heads": (15, 30),
       "linear_num_value_heads": (15, 30), "vocab_size": (12544, 100352)}


def test_config_holds_the_catalog_numbers_and_lists_exactly_its_three_cuts():
    c = load("configs", CONFIG + ".json")
    for key, value in CATALOG.items():
        assert c[key] == value, key
    for key, (held, published) in CUT.items():
        assert (c[key], c[key + "_published"]) == (held, published), key
    assert 100352 // 8 == 12544
    assert len(c["reduced"]) == 3
    assert [r.split(" ")[0] for r in c["reduced"]] == [
        "num_hidden_layers", "num_attention_heads", "vocab_size"]
    for key in ("num_key_value_heads", "linear_num_key_heads",
                "linear_num_value_heads", "30 -> 15 HELD"):
        assert key in c["reduced"][1], key
    assert c["source"] == ("https://huggingface.co/allenai/Olmo-Hybrid-7B/"
                           "blob/main/config.json")
    args = c["build_args"]
    assert "seq_len" not in args
    assert (args["d_model"], args["d_ff"], args["head_dim"], args["key_dim"],
            args["value_dim"], args["conv_kernel"], args["rms_eps"]) == \
        (3840, 11008, 128, 96, 192, 4, 1e-6)      # no width is cut
    assert (args["n_head"], args["heads_held"], args["n_layer"]) == \
        (30, 15, 4)
    assert args["layer_types"] == PERIOD and args["rope_theta"] is None
    assert args["allow_neg_eigval"] is True
    assert c["optimizer"] == {"type": "Adam",
                              "args": {"learning_rate": 1e-06}}
    assert "2 chips share each layer's mixers by heads" in c["deployment"]
    for key in ("the block", "QK-norm", "rope_theta", "GatedDeltaNet",
                "GatedDeltaNet's initial values", "initialisation",
                "optimizer", "labels", "precision"):
        assert key in c["assumed"], key
    assert "null" in c["assumed"]["rope_theta"] \
        and "500000" in c["assumed"]["rope_theta"]
    check = c["reference"]["check"]
    assert len(check["faults"]) == 12
    reference = importlib.import_module(
        "references." + check["module"])
    assert sorted(check["faults"]) == sorted(reference.FAULTS)
    for name in ("l0.gdn.q.w", "l0.gdn.b.w", "l0.gdn.A_log", "l3.attn.q.w",
                 "l3.attn.q_norm.w", "l0.mlp.up.w", "l0.mixer_norm.w",
                 "l0.mlp_norm.w", "embed.w", "final_norm.w", "head.w"):
        assert name in check["gradients"], name
    tiny = c["tiny"]["build_args"]
    assert (tiny["seq_len"], tiny["n_head"], tiny["heads_held"]) \
        == (128, 4, 2)
    assert "TO BE" not in json.dumps(c)


def test_traffic_is_qwen3_nexts_but_for_the_pool_and_the_reference():
    mine = load("traffic", TRAFFIC + ".json")
    theirs = load("traffic", "steady_b1_s4096_qwen3_next.json")
    for key in ("generator", "batch", "build_args", "feed", "in_flight",
                "warmup", "traced"):
        assert mine[key] == theirs[key], key
    assert mine["generator"] == "train_loop_reference"
    assert mine["pool_batches"] == 128 and mine["in_flight"] == 1
    check = mine["reference_check"]
    assert check["reference"] == "olmo_hybrid_reference"
    assert check["reference_args"] == {"q_block": 512, "token_block": 64}
    assert 0 < check["loss_atol"] < 0.02 and "PR 63" in check["loss_atol_why"]
    assert "TO BE" not in json.dumps(mine)


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
         os.path.join(BENCH, "reference_check_olmo_hybrid.py"), "--tiny"],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "reference_check_olmo_hybrid: PASS" in out.stdout
    assert "reference_check_olmo_hybrid: planted" in out.stdout
    c = load("configs", CONFIG + ".json")
    rehearsed = c["tiny"]["reference"]["check"]["faults"]
    assert sorted(rehearsed + ["gate_before_norm"]) \
        == sorted(c["reference"]["check"]["faults"])
    for fault in rehearsed:
        assert f"ok   fault {fault} must NOT be judged correct" in out.stdout
