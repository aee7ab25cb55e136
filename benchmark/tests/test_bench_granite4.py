"""The granite-4.0-h-micro configuration's own pieces of the yardstick: its
FLOP, byte, share and parameter counts against numbers worked out by hand,
each prepared metric file's expression against instruction texts and owners
recorded from the cell's compiled step on the chip (PR 65) and against the
sibling cell's recorded texts, the roofline reader on hand-made inputs, the
reference kept identical to the tests' copy, the configuration against the
catalog's numbers and its two cuts, the traffic and cell files found by name,
`run.py --tiny` over the new cell both ways and
`reference_check_granite4.py --tiny`.

`BENCHMARK.json` lists the configuration and the cell. It does NOT list the
three metrics whose files are here: `per_layer` holds 128 of the 128 entries
it may (ROADMAP D18), so they wait, as the fifteen `ssm_*`, the ten `kda_*`
and Olmo-Hybrid's four files do, for a `benchmark` PR that makes room; until
then the readers are held to their files by this test and the cell reports
the metrics that carry no `workloads` list. Nothing here holds a list to its
present length."""

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
CELL = "granite_4_0_h_micro.s2048"
CONFIG = "granite_4_0_h_micro"
TRAFFIC = "steady_b1_s2048_granite4"
PREPARED = ["ssm_rule_roofline_pct.train", "ssm_heads_per_group.train",
            "tied_table_op_ms.train"]
TYPES = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def flops(seq_len=2048, **over):
    c = load("configs", CONFIG + ".json")
    module = importlib.import_module("flops." + c["flops"])
    return module.flops_per_example(
        **dict(c["build_args"], seq_len=seq_len, **over))


# -- counts by hand ---------------------------------------------------------------

def test_granite4_flops_by_hand():
    f = flops()
    per = f["multiply_adds_per_token"]
    assert per["mamba_projections"] == 2048 * 8512 + 4096 * 2048 \
        == 25_821_184
    assert 2 * 4096 + 2 * 128 + 64 == 8512
    assert per["mamba_convolution"] == 4352 * 4
    # C B^T once for the ONE group; per head the decayed tile times x and
    # the two products with the state
    assert per["mamba_scan"] == 256 * 128 + 64 * (256 * 64 + 2 * 128 * 64) \
        == 2_129_920
    assert per["attention_projections"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert per["attention"] == 2048 * 32 * 64
    assert per["mlp"] == 3 * 2048 * 8192 == 50_331_648
    assert f["multiply_adds_per_token_head"] == 2048 * 12544
    mamba = 25_821_184 + 17_408 + 2_129_920
    total = 9 * mamba + (10_485_760 + 4_194_304) + 10 * 50_331_648 \
        + 25_690_112
    assert f["forward"] == 2 * total * 2048
    assert f["forward_backward"] == 3 * f["forward"]
    # the issue's: 1.59 GFLOP a token forward, 9.8 TFLOP a step; the
    # feed-forwards 63%, the Mamba mixers 32% (scans 2.4%), attention 1.8%,
    # the head 3.2%
    assert round(f["forward"] / 2048 / 1e6) == 1591
    assert round(f["forward_backward"] / 1e12, 2) == 9.77
    assert round(100 * f["mlp_share"], 1) == 63.3
    assert round(100 * f["mamba_mixers_share"], 1) == 31.6
    assert round(100 * f["mamba_scans_share"], 1) == 2.4
    assert round(100 * f["attention_mixers_share"], 1) == 1.8
    assert round(100 * f["head_share"], 1) == 3.2
    assert f["layers"] == {"state_space": 9, "full_attention": 1}
    shares = [f[k] for k in ("mamba_mixers_share", "attention_mixers_share",
                             "mlp_share", "head_share")]
    assert abs(sum(shares) - 1) < 1e-12
    # the whole model: four periods
    whole = flops(layer_types=TYPES * 4, vocab_size=100352)
    assert whole["layers"] == {"state_space": 36, "full_attention": 4}


def test_scan_counts_by_hand():
    f = flops()
    assert f["ssd_flops"] == 9 * 3 * 2 * 2048 * 2_129_920
    # x and y 4096 each and B, C 128 each in bf16, dt and a 64 float32 each,
    # the state 64 x 64 x 128 float32 once a chunk of 256
    token = (2 * 4096 + 2 * 128) * 2 + 2 * 64 * 4 + 64 * 64 * 128 * 4 // 256
    assert token == f["ssd_bytes_per_token_forward"] == 25_600
    assert 64 * 64 * 128 * 4 // 256 == 8_192
    assert f["ssd_bytes"] == 9 * 3 * 2048 * token
    # bytes bound it: 1.73 ms a step for nine layers at 819 GB/s, 1.20 by
    # FLOPs at 197 TFLOP/s
    assert f["ssd_bytes"] / 819e9 > f["ssd_flops"] / 197e12
    assert round(f["ssd_bytes"] / 819e9 * 1e3, 2) == 1.73
    assert round(f["ssd_flops"] / 197e12 * 1e3, 2) == 1.20
    # the same keys as Nemotron-H's module, so `ssm_scan_roofline_pct.train`
    # reads either configuration's kernels through them
    nemotron = importlib.import_module("flops.nemotron_h_hybrid")
    theirs = nemotron.flops_per_example(2048)
    assert {"ssd_flops", "ssd_bytes"} <= set(theirs) & set(f)
    # one count of the chunked scan for both configurations
    mine = importlib.import_module("flops.granite_hybrid")
    assert mine.ssd_macs_per_token is nemotron.ssd_macs_per_token
    long = flops(seq_len=4096)
    assert long["ssd_flops"] == 2 * f["ssd_flops"]


def test_the_parameters_are_the_issues_772_million():
    f = flops()
    mamba = 2048 * 8512 + 4096 * 2048 + 4352 * 4 + 4352 + 3 * 64 + 4096
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    assert (mamba, attn, mlp) == (25_847_232, 10_485_760, 50_331_648)
    assert (mamba + mlp + 4096, attn + mlp + 4096) \
        == (76_182_976, 60_821_504)
    total = 9 * mamba + attn + 10 * (mlp + 2 * 2048) + 2048 + 12544 * 2048
    assert f["parameters"] == total == 772_160_448
    c = load("configs", CONFIG + ".json")
    assert c["parameters"] == total
    assert c["parameter_bytes"]["that_stay"] == 12 * total
    assert c["parameter_bytes"]["inside_a_step"] == 16 * total
    assert "76,182,976" in c["deployment"] and "60,821,504" in c["deployment"]
    assert round(total * 12 / 1e9, 3) == 9.266     # the compiled step's args
    assert round(total * 16 / 1e9, 2) == 12.35
    # untied the table counts twice; whole, the issue's 952 M, over the room
    assert flops(tie_embeddings=False)["parameters"] == total + 12544 * 2048
    whole_table = flops(vocab_size=100352)["parameters"]
    assert round(whole_table / 1e6) == 952 and whole_table * 16 / 1e9 > 15.2


# -- the expressions on recorded names -------------------------------------------------

with open(os.path.join(BENCH, "tests", "granite4_trace_names.json")) as f:
    RECORDED = json.load(f)
NAMES = {k: v for k, v in RECORDED.items() if not k.startswith("_")}
# the scan's ms a step in the builder's traced run (my chip run, PR 65)
SCAN_MS = RECORDED["_scan_ms_a_step"]
with open(os.path.join(BENCH, "tests", "nemotron_h_trace_names.json")) as f:
    NEMOTRON = {k: v for k, v in json.load(f).items()
                if not k.startswith("_")}


def owner(text):
    """(name scope, op type) of a recorded instruction, from its op_name."""
    path = re.search(r'op_name="jit\(step\)/([^"]*)"', text).group(1)
    parts = path.split("/")
    scoped = re.fullmatch(r"l\d+\.\w+", parts[0]) is not None
    return (parts[0] if scoped else "", parts[1] if scoped else parts[0])


def test_trace_names_are_the_cells():
    """The scan is XLA ops under `ssd_scan` / `ssd_scan_grad` (no `ssd_fwd`,
    no `ssd_bwd`); the convolution at 4352 channels and the gated norm over
    one group of 4096 lanes run their kernels; the attention layer the flash
    pair at 32 heads of 64."""
    assert owner(NAMES["scan_tiles"]) == ("l0.mamba", "ssd_scan")
    assert "256,256]" in NAMES["scan_tiles"]            # the decay tiles
    assert owner(NAMES["scan_grad"])[1] == "ssd_scan_grad"
    assert owner(NAMES["gates"])[1] in ("ssd_gates", "ssd_gates_grad")
    assert NAMES["conv_fwd"].startswith("%causal_conv_fwd") \
        and "bf16[1,2048,4352]{" in NAMES["conv_fwd"]
    assert NAMES["conv_bwd"].startswith("%causal_conv_bwd")
    assert NAMES["norm_fwd"].startswith("%gated_norm_fwd") \
        and "bf16[1,2048,4096]{" in NAMES["norm_fwd"]
    assert NAMES["norm_bwd"].startswith("%gated_norm_bwd") \
        and "f32[1,64,8,4096]{" in NAMES["norm_bwd"]    # 32 rows a step
    assert owner(NAMES["norm_fwd"]) == ("l0.mamba", "gated_rms_norm")
    assert NAMES["flash_fwd"].startswith("%flash_fwd")
    assert owner(NAMES["flash_fwd"]) == ("l5.attn", "fused_attention")
    assert not [k for k, text in NAMES.items()
                if re.search(r"%ssd_(fwd|bwd)", text)]


def test_the_accepted_kernel_pattern_finds_nothing_here_and_the_by_op_ones_do():
    """`ssm_scan_roofline_pct` / `ssm_scan_kernel_ms` (by kernel name) read
    nothing in this cell and Nemotron-H's kernels in its own; `ssm_scan_op_ms`
    and this PR's `ssm_rule_roofline_pct` (by owner) read both;
    `ssm_conv_op_ms` and `ssm_gated_norm_op_ms` read this cell as they
    stand once a list takes it."""
    for accepted in ("ssm_scan_roofline_pct.train",
                     "ssm_scan_kernel_ms.train"):
        pattern = re.compile(load(
            "metrics", accepted + ".json")["args"]["pattern"])
        assert not [k for k, text in NAMES.items() if pattern.search(text)]
        assert [k for k, text in NEMOTRON.items() if pattern.search(text)]
    rule = load("metrics", "ssm_rule_roofline_pct.train.json")["args"]
    assert rule["op"] == load(
        "metrics", "ssm_scan_op_ms.train.json")["args"]["op"]
    op = re.compile(rule["op"])
    assert {k for k, text in NAMES.items() if op.search(owner(text)[1])} \
        == {"scan_tiles", "scan_states", "scan_grad", "scan_grad_states"}
    assert [k for k, text in NEMOTRON.items()
            if "op_name" in text and op.search(owner(text)[1])]
    for by_op, found in (("ssm_conv_op_ms.train", {"conv_fwd", "conv_bwd"}),
                         ("ssm_gated_norm_op_ms.train",
                          {"norm_fwd", "norm_bwd"})):
        args = load("metrics", by_op + ".json")["args"]
        op = re.compile(args["op"])
        assert {k for k, text in NAMES.items()
                if op.search(owner(text)[1])} == found, by_op


def test_the_tied_tables_pattern_finds_its_ops_and_no_others():
    """By the table's shape: the cast for the head, the head's weight
    gradient (the fan-in sum of the two gradients rides in its fusion), the
    look-up's row scatter and Adam's pass as the compiled step names them
    (a trace event's name holds the operands' shapes too, so the look-up's
    gather and the head's product, which read the table, match there); not
    the cross-entropy on the logits, not another parameter's update, not
    Ouro's or Olmo-Hybrid's vocabulary."""
    spec = load("metrics", "tied_table_op_ms.train.json")
    assert spec["reader"] == "trace_ops"
    pattern = re.compile(spec["args"]["pattern"])
    found = {k for k, text in NAMES.items() if pattern.search(text)}
    assert found == {k for k in NAMES if k.startswith("table_")}
    assert len(found) >= 4
    for other in ("%fusion.0 = f32[2048,12544]{1,0} fusion(%p), kind=kLoop",
                  "%fusion.1 = f32[12544,3840]{1,0} fusion(%p), kind=kLoop",
                  "%fusion.2 = bf16[2048,49152]{1,0} fusion(%p), kind=kLoop",
                  "%fusion.3 = f32[2048,8192]{1,0} fusion(%p), "
                  "calls=%fused.12544,2048]"):
        assert not pattern.search(other), other
    accepted = re.compile(load(
        "metrics", "vocab_ops_ms.train.json")["args"]["pattern"])
    assert not [k for k, text in NAMES.items() if accepted.search(text)]


# (name scope, op type) of owners a traced step of the cell shows
OWNERS = sorted({owner(text) for text in NAMES.values()} | {
    ("l0.mamba", "mul"), ("l2.mamba", "mul_grad"), ("l1.mamba", "slice"),
    ("l0.mamba", "causal_conv1d"), ("l2.mamba", "causal_conv1d_grad"),
    ("l0.mamba", "rms_norm"), ("l3.mamba", "rms_norm_grad"),
    ("l0.mamba", "scale"), ("l0.mamba", "elementwise_add"),
    ("l5.attn", "fused_attention_grad"), ("l5.attn", "rms_norm"),
    ("l5.attn", "mul"), ("l5.attn", "mul_grad"), ("l5.attn", "expand"),
    ("l0.mlp", "mul"), ("l9.mlp", "mul_grad"), ("l1.mlp", "swiglu"),
    ("l2.mlp", "swiglu_grad"), ("l0.mlp", "rms_norm"), ("", "adam"),
    ("", "rms_norm"), ("", "rms_norm_grad"), ("", "matmul"),
    ("", "matmul_grad"), ("", "lookup_table"), ("", "lookup_table_grad"),
    ("", "sum"), ("", "softmax_with_cross_entropy")})


def owned(name):
    spec = load("metrics", name + ".json")
    assert spec["reader"] in ("trace_scopes", "roofline_by_op")
    args = spec["args"]
    op = re.compile(args["op"]) if "op" in args else None
    scope = re.compile(args["scope"]) if "scope" in args else None
    return {(s, o) for s, o in OWNERS
            if (op is None or op.search(o))
            and (scope is None or scope.search(s))}


def test_the_accepted_scope_metrics_read_the_cell_as_they_stand():
    """`ssm_mixer_op_ms` (`^l\\d+\\.mamba`), `dense_mlp_op_ms`
    (`^l\\d+\\.mlp`) and `full_mixer_op_ms` (`^l\\d+\\.attn`) find this
    cell's three kinds of sublayer, norms and scale ops included, and nothing
    outside them: a file of this PR's over any of the three scopes would be a
    twin, and none is here."""
    for name, suffix, least in (("ssm_mixer_op_ms.train", ".mamba", 10),
                                ("dense_mlp_op_ms.train", ".mlp", 5),
                                ("full_mixer_op_ms.train", ".attn", 5)):
        mine = {(s, o) for s, o in OWNERS if s.endswith(suffix)}
        assert owned(name) == mine and len(mine) >= least, name
    scan = {(s, o) for s, o in OWNERS if o in ("ssd_scan", "ssd_scan_grad")}
    assert owned("ssm_rule_roofline_pct.train") == scan and len(scan) >= 2
    assert owned("optimizer_op_ms.train") == {("", "adam")}
    # no twin of an accepted file
    mine = {json.dumps([load("metrics", n + ".json")["reader"],
                        load("metrics", n + ".json")["args"]],
                       sort_keys=True) for n in PREPARED}
    assert len(mine) == len(PREPARED)
    for other in sorted(os.listdir(os.path.join(BENCH, "metrics"))):
        if other[:-5] not in PREPARED:
            spec = load("metrics", other)
            assert json.dumps([spec["reader"], spec.get("args", {})],
                              sort_keys=True) not in mine, other


def _scopes_context(ms):
    """A context whose `trace_scopes.read` is a table lookup: what
    `roofline_by_op` adds to it is the arithmetic."""
    return {"obs": {"batch": 1}, "flops": flops(),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "_ms": ms}


def test_the_scans_roofline_is_the_roofline_over_the_owners_time(monkeypatch):
    spec = load("metrics", "ssm_rule_roofline_pct.train.json")
    assert spec["reader"] == "roofline_by_op"
    asked = []

    def table(ctx, op=None, scope=None, share=False):
        asked.append((op, scope))
        return ctx["_ms"]

    monkeypatch.setattr(trace_scopes, "read", table)
    f = flops()
    got = roofline_by_op.read(_scopes_context(SCAN_MS), **spec["args"])
    assert got == pytest.approx(100 * f["ssd_bytes"] / 819e9 / (SCAN_MS / 1e3),
                                rel=1e-9)
    assert 0 < got < 100 and asked == [(spec["args"]["op"], None)]
    assert got == pytest.approx(roofline.share(
        f["ssd_flops"], f["ssd_bytes"], SCAN_MS / 1e3,
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})[0])
    # a program without the op (the parent on another cell), a rehearsal, a
    # count without the keys: nothing, and nothing raised
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
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == load("configs", CONFIG + ".json")["source"]
    assert len(entry["why"]) <= 200
    tail, = [m for m in bench["end_to_end"]
             if m["name"] == "train_step_ms_p95"]
    assert CELL not in tail["workloads"]
    for name in PREPARED:
        spec = load("metrics", name + ".json")
        assert spec["reader"] in ("roofline_by_op", "trace_ops",
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
              event(5, {"version": 2, "grad_fanin_max": 2,
                        "layer_kinds": {"state_space": 9,
                                        "full_attention": 1},
                        "state_space_layers": 9, "state_space_groups": 1,
                        "state_space_heads_per_group": 64,
                        "state_space_chunk": 256, "ssd_plan": "xla",
                        "tied_heads": 1, "residual_scaled_sublayers": 20,
                        "attention_unrotated_layers": 1})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    spec = load("metrics", "ssm_heads_per_group.train.json")
    assert spec["args"] == {"key": "state_space_heads_per_group"}
    assert compile_detail.read({"system": system}, **spec["args"]) == 64.0
    for accepted, value in (("ssm_layers.train", 9.0),
                            ("unrotated_attention_layers.train", 1.0),
                            ("grad_fanin_max.train", 2.0)):
        args = load("metrics", accepted + ".json")["args"]
        assert compile_detail.read({"system": system}, **args) == value
    # the kernels' grid steps are tallied where the kernels run: not here
    steps = load("metrics", "ssm_grid_steps.train.json")["args"]
    assert compile_detail.read({"system": system}, **steps) is None
    older = types.SimpleNamespace(main=types.SimpleNamespace(_uid=3))
    assert compile_detail.read({"system": older}, **spec["args"]) is None


# -- the data files ---------------------------------------------------------------

def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "granite_hybrid_reference.py"),
        os.path.join(ROOT, "tests", "granite_hybrid_reference.py"),
        shallow=False)


CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": TYPES * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True}
CUT = {"num_hidden_layers": (10, 40), "vocab_size": (12544, 100352)}


def test_config_holds_the_catalog_numbers_and_lists_exactly_its_two_cuts():
    c = load("configs", CONFIG + ".json")
    for key, value in CATALOG.items():
        assert c[key] == value, key
    for key, (held, published) in CUT.items():
        assert (c[key], c[key + "_published"]) == (held, published), key
    assert 100352 // 8 == 12544
    assert len(c["reduced"]) == 2
    assert [r.split(" ")[0] for r in c["reduced"]] == [
        "num_hidden_layers", "vocab_size"]
    assert "layer_types" in c["reduced"][0] and "9 : 1" in c["reduced"][0]
    assert c["source"] == ("https://huggingface.co/ibm-granite/"
                           "granite-4.0-h-micro/blob/main/config.json")
    args = c["build_args"]
    assert "seq_len" not in args
    assert (args["d_model"], args["d_ff"], args["mamba_heads"],
            args["mamba_head_dim"], args["n_groups"], args["ssm_state"],
            args["conv_kernel"], args["chunk"], args["n_head"],
            args["n_kv_head"], args["head_dim"], args["rms_eps"]) == \
        (2048, 8192, 64, 64, 1, 128, 4, 256, 32, 8, 64, 1e-5)  # no width cut
    assert (args["embedding_multiplier"], args["residual_multiplier"],
            args["attention_multiplier"], args["logits_scaling"],
            args["tie_embeddings"]) == (12, 0.22, 0.015625, 8, True)
    assert args["layer_types"] == TYPES and args["vocab_size"] == 12544
    assert c["optimizer"] == {"type": "Adam",
                              "args": {"learning_rate": 1e-06}}
    assert c["amp"] is True
    assert "four pipeline stages" in c["deployment"] \
        and "eight chips" in c["deployment"]
    for key in ("the equations", "the order of W_in's columns",
                "the gate before the norm", "Mamba-2 initialisation",
                "initialisation", "attention", "optimizer", "labels",
                "precision"):
        assert key in c["assumed"], key
    check = c["reference"]["check"]
    assert len(check["faults"]) == 16
    reference = importlib.import_module("references." + check["module"])
    assert sorted(check["faults"]) == sorted(reference.FAULTS)
    for name in ("embed.w", "l0.mamba.in.w", "l0.mamba.A_log",
                 "l0.mamba.dt_bias", "l0.mamba.conv.b", "l0.mamba.norm.w",
                 "l4.mamba.out.w", "l5.attn.q.w", "l5.attn.k.w",
                 "l0.mlp.gate.w", "l0.mlp.up.w", "l0.mlp.down.w",
                 "final_norm.w"):
        assert name in check["gradients"], name
    assert "head.w" not in check["gradients"]           # tied
    planted = check["planted"]
    assert planted["conv_bias_std"] > 0
    assert set(planted["head_ramp"]) == {"l5.attn.q.w", "l5.attn.k.w",
                                         "l5.attn.v.w"}
    tiny = c["tiny"]["build_args"]
    assert (tiny["seq_len"], tiny["chunk"], tiny["mamba_heads"]) \
        == (128, 64, 4)
    assert "TO BE" not in json.dumps(c)


def test_traffic_is_nemotrons_but_for_the_reference():
    mine = load("traffic", TRAFFIC + ".json")
    theirs = load("traffic", "steady_b1_s2048_nemotron3.json")
    for key in ("generator", "batch", "build_args", "pool_batches", "feed",
                "in_flight", "warmup", "traced"):
        assert mine[key] == theirs[key], key
    assert mine["generator"] == "train_loop_reference"
    assert mine["build_args"] == {"seq_len": 2048} and mine["batch"] == 1
    check = mine["reference_check"]
    assert check["reference"] == "granite_hybrid_reference"
    assert check["reference_args"] == {"q_block": 512, "token_block": 64}
    assert 0 < check["loss_atol"] < 0.02 and "PR 65" in check["loss_atol_why"]
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
         os.path.join(BENCH, "reference_check_granite4.py"), "--tiny"],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "reference_check_granite4: PASS" in out.stdout
    assert "reference_check_granite4: planted" in out.stdout
    c = load("configs", CONFIG + ".json")
    for fault in c["reference"]["check"]["faults"]:
        assert f"ok   fault {fault} must NOT be judged correct" in out.stdout
