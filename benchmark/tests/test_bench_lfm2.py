"""The LFM2-8B-A1B configuration's own pieces of the yardstick: its FLOP, byte,
share and parameter counts against numbers worked out by hand, each prepared
metric file's expression against instruction texts and owners recorded from
the cell's compiled step on the chip (PR 69), the new reader
`roofline_by_scope` on hand-made inputs, the reference kept identical to the
tests' copy, the configuration against the catalog's numbers and its three
cuts, the traffic and cell files found by name, `run.py --tiny` over the new
cell both ways and `reference_check_lfm2.py --tiny`.

`BENCHMARK.json` lists the configuration and the cell. It does NOT list the
six metrics whose files are here: `per_layer` holds 128 of the 128 entries it
may (ROADMAP D18), so they wait, as the files of the four cells before this
one do, for a `benchmark` PR that makes room; until then the readers are held
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

from readers import (compile_detail, roofline, roofline_by_op,
                     roofline_by_scope, trace_scopes)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "lfm2_8b_a1b.s4096"
CONFIG = "lfm2_8b_a1b"
TRAFFIC = "steady_b1_s4096_lfm2"
PREPARED = ["short_conv_op_ms.train", "short_conv_mixer_op_ms.train",
            "short_conv_roofline_pct.train", "short_conv_layers.train",
            "short_conv_taps.train", "lfm2_tied_table_op_ms.train"]
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]
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

def test_lfm2_flops_by_hand():
    f = flops()
    per = f["multiply_adds_per_token"]
    assert per["conv_projections"] == 2048 * 6144 + 2048 * 2048 == 16_777_216
    assert per["conv_taps"] == 2048 * 3
    assert per["attention_projections"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    # QK^T and PV over the causal triangle: 2048.5 visible keys a query
    assert per["attention"] == 2 * 32 * 64 * (4096 * 4097 // 2) // 4096 \
        == 8_390_656
    assert per["dense_mlp"] == 3 * 2048 * 7168 == 44_040_192
    assert per["router"] == 2048 * 32
    # one of a token's four choices lands on the 8 of 32 held
    assert per["routed_experts"] == 4 * 8 * 3 * 2048 * 1792 // 32 \
        == 11_010_048
    assert f["multiply_adds_per_token_head"] == 2048 * 16384
    conv, attn = 16_777_216 + 6_144, 10_485_760 + 8_390_656
    total = 4 * conv + attn + 44_040_192 + 4 * (65_536 + 11_010_048) \
        + 33_554_432
    assert f["forward"] == 2 * total * 4096
    assert f["forward_backward"] == 3 * f["forward"]
    # the issue's: 5.11 TFLOP a step; the conv operators 32%, the held
    # experts and routers 21%, the dense MLP 21%, the head 16%, attention 9%
    assert round(f["forward_backward"] / 1e12, 2) == 5.11
    assert round(100 * f["conv_operators_share"]) == 32
    assert round(100 * f["experts_share"]) == 21
    assert round(100 * f["dense_mlp_share"]) == 21
    assert round(100 * f["head_share"]) == 16
    assert round(100 * f["attention_operators_share"]) == 9
    assert f["layers"] == {"short_conv": 4, "full_attention": 1, "dense": 1,
                           "moe": 4}
    shares = [f[k] for k in ("conv_operators_share",
                             "attention_operators_share", "dense_mlp_share",
                             "experts_share", "head_share")]
    assert abs(sum(shares) - 1) < 1e-12
    # the whole model: 18 conv and 6 attention layers, 2 dense and 22 sparse
    module = importlib.import_module("flops.lfm2_moe")
    whole = module.flops_per_example(4096)
    assert whole["layers"] == {"short_conv": 18, "full_attention": 6,
                               "dense": 2, "moe": 22}


def test_short_conv_cost_by_hand():
    """What ANY implementation of the operator has to compute and move, from
    the residual stream back to it: no implementation detail enters (not the
    passes XLA makes, not the kernels' blocks, not what is fused into
    what)."""
    f = flops()
    module = importlib.import_module("flops.lfm2_moe")
    cost = module.short_conv_cost(**dict(
        load("configs", CONFIG + ".json")["build_args"], seq_len=4096))
    assert (cost["flops"], cost["bytes"]) \
        == (f["short_conv_flops"], f["short_conv_bytes"])
    # both projections and the taps at 2 FLOPs a multiply-add, the gates'
    # two products a channel; the backward twice the forward
    macs = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    assert macs == 16_783_360
    assert f["short_conv_flops"] == 4 * 4096 * 3 * (2 * macs + 2 * 2048)
    # the stream read and written forward (2 d), read with its gradient and
    # the gradient written backward (3 d), bf16
    token = (2 + 3) * 2048 * 2
    assert token == f["short_conv_bytes_per_token_and_layer"] == 20_480
    # W_in and W_out in bf16 three times, their float32 gradients once, the
    # taps' weight twice and its gradient once
    weights = (2048 * 6144 + 2048 * 2048) * (3 * 2 + 4) + 3 * 2048 * 3 * 4
    assert f["short_conv_bytes"] == 4 * (4096 * token + weights)
    # bound by compute: 8.38 ms a step for four layers at 197 TFLOP/s, 1.23
    # by bytes at 819 GB/s
    assert f["short_conv_flops"] / 197e12 > f["short_conv_bytes"] / 819e9
    assert round(f["short_conv_flops"] / 197e12 * 1e3, 2) == 8.38
    assert round(f["short_conv_bytes"] / 819e9 * 1e3, 2) == 1.23
    # it is the operators' share of the step's FLOPs, but for the gates
    assert abs(f["short_conv_flops"] / f["forward_backward"]
               - f["conv_operators_share"]) < 1e-3
    long = flops(seq_len=8192)
    assert long["short_conv_flops"] == 2 * f["short_conv_flops"]
    # the keys the accepted attention and share metrics read are here too
    for key in ("full_attention_flops", "full_attention_bytes",
                "share_expert_flops", "share_expert_bytes"):
        assert f[key] > 0, key
    assert f["share_expert_rows"] == 4096       # 512 an expert under even routing
    assert f["share_expert_flops"] == 9 * 4 * 2 * 4096 * 2048 * 1792


def test_the_parameters_are_the_issues_508_million():
    f = flops()
    conv = 2048 * 6144 + 2048 * 2048 + 2048 * 3
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense, router, held = 3 * 2048 * 7168, 2048 * 32, 8 * 3 * 2048 * 1792
    assert (conv, attn, dense, router, held) \
        == (16_783_360, 10_485_888, 44_040_192, 65_536, 88_080_384)
    layer1 = conv + dense + 4096
    layer2 = attn + router + held + 4096
    layers35 = 3 * (conv + router + held + 4096)
    assert (layer1, layer2, layers35) \
        == (60_827_648, 98_635_904, 314_800_128)
    issue = layer1 + layer2 + layers35 + 16384 * 2048 + 2048
    assert issue == 507_820_160
    # `build()` also holds the four routers' biases, 32 numbers each, which
    # are parameters of the Program that are not trained
    assert f["parameters"] == issue + 4 * 32 == 507_820_288
    c = load("configs", CONFIG + ".json")
    assert c["parameters"] == f["parameters"]
    assert c["parameter_bytes"]["that_stay"] == 12 * f["parameters"]
    assert c["parameter_bytes"]["inside_a_step"] == 16 * f["parameters"]
    assert "60,827,648" in c["deployment"] and "98,635,904" in c["deployment"]
    assert round(f["parameters"] * 12 / 1e9, 2) == 6.09
    assert round(f["parameters"] * 16 / 1e9, 2) == 8.13
    # untied the table counts twice; the whole model is the published 8.3 B
    assert flops(tie_embeddings=False)["parameters"] \
        == f["parameters"] + 16384 * 2048
    module = importlib.import_module("flops.lfm2_moe")
    whole = module.flops_per_example(4096)["parameters"]
    assert round(whole / 1e9, 2) == 8.34


# -- the expressions on recorded names -------------------------------------------------

with open(os.path.join(BENCH, "tests", "lfm2_trace_names.json")) as f:
    RECORDED = json.load(f)
NAMES = {k: v for k, v in RECORDED.items() if not k.startswith("_")}
# the operators' ms a step in the builder's traced run (my chip run, PR 69)
MIXER_MS = RECORDED["_short_conv_mixer_ms_a_step"]
with open(os.path.join(BENCH, "tests", "granite4_trace_names.json")) as f:
    GRANITE = {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def owner(text):
    """(name scope, op type) of a recorded instruction, from its op_name: the
    scope is every part of the path before the op type, nested scopes joined
    by `/` as `paddle_tpu/profiler.py::parse_op_name` joins them."""
    path = re.search(r'op_name="jit\(step\)/([^"]*)"', text).group(1)
    parts = path.split("/")
    scope = []
    while parts and (re.fullmatch(r"l\d+\.\w+", parts[0])
                     or (scope and parts[0] == "core")):
        scope.append(parts.pop(0))
    return "/".join(scope), parts[0]


def test_trace_names_are_the_cells():
    """The convolution runs its kernels at `[1, 4096, 2048]` with three taps
    (a weight block of three rows, no bias row); the gates are XLA fusions
    owned by `elementwise_mul` / `elementwise_mul_grad` under
    `l<p>.conv/core`; the attention layer runs the flash pair at 32 heads of
    64; the held experts' products are `gmm` / `tgmm` calls over the
    17408-row buffer."""
    assert NAMES["conv_fwd"].startswith("%causal_conv_fwd") \
        and "bf16[1,4096,2048]{" in NAMES["conv_fwd"] \
        and "f32[3,2048]{" in NAMES["conv_fwd"]
    assert NAMES["conv_bwd"].startswith("%causal_conv_bwd") \
        and "f32[3,2048]{" in NAMES["conv_bwd"]
    assert owner(NAMES["conv_fwd"])[1] == "causal_conv1d"
    assert owner(NAMES["conv_bwd"])[1] == "causal_conv1d_grad"
    for key in ("conv_fwd", "conv_bwd", "gate", "gate_grad"):
        assert re.fullmatch(r"l[1345]\.conv/core", owner(NAMES[key])[0]), key
    assert owner(NAMES["gate"])[1] == "elementwise_mul"
    assert owner(NAMES["gate_grad"])[1] == "elementwise_mul_grad"
    assert NAMES["flash_fwd"].startswith("%flash_fwd") \
        and "bf16[32,4096,64]{" in NAMES["flash_fwd"]
    assert owner(NAMES["flash_fwd"]) == ("l2.attn", "fused_attention")
    assert NAMES["flash_bwd"].startswith("%flash_dq_flash_dkv")
    assert NAMES["gmm"].startswith("%gmm") and "[17408," in NAMES["gmm"]
    assert NAMES["tgmm"].startswith("%tgmm")
    assert re.fullmatch(r"l[2345]\.moe", owner(NAMES["gmm"])[0])
    assert owner(NAMES["in_projection"]) == ("l1.conv", "mul")


def test_the_accepted_kernel_patterns_read_the_cell_as_they_stand():
    """`causal_conv_kernel_ms` / `_calls`, `flash_fwd_ms`, `flash_bwd_ms`
    (by kernel name) find this cell's kernels once a list takes it; the
    scans', delta rules' and windowed kernels' patterns find nothing."""
    def found(metric):
        pattern = re.compile(load("metrics", metric + ".json")
                             ["args"]["pattern"])
        return {k for k, text in NAMES.items() if pattern.search(text)}

    assert found("causal_conv_kernel_ms.train") == {"conv_fwd", "conv_bwd"}
    assert found("causal_conv_kernel_calls.train") == {"conv_fwd", "conv_bwd"}
    assert found("flash_fwd_ms.train") == {"flash_fwd"}
    assert found("flash_bwd_ms.train") == {"flash_bwd"}
    assert found("share_expert_matmul_ms.train") == {"gmm", "tgmm"}
    for other in ("ssm_scan_kernel_ms.train", "gdn_kernel_ms.train",
                  "kda_scan_kernel_ms.train",
                  "window_attention_kernels_ms.train",
                  "rotary_kernel_ms.train", "tied_table_op_ms.train",
                  "vocab_ops_ms.train"):
        assert not found(other), other


def test_the_tied_tables_pattern_finds_its_ops_and_no_others():
    """By the table's shape, `[16384,2048]`: the cast for the head, the
    head's weight gradient, the look-up's row scatter and Adam's pass as the
    compiled step names them; not the logits, not another parameter's
    update, not Granite's table."""
    spec = load("metrics", "lfm2_tied_table_op_ms.train.json")
    assert spec["reader"] == "trace_ops"
    pattern = re.compile(spec["args"]["pattern"])
    found = {k for k, text in NAMES.items() if pattern.search(text)}
    assert found == {k for k in NAMES if k.startswith("table_")}
    assert len(found) >= 4
    for other in ("%fusion.0 = f32[2048,16384]{1,0} fusion(%p), kind=kLoop",
                  "%fusion.1 = bf16[1,4096,16384]{2,1,0} fusion(%p)",
                  "%fusion.2 = f32[12544,2048]{1,0} fusion(%p), kind=kLoop",
                  "%fusion.3 = f32[2048,6144]{1,0} fusion(%p), "
                  "calls=%fused.16384,2048]"):
        assert not pattern.search(other), other
    assert not [k for k, text in GRANITE.items() if pattern.search(text)]


# (name scope, op type) of owners a traced step of the cell shows
OWNERS = sorted({owner(text) for text in NAMES.values()
                 if "op_name" in text} | {
    ("l1.conv", "rms_norm"), ("l1.conv", "mul"), ("l3.conv", "mul_grad"),
    ("l1.conv", "elementwise_add"), ("l5.conv", "rms_norm_grad"),
    ("l1.conv/core", "slice"), ("l3.conv/core", "slice_grad"),
    ("l1.conv/core", "elementwise_mul"),
    ("l4.conv/core", "elementwise_mul_grad"),
    ("l1.conv/core", "causal_conv1d"), ("l5.conv/core", "causal_conv1d_grad"),
    ("l2.attn", "fused_attention"), ("l2.attn", "fused_attention_grad"),
    ("l2.attn", "rms_norm"), ("l2.attn", "rotary_embedding"),
    ("l2.attn", "mul"), ("l2.attn", "mul_grad"), ("l2.attn", "expand"),
    ("l1.mlp", "mul"), ("l1.mlp", "mul_grad"), ("l1.mlp", "swiglu"),
    ("l1.mlp", "swiglu_grad"), ("l1.mlp", "rms_norm"),
    ("l1.mlp", "elementwise_add"),
    ("l2.moe", "moe_router"), ("l3.moe", "moe_router_grad"),
    ("l2.moe", "grouped_matmul"), ("l4.moe", "grouped_matmul_grad"),
    ("l2.moe", "moe_dispatch"), ("l5.moe", "moe_combine"),
    ("l2.moe", "swiglu"), ("l2.moe", "rms_norm"), ("l2.moe", "scale"),
    ("", "adam"), ("", "rms_norm"),
    ("", "rms_norm_grad"), ("", "matmul"), ("", "matmul_grad"),
    ("", "lookup_table"), ("", "lookup_table_grad"), ("", "sum"),
    ("", "softmax_with_cross_entropy")})


def owned(name):
    spec = load("metrics", name + ".json")
    assert spec["reader"] in ("trace_scopes", "roofline_by_op",
                              "roofline_by_scope")
    args = spec["args"]
    op = re.compile(args["op"]) if "op" in args else None
    scope = re.compile(args["scope"]) if "scope" in args else None
    return {(s, o) for s, o in OWNERS
            if (op is None or op.search(o))
            and (scope is None or scope.search(s))}


def test_the_short_conv_metrics_find_the_operator_and_nothing_of_another_layer():
    """`short_conv_mixer_op_ms` and `short_conv_roofline_pct`: the whole
    `l<p>.conv` scope, norm, projections, core and add. `short_conv_op_ms`:
    what the convolution, the gates, the slices and their grads OWN there
    (all of it under `l<p>.conv/core`). None reads an op of an expert layer,
    the attention layer, the dense MLP or the optimizer."""
    mixer = owned("short_conv_mixer_op_ms.train")
    assert mixer == {(s, o) for s, o in OWNERS if ".conv" in s}
    assert len(mixer) >= 10
    assert owned("short_conv_roofline_pct.train") == mixer
    core_ops = {"causal_conv1d", "causal_conv1d_grad", "elementwise_mul",
                "elementwise_mul_grad", "slice", "slice_grad"}
    between = owned("short_conv_op_ms.train")
    assert between == {(s, o) for s, o in mixer if o in core_ops}
    assert {o for _, o in between} == core_ops
    assert all(s.endswith("/core") for s, _ in between)
    for other in [(s, o) for s, o in OWNERS if ".conv" not in s]:
        assert other not in mixer | between, other
    # the same op types in another model's layers (Trinity's output gate,
    # the slices and the silu convolution of a Mamba mixer) are not read
    specs = [load("metrics", n + ".json")["args"] for n in PREPARED[:3]]
    for scope, op in (("l1.swa", "elementwise_mul"), ("l0.mamba", "slice"),
                      ("l0.mamba", "causal_conv1d"), ("l0.gdn", "slice_grad"),
                      ("", "elementwise_mul")):
        for args in specs:
            assert not (re.search(args["scope"], scope)
                        and re.search(args.get("op", ""), op)), (scope, op)
    # the accepted scope metrics read their own sublayers of this cell
    assert owned("dense_mlp_op_ms.train") \
        == {(s, o) for s, o in OWNERS if s.endswith(".mlp")}
    assert owned("full_mixer_op_ms.train") \
        == {(s, o) for s, o in OWNERS if s.endswith(".attn")}
    assert owned("sigmoid_router_op_ms.train") \
        == {(s, o) for s, o in OWNERS if o.startswith("moe_router")}
    assert owned("optimizer_op_ms.train") == {("", "adam")}
    # Granite's convolution (silu, in front of a scan) is no short conv
    granite = {owner(t) for t in GRANITE.values() if "op_name" in t}
    scope = re.compile(load("metrics", "short_conv_mixer_op_ms.train.json")
                       ["args"]["scope"])
    assert not [s for s, _ in granite if scope.search(s)]


def test_no_prepared_file_is_a_twin_of_an_accepted_one():
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
    `roofline_by_scope` adds to it is the arithmetic."""
    return {"obs": {"batch": 1}, "flops": flops(), "peaks": PEAKS, "_ms": ms}


def test_the_operators_roofline_is_the_roofline_over_the_scopes_time(
        monkeypatch):
    spec = load("metrics", "short_conv_roofline_pct.train.json")
    assert spec["reader"] == "roofline_by_scope"
    asked = []

    def table(ctx, op=None, scope=None, share=False):
        asked.append((op, scope))
        return ctx["_ms"]

    monkeypatch.setattr(trace_scopes, "read", table)
    f = flops()
    got = roofline_by_scope.read(_scopes_context(MIXER_MS), **spec["args"])
    assert got == pytest.approx(
        100 * f["short_conv_flops"] / 197e12 / (MIXER_MS / 1e3), rel=1e-9)
    assert 0 < got < 100
    assert asked == [(None, spec["args"]["scope"])]
    assert got == pytest.approx(roofline.share(
        f["short_conv_flops"], f["short_conv_bytes"], MIXER_MS / 1e3,
        PEAKS)[0])
    # with an op beside the scope both go to the table, and with the same
    # table it is `roofline_by_op`'s number
    keys = {k: spec["args"][k] for k in ("flops_key", "bytes_key")}
    assert roofline_by_scope.read(_scopes_context(MIXER_MS), op="^mul$",
                                  scope="^l1", **keys) == got
    assert asked[-1] == ("^mul$", "^l1")
    assert roofline_by_op.read(_scopes_context(MIXER_MS), op="^mul$",
                               **keys) == got
    # a program without the scope (the parent on another cell), a rehearsal,
    # a count without the keys: nothing, and nothing raised
    assert roofline_by_scope.read(_scopes_context(None), **spec["args"]) \
        is None
    no_peaks = {**_scopes_context(5.0), "peaks": None}
    assert roofline_by_scope.read(no_peaks, **spec["args"]) is None
    older = {**_scopes_context(5.0), "flops": {"forward": 1}}
    assert roofline_by_scope.read(older, **spec["args"]) is None


def test_the_cell_and_the_configuration_are_listed_and_the_metrics_wait():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == load("workloads", CELL + ".json")
    assert len(cell["why"]) <= 200 and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    for said in ("1 x 4096", "8 of 32", "1792", "3 taps"):
        assert said in cell["why"], said
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == load("configs", CONFIG + ".json")["source"]
    assert len(entry["why"]) <= 200
    tail, = [m for m in bench["end_to_end"]
             if m["name"] == "train_step_ms_p95"]
    assert CELL not in tail["workloads"]
    for name in PREPARED:
        spec = load("metrics", name + ".json")
        assert spec["reader"] in ("trace_scopes", "roofline_by_scope",
                                  "trace_ops", "compile_detail"), name
        assert "TO FILL" not in spec["what"], name
    listed = [m for m in bench["per_layer"] if m["name"] in PREPARED]
    for m in listed:            # once a `benchmark` PR lists them
        assert CELL in m["workloads"] and \
            m["moves"] == "train_examples_per_s", m["name"]
    # no accepted metric's list names the cell: none was edited
    assert not [m["name"] for m in bench["per_layer"] if m["name"]
                not in PREPARED and CELL in m.get("workloads", [])]
    assert [m for m in bench["per_layer"] if "workloads" not in m
            and m["moves"] == "train_examples_per_s"]


def test_the_counter_reader_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "grad_fanin_max": 2,
                        "layer_kinds": {"full_attention": 1,
                                        "short_conv": 4},
                        "short_conv_layers": 4, "short_conv_taps": 3,
                        "short_conv_gates": 8, "causal_conv_plan": "kernel",
                        "attention_kv_group": 4, "attention_rotary_layers": 1,
                        "moe_experts_routed": 32, "moe_experts_held": 8,
                        "moe_router_score": "sigmoid",
                        "moe_router_bias_updates": 4, "tied_heads": 1,
                        "dense_ffn_layers": 1})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    for name, key, value in (
            ("short_conv_layers.train", "short_conv_layers", 4.0),
            ("short_conv_taps.train", "short_conv_taps", 3.0)):
        spec = load("metrics", name + ".json")
        assert spec["reader"] == "compile_detail"
        assert spec["args"] == {"key": key}
        assert compile_detail.read({"system": system}, **spec["args"]) == value
    for accepted, value in (("router_bias_updates.train", 4.0),
                            ("grad_fanin_max.train", 2.0)):
        args = load("metrics", accepted + ".json")["args"]
        assert compile_detail.read({"system": system}, **args) == value
    older = types.SimpleNamespace(main=types.SimpleNamespace(_uid=3))
    spec = load("metrics", "short_conv_layers.train.json")
    assert compile_detail.read({"system": older}, **spec["args"]) is None


# -- the data files ---------------------------------------------------------------

def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "lfm2_moe_reference.py"),
        os.path.join(ROOT, "tests", "lfm2_moe_reference.py"), shallow=False)


PUBLISHED = ["full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
             for i in range(24)]
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": PUBLISHED,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts_per_tok": 4, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True}
CUT = {"num_hidden_layers": (5, 24), "num_experts": (8, 32),
       "vocab_size": (16384, 65536)}


def test_config_holds_the_catalog_numbers_and_lists_exactly_its_three_cuts():
    c = load("configs", CONFIG + ".json")
    for key, value in CATALOG.items():
        assert c[key] == value, key
    for key, (held, published) in CUT.items():
        assert (c[key], c[key + "_published"]) == (held, published), key
    assert 65536 // 4 == 16384
    assert len(c["reduced"]) == 3
    assert [r.split(" ")[0] for r in c["reduced"]] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert "layers 1-5" in c["reduced"][0] and "top-4" in c["reduced"][1]
    assert c["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                           "blob/main/config.json")
    args = c["build_args"]
    assert "seq_len" not in args
    assert (args["d_model"], args["d_dense"], args["d_expert"],
            args["n_head"], args["n_kv_head"], args["head_dim"],
            args["conv_taps"], args["n_expert"], args["top_k"],
            args["rope_theta"], args["rms_eps"], args["route_norm_eps"],
            args["route_scale"]) == \
        (2048, 7168, 1792, 32, 8, 64, 3, 32, 4, 1e6, 1e-5, 1e-6, 1.0)
    assert (args["layer_types"], args["first_layer"], args["n_dense_layer"],
            args["vocab_size"], args["experts_held"], args["first_expert"],
            args["tie_embeddings"], args["bias_update_rate"]) \
        == (TYPES, 1, 2, 16384, 8, 0, True, 0.001)
    assert TYPES == PUBLISHED[1:6]
    assert c["optimizer"] == {"type": "Adam",
                              "args": {"learning_rate": 1e-06}}
    assert c["amp"] is True
    assert "four chips" in c["deployment"] \
        and "expert parallelism" in c["deployment"]
    for key in ("the equations", "the tied table", "the bias rule", "losses",
                "the order of W_in's columns", "initialisation", "attention",
                "optimizer", "labels", "precision"):
        assert key in c["assumed"], key
    check = c["reference"]["check"]
    assert len(check["faults"]) == 19
    reference = importlib.import_module("references." + check["module"])
    assert sorted(check["faults"]) == sorted(reference.FAULTS)
    for name in ("embed.w", "l1.conv.in.w", "l1.conv.conv.w",
                 "l1.conv.out.w", "l1.mlp.gate.w", "l1.mlp.up.w",
                 "l1.mlp.down.w", "l2.attn.q.w", "l2.attn.k.w",
                 "l2.attn.q_norm.w", "l2.router.w", "l2.experts.gate.w",
                 "l2.experts.up.w", "l2.experts.down.w", "l5.conv.in.w",
                 "final_norm.w"):
        assert name in check["gradients"], name
    assert "head.w" not in check["gradients"]           # tied
    planted = check["planted"]
    assert planted["router_bias_std"] > 0 and len(planted["tap_ramp"]) == 3
    assert set(planted["head_ramp"]) == {"l2.attn.q.w", "l2.attn.k.w",
                                         "l2.attn.v.w"}
    tiny = c["tiny"]["build_args"]
    assert (tiny["seq_len"], tiny["n_expert"], tiny["experts_held"]) \
        == (128, 16, 4)
    assert "TO FILL" not in json.dumps(c)


def test_traffic_is_trinitys_but_for_the_reference():
    mine = load("traffic", TRAFFIC + ".json")
    theirs = load("traffic", "steady_b1_s4096_trinity_mini.json")
    for key in ("batch", "build_args", "pool_batches", "feed", "in_flight",
                "warmup", "traced"):
        assert mine[key] == theirs[key], key
    assert mine["generator"] == "train_loop_reference"
    assert mine["build_args"] == {"seq_len": 4096} and mine["batch"] == 1
    check = mine["reference_check"]
    assert check["reference"] == "lfm2_moe_reference"
    assert check["reference_args"] == {"q_block": 512}
    assert 0 < check["loss_atol"] < 0.02 and "PR 69" in check["loss_atol_why"]
    assert "TO FILL" not in json.dumps(mine)


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
         os.path.join(BENCH, "reference_check_lfm2.py"), "--tiny"],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "reference_check_lfm2: PASS" in out.stdout
    assert "reference_check_lfm2: planted" in out.stdout
    c = load("configs", CONFIG + ".json")
    for fault in c["reference"]["check"]["faults"]:
        assert f"ok   fault {fault} must NOT be judged correct" in out.stdout
