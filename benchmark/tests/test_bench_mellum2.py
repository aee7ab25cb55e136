"""The Mellum2 configuration's own pieces of the yardstick: its FLOP, band
and share counts against numbers worked out by hand, each new metric's
pattern against instruction text at the cell's shapes (recorded from the
chip's trace of the cell, PR 40) on a hand-made event list: the windowed
patterns find the windowed calls and not the full ones, and the reverse; the
scope metrics' expressions against the owners the chip's table showed, the
counters' reader on a hand-made observatory, the reference kept identical to
the tests' copy, the configuration against the catalog's numbers, `run.py
--tiny` over the new cell both ways and `reference_check_mellum2.py --tiny`.
The new `per_layer` entries are found BY NAME, wherever later PRs put
theirs."""

import filecmp
import importlib
import json
import os
import re
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
CELL = "mellum2_12b_a2_5b.s8192"
CONFIG = "mellum2_12b_a2_5b"
TRAFFIC = "steady_b1_s8192_mellum2"
NEW = ["window_attention_kernels_ms.train", "window_attention_calls.train",
       "window_attention_roofline_pct.train",
       "full_attention_kernels_ms.train", "full_attention_roofline_pct.train",
       "swa_mixer_op_ms.train", "full_mixer_op_ms.train",
       "kv_repeat_op_ms.train", "rotary_op_ms.train",
       "swa_moe_expert_matmul_ms.train",
       "swa_moe_expert_matmul_roofline_pct.train",
       "swa_moe_layout_op_ms.train", "window_attention_layers.train",
       "window_tiles_computed.train"]


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def counts_module():
    return importlib.import_module(
        "flops." + load("configs", CONFIG + ".json")["flops"])


def flops(seq_len=8192, **over):
    c = load("configs", CONFIG + ".json")
    return counts_module().flops_per_example(
        **dict(c["build_args"], seq_len=seq_len, **over))


def test_visible_pairs_by_hand():
    pairs = counts_module().visible_pairs
    # a window of 1024 over 8192: the first 1024 rows see 1, 2, ..., 1024
    # keys, the other 7168 see 1024 each
    assert pairs(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024 == 7_864_832
    assert pairs(8192) == 8192 * 8193 // 2 == 33_558_528
    assert pairs(8192, 8192) == pairs(8192, 10 ** 6) == pairs(8192)
    assert pairs(4, 2) == 1 + 2 + 2 + 2 and pairs(4, 1) == 4
    # the band is 23.4% of the triangle; whole 512 x 512 tiles at its edges
    # compute 45 of 136: 33.1% (1024 x 1024 tiles: 15 of 36, 41.7%)
    assert pairs(8192, 1024) / pairs(8192) == pytest.approx(0.2344, abs=1e-4)
    assert 45 / 136 == pytest.approx(0.3309, abs=1e-4)
    assert 15 / 36 == pytest.approx(0.4167, abs=1e-4)
    # brute force at a small size
    assert pairs(37, 5) == sum(1 for i in range(37) for j in range(37)
                               if 0 <= i - j < 5)


def test_mellum2_flops_by_hand():
    # multiply-adds a token. W_q 2304 x (32 x 128), W_k and W_v 2304 x
    # (4 x 128), W_o (32 x 128) x 2304
    projections = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    assert projections == 21_233_664                     # "21.23 M"
    # QK^T and PV over the visible pairs, 32 heads of 128, a token's mean
    window = 2 * 32 * 128 * 7_864_832 // 8192
    full = 2 * 32 * 128 * 33_558_528 // 8192
    assert (window, full) == (7_864_832, 33_558_528)     # 8192 = 2 x 32 x 128
    router = 2304 * 64
    routed = 8 * 8 * 3 * 2304 * 896 // 64               # one whole expert
    assert (router, routed) == (147_456, 6_193_152)
    head = 2304 * 12288
    per_token = 4 * projections + 3 * window + full \
        + 4 * (router + routed) + head
    got = flops()
    assert got["multiply_adds_per_token"] == {
        "projections": projections, "window_attention": window,
        "full_attention": full, "router": router, "routed_experts": routed}
    assert got["forward"] == 2 * 8192 * per_token
    assert got["forward_backward"] == 3 * got["forward"]
    assert got["forward_backward"] / 1e12 == pytest.approx(9.622, abs=0.001)
    assert got["positions_per_example"] == 8192
    assert got["layers"] == {"window_attention": 3, "full_attention": 1}
    assert got["mixers_share"] == pytest.approx(0.726, abs=1e-3)
    assert got["attention_kernels_share"] == pytest.approx(0.292, abs=1e-3)
    assert got["experts_share"] == pytest.approx(0.130, abs=1e-3)
    assert got["head_share"] == pytest.approx(0.145, abs=1e-3)
    # run as full causal layers the three windowed ones would cost 4.3 times
    # what they cost
    assert full / window == pytest.approx(4.267, abs=1e-3)
    # all 64 experts held: eight whole experts a token
    assert flops(experts_held=None)["multiply_adds_per_token"][
        "routed_experts"] == 8 * 3 * 2304 * 896
    # the published depth: 21 windowed layers and 7 full ones
    assert flops(n_layer=28)["layers"] == {"window_attention": 21,
                                           "full_attention": 7}
    assert flops(n_layer=5, layer_types=["full_attention",
                                         "sliding_attention"])["layers"] == {
        "window_attention": 2, "full_attention": 3}
    # at 4096 tokens the band is 44% of the triangle
    short = flops(seq_len=4096)
    assert short["window_visible_pairs"] / short["full_visible_pairs"] == \
        pytest.approx(0.4374, abs=1e-3)


def test_attention_counts_by_hand():
    got = flops()
    # seven T x T products a head (scores, context; scores again, dP, dV,
    # dK, dQ), 2 FLOPs a multiply-add, the visible pairs, 128 wide, 32
    # heads; three windowed layers, one full
    assert got["window_attention_flops"] == \
        7 * 2 * 7_864_832 * 128 * 32 * 3 == 1_353_002_778_624
    assert got["full_attention_flops"] == \
        7 * 2 * 33_558_528 * 128 * 32 == 1_924_380_229_632
    # q, dq, Out, dOut and the repeated k, v, dk, dv [8192, 32 x 128] once
    # each in bf16
    layer = 8 * 8192 * 32 * 128 * 2
    assert got["window_attention_bytes"] == 3 * layer == 1_610_612_736
    assert got["full_attention_bytes"] == layer == 536_870_912
    # the products bound both: 6.9 ms and 9.8 ms against 2.0 and 0.66
    assert got["window_attention_flops"] / 197e12 == pytest.approx(
        6.868e-3, rel=1e-3)
    assert got["full_attention_flops"] / 197e12 == pytest.approx(
        9.768e-3, rel=1e-3)
    assert got["window_attention_bytes"] / 819e9 == pytest.approx(
        1.967e-3, rel=1e-3)
    # a kernel that computed every one of the band's 45 tiles of 512 x 512
    # at the MXU's peak would read 7.86 M of 11.8 M pairs: 66.7% of its
    # roofline (50% at 15 tiles of 1024 x 1024)
    assert 7_864_832 / (45 * 512 * 512) == pytest.approx(0.6667, abs=0.001)
    assert 7_864_832 / (15 * 1024 * 1024) == pytest.approx(0.50, abs=0.001)


def test_share_expert_counts_by_hand():
    got = flops()
    rows = 8192 * 8 * 8 // 64
    assert rows == got["share_expert_rows"] == 8192      # 1024 an expert
    # nine products a layer, four layers, each rows x 2304 x 896
    assert got["share_expert_flops"] == 36 * 2 * 8192 * 2304 * 896 == \
        1_217_623_228_416
    one = (8192 * 2304 + 8192 * 896) * 2
    assert got["share_expert_bytes"] == 36 * one == 1_887_436_800
    assert got["share_expert_flops"] / 197e12 == pytest.approx(6.181e-3,
                                                                rel=1e-3)
    # the layout's rows, of which the held groups use about 8192 + padding
    assert 8192 * 8 + 8 * 128 == 66560


OPS = tr.OPS_LINE
D0 = "/device:TPU:0"
# instruction texts of the cell's step (see the file's "_from")
with open(os.path.join(BENCH, "tests", "mellum2_trace_names.json")) as f:
    NAMES = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
SWA, FULL = ("swa_fwd", "swa_dq", "swa_dkv"), ("fwd", "dq", "dkv")
MS = {"swa_fwd": 2.25, "swa_dq": 2.5, "swa_dkv": 3.0,       # 7.75 a layer
      "fwd": 5.5, "dq": 6.5, "dkv": 7.5,                     # 19.5
      "gmm": 0.75, "tgmm": 0.25,
      "rope": 0.75, "kv_repeat": 0.125, "while": 0.5, "copy_done": 0.125}


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
    for key in SWA + FULL:
        assert "bf16[32,8192,128]{" in NAMES[key], key
    for key in SWA:
        assert NAMES[key].startswith("%swa_flash_" + key[4:]), key
    for key in FULL:
        assert NAMES[key].startswith("%flash_" + key), key
    assert "bf16[66560," in NAMES["gmm"] and "bf16[8,896,2304]" in \
        NAMES["tgmm"]
    assert "bf16[4,8,8192,128]" in NAMES["kv_repeat"]


@pytest.mark.parametrize("name,found", [
    ("window_attention_kernels_ms.train", SWA),
    ("full_attention_kernels_ms.train", FULL),
    ("swa_moe_expert_matmul_ms.train", ("gmm", "tgmm"))])
def test_cell_pattern_finds_its_ops_and_no_others(name, found):
    pattern = re.compile(load("metrics", name + ".json")["args"]["pattern"])
    hit = {key for key, text in NAMES.items() if pattern.search(text)}
    assert hit == set(found)
    assert metric(name) == pytest.approx(sum(MS[k] for k in found))


@pytest.mark.parametrize("text,windowed", [
    ("%swa_flash_fwd.3 = (bf16[32,8192,128]{2,1,0}) custom-call(", True),
    ("%swa_flash_fwd_onepass = (bf16[8,256,64]{2,1,0}) custom-call(", True),
    ("%swa_flash_dq_flash_dkv.2 = (bf16[8,256,64]{2,1,0}) custom-call(",
     True),
    ("%jvp_swa_flash_fwd_.1 = (bf16[8,256,64]{2,1,0}) custom-call(", True),
    ("%flash_fwd.1 = (bf16[32,8192,128]{2,1,0}) custom-call(", False),
    ("%flash_fwd_onepass.7 = (bf16[8,256,64]{2,1,0}) custom-call(", False),
    ("%flash_dq_flash_dkv = (bf16[8,256,64]{2,1,0}) custom-call(", False),
    ("%jvp_flash_fwd_.1 = (bf16[8,256,64]{2,1,0}) custom-call(", False),
    ("flash_dkv.12 = (bf16[8,256,64]{2,1,0}) custom-call(", False)])
def test_one_expression_tells_windowed_calls_from_full_ones(text, windowed):
    """Every kernel name `ops/pallas_attention.py` can give, with the
    prefixes and suffixes XLA adds: each is found by exactly one of the two
    patterns; the accepted patterns of the other cells (`flash_(fwd|dq|dkv)`
    anywhere in the name) find both."""
    swa = re.compile(load(
        "metrics", "window_attention_kernels_ms.train.json")["args"]["pattern"])
    full = re.compile(load(
        "metrics", "full_attention_kernels_ms.train.json")["args"]["pattern"])
    both = re.compile(load(
        "metrics", "attention_kernels_ms.train.json")["args"]["pattern"])
    assert bool(swa.search(text)) is windowed
    assert bool(full.search(text)) is not windowed
    assert both.search(text)
    for name in ("window_attention_calls.train",
                 "window_attention_roofline_pct.train"):
        assert load("metrics", name + ".json")["args"]["pattern"] == \
            swa.pattern
    assert load("metrics", "full_attention_roofline_pct.train.json")[
        "args"]["pattern"] == full.pattern


def test_call_counts_and_roofline_shares_on_a_hand_made_trace():
    # three distinct windowed call sites in this trace (one layer's)
    assert metric("window_attention_calls.train") == 3.0
    # the windowed kernels need 6.868 ms of products a step; this trace
    # shows 7.75 ms (one layer of three): 88.6%, three times what a real
    # trace of all three can read; the reader does not clip
    assert metric("window_attention_roofline_pct.train") == pytest.approx(
        100 * (1_353_002_778_624 / 197e12) / 7.75e-3)
    assert metric("full_attention_roofline_pct.train") == pytest.approx(
        100 * (1_924_380_229_632 / 197e12) / 19.5e-3)
    assert metric("full_attention_roofline_pct.train") == pytest.approx(
        50.09, abs=0.01)
    assert metric("swa_moe_expert_matmul_roofline_pct.train") == \
        pytest.approx(100 * (1_217_623_228_416 / 197e12) / 1.0e-3)
    for name, keys in (
            ("window_attention_roofline_pct.train",
             ("window_attention_flops", "window_attention_bytes")),
            ("full_attention_roofline_pct.train",
             ("full_attention_flops", "full_attention_bytes")),
            ("swa_moe_expert_matmul_roofline_pct.train",
             ("share_expert_flops", "share_expert_bytes"))):
        args = load("metrics", name + ".json")["args"]
        assert (args["flops_key"], args["bytes_key"]) == keys
        assert set(keys) <= set(flops())
    # a count without the keys (another configuration's): nothing, no raise
    other = dict(ctx(), flops={"forward": 1})
    assert metric("window_attention_roofline_pct.train", other) is None
    # a trace without a windowed call (the parent's, any other cell's)
    plain = dict(MS)
    for key in SWA:
        plain.pop(key)
    events = [Event(D0, OPS, NAMES[k], i * 10 ** 7, int(ms * 1e6))
              for i, (k, ms) in enumerate(plain.items())]
    summary = tr.device_summary(events)
    bare = dict(ctx(), trace=lambda: {
        "summary": summary, "device": tr.busiest(summary), "steps": 1})
    for name in NEW[:3]:
        assert metric(name, bare) is None, name


# (name_scope, op type) of instructions' owners, as the chip's table of the
# cell's traced run listed them (my chip run, PR 40, call 1)
OWNERS = [("l0.swa", "mul"), ("l0.swa", "mul_grad"), ("l1.swa", "rms_norm"),
          ("l1.swa", "rms_norm_grad"), ("l2.swa", "fused_attention"),
          ("l0.swa", "fused_attention_grad"), ("l1.swa", "rotary_embedding"),
          ("l2.swa", "rotary_embedding_grad"), ("l0.swa", "expand"),
          ("l1.swa", "expand_grad"), ("l0.swa", "transpose"),
          ("l2.swa", "transpose_grad"), ("l0.swa", "reshape_grad"),
          ("l3.attn", "mul"), ("l3.attn", "mul_grad"),
          ("l3.attn", "rms_norm"), ("l3.attn", "fused_attention"),
          ("l3.attn", "fused_attention_grad"),
          ("l3.attn", "rotary_embedding"),
          ("l3.attn", "rotary_embedding_grad"), ("l3.attn", "expand"),
          ("l3.attn", "expand_grad"), ("l3.attn", "transpose_grad"),
          ("l0.moe", "moe_router"), ("l2.moe", "moe_router_grad"),
          ("l1.moe", "moe_dispatch"), ("l3.moe", "moe_dispatch_grad"),
          ("l0.moe", "moe_combine"), ("l2.moe", "moe_combine_grad"),
          ("l1.moe", "grouped_matmul"), ("l1.moe", "grouped_matmul_grad"),
          ("l1.moe", "swiglu"), ("l1.moe", "rms_norm"),
          ("", "adam"), ("", "lookup_table"), ("", "rms_norm"),
          ("", "elementwise_add"), ("", "softmax_with_cross_entropy")]


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
    swa = {(s, o) for s, o in OWNERS if s.endswith(".swa")}
    full = {(s, o) for s, o in OWNERS if s.endswith(".attn")}
    assert owned("swa_mixer_op_ms.train") == swa and len(swa) == 13
    assert owned("full_mixer_op_ms.train") == full and len(full) == 10
    assert not swa & full
    assert owned("kv_repeat_op_ms.train") == {
        ("l0.swa", "expand"), ("l1.swa", "expand_grad"),
        ("l3.attn", "expand"), ("l3.attn", "expand_grad")}
    assert owned("rotary_op_ms.train") == {
        ("l1.swa", "rotary_embedding"), ("l2.swa", "rotary_embedding_grad"),
        ("l3.attn", "rotary_embedding"),
        ("l3.attn", "rotary_embedding_grad")}
    assert owned("swa_moe_layout_op_ms.train") == {
        ("l1.moe", "moe_dispatch"), ("l3.moe", "moe_dispatch_grad"),
        ("l0.moe", "moe_combine"), ("l2.moe", "moe_combine_grad")}
    # the same expression as the accepted metric of the other expert cells
    assert load("metrics", "swa_moe_layout_op_ms.train.json")["args"] == \
        load("metrics", "moe_layout_op_ms.train.json")["args"]
    # Kanana's and Qwen3-Next's scopes are not these
    for scope in ("l0.mla", "l3.gdn", "l1.moe", "l0.mlp", ""):
        assert not re.search(load(
            "metrics", "swa_mixer_op_ms.train.json")["args"]["scope"], scope)
    assert not re.search(load(
        "metrics", "full_mixer_op_ms.train.json")["args"]["scope"], "l0.swa")


def test_new_entries_are_listed_for_the_cell_alone_found_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(listed) == set(NEW)
    # in this order among themselves, wherever later entries follow
    assert [m["name"] for m in bench["per_layer"] if m["name"] in NEW] == NEW
    layers = {m["layer"] for m in bench["per_layer"]}
    for m in listed.values():
        assert m["workloads"] == [CELL] and \
            m["moves"] == "train_examples_per_s", m["name"]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json"))
        assert m["layer"] in layers
    for name in NEW:
        if "roofline" in name:
            assert name.endswith("_roofline_pct.train")
            assert (listed[name]["unit"], listed[name]["better"]) == \
                ("%", "higher")
    assert listed["window_attention_layers.train"]["source"] == \
        "program_counter"
    # later metrics may take the cell in (moe_share_bounded_ops.train did)
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == load("workloads", CELL + ".json")
    assert len(cell["why"]) <= 200 and cell["chips"] == 1
    assert sum(w["config"] == CONFIG for w in bench["workloads"]) == 1
    # every cell reports train_examples_per_s and setup_s; the tail metric
    # keeps its two cells
    tail, = [m for m in bench["end_to_end"]
             if m["name"] == "train_step_ms_p95"]
    assert CELL not in tail["workloads"]
    assert bench["run_seconds"] == 36


def test_counter_readers_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "grad_fanin_max": 1,
                        "layer_kinds": {"window_attention": 3,
                                        "full_attention": 1},
                        "attention_window_layers": 3,
                        "attention_window": 1024, "attention_kv_group": 8,
                        "moe_experts_routed": 64, "moe_experts_held": 8,
                        "moe_row_buffer_rows": 66560,
                        "moe_share_bounded_moves": 16,
                        "window_tiles_computed": 4320})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    assert metric("window_attention_layers.train", {"system": system}) == 3.0
    assert metric("window_tiles_computed.train", {"system": system}) == 4320.0
    assert 3 * 32 * 45 == 4320
    system.main._uid = 3        # a program older than the keys: left out
    assert metric("window_attention_layers.train", {"system": system}) is None
    assert metric("window_tiles_computed.train", {"system": system}) is None


def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "mellum2_reference.py"),
        os.path.join(ROOT, "tests", "mellum2_reference.py"), shallow=False)


def test_config_holds_the_catalog_numbers_and_lists_its_three_cuts():
    c = load("configs", CONFIG + ".json")
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-6, "sliding_window": 1024,
        "tie_word_embeddings": False, "use_sliding_window": True}
    for key, value in published.items():
        assert c[key] == value, key
    assert c["layer_types"] == (["sliding_attention"] * 3
                                + ["full_attention"]) * 7
    assert c["mlp_layer_types"] == ["sparse"] * 28
    assert c["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    # the three cuts, each with what was published beside it
    assert (c["num_hidden_layers"], c["num_hidden_layers_published"]) == \
        (4, 28)
    assert (c["num_experts"], c["num_experts_published"]) == (8, 64)
    assert (c["vocab_size"], c["vocab_size_published"]) == (12288, 98304)
    assert c["vocab_size"] * 8 == c["vocab_size_published"]
    b = c["build_args"]
    assert (b["d_model"], b["n_head"], b["n_kv_head"], b["head_dim"],
            b["sliding_window"], b["rope_theta"], b["n_expert"], b["top_k"],
            b["d_expert"], b["norm_topk_prob"], b["rms_eps"]) == \
        (2304, 32, 4, 128, 1024, 5e5, 64, 8, 896, True, 1e-6)
    assert b["layer_types"] == c["layer_types"][:4]
    yarn = c["rope_parameters"]["full_attention"]
    assert b["rope_scaling"] == {k: yarn[k] for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "attention_factor")}
    assert (b["n_layer"], b["experts_held"], b["first_expert"],
            b["vocab_size"], b["aux_coef"]) == (4, 8, 0, 12288, 0.001)
    assert c["feed_ranges"] == {"tokens": [0, 12288], "labels": [0, 12288]}
    assert [r.split()[0] for r in c["reduced"]] == \
        ["num_hidden_layers", "num_experts", "vocab_size"]
    assert "8 chips share each layer" in c["deployment"]
    for key in ("QK-norm", "balance loss", "multi-token prediction",
                "initialisation", "optimizer", "labels", "attention",
                "rotary"):
        assert len(c["assumed"][key]) > 30, key
    # the issue's rate, and what it does in a share with no shared expert
    assert c["optimizer"] == {"type": "Adam",
                              "args": {"learning_rate": 1e-6}}
    assert "DRIFTS TO THE 8 HELD EXPERTS" in c["assumed"]["optimizer"]
    check = c["reference"]["check"]
    assert {"l0.attn.q.w", "l0.attn.k.w", "l0.attn.v.w", "l0.attn.q_norm.w",
            "l3.attn.q.w", "l3.attn.k.w", "l0.router.w",
            "l0.experts.gate.w", "embed.w", "head.w"} <= \
        set(check["gradients"])
    assert set(check["loss_atol"]) == {"loss", "ce", "load_balance"}
    assert set(check["faults"]) == {
        "window_off_by_one", "no_window", "window_on_full", "no_yarn",
        "yarn_on_sliding", "wrong_group"}
    for why in (c["reference"]["first_loss_atol_why"], check["why"]):
        assert len(why) > 200
    assert "TO BE READ" not in json.dumps(c) and "TO WRITE" not in \
        json.dumps(c)
    # the tiny block passes through both masks and the share
    t = c["tiny"]["build_args"]
    assert t["sliding_window"] < t["seq_len"] and t["sliding_window"] % 128
    assert t["n_head"] // t["n_kv_head"] > 1 and t["n_layer"] == 4
    assert 0 < t["first_expert"] and t["experts_held"] < t["n_expert"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [e for e in json.load(f)["configs"] if e["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/" + CONFIG + ".json"


def test_traffic_is_kanana2s_but_for_the_length_the_pool_and_the_reference():
    old = load("traffic", "steady_b1_s4096_kanana2.json")
    new = load("traffic", TRAFFIC + ".json")
    for key in ("generator", "batch", "feed", "in_flight", "warmup",
                "traced"):
        assert new[key] == old[key], key
    assert new["build_args"] == {"seq_len": 8192}
    assert set(new) == set(old) | {"pool_batches_why"}
    assert len(new["pool_batches_why"]) > 200
    assert "TO BE READ" not in new["pool_batches_why"]
    # the in-run comparison says what it cannot refuse
    assert "DOES NOT HOLD: THE WINDOW" in \
        new["reference_check"]["loss_atol_why"]
    assert new["reference_check"]["reference"] == "mellum2_reference"
    assert new["reference_check"]["reference_args"] == {"q_block": 512}
    assert len(new["reference_check"]["loss_atol_why"]) > 200
    assert "TO BE READ" not in new["reference_check"]["loss_atol_why"]
    cell = load("workloads", CELL + ".json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)


def test_the_pool_is_what_the_cells_why_says():
    """PR 45 widened the pool from 8 to 128: a run that sees 8 batches ~35
    times each memorises them, the router drifts to the held experts and a
    step slows through the window by an amount that follows the seed's draw
    (the driver read spreads of 1.35-2.06% against the 1% bound). The number
    in the cell's `why`, in BENCHMARK.json's copy of it and in the traffic
    file's own reason is the traffic file's."""
    traffic = load("traffic", TRAFFIC + ".json")
    assert traffic["pool_batches"] == 128
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [w for w in json.load(f)["workloads"] if w["name"] == CELL]
    for why in (load("workloads", CELL + ".json")["why"], entry["why"],
                traffic["pool_batches_why"]):
        said = re.search(r"pool of (\d+)", why)
        assert said and int(said.group(1)) == traffic["pool_batches"], why


@pytest.mark.parametrize("trace", [0, 1])
def test_run_tiny_over_the_new_cell(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000007", "--seconds", "1", "--trace", str(trace),
         "--tiny"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "REHEARSAL" in p.stdout and "reference check after" in p.stdout
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    if trace:
        # the counters are the program's: read on the CPU too; the device
        # metrics find no TPU plane and are left out, none raises
        assert {"window_attention_layers.train",
                "window_tiles_computed.train"} <= set(line["metrics"])
        assert "window_attention_kernels_ms.train" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_examples_per_s", "setup_s"}


def test_reference_check_tiny():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference_check_mellum2.py"),
         "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "reference_check_mellum2: PASS" in p.stdout
    assert "the bfloat16 reference must NOT be judged correct" in p.stdout
    for fault in ("window_off_by_one", "no_window", "window_on_full",
                  "no_yarn", "yarn_on_sliding", "wrong_group"):
        assert f"ok   fault {fault} must NOT be judged correct" in p.stdout
    assert "mask probe, fault window_off_by_one" in p.stdout
