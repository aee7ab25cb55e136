"""The Trinity-Mini configuration's own pieces of the yardstick: its FLOP, band
and share counts against numbers worked out by hand, each new metric's
pattern against instruction text at the cell's shapes (recorded from the
chip's trace of the cell, PR 49) and against the other cells' recorded texts
(the windowed and the full patterns find this cell's calls and none of
Mellum2's or Kanana-2's shapes by accident: the patterns read names, the
recorded texts say which cell a name came from), the scope metrics'
expressions against the owners the chip's table showed, the counters' reader
on a hand-made observatory, the reference kept identical to the tests' copy,
the configuration against the catalog's numbers, `run.py --tiny` over the new
cell both ways and `reference_check_trinity.py --tiny`. The new `per_layer`
entries are found BY NAME, wherever later PRs put theirs."""

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
CELL = "trinity_mini_26b_a3b.s4096"
CONFIG = "trinity_mini_26b_a3b"
TRAFFIC = "steady_b1_s4096_trinity_mini"
KERNELS = ["gated_swa_window_kernels_ms.train",
           "gated_swa_window_calls.train",
           "gated_swa_window_roofline_pct.train",
           "gated_swa_full_kernels_ms.train",
           "gated_swa_full_roofline_pct.train"]
SCOPES = ["attn_gate_op_ms.train", "sandwich_norm_op_ms.train",
          "gated_swa_mixer_op_ms.train", "gated_full_mixer_op_ms.train"]
EXPERTS = ["gated_swa_moe_expert_matmul_ms.train",
           "gated_swa_moe_expert_matmul_roofline_pct.train",
           "gated_swa_moe_layout_op_ms.train", "gated_swa_router_op_ms.train"]
COUNTERS = {"gated_swa_window_layers.train": "attention_window_layers",
            "gated_swa_window_tiles_computed.train": "window_tiles_computed",
            "gated_swa_router_bias_updates.train": "moe_router_bias_updates",
            "gated_swa_share_bounded_ops.train": "moe_share_bounded_ops",
            "unrotated_attention_layers.train": "attention_unrotated_layers",
            "gated_attention_layers.train": "attention_gated_layers",
            "residual_out_norms.train": "residual_out_norms"}
ROTARY = ["gated_swa_rotary_kernel_calls.train",
          "gated_swa_rotary_kernel_ms.train"]
# what the cell's ops own that Mellum2's cell reads under names whose
# accepted test holds their lists (the K/V repeat: ROADMAP M2 is judged here
# too; rotary outside its kernels)
SHARED_OPS = {"gated_kv_repeat_op_ms.train": "kv_repeat_op_ms.train",
              "gated_swa_rotary_op_ms.train": "rotary_op_ms.train"}
# accepted metrics whose tests hold no list: the cell was appended to theirs
APPENDED = ["op_attributed_pct.train", "optimizer_op_ms.train"]
# the order of BENCHMARK.json's entries
NEW = KERNELS + SCOPES + EXPERTS + list(COUNTERS)[:4] + ROTARY \
    + list(COUNTERS)[4:] + list(SHARED_OPS)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def counts_module():
    return importlib.import_module(
        "flops." + load("configs", CONFIG + ".json")["flops"])


def flops(seq_len=4096, **over):
    c = load("configs", CONFIG + ".json")
    return counts_module().flops_per_example(
        **dict(c["build_args"], seq_len=seq_len, **over))


def test_the_band_of_2048_over_4096_by_hand():
    pairs = counts_module().visible_pairs
    # the first 2048 rows see 1, 2, ..., 2048 keys, the other 2048 see 2048
    assert pairs(4096, 2048) == 2048 * 2049 // 2 + 2048 * 2048 == 6_292_480
    assert pairs(4096) == 4096 * 4097 // 2 == 8_390_656
    assert pairs(4096, 2048) / pairs(4096) == pytest.approx(0.75, abs=1e-3)
    # whole tiles at the band's edges: 9 of 1024 x 1024 (of the causal 10),
    # 30 of 512 x 512 (of 36)
    assert 9 * 1024 ** 2 / pairs(4096, 2048) == pytest.approx(1.4997, abs=1e-3)
    assert 30 * 512 ** 2 / pairs(4096, 2048) == pytest.approx(1.2498, abs=1e-3)


def test_trinity_flops_by_hand():
    # multiply-adds a token. W_q, W_g, W_o 2048 x (32 x 128); W_k, W_v
    # 2048 x (4 x 128)
    projections = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert projections == 27_262_976
    window = 2 * 32 * 128 * 6_292_480 // 4096       # 1536.25 keys a query
    full = 2 * 32 * 128 * 8_390_656 // 4096         # 2048.5
    assert (window, full) == (12_584_960, 16_781_312)
    dense = 3 * 2048 * 6144
    router, shared = 2048 * 128, 3 * 2048 * 1024
    routed = 8 * 8 * 3 * 2048 * 1024 // 128         # half an expert a token
    assert (dense, router, shared, routed) == \
        (37_748_736, 262_144, 6_291_456, 3_145_728)
    head = 2048 * 25024
    total = 5 * projections + 4 * window + full + dense \
        + 4 * (router + shared + routed) + head
    got = flops()
    assert got["multiply_adds_per_token"] == {
        "projections": projections, "gate_projection": 2048 * 4096,
        "window_attention": window, "full_attention": full,
        "dense_mlp": dense, "router": router, "shared_experts": shared,
        "routed_experts": routed}
    assert got["forward"] == 2 * total * 4096 == 2_713_446_252_544
    assert got["forward_backward"] == 3 * got["forward"]
    assert got["positions_per_example"] == 4096
    assert got["layers"] == {"window_attention": 4, "full_attention": 1,
                             "dense": 1, "moe": 4}
    # where the FLOPs are: the gated attention block 61% (projections with
    # the gate 41%, kernels 20%), the head 15.5%, the dense MLP 11%, the
    # shared experts 7.6%, the held routed experts 3.8%
    assert got["mixers_share"] == pytest.approx(0.614, abs=1e-3)
    assert got["projections_share"] == pytest.approx(0.4115, abs=1e-3)
    assert got["attention_kernels_share"] == pytest.approx(0.2026, abs=1e-3)
    assert got["head_share"] == pytest.approx(0.1547, abs=1e-3)
    assert got["dense_mlp_share"] == pytest.approx(0.114, abs=1e-3)
    assert got["shared_experts_share"] == pytest.approx(0.076, abs=1e-3)
    assert got["routed_experts_share"] == pytest.approx(0.038, abs=1e-3)
    # the published model at full depth: 2 dense layers, 30 expert layers,
    # 24 windowed and 8 full, every expert held, the whole vocabulary
    whole = counts_module().flops_per_example(seq_len=4096)
    assert whole["layers"] == {"window_attention": 24, "full_attention": 8,
                               "dense": 2, "moe": 30}
    assert whole["multiply_adds_per_token"]["routed_experts"] == \
        8 * 3 * 2048 * 1024


def test_kernel_counts_by_hand():
    got = flops()
    # seven T x T products a head and layer over the visible pairs, 128
    # wide, 32 heads; four windowed layers, one full
    assert got["window_attention_flops"] == \
        4 * 7 * 2 * 6_292_480 * 128 * 32 == 1_443_343_892_480
    assert got["full_attention_flops"] == \
        7 * 2 * 8_390_656 * 128 * 32 == 481_153_777_664
    # q, dq, Out, dOut and the repeated k, v, dk, dv in bf16, once each
    one = 8 * 4096 * 32 * 128 * 2
    assert got["window_attention_bytes"] == 4 * one == 1_073_741_824
    assert got["full_attention_bytes"] == one == 268_435_456
    assert got["window_attention_flops"] / 197e12 == pytest.approx(
        7.327e-3, rel=1e-3)
    assert got["full_attention_flops"] / 197e12 == pytest.approx(
        2.442e-3, rel=1e-3)
    # the held experts under even routing: 4096 x 8 x 8 / 128 = 2048 rows a
    # layer, nine products a layer of 2048 x 2048 x 1024, four layers
    assert got["share_expert_rows"] == 2048
    assert got["share_expert_flops"] == \
        4 * 9 * 2 * 2048 * 2048 * 1024 == 309_237_645_312
    assert got["share_expert_bytes"] == \
        4 * 9 * (2048 * 2048 + 2048 * 1024) * 2 == 452_984_832
    # the layout's rows, of which the held groups use about 2048 + padding
    assert 4096 * 8 + 8 * 128 == 33792


OPS = tr.OPS_LINE
D0 = "/device:TPU:0"
# instruction texts of the cell's step (see the file's "_from")
with open(os.path.join(BENCH, "tests", "trinity_trace_names.json")) as f:
    NAMES = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
SWA, FULL = ("swa_fwd", "swa_bwd"), ("fwd", "bwd")
MS = {"swa_fwd": 0.75, "swa_bwd": 1.25,      # 2.0 a layer
      "fwd": 1.0, "bwd": 1.5,                  # 2.5
      "gmm": 0.25, "tgmm": 0.25, "rotary_fwd": 0.125, "rotary_bwd": 0.125,
      "kv_repeat": 0.125, "while": 0.5, "copy": 0.125}


def others(which):
    """The recorded instruction texts of another cell."""
    with open(os.path.join(BENCH, "tests", which + "_trace_names.json")) as f:
        return {k: v for k, v in json.load(f).items()
                if not k.startswith("_")}


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
        assert "bf16[32,4096,128]{" in NAMES[key], key
    assert NAMES["swa_fwd"].startswith("%swa_flash_fwd")
    assert NAMES["swa_bwd"].startswith("%swa_flash_dq_flash_dkv")
    assert NAMES["fwd"].startswith("%flash_fwd")
    assert NAMES["bwd"].startswith("%flash_dq_flash_dkv")
    assert "bf16[33792," in NAMES["gmm"]
    assert "bf16[8,1024,2048]" in NAMES["tgmm"] \
        or "bf16[8,2048,1024]" in NAMES["tgmm"]
    assert NAMES["rotary_fwd"].startswith("%rotary_fwd")
    assert NAMES["rotary_bwd"].startswith("%rotary_bwd")
    assert "4096,128]" in NAMES["rotary_fwd"]
    assert "bf16[4,8,4096,128]" in NAMES["kv_repeat"]


@pytest.mark.parametrize("name,found", [
    ("gated_swa_window_kernels_ms.train", SWA),
    ("gated_swa_full_kernels_ms.train", FULL),
    ("gated_swa_moe_expert_matmul_ms.train", ("gmm", "tgmm")),
    ("gated_swa_rotary_kernel_ms.train", ("rotary_fwd", "rotary_bwd"))])
def test_cell_pattern_finds_its_ops_and_no_others(name, found):
    pattern = re.compile(load("metrics", name + ".json")["args"]["pattern"])
    hit = {key for key, text in NAMES.items() if pattern.search(text)}
    assert hit == set(found)
    assert metric(name) == pytest.approx(sum(MS[k] for k in found))


@pytest.mark.parametrize("which,windowed,full", [
    ("mellum2", {"swa_fwd", "swa_dq", "swa_dkv"}, {"fwd", "dq", "dkv"}),
    ("kanana2", set(), None)])
def test_the_patterns_read_names_not_shapes(which, windowed, full):
    """The windowed pattern finds another cell's windowed calls too and
    none of its other ops; the full pattern its unwindowed flash calls: the
    metrics are told apart by the cell that lists them (`workloads`), not by
    a shape in the expression. So each is listed for this cell alone."""
    texts = others(which)
    swa = re.compile(load(
        "metrics", "gated_swa_window_kernels_ms.train.json")["args"]["pattern"])
    plain = re.compile(load(
        "metrics", "gated_swa_full_kernels_ms.train.json")["args"]["pattern"])
    assert {k for k, v in texts.items() if swa.search(v)} == windowed
    hit = {k for k, v in texts.items() if plain.search(v)}
    if full is not None:
        assert hit == full
    assert not hit & windowed
    # the same expressions as Mellum2's accepted metrics
    for mine, theirs in (("gated_swa_window_kernels_ms.train",
                          "window_attention_kernels_ms.train"),
                         ("gated_swa_window_calls.train",
                          "window_attention_calls.train"),
                         ("gated_swa_full_kernels_ms.train",
                          "full_attention_kernels_ms.train"),
                         ("gated_swa_rotary_kernel_calls.train",
                          "rotary_kernel_calls.train"),
                         ("gated_swa_rotary_kernel_ms.train",
                          "rotary_kernel_ms.train")):
        assert load("metrics", mine + ".json")["args"] == \
            load("metrics", theirs + ".json")["args"]


def test_call_counts_and_roofline_shares_on_a_hand_made_trace():
    # two distinct windowed call sites in this trace (one layer's)
    assert metric("gated_swa_window_calls.train") == 2.0
    assert metric("gated_swa_rotary_kernel_calls.train") == 2.0
    # the windowed kernels need 7.327 ms of products a step; this trace
    # shows 2.0 ms (one layer of four): the reader does not clip
    assert metric("gated_swa_window_roofline_pct.train") == pytest.approx(
        100 * (1_443_343_892_480 / 197e12) / 2.0e-3)
    assert metric("gated_swa_full_roofline_pct.train") == pytest.approx(
        100 * (481_153_777_664 / 197e12) / 2.5e-3)
    assert metric("gated_swa_full_roofline_pct.train") == pytest.approx(
        97.70, abs=0.01)
    assert metric("gated_swa_moe_expert_matmul_roofline_pct.train") == \
        pytest.approx(100 * (309_237_645_312 / 197e12) / 0.5e-3)
    for name, keys in (
            ("gated_swa_window_roofline_pct.train",
             ("window_attention_flops", "window_attention_bytes")),
            ("gated_swa_full_roofline_pct.train",
             ("full_attention_flops", "full_attention_bytes")),
            ("gated_swa_moe_expert_matmul_roofline_pct.train",
             ("share_expert_flops", "share_expert_bytes"))):
        args = load("metrics", name + ".json")["args"]
        assert (args["flops_key"], args["bytes_key"]) == keys
        assert set(keys) <= set(flops())
    # a count without the keys (another configuration's): nothing, no raise
    other = dict(ctx(), flops={"forward": 1})
    assert metric("gated_swa_window_roofline_pct.train", other) is None
    # a trace without a windowed call: nothing, no raise
    plain = {k: v for k, v in MS.items() if k not in SWA}
    events = [Event(D0, OPS, NAMES[k], i * 10 ** 7, int(ms * 1e6))
              for i, (k, ms) in enumerate(plain.items())]
    summary = tr.device_summary(events)
    bare = dict(ctx(), trace=lambda: {
        "summary": summary, "device": tr.busiest(summary), "steps": 1})
    for name in KERNELS[:3]:
        assert metric(name, bare) is None, name


# (name_scope, op type) of instructions' owners, as the chip's table of the
# cell's traced run listed them (my chip run, PR 49)
OWNERS = [("l0.swa", "mul"), ("l0.swa", "mul_grad"), ("l1.swa", "rms_norm"),
          ("l1.swa", "rms_norm_grad"), ("l2.swa", "fused_attention"),
          ("l0.swa", "fused_attention_grad"), ("l1.swa", "rotary_embedding"),
          ("l2.swa", "rotary_embedding_grad"), ("l0.swa", "expand_grad"),
          ("l3.swa", "sigmoid"), ("l1.swa", "sigmoid_grad"),
          ("l2.swa", "elementwise_mul"), ("l0.swa", "elementwise_mul_grad"),
          ("l0.swa", "transpose"), ("l2.swa", "transpose_grad"),
          ("l4.attn", "mul"), ("l4.attn", "mul_grad"),
          ("l4.attn", "rms_norm"), ("l4.attn", "rms_norm_grad"),
          ("l4.attn", "fused_attention"), ("l4.attn", "fused_attention_grad"),
          ("l4.attn", "sigmoid"), ("l4.attn", "elementwise_mul_grad"),
          ("l4.attn", "expand_grad"), ("l4.attn", "transpose_grad"),
          ("l0.mlp", "mul"), ("l0.mlp", "swiglu"), ("l0.mlp", "rms_norm"),
          ("l0.mlp", "rms_norm_grad"),
          ("l1.moe", "moe_router"), ("l2.moe", "moe_router_grad"),
          ("l1.moe", "moe_dispatch"), ("l3.moe", "moe_dispatch_grad"),
          ("l4.moe", "moe_combine"), ("l2.moe", "moe_combine_grad"),
          ("l1.moe", "grouped_matmul"), ("l1.moe", "grouped_matmul_grad"),
          ("l1.moe", "swiglu"), ("l1.moe", "rms_norm"),
          ("l3.moe", "rms_norm_grad"), ("l2.moe", "elementwise_add"),
          ("", "adam"), ("", "lookup_table"), ("", "scale"),
          ("", "rms_norm"), ("", "rms_norm_grad"), ("", "elementwise_add"),
          ("", "softmax_with_cross_entropy")]


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
    assert owned("gated_swa_mixer_op_ms.train") == swa and len(swa) == 15
    assert owned("gated_full_mixer_op_ms.train") == full and len(full) == 10
    assert not swa & full
    # the full layer owns no rotary instruction
    assert not any(o.startswith("rotary") for _, o in full)
    assert owned("attn_gate_op_ms.train") == {
        ("l3.swa", "sigmoid"), ("l1.swa", "sigmoid_grad"),
        ("l2.swa", "elementwise_mul"), ("l0.swa", "elementwise_mul_grad"),
        ("l4.attn", "sigmoid"), ("l4.attn", "elementwise_mul_grad")}
    # every norm under a layer's scopes, mixer and feed-forward alike, and
    # not the final norm, which is built under none
    assert owned("sandwich_norm_op_ms.train") == {
        (s, o) for s, o in OWNERS if o.startswith("rms_norm") and s}
    assert len(owned("sandwich_norm_op_ms.train")) == 8
    assert owned("gated_swa_moe_layout_op_ms.train") == {
        ("l1.moe", "moe_dispatch"), ("l3.moe", "moe_dispatch_grad"),
        ("l4.moe", "moe_combine"), ("l2.moe", "moe_combine_grad")}
    assert owned("gated_swa_router_op_ms.train") == {
        ("l1.moe", "moe_router"), ("l2.moe", "moe_router_grad")}
    assert owned("gated_kv_repeat_op_ms.train") == {
        ("l0.swa", "expand_grad"), ("l4.attn", "expand_grad")}
    assert owned("gated_swa_rotary_op_ms.train") == {
        ("l1.swa", "rotary_embedding"), ("l2.swa", "rotary_embedding_grad")}
    for mine, theirs in SHARED_OPS.items():
        assert load("metrics", mine + ".json")["args"] == \
            load("metrics", theirs + ".json")["args"]
    # the same expressions as the accepted metrics of the other expert cells
    assert load("metrics", "gated_swa_moe_layout_op_ms.train.json")["args"] \
        == load("metrics", "moe_layout_op_ms.train.json")["args"]
    assert load("metrics", "gated_swa_router_op_ms.train.json")["args"] == \
        load("metrics", "sigmoid_router_op_ms.train.json")["args"]
    # Kanana's and Qwen3-Next's scopes are not the mixers'
    for scope in ("l0.mla", "l3.gdn", "l1.moe", "l0.mlp", ""):
        for name in ("gated_swa_mixer_op_ms.train",
                     "gated_full_mixer_op_ms.train", "attn_gate_op_ms.train"):
            assert not re.search(
                load("metrics", name + ".json")["args"]["scope"], scope)


def test_new_entries_are_listed_for_the_cell_alone_found_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(listed) == set(NEW) and len(NEW) == 24
    # in this order among themselves, wherever later entries follow
    assert [m["name"] for m in bench["per_layer"] if m["name"] in NEW] == NEW
    known = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for m in listed.values():
        assert m["workloads"][0] == CELL and \
            m["moves"] == "train_examples_per_s", m["name"]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json"))
    assert {m["layer"] for m in listed.values()} - known == \
        {"gated attention"}
    for name in NEW:
        if "roofline" in name:
            assert listed[name]["unit"] == "%" and \
                listed[name]["better"] == "higher"
    for name in COUNTERS:
        assert listed[name]["source"] == "program_counter"
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == load("workloads", CELL + ".json")
    assert len(cell["why"]) <= 200 and cell["chips"] == 1
    assert sum(w["config"] == CONFIG for w in bench["workloads"]) == 1
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    # the accepted metrics whose tests hold no list took the cell in, at
    # the end of theirs; each other one's accepted test holds its list, so
    # the counters, the rotary kernel, the K/V repeat and the rotary op have
    # files of this cell's own (the same reader and arguments)
    for m in bench["per_layer"]:
        if m["name"] in APPENDED:
            assert m["workloads"][-1] == CELL, m["name"]
        elif m["name"] not in NEW:
            assert CELL not in m.get("workloads", []), m["name"]
    tail, = [m for m in bench["end_to_end"]
             if m["name"] == "train_step_ms_p95"]
    assert CELL not in tail["workloads"]
    assert bench["run_seconds"] == 36
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
    assert len(bench["workloads"]) == 9


@pytest.mark.parametrize("name,theirs", [
    ("gated_swa_window_layers.train", "window_attention_layers.train"),
    ("gated_swa_window_tiles_computed.train", "window_tiles_computed.train"),
    ("gated_swa_router_bias_updates.train", "router_bias_updates.train"),
    ("gated_swa_share_bounded_ops.train", "moe_share_bounded_ops.train")])
def test_a_counter_of_its_own_reads_what_the_accepted_one_reads(name, theirs):
    mine = load("metrics", name + ".json")
    assert (mine["reader"], mine["args"]) == tuple(
        load("metrics", theirs + ".json")[k] for k in ("reader", "args"))


def test_counter_readers_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    detail = {"version": 2, "grad_fanin_max": 1,
              "layer_kinds": {"window_attention": 4, "full_attention": 1},
              "attention_window_layers": 4, "attention_window": 2048,
              "attention_kv_group": 8, "attention_rotary_layers": 4,
              "attention_unrotated_layers": 1, "attention_gated_layers": 5,
              "residual_out_norms": 10, "dense_ffn_layers": 1,
              "moe_router_score": "sigmoid", "moe_router_bias_updates": 4,
              "moe_experts_routed": 128, "moe_experts_held": 8,
              "moe_row_buffer_rows": 33792, "moe_share_bounded_moves": 16,
              "moe_share_bounded_ops": 12, "window_tiles_computed": 3840}
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, detail)]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    for name, key in COUNTERS.items():
        assert metric(name, {"system": system}) == float(detail[key]), name
    assert 4 * 32 * 30 == 3840
    system.main._uid = 3    # a program older than the keys (the parent's)
    for name in COUNTERS:
        assert metric(name, {"system": system}) is None, name


def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "trinity_reference.py"),
        os.path.join(ROOT, "tests", "trinity_reference.py"), shallow=False)


def test_config_holds_the_catalog_numbers_and_lists_its_three_cuts():
    c = load("configs", CONFIG + ".json")
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_expert_groups": 1,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True}
    for key, value in published.items():
        assert c[key] == value, key
    assert c["layer_types"] == (["sliding_attention"] * 3
                                + ["full_attention"]) * 8
    # the three cuts, each with what was published beside it
    assert (c["num_hidden_layers"], c["num_hidden_layers_published"]) == \
        (5, 32)
    assert (c["num_experts"], c["num_experts_published"]) == (8, 128)
    assert (c["vocab_size"], c["vocab_size_published"]) == (25024, 200192)
    assert c["vocab_size"] * 8 == c["vocab_size_published"]
    b = c["build_args"]
    assert (b["d_model"], b["d_dense"], b["n_head"], b["n_kv_head"],
            b["head_dim"], b["sliding_window"], b["rope_theta"],
            b["n_expert"], b["top_k"], b["d_expert"], b["n_shared"],
            b["route_scale"], b["bias_update_rate"], b["rms_eps"]) == \
        (2048, 6144, 32, 4, 128, 2048, 1e4, 128, 8, 1024, 1, 2.826, 0.001,
         1e-5)
    # published layer 0 (sliding, dense, counted once) and layers 4-7
    assert b["layer_types"] == [c["layer_types"][0]] + c["layer_types"][4:8]
    assert (b["n_layer"], b["n_dense_layer"], b["experts_held"],
            b["first_expert"], b["vocab_size"]) == (5, 1, 8, 0, 25024)
    assert c["feed_ranges"] == {"tokens": [0, 25024], "labels": [0, 25024]}
    assert [r.split()[0] for r in c["reduced"]] == \
        ["num_hidden_layers", "num_experts", "vocab_size"]
    assert "16 chips share each layer" in c["deployment"]
    for key in ("output gate", "four norms", "QK-norm",
                "no rotary on the full layers", "router weights",
                "bias update", "initialisation", "balance loss", "labels",
                "optimizer", "attention", "precision"):
        assert len(c["assumed"][key]) > 30, key
    assert c["optimizer"] == {"type": "Adam",
                              "args": {"learning_rate": 1e-6}}
    check = c["reference"]["check"]
    assert {"l1.attn.q.w", "l1.attn.k.w", "l1.attn.gate.w",
            "l1.attn.q_norm.w", "l4.attn.q.w", "l4.attn.k.w",
            "l4.attn.gate.w", "l4.attn.q_norm.w", "l2.post_attn_norm.w",
            "l2.post_mlp_norm.w", "l1.router.w", "l1.experts.gate.w",
            "l4.experts.down.w", "l1.shared.down.w", "l0.mlp.down.w",
            "final_norm.w", "head.w", "embed.w"} == set(check["gradients"])
    assert set(check["loss_atol"]) == {"loss", "ce"}
    assert set(check["faults"]) == {
        "no_gate", "gate_before_merge_wrong_head", "rotary_on_full",
        "no_rotary_on_sliding", "window_off_by_one", "no_window",
        "no_post_norms", "no_mup_scale", "bias_in_weights", "wrong_group"}
    assert check["planted_bias"] == {"faults": ["bias_in_weights"],
                                     "std": 0.5}
    for why in (c["reference"]["first_loss_atol_why"], check["why"]):
        assert len(why) > 200
    assert "TO BE SET" not in json.dumps(c)
    # the tiny block passes through both masks, both rotary regimes and the
    # share
    t = c["tiny"]["build_args"]
    assert t["sliding_window"] < t["seq_len"] and t["sliding_window"] % 128
    assert t["n_head"] // t["n_kv_head"] > 1 and t["n_layer"] == 5
    assert 0 < t["first_expert"] and t["experts_held"] < t["n_expert"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [e for e in json.load(f)["configs"] if e["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/" + CONFIG + ".json"


def test_traffic_is_kanana2s_but_for_the_pool_and_the_reference():
    old = load("traffic", "steady_b1_s4096_kanana2.json")
    new = load("traffic", TRAFFIC + ".json")
    for key in ("batch", "feed", "in_flight", "warmup", "traced",
                "build_args"):
        assert new[key] == old[key], key
    assert set(new) == set(old) | {"pool_batches_why"}
    assert new["pool_batches"] == 128
    # the comparison after the window holds the loss AND the step's update
    assert (old["generator"], new["generator"]) == \
        ("train_loop_reference", "train_loop_reference_update")
    check = new["reference_check"]
    assert check["reference"] == "trinity_reference"
    assert check["reference_args"] == {"q_block": 512}
    assert len(check["loss_atol_why"]) > 200
    assert check["loss_atol"] <= 0.002      # under every reading of the
    # bfloat16 reference's loss that the file gives
    update = check["update"]
    assert set(update) == {"parameters", "rel_atol", "rel_atol_why"}
    assert len(update["rel_atol_why"]) > 200
    # between the system's readings and 1, which a state left unchanged reads
    assert 0.1 <= update["rel_atol"] <= 0.5
    names = {p for p in update["parameters"]}
    assert "head.w" in names and len(names) == len(update["parameters"])
    assert "TO BE SET" not in json.dumps(new)
    cell = load("workloads", CELL + ".json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert re.search(r"pool (\d+)", cell["why"]).group(1) == "128"


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
    limits = load("traffic", TRAFFIC + ".json")["reference_check"]
    assert line["compared"]["reference_loss_gap"]["limit"] == \
        limits["loss_atol"]
    moved = line["compared"]["reference_update_gap"]
    assert moved["limit"] == limits["update"]["rel_atol"]
    assert 0 < moved["value"] <= moved["limit"]
    if trace:
        # the counters are the program's: read on the CPU too; the device
        # metrics find no TPU plane and are left out, none raises
        assert set(COUNTERS) <= set(line["metrics"])
        assert not set(KERNELS + SCOPES + EXPERTS + ROTARY) \
            & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_examples_per_s", "setup_s"}


@pytest.mark.parametrize("steps", [0, 3])
def test_reference_check_tiny(steps):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference_check_trinity.py"),
         "--tiny", "--steps", str(steps)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "reference_check_trinity: PASS" in p.stdout
    assert "the bfloat16 reference must NOT be judged correct" in p.stdout
    assert "is next_bias(b, the system's counts" in p.stdout
    for fault in load("configs", CONFIG + ".json")["reference"]["check"][
            "faults"]:
        said = f"ok   fault {fault} must NOT be judged correct" in p.stdout
        assert said == (not steps), fault
    assert ("mask probe, fault window_off_by_one" in p.stdout) == (not steps)
    # the step's update: a reading on moments of 0, where Adam is a sign
    # function; after steps it decides, and a bfloat16 state is refused
    names = load("traffic", TRAFFIC + ".json")["reference_check"]["update"][
        "parameters"]
    for n in names:
        assert (f"ok   update of {n} against Adam" in p.stdout) == bool(steps)
        assert (f"the bfloat16 reference's update of {n}: " in p.stdout
                and "refused by" in p.stdout) == bool(steps)


# -- the in-run comparison's second number: the step's update -----------------

def adam_program(lr, seed=0):
    """One parameter `w` [6, 5] under Adam with loss = mean(x * w): the
    gradient is x / 30, known without the program."""
    import numpy as np
    sys.path.insert(0, ROOT)
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[6, 5], dtype="float32",
                              append_batch_size=False)
        w = fluid.layers.create_parameter(
            shape=[6, 5], dtype="float32", name="w",
            default_initializer=fluid.initializer.Normal(scale=0.02,
                                                         seed=seed + 1))
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(x, w))
        fluid.optimizer.Adam(learning_rate=lr).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feeds = [np.random.RandomState(seed + i).randn(6, 5).astype(np.float32)
             for i in range(4)]
    system = types.SimpleNamespace(main=main, scope=scope)
    step = lambda x: exe.run(main, feed={"x": x}, fetch_list=[loss],
                             scope=scope)
    return system, step, feeds


@pytest.mark.parametrize("steps_before", [0, 3])
def test_the_update_is_adams_on_the_gradient_and_bfloat16_stays_put(
        steps_before):
    import numpy as np
    from generators.train_loop_reference_update import (
        optimizer_state, reference_delta, update_gap)
    system, step, feeds = adam_program(lr=1e-6)
    for x in feeds[:steps_before]:
        step(x)
    (attrs, state), = optimizer_state(system, ["w"]).values()
    assert (attrs["beta1"], attrs["beta2"], attrs["epsilon"]) == \
        (0.9, 0.999, 1e-8)
    assert state["Beta1Pow"].reshape(()) == pytest.approx(
        0.9 ** (steps_before + 1))
    x = feeds[steps_before]
    step(x)
    moved = np.asarray(system.scope.find_var("w")) - state["Param"]
    assert np.abs(moved).max() > 0
    grad = x / 30
    # the program's own step is the rule on the true gradient, to a float32
    # rounding of a weight of 0.02 moved by 1e-6
    assert update_gap(moved, reference_delta(attrs, state, grad)) < 2e-3
    # a gradient of the wrong sign, half the gradient on fresh moments'
    # sign function or none at all are far from it
    assert update_gap(moved, reference_delta(attrs, state, -grad)) > 0.15
    assert update_gap(np.zeros_like(moved),
                      reference_delta(attrs, state, grad)) == 1.0
    # a state held in bfloat16 does not move: the step is far under the
    # spacing of its weights
    low = reference_delta(attrs, state, grad, dtype="bfloat16")
    assert update_gap(low, reference_delta(attrs, state, grad)) > 0.9


def test_the_copies_of_the_state_outlive_the_step():
    """`optimizer_state` copies: the step donates the scope's buffers."""
    import numpy as np
    from generators.train_loop_reference_update import optimizer_state
    system, step, feeds = adam_program(lr=1e-3)
    (_, state), = optimizer_state(system, ["w"]).values()
    kept = state["Param"].copy()
    step(feeds[0])
    assert np.array_equal(state["Param"], kept)
    assert not np.array_equal(np.asarray(system.scope.find_var("w")), kept)
