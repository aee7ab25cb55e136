"""The Keye-VL-2.0 configuration's own pieces of the yardstick: its FLOP, pair
and share counts against numbers worked out by hand, each new metric's
pattern against instruction texts at the cell's shapes (the chip's compiled
step, PR 54) and against the other cells' recorded texts, the
scope metrics' expressions against owners, the counters' reader on a
hand-made observatory, the reference kept identical to the tests' copy, the
configuration against the catalog's numbers, `run.py --tiny` over the new
cell both ways and `reference_check_keye_vl2.py --tiny`. The new `per_layer`
entries are found BY NAME, wherever later PRs put theirs; nothing here holds
a list to its present length."""

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
CELL = "keye_vl_2_30b_a3b.s8192"
CONFIG = "keye_vl_2_30b_a3b"
TRAFFIC = "steady_b1_s8192_keye_vl2"
KERNELS = ["dsa_attention_kernels_ms.train", "dsa_attention_calls.train",
           "dsa_attention_roofline_pct.train"]
INDEX = ["dsa_index_score_op_ms.train", "dsa_index_roofline_pct.train",
         "dsa_select_op_ms.train", "dsa_mixer_op_ms.train"]
COUNTERS = {"dsa_layers.train": "dsa_layers",
            "dsa_keys_kept.train": "dsa_keys_kept",
            "dsa_tiles_computed.train": "dsa_tiles_computed",
            "dsa_share_bounded_ops.train": "moe_share_bounded_ops"}
SHARED = ["dsa_moe_expert_matmul_ms.train",
          "dsa_moe_expert_matmul_roofline_pct.train",
          "dsa_moe_layout_op_ms.train", "dsa_router_op_ms.train",
          "dsa_kv_repeat_op_ms.train", "dsa_rotary_kernel_ms.train",
          "dsa_token_sum_kernel_ms.train"]
NEW = KERNELS + INDEX + list(COUNTERS) + SHARED
D0, OPS = "/device:TPU:0", tr.OPS_LINE


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


# -- counts by hand ---------------------------------------------------------------

def test_kept_pairs_by_hand():
    pairs = counts_module().kept_pairs
    # the first 2048 rows keep 1, 2, ..., 2048 keys, the other 6144 keep 2048
    assert pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048 == 14_681_088
    assert counts_module().causal_pairs(8192) == 8192 * 8193 // 2 \
        == 33_558_528
    assert pairs(8192, 2048) / 33_558_528 == pytest.approx(0.4375, abs=1e-3)
    assert pairs(4096, 2048) / counts_module().causal_pairs(4096) == \
        pytest.approx(0.75, abs=1e-3)
    assert pairs(16384, 2048) / counts_module().causal_pairs(16384) == \
        pytest.approx(0.234, abs=1e-3)
    assert pairs(2048, 2048) == counts_module().causal_pairs(2048)
    assert 4 * pairs(8192, 2048) == 58_724_352      # dsa_keys_kept a step


def test_keye_flops_by_hand():
    # multiply-adds a token. W_q, W_o 2048 x (32 x 128); W_k, W_v 2048 x 512
    projections = 2 * 2048 * 4096 + 2 * 2048 * 512
    index_projections = 2048 * (16 * 64 + 64 + 16)
    kept = 2 * 32 * 128 * 14_681_088 // 8192        # 1792.1 keys a query
    scores = 16 * 64 * 33_558_528 // 8192           # 4096.5 keys a query
    router, routed = 2048 * 128, 8 * 8 * 3 * 2048 * 768 // 128
    assert (projections, index_projections, kept, scores, router, routed) \
        == (18_874_368, 2_260_992, 14_681_088, 4_194_816, 262_144, 2_359_296)
    head = 2048 * 18992
    trained = 4 * (projections + kept + router + routed) + head
    index = 4 * (index_projections + scores)
    got = flops()
    assert got["multiply_adds_per_token"] == {
        "projections": projections, "index_projections": index_projections,
        "kept_attention": kept, "index_scores": scores, "router": router,
        "routed_experts": routed}
    assert got["forward"] == 2 * (trained + index) * 8192
    # the indexer has no backward pass: its products count once, not thrice
    assert got["forward_backward"] == 2 * (3 * trained + index) * 8192 \
        == 9_447_552_319_488
    assert got["positions_per_example"] == 8192
    assert got["layers"] == {"sparse_attention": 4}
    assert got["kept_pairs"] == 14_681_088
    assert got["attention_kernels_share"] == pytest.approx(0.280, abs=2e-3)
    assert got["indexer_share"] == pytest.approx(0.123, abs=2e-3)
    assert got["experts_share"] == pytest.approx(0.050, abs=2e-3)
    assert got["head_share"] == pytest.approx(0.186, abs=2e-3)
    # 48.0 ms of a v5e's peak a step
    assert got["forward_backward"] / 197e12 == pytest.approx(0.04796, rel=1e-3)


def test_kernel_counts_by_hand():
    got = flops()
    # seven products a head over the kept pairs, 128 wide, 32 heads, 4 layers
    assert got["dsa_attention_flops"] == 4 * 7 * 2 * 14_681_088 * 128 * 32 \
        == 3_367_489_241_088
    assert got["dsa_attention_flops"] / 197e12 == pytest.approx(0.01709,
                                                                rel=1e-3)
    # q, dq, Out, dOut, k, v, dk, dv [8192, 4096] bf16, the int8 set twice
    assert got["dsa_attention_bytes"] == 4 * (
        8 * 8192 * 4096 * 2 + 2 * 8192 * 8192) == 2_684_354_560
    # one product of 64 a causal pair an index head, forward only
    assert got["dsa_index_flops"] == 4 * 2 * 33_558_528 * 16 * 64 \
        == 274_911_461_376
    assert got["dsa_index_bytes"] == 4 * (
        4 * 8192 * 8192 + 2 * 8192 * (1024 + 64 + 16)) == 1_146_093_568
    # the two bounds of the index kernel meet: 1.40 ms each
    assert got["dsa_index_flops"] / 197e12 == pytest.approx(
        got["dsa_index_bytes"] / 819e9, rel=0.01)
    # nine products a layer over 8192 x 8 x 8 / 128 = 4096 rows
    assert got["share_expert_rows"] == 4096
    assert got["share_expert_flops"] == 4 * 9 * 2 * 4096 * 2048 * 768
    assert got["share_expert_bytes"] == 4 * 9 * 2 * 4096 * (2048 + 768)


def test_a_shorter_topk_and_a_whole_model_scale_as_written():
    assert flops(topk=8192)["kept_pairs"] == 33_558_528
    assert flops(seq_len=2048)["kept_pairs"] == 2_098_176
    whole = flops(experts_held=None)
    assert whole["multiply_adds_per_token"]["routed_experts"] == \
        8 * 3 * 2048 * 768


# -- the patterns on recorded names -----------------------------------------------

with open(os.path.join(BENCH, "tests", "keye_vl2_trace_names.json")) as f:
    NAMES = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
with open(os.path.join(BENCH, "tests", "mellum2_trace_names.json")) as f:
    MELLUM2 = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
DSA = ("dsa_fwd", "dsa_bwd")
MS = {"dsa_fwd": 12.0, "dsa_bwd": 28.0, "index": 2.0, "select": 3.0,
      "gmm": 0.75, "tgmm": 0.25, "rotary_fwd": 0.5, "rotary_bwd": 0.25,
      "token_sum": 0.5}


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
    assert NAMES["dsa_fwd"].startswith("%dsa_flash_fwd")
    assert NAMES["dsa_bwd"].startswith("%dsa_flash_dq_flash_dkv")
    for key in DSA:
        assert "bf16[32,8192,128]{" in NAMES[key], key
        assert "s8[1,8192,8192]{" in NAMES[key], key     # the kept set
    assert NAMES["index"].startswith("%dsa_index_scores") \
        and "f32[1,8192,8192]{" in NAMES["index"]
    assert NAMES["select"].startswith("%dsa_select") \
        and "s8[1,8192,8192]{" in NAMES["select"]


@pytest.mark.parametrize("name,found", [
    ("dsa_attention_kernels_ms.train", DSA),
    ("dsa_moe_expert_matmul_ms.train", ("gmm", "tgmm")),
    ("dsa_rotary_kernel_ms.train", ("rotary_fwd", "rotary_bwd")),
    ("dsa_token_sum_kernel_ms.train", ("token_sum",))])
def test_cell_pattern_finds_its_ops_and_no_others(name, found):
    pattern = re.compile(load("metrics", name + ".json")["args"]["pattern"])
    hit = {key for key, text in NAMES.items() if pattern.search(text)}
    assert hit == set(found)
    assert metric(name) == pytest.approx(sum(MS[k] for k in found))


def test_the_dsa_pattern_finds_no_call_of_the_other_cells():
    """Mellum2's recorded windowed and full calls carry no `dsa_`; the index
    kernels carry no `flash_`, so no accepted attention pattern counts
    them."""
    dsa = re.compile(load(
        "metrics", "dsa_attention_kernels_ms.train.json")["args"]["pattern"])
    assert not [k for k, text in MELLUM2.items() if dsa.search(text)]
    every = re.compile(load(
        "metrics", "attention_kernels_ms.train.json")["args"]["pattern"])
    for key in ("index", "select"):
        assert not every.search(NAMES[key]) and not dsa.search(NAMES[key])
    for name in ("dsa_attention_calls.train",
                 "dsa_attention_roofline_pct.train"):
        assert load("metrics", name + ".json")["args"]["pattern"] == \
            dsa.pattern
    for text, hit in [
            ("%dsa_flash_fwd.3 = (bf16[32,8192,128]{2,1,0}) custom-call(", 1),
            ("%dsa_flash_fwd_onepass = (bf16[8,256,64]{2,1,0}) custom-call(",
             1),
            ("%dsa_flash_dq.2 = bf16[8,256,64]{2,1,0} custom-call(", 1),
            ("%dsa_flash_dkv.2 = (bf16[8,256,64]{2,1,0}) custom-call(", 1),
            ("%jvp_dsa_flash_fwd_.1 = (bf16[8,256,64]{2,1,0}) custom-call(",
             1),
            ("%flash_fwd.1 = (bf16[32,8192,128]{2,1,0}) custom-call(", 0),
            ("%swa_flash_fwd.1 = (bf16[32,8192,128]{2,1,0}) custom-call(", 0),
            ("%dsa_select.1 = s8[1,8192,8192]{2,1,0} custom-call(", 0)]:
        assert bool(dsa.search(text)) is bool(hit), text


def test_call_counts_and_roofline_shares_on_a_hand_made_trace():
    assert metric("dsa_attention_calls.train") == 2.0
    # the kernels need 17.09 ms of products a step; this trace gives them 40
    assert metric("dsa_attention_roofline_pct.train") == pytest.approx(
        100 * 3_367_489_241_088 / 197e12 / 40e-3, rel=1e-6)
    # under 43.75% while every causal tile is computed at the kernels' rate
    assert metric("dsa_attention_roofline_pct.train") < 43.75
    # the index kernel: 1.40 ms of either bound in this trace's 2 ms
    assert metric("dsa_index_roofline_pct.train") == pytest.approx(
        100 * max(274_911_461_376 / 197e12, 1_146_093_568 / 819e9) / 2e-3,
        rel=1e-6)
    assert metric("dsa_moe_expert_matmul_roofline_pct.train") == \
        pytest.approx(100 * flops()["share_expert_flops"] / 197e12 / 1e-3,
                      rel=1e-6)
    empty = ctx()
    empty["trace"] = lambda: None
    for name in KERNELS + ["dsa_index_roofline_pct.train"]:
        assert metric(name, empty) is None


# (name scope, op type) of owners a traced step of the cell shows
OWNERS = [("l0.dsa", "rms_norm"), ("l0.dsa", "mul"), ("l1.dsa", "mul_grad"),
          ("l0.dsa", "layer_norm"), ("l2.dsa", "rotary_embedding"),
          ("l2.dsa", "rotary_embedding_grad"), ("l0.dsa", "expand"),
          ("l3.dsa", "expand_grad"), ("l1.dsa", "dsa_index_scores"),
          ("l1.dsa", "dsa_select"), ("l3.dsa", "fused_attention"),
          ("l3.dsa", "fused_attention_grad"), ("l1.moe", "moe_router"),
          ("l1.moe", "moe_router_grad"), ("l0.moe", "moe_dispatch"),
          ("l2.moe", "moe_combine_grad"), ("l2.moe", "grouped_matmul"),
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
    mixer = {(s, o) for s, o in OWNERS if s.endswith(".dsa")}
    assert owned("dsa_mixer_op_ms.train") == mixer and len(mixer) == 12
    assert owned("dsa_index_score_op_ms.train") == {
        ("l1.dsa", "dsa_index_scores")}
    assert owned("dsa_select_op_ms.train") == {("l1.dsa", "dsa_select")}
    assert owned("dsa_kv_repeat_op_ms.train") == {
        ("l0.dsa", "expand"), ("l3.dsa", "expand_grad")}
    assert owned("dsa_router_op_ms.train") == {
        ("l1.moe", "moe_router"), ("l1.moe", "moe_router_grad")}
    assert owned("dsa_moe_layout_op_ms.train") == {
        ("l0.moe", "moe_dispatch"), ("l2.moe", "moe_combine_grad")}
    # the same expressions as the accepted metrics of the other share cells
    assert load("metrics", "dsa_moe_layout_op_ms.train.json")["args"] == \
        load("metrics", "moe_layout_op_ms.train.json")["args"]
    assert load("metrics", "dsa_kv_repeat_op_ms.train.json")["args"]["op"] \
        == load("metrics", "kv_repeat_op_ms.train.json")["args"]["op"]
    for scope in ("l0.swa", "l3.attn", "l0.mla", "l3.gdn", "l1.moe", ""):
        assert not re.search(load(
            "metrics", "dsa_mixer_op_ms.train.json")["args"]["scope"], scope)


def test_new_entries_are_listed_for_the_cell_found_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(listed) == set(NEW)
    for m in listed.values():
        assert CELL in m["workloads"] and \
            m["moves"] == "train_examples_per_s", m["name"]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json"))
    for name in NEW:
        if "roofline" in name:
            assert name.endswith("_roofline_pct.train")
            assert (listed[name]["unit"], listed[name]["better"]) == \
                ("%", "higher")
    for name in COUNTERS:
        assert listed[name]["source"] == "program_counter"
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == load("workloads", CELL + ".json")
    assert len(cell["why"]) <= 200 and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    tail, = [m for m in bench["end_to_end"]
             if m["name"] == "train_step_ms_p95"]
    assert CELL not in tail["workloads"]


def test_counter_readers_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "grad_fanin_max": 1,
                        "layer_kinds": {"sparse_attention": 4},
                        "dsa_layers": 4, "dsa_keys_kept": 58_724_352,
                        "dsa_tiles_computed": 4608, "frozen_parameters": 20,
                        "moe_share_bounded_ops": 12})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    want = {"dsa_layers.train": 4.0, "dsa_keys_kept.train": 58_724_352.0,
            "dsa_tiles_computed.train": 4608.0,
            "dsa_share_bounded_ops.train": 12.0}
    for name, key in COUNTERS.items():
        assert load("metrics", name + ".json")["args"]["key"] == key
        assert metric(name, {"system": system}) == want[name]
    assert 4 * 32 * 36 == 4608
    system.main._uid = 3        # a program older than the keys: left out
    for name in COUNTERS:
        assert metric(name, {"system": system}) is None


# -- the files ------------------------------------------------------------------------

def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "keye_vl2_reference.py"),
        os.path.join(ROOT, "tests", "keye_vl2_reference.py"), shallow=False)


def test_config_holds_the_catalog_numbers_and_lists_its_three_cuts():
    c = load("configs", CONFIG + ".json")
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    for key, value in published.items():
        assert c[key] == value, key
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == \
        (4, 8, 18992)
    assert (c["num_hidden_layers_published"], c["num_experts_published"],
            c["vocab_size_published"]) == (48, 128, 151936)
    assert 151936 // 8 == 18992
    assert [r.split(" ")[0] for r in c["reduced"]] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert c["source"] == ("https://huggingface.co/Kwai-Keye/"
                           "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    args = c["build_args"]
    assert (args["d_model"], args["n_head"], args["n_kv_head"],
            args["head_dim"], args["d_expert"], args["n_expert"],
            args["top_k"], args["experts_held"], args["first_expert"]) == (
        2048, 32, 4, 128, 768, 128, 8, 8, 0)
    assert (args["n_index_head"], args["index_dim"], args["topk"],
            args["index_tile"], args["rope_theta"]) == (16, 64, 2048, 512,
                                                        1e7)
    assert "16 chips share each layer" in c["deployment"]
    for key in ("QK-norm", "indexer: the key's norm", "indexer: rotary",
                "indexer: scales", "selection", "training step",
                "precision"):
        assert key in c["assumed"], key
    tiny = c["tiny"]["build_args"]
    assert (tiny["seq_len"], tiny["topk"], tiny["n_index_head"],
            tiny["index_dim"], tiny["n_expert"], tiny["experts_held"],
            tiny["first_expert"]) == (256, 64, 4, 16, 16, 4, 4)
    # parameters: 59.15 M a layer, 314.4 M in all, the indexer's 9.0 M frozen
    mixer = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    indexer = 2048 * (1024 + 64 + 16) + 2 * 64
    layer = mixer + indexer + 2048 * 128 + 8 * 3 * 2048 * 768 + 2 * 2048
    total = 4 * layer + 2 * 18992 * 2048 + 2048
    assert round(total / 1e6, 1) == 314.4
    assert round(4 * indexer / 1e6, 1) == 9.0


def test_traffic_is_trinitys_but_for_the_length_and_the_reference():
    mine = load("traffic", TRAFFIC + ".json")
    theirs = load("traffic", "steady_b1_s4096_trinity_mini.json")
    for key in ("generator", "batch", "pool_batches", "feed", "in_flight",
                "warmup", "traced"):
        assert mine[key] == theirs[key], key
    assert mine["generator"] == "train_loop_reference_update"
    assert mine["build_args"] == {"seq_len": 8192}
    check = mine["reference_check"]
    assert check["reference"] == "keye_vl2_reference"
    # one of the two updated parameters is a DSA layer's query projection:
    # a backward pass that ignored the kept set would move it otherwise
    assert check["update"]["parameters"] == ["head.w", "l3.attn.q.w"]
    assert check["update"]["rel_atol"] < 1


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
    assert "reference_update_gap" in line["compared"]
    if trace:       # the counters are read off the compile event even here
        assert set(COUNTERS) <= set(line["metrics"])


def test_reference_check_tiny():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference_check_keye_vl2.py"),
         "--tiny"], capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "REHEARSAL passed" in out.stdout
    faults = load("configs", CONFIG + ".json")["reference"]["check"]["faults"]
    assert len(faults) == 9
    for fault in faults:
        assert f"ok   fault {fault} must NOT be judged correct" in out.stdout
